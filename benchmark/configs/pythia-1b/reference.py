"""Plain reference of the GPT-NeoX decoder the pythia configurations
train: forward pass, next-token loss and gradients in straightforward
``jax.numpy`` and float32 under ``jax.default_matmul_precision
("highest")``. No kernel, no cache, no flax; it imports nothing from
``elasticdl_tpu.models`` or ``elasticdl_tpu.ops``. It reads the same
parameter tree the system trains (names below), so seeded weights feed
both sides.

Written from the published description (GPT-NeoX, arXiv:2204.06745;
Pythia, arXiv:2304.01373): token embedding, ``num_hidden_layers``
pre-LayerNorm blocks of causal multi-head attention with rotary
position embedding and a GELU MLP of ``intermediate_size``, a final
LayerNorm and an untied output head. Where the system departs from the
source, the reference follows the system and says so, because the two
must compute the same function (config.json ``departs``):

1. sequential residual: ``x += attn(ln1(x)); x += mlp(ln2(x))``, where
   the source computes both branches from the same ``x``;
2. rotary embedding on the whole head in the half-split ("NeoX") form,
   where the source rotates only ``rotary_pct`` of it;
3. no biases in the linear layers (LayerNorm keeps scale and bias);
4. LayerNorm epsilon 1e-6 and the tanh form of GELU.

Memory, not mathematics: at long sequences one head's S x S score
matrix is already 1 GB in float32, so attention then runs one head at
a time, and each block is wrapped in ``jax.checkpoint`` so the backward
pass holds one block's activations. Both only re-order when the same
numbers are computed.
"""

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
ROTARY_BASE = 10000.0
# above this many float32 score elements, attention goes head by head
SCORES_AT_ONCE = 1 << 28


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)
    ))


def rotary(x):
    """x: (S, D) of one head. Pairs (i, i + D/2) rotate by
    pos * base^(-i / (D/2))."""
    seq, dim = x.shape
    half = dim // 2
    inv_freq = ROTARY_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def head_attention(q, k, v):
    """One head, (S, D) each: softmax(q k^T / sqrt(D) + causal) v."""
    seq, dim = q.shape
    q, k = rotary(q), rotary(k)
    scores = (q @ k.T) / jnp.sqrt(jnp.float32(dim))
    causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def attention(x, p):
    """x: (S, d). Kernels: query/key/value (d, H, D), out_proj
    (H, D, d)."""
    q = jnp.einsum("sd,dhk->hsk", x, p["query"]["kernel"])
    k = jnp.einsum("sd,dhk->hsk", x, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    heads, seq, _ = q.shape
    if heads * seq * seq > SCORES_AT_ONCE:
        out = jax.lax.map(
            lambda qkv: jax.checkpoint(head_attention)(*qkv), (q, k, v)
        )
    else:
        out = jax.vmap(head_attention)(q, k, v)
    return jnp.einsum("hsk,hkd->sd", out, p["out_proj"]["kernel"])


def block(x, p):
    x = x + attention(layer_norm(x, p["ln_attn"]), p["attn"])
    h = layer_norm(x, p["ln_mlp"]) @ p["mlp_up"]["kernel"]
    return x + gelu_tanh(h) @ p["mlp_down"]["kernel"]


def forward(params, tokens, num_layers, last=None, remat=False):
    """tokens: (S,) int32 -> logits (S, V), or of the ``last``
    positions only (every layer still sees the whole context)."""
    x = params["wte"]["embedding"][tokens]
    step = jax.checkpoint(block) if remat else block
    for i in range(num_layers):
        x = step(x, params["block_%d" % i])
    if last is not None:
        x = x[-last:]
    return layer_norm(x, params["ln_f"]) @ params["lm_head"]["kernel"]


def next_token_loss(logits, targets):
    """Mean over positions of -log softmax(logits)[target]; ``logits``
    at position t predict ``targets[t]`` (already shifted)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)
    return -picked.mean()


def logits_and_loss(params, tokens, num_layers, last=None, remat=False):
    """The comparison's unit: logits of the last ``last`` positions (all
    when None) and the loss of predicting each of those positions'
    successor (the final position has none and is left out)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params
        )
        logits = forward(params, tokens, num_layers, last, remat)
        n = logits.shape[0]
        return logits, next_token_loss(logits[:-1], tokens[-n + 1:])
