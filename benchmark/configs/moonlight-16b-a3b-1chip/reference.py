"""Plain reference of the decoder the ``moonlight-16b-a3b-1chip``
configuration trains (Moonlight-16B-A3B, arXiv:2502.16982; the block is
DeepSeek-V3's, arXiv:2412.19437 2.1): forward pass, loss and gradients
in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sort, no
grouped matmul, no flax; it imports nothing from ``elasticdl_tpu``. It
reads the same parameter tree the system trains (names below), so
seeded weights feed both sides.

Token embedding, ``num_hidden_layers`` pre-RMSNorm blocks, a final
RMSNorm and an untied head. Every block has

- multi-head latent attention without a q latent: ``q = x W_q`` (H
  heads of nope | rope); ``c = x W_kva`` (rank | rope); ``c_kv =
  RMSNorm(c[:rank])``; ``k_nope | v = c_kv W_kvb`` (H heads of nope |
  v); rotary (base ``rope_theta``) over the rope lanes of q and of the
  ONE key head ``c[rank:]`` that all heads share; ``o = causal
  softmax([q_nope | q_rope] [k_nope | k_rope]^T / sqrt(nope + rope)) v``
  and the output projection;

the first ``first_k_dense_replace`` blocks a dense SwiGLU MLP of
``intermediate_size``, the others the expert layer: ``s = sigmoid(h
W_r)``; the ``k`` experts with the largest ``s + b`` (``b``: the
balancing bias, which takes part in the selection only); gates ``s`` of
the chosen, divided by their sum and times ``routed_scaling_factor``;
``y = sum_j g_j expert_j(h) + shared(h)``, every expert and the shared
MLP (``n_shared_experts x moe_intermediate_size`` wide) SwiGLU.

The loss is cross-entropy + ``aux_loss_alpha`` x the sequence-wise
balance loss summed over the expert layers: within the sequence,
``sum_e f_e P_e`` with ``f_e = E / (k T) x`` the pairs that chose e and
``P_e`` the mean of ``s_e / sum_e' s_e'`` (arXiv:2412.19437 eq. 17-20).

Where the system departs from the source the reference follows the
system and the configuration says so (``departs``): rotary rotates the
two HALVES of the rope lanes where the published code rotates
interleaved pairs (with seeded weights a fixed permutation of those
columns of ``W_q`` and ``W_kva``).

The experts are a plain loop: every expert computes every token and a
0/gate mask keeps what the router chose. Memory, not mathematics: the
loops over experts and over heads are ``lax.map`` with each step under
``jax.checkpoint``, so the backward pass holds one expert's activations
and one head's (S, S) scores.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def rotary(x, base):
    """x: (S, D). Pairs (i, i + D/2) rotate by pos * base^(-i / (D/2))."""
    seq, dim = x.shape
    half = dim // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def head_attention(args):
    """One head: q, k (S, nope + rope), v (S, v) -> (S, v)."""
    q, k, v = args
    seq, dim = q.shape
    scores = (q @ k.T) / jnp.sqrt(jnp.float32(dim))
    causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def latent_attention(x, p, config):
    """x: (S, d). Kernels: q_proj (d, H, nope + rope), kv_down (d, rank
    + rope), kv_norm scale (rank,), kv_up (rank, H, nope + v), out_proj
    (H, v, d)."""
    eps, base = config["rms_norm_eps"], float(config["rope_theta"])
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    q = jnp.einsum("sd,dhk->hsk", x, p["q_proj"]["kernel"])
    c = x @ p["kv_down"]["kernel"]
    c_kv = rms_norm(c[:, :rank], p["kv_norm"]["scale"], eps)
    kv = jnp.einsum("sr,rhk->hsk", c_kv, p["kv_up"]["kernel"])
    k_rope = rotary(c[:, rank:], base)
    q = jnp.concatenate([
        q[..., :nope],
        jax.vmap(lambda t: rotary(t, base))(q[..., nope:]),
    ], axis=-1)
    k = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(k_rope, (kv.shape[0],) + k_rope.shape),
    ], axis=-1)
    out = jax.lax.map(
        jax.checkpoint(head_attention), (q, k, kv[..., nope:]))
    return jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, p, bias, config, forced=None):
    """(normalised scores (S, E), gates (S, k), the experts applied
    (S, k), the experts this router would choose (S, k)). The last two
    are the same unless ``forced`` names the experts to apply; the
    gates are always this router's own scores of the applied experts."""
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + bias, config["num_experts_per_tok"])
    applied = chosen if forced is None else forced
    gates = jnp.take_along_axis(scores, applied, axis=-1)
    if config["norm_topk_prob"]:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    gates = gates * config["routed_scaling_factor"]
    return scores / scores.sum(axis=-1, keepdims=True), gates, applied, chosen


def experts_mlp(x, p, gates, experts):
    """sum_j gates[t, j] expert_{experts[t, j]}(x[t]) by a loop over all
    experts and a mask."""
    num_experts = p["w_gate"].shape[0]
    weight = (
        gates[:, :, None]
        * (experts[:, :, None] == jnp.arange(num_experts)[None, None, :])
    ).sum(axis=1)

    def term(args):
        w_gate, w_up, w_down, column = args
        return column[:, None] * swiglu(x, w_gate, w_up, w_down)

    return jax.lax.map(
        jax.checkpoint(term),
        (p["w_gate"], p["w_up"], p["w_down"], weight.T),
    ).sum(axis=0)


def sequence_balance(probs, chosen):
    """sum_e f_e P_e of one sequence."""
    tokens, num_experts = probs.shape
    counts = (
        chosen[:, :, None] == jnp.arange(num_experts)[None, None, :]
    ).sum(axis=(0, 1))
    share = counts * (num_experts / (chosen.shape[1] * tokens))
    return jnp.sum(share * probs.mean(axis=0))


def block(x, p, bias, config, forced=None):
    """(x after the block, the layer's balance loss or 0, the experts
    its router chose (S, k) or None for a dense block)."""
    eps = config["rms_norm_eps"]
    x = x + latent_attention(
        rms_norm(x, p["ln_attn"]["scale"], eps), p["attn"], config)
    h = rms_norm(x, p["ln_mlp"]["scale"], eps)
    if "moe_mlp" not in p:
        return x + swiglu(
            h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
            p["mlp_down"]["kernel"]), 0.0, None
    moe = p["moe_mlp"]
    probs, gates, applied, chosen = route(h, moe, bias, config, forced)
    y = experts_mlp(h, moe, gates, applied) + swiglu(
        h, moe["shared_gate"]["kernel"], moe["shared_up"]["kernel"],
        moe["shared_down"]["kernel"])
    # the loss counts this router's own choices, forced or not
    return x + y, sequence_balance(probs, chosen), chosen


def forward(params, biases, tokens, config, forced=None, last=None):
    """tokens: (S,) int32 -> (logits (S, V), or of the ``last``
    positions; the summed balance loss; the experts every expert
    layer's router chose (L_moe, S, k)). ``biases``: {block name: (E,)}
    of the expert layers; ``forced`` (L_moe, S, k): the experts to
    apply instead."""
    x = params["wte"]["embedding"][tokens]
    balance, chosen = 0.0, []
    for i in range(config["num_hidden_layers"]):
        name = "block_%d" % i
        x, b, experts = block(
            x, params[name], biases.get(name), config,
            None if forced is None else forced[len(chosen)])
        balance = balance + b
        if experts is not None:
            chosen.append(experts)
    if last is not None:
        x = x[-last:]
    x = rms_norm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return x @ params["lm_head"]["kernel"], balance, jnp.stack(chosen)


def next_token_loss(logits, targets):
    """Mean over positions of -log softmax(logits)[target]; ``logits``
    at position t predict ``targets[t]`` (already shifted)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)
    return -picked.mean()


def logits_loss_and_choices(params, biases, tokens, config, forced=None,
                            last=None):
    """The comparison's unit: the logits (of the last ``last`` positions;
    every layer still attends over the whole context), the loss
    (cross-entropy of predicting each compared position's successor,
    the final position has none; plus the weighted balance loss) and
    the experts each token's router chose in each expert layer.

    Top-k is discontinuous, so the comparison has two parts
    (``check.py``): ``forced`` applies the experts another
    implementation chose, with this reference's own gates for them; the
    returned choices, and the balance loss's counts, are always this
    reference's own."""
    with jax.default_matmul_precision("highest"):
        to_f32 = lambda tree: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), tree)
        logits, balance, chosen = forward(
            to_f32(params), to_f32(biases), tokens, config, forced, last)
        targets = tokens if last is None else tokens[-last:]
        loss = (
            next_token_loss(logits[:-1], targets[1:])
            + config["assumed"]["aux_loss_alpha"] * balance
        )
        return logits, loss, chosen
