"""Plain reference of the OLMoE decoder the ``olmoe-1b-7b-1chip``
configuration trains: forward pass, the three-part loss and gradients
in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sort, no
grouped matmul, no flax; it imports nothing from ``elasticdl_tpu``. It
reads the same parameter tree the system trains (names below), so
seeded weights feed both sides.

Written from the published description (OLMoE, arXiv:2409.02060, and
its published implementation): token embedding, ``num_hidden_layers``
pre-RMSNorm blocks of

- causal multi-head attention with QK-norm (RMSNorm over the whole
  query and key projections, all heads together, before the heads are
  split) and rotary position embedding on the whole head;
- a mixture of ``num_experts`` SwiGLU experts: router logits ``h W_r``,
  softmax over all experts, the ``k`` largest probabilities chosen and
  used as gates WITHOUT renormalising, every chosen expert applied (no
  capacity: nothing is dropped), ``y = sum_j g_j down_e(silu(gate_e h)
  * up_e h)``;

a final RMSNorm and an untied output head. The loss is cross-entropy +
``alpha`` x load balancing + ``beta`` x router z-loss with

- load balancing, a layer: ``E sum_e f_e P_e``, ``f_e`` the share of
  the tokens that chose expert e among their k (the f sum to k), ``P_e``
  the mean router probability of e;
- z-loss, a layer: the mean over tokens of ``logsumexp(logits)^2``.

Where the system departs from the source, the reference follows the
system and says so, because the two must compute the same function
(config.json ``departs``): both losses are SUMMED over the layers
(the published implementation averages; equal at one layer).

The experts are a plain loop: every expert computes every token and a
0/gate mask keeps what the router chose (eight times the work the
routing needs, and exactly its result). Memory, not mathematics: the
loop is a ``lax.map`` over the experts' stacked weights with each
expert under ``jax.checkpoint``, so the backward pass holds one
expert's activations.
"""

import jax
import jax.numpy as jnp

ROTARY_BASE = 10000.0


def rms_norm(x, scale, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


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


def attention(x, p, eps):
    """x: (S, d). Kernels: query/key/value (d, H, D), out_proj
    (H, D, d); q_norm / k_norm scale (d,) over the whole projection."""
    d, heads, head_dim = p["query"]["kernel"].shape

    def project(name, norm=None):
        y = x @ p[name]["kernel"].reshape(d, heads * head_dim)
        if norm:
            y = rms_norm(y, p[norm]["scale"], eps)
        return y.reshape(-1, heads, head_dim).transpose(1, 0, 2)

    q, k = project("query", "q_norm"), project("key", "k_norm")
    out = jax.vmap(head_attention)(q, k, project("value"))
    return jnp.einsum("hsk,hkd->sd", out, p["out_proj"]["kernel"])


def route(x, p, top_k, forced=None):
    """(router logits (S, E), probabilities, gates (S, k), the experts
    applied (S, k), the experts this router would choose (S, k)). The
    last two are the same unless ``forced`` names the experts to apply
    (see ``logits_loss_and_choices``); the gates are always this
    router's own probabilities of the applied experts."""
    logits = x @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, top_k)
    if forced is None:
        return logits, probs, gates, chosen, chosen
    gates = jnp.take_along_axis(probs, forced, axis=-1)
    return logits, probs, gates, forced, chosen


def one_expert(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def experts_mlp(x, p, gates, experts):
    """sum_j gates[t, j] expert_{experts[t, j]}(x[t]) by a loop over all
    experts and a mask."""
    num_experts = p["w_gate"].shape[0]
    # weight[t, e]: the gate token t gives expert e, 0 where not chosen
    weight = (
        gates[:, :, None]
        * (experts[:, :, None] == jnp.arange(num_experts)[None, None, :])
    ).sum(axis=1)

    def term(args):
        w_gate, w_up, w_down, column = args
        return column[:, None] * one_expert(x, w_gate, w_up, w_down)

    return jax.lax.map(
        jax.checkpoint(term),
        (p["w_gate"], p["w_up"], p["w_down"], weight.T),
    ).sum(axis=0)


def load_balancing(probs, experts):
    tokens, num_experts = probs.shape
    chosen = (
        experts[:, :, None] == jnp.arange(num_experts)[None, None, :]
    ).sum(axis=(0, 1))
    return num_experts * jnp.sum(chosen / tokens * probs.mean(axis=0))


def z_loss(logits):
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)


def block(x, p, top_k, eps, forced=None):
    """(x after the block, this layer's load-balancing loss, its router
    z-loss, the experts its router chose (S, k))."""
    x = x + attention(rms_norm(x, p["ln_attn"]["scale"], eps), p["attn"], eps)
    h = rms_norm(x, p["ln_mlp"]["scale"], eps)
    moe = p["moe_mlp"]
    logits, probs, gates, experts, chosen = route(h, moe, top_k, forced)
    x = x + experts_mlp(h, moe, gates, experts)
    # the loss counts this router's own choices, forced or not
    return x, load_balancing(probs, chosen), z_loss(logits), chosen


def forward(params, tokens, config, forced=None):
    """tokens: (S,) int32 -> (logits (S, V), summed load-balancing loss,
    summed z-loss, the experts every layer's router chose (L, S, k)).
    ``forced`` (L, S, k): the experts to apply instead."""
    x = params["wte"]["embedding"][tokens]
    eps, top_k = config["rms_norm_eps"], config["num_experts_per_tok"]
    balance, z, chosen = 0.0, 0.0, []
    for i in range(config["num_hidden_layers"]):
        x, b, zl, experts = block(
            x, params["block_%d" % i], top_k, eps,
            None if forced is None else forced[i])
        balance, z = balance + b, z + zl
        chosen.append(experts)
    x = rms_norm(x, params["ln_f"]["scale"], eps)
    return x @ params["lm_head"]["kernel"], balance, z, jnp.stack(chosen)


def next_token_loss(logits, targets):
    """Mean over positions of -log softmax(logits)[target]; ``logits``
    at position t predict ``targets[t]`` (already shifted)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)
    return -picked.mean()


def logits_loss_and_choices(params, tokens, config, forced=None):
    """The comparison's unit: the logits of every position, the
    three-part loss (cross-entropy of predicting each position's
    successor; the final position has none and is left out) and the
    experts each token's router chose in each layer.

    Top-k is discontinuous: where a token's k-th and (k+1)-th
    probabilities lie within rounding of each other, two correct
    implementations in different precisions choose differently, and the
    token's output then differs by a whole (small-gated) expert. So the
    comparison has two parts. ``forced`` (L, S, k) applies the experts
    another implementation chose, with this reference's own gates for
    them: logits, loss and gradients then compare the arithmetic alone.
    The returned choices, and the load-balancing loss's counts, are
    always this reference's own: they compare the routing."""
    weights = config["assumed"]["loss_weights"]
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params
        )
        logits, balance, z, chosen = forward(params, tokens, config, forced)
        loss = (
            next_token_loss(logits[:-1], tokens[1:])
            + weights["router_aux_loss_coef"] * balance
            + weights["router_z_loss_coef"] * z
        )
        return logits, loss, chosen
