"""Plain reference of the training step the ``sdar-30b-a3b-1chip``
configuration runs (JetLM/SDAR-30B-A3B-Chat, ``model_type``
``sdar_moe``, trained by block diffusion): forward pass, loss and
gradients in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no tiles, no
sort, no grouped matmul, no flax; it imports nothing from
``elasticdl_tpu``. It reads the same parameter tree the system trains
(names below), so seeded weights feed both sides, and it is GIVEN the
noise (``x_t``, ``w``): ``check.py`` draws it from the key the system
drew from.

The layer, written down (``B`` = ``assumed.block_length``, ``L`` the
sequence, ``[MASK]`` one id):

    noise:   t_b ~ U(t_min, 1) a block b;  m_i ~ Bernoulli(t_blk(i))
             x_t,i = [MASK] if m_i else x_0,i;   w_i = m_i / t_blk(i)
    input:   tokens [x_t ; x_0], 2 L positions; position p rotates by
             p mod L
    mask:    half(p) = p // L (0 noisy, 1 clean), blk(p) = (p mod L) // B;
             q may see k iff
               half(q)=0, half(k)=0:  blk(k) == blk(q)
               half(q)=0, half(k)=1:  blk(k) <  blk(q)
               half(q)=1, half(k)=0:  never
               half(q)=1, half(k)=1:  blk(k) <= blk(q)
    layer:   h = x + W_o . softmax_mask(RoPE(RMSNorm_head(x' W_q))
                 RoPE(RMSNorm_head(x' W_k))^T / sqrt(D)) (x' W_v),
             x' = RMSNorm(x), query head h reads kv head h // group;
             y = h + sum_{e in top-k of softmax(h' W_r), gates
                 renormalised, e HELD here} g_e W_down,e(silu(h' W_gate,e)
                 * h' W_up,e),   h' = RMSNorm(h)
    loss:    (1 / L) sum_{i < L} w_i CE(lm_head(RMSNorm(y_i^noisy)), x_0,i)
             + router_aux_loss_coef x balance

``RMSNorm(x) = x rsqrt(mean(x^2) + eps) w``. The balance loss is ``E
sum_e f_e P_e`` over ALL experts and all 2 L positions, summed over the
layers. ``expert_layer(..., held=(0, all))`` is the uncut layer: the
test that adds the shares up calls it.

Memory, not mathematics: each block runs under ``jax.checkpoint``;
attention is computed a head and ``QUERY_BLOCK`` queries at a time,
each block's rows of the dense ``(2 L, 2 L)`` boolean mask made where
they are used, and the experts one at a time (every held expert
computes every position and a 0 / gate mask keeps what the router
chose).
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048


def norm(x, w, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def rotary(x, positions, base):
    """x: (S, D). Pairs (i, i + D/2) rotate by position * base^(-i /
    (D/2))."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def may_see(q_pos, k_pos, length, block):
    """The mask's equations: (Q, 1) query positions against (1, K) key
    positions of the 2 x ``length`` inputs."""
    half_q, half_k = q_pos // length, k_pos // length
    blk_q, blk_k = (q_pos % length) // block, (k_pos % length) // block
    return (
        ((half_q == 0) & (half_k == 0) & (blk_k == blk_q))
        | ((half_q == 0) & (half_k == 1) & (blk_k < blk_q))
        | ((half_q == 1) & (half_k == 1) & (blk_k <= blk_q))
    )


def head_attention(q, k, v, length, block):
    """One head: q (2 L, D) over k, v (2 L, D) under the mask,
    ``QUERY_BLOCK`` queries at a time."""
    seq, dim = q.shape
    rows = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq

    @jax.checkpoint
    def queries(args):
        q_b, start = args
        scores = (q_b @ k.T) / jnp.sqrt(jnp.float32(dim))
        allowed = may_see(
            (start + jnp.arange(rows))[:, None], jnp.arange(seq)[None, :],
            length, block)
        scores = jnp.where(allowed, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    return jax.lax.map(
        queries,
        (q.reshape(seq // rows, rows, dim), jnp.arange(0, seq, rows)),
    ).reshape(seq, v.shape[1])


def attention(x, p, positions, config, block_length):
    """x: (2 L, d). Kernels: query (d, H, D), key, value (d, Hkv, D),
    q_norm, k_norm scale (D,), out_proj (H, D, d)."""
    eps, base = config["rms_norm_eps"], float(config["rope_theta"])
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    q = jnp.einsum("sd,dhk->hsk", x, p["query"]["kernel"])
    k = jnp.einsum("sd,dhk->hsk", x, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    q = norm(q, p["q_norm"]["scale"], eps)
    k = norm(k, p["k_norm"]["scale"], eps)
    turn = jax.vmap(lambda t: rotary(t, positions, base))
    q, k = turn(q), turn(k)
    out = jax.lax.map(
        lambda args: head_attention(*args, x.shape[0] // 2, block_length),
        (q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)))
    return jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, p, config, forced=None):
    """(probabilities (S, E) over all experts, gates (S, k), the
    experts applied (S, k), the experts this router would choose (S,
    k)). The last two are the same unless ``forced`` names the experts
    to apply; the gates are always this router's own probabilities of
    the applied experts, divided by their sum."""
    probs = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
    _, chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])
    applied = chosen if forced is None else forced
    gates = jnp.take_along_axis(probs, applied, axis=-1)
    if config["norm_topk_prob"]:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    return probs, gates, applied, chosen


def held_experts_mlp(x, weights, gates, experts, first):
    """sum over the choices j whose expert is one of ``weights``'
    (experts ``first`` on): gates[t, j] expert(x[t]); by a loop over
    those experts and a mask."""
    w_gate, w_up, w_down = weights
    ids = first + jnp.arange(w_gate.shape[0])
    weight = (
        gates[:, :, None] * (experts[:, :, None] == ids[None, None, :])
    ).sum(axis=1)

    def term(total, args):
        w_g, w_u, w_d, column = args
        return total + column[:, None] * swiglu(x, w_g, w_u, w_d), None

    total, _ = jax.lax.scan(
        jax.checkpoint(term), jnp.zeros_like(x),
        (w_gate, w_up, w_down, weight.T))
    return total


def balance_loss(probs, chosen):
    """E sum_e f_e P_e over all experts."""
    tokens, num_experts = probs.shape
    counts = (
        chosen[:, :, None] == jnp.arange(num_experts)[None, None, :]
    ).sum(axis=(0, 1))
    return num_experts * jnp.sum(counts / tokens * probs.mean(axis=0))


def expert_layer(x, p, config, held, forced=None):
    """(this share's part of the layer's output, its balance loss, the
    experts its router chose). ``held`` = (first, count): ``p``'s
    ``w_gate / w_up / w_down`` are those experts' kernels."""
    probs, gates, applied, chosen = route(x, p, config, forced)
    y = held_experts_mlp(
        x, (p["w_gate"], p["w_up"], p["w_down"]), gates, applied, held[0])
    return y, balance_loss(probs, chosen), chosen


def block(x, p, forced, positions, config):
    eps = config["rms_norm_eps"]
    x = x + attention(
        norm(x, p["ln_attn"]["scale"], eps), p["attn"], positions, config,
        config["assumed"]["block_length"])
    y, balance, chosen = expert_layer(
        norm(x, p["ln_mlp"]["scale"], eps), p["moe_mlp"], config,
        config["held_experts"], forced)
    return x + y, balance, chosen


def forward(params, noisy, clean, config, forced=None, last=None):
    """noisy, clean: (L,) int32 -> (logits (L, V) of the noisy half, or
    of its ``last`` positions; the summed balance loss; the experts
    every layer's router chose (layers, 2 L, k)). ``forced`` (layers,
    2 L, k): the experts to apply instead."""
    length = clean.shape[0]
    x = params["wte"]["embedding"][jnp.concatenate([noisy, clean])]
    positions = jnp.arange(2 * length) % length
    balance, chosen = 0.0, []
    for i in range(config["num_hidden_layers"]):
        x, b, experts = jax.checkpoint(
            functools.partial(block, positions=positions, config=config)
        )(x, params["block_%d" % i], None if forced is None else forced[i])
        balance = balance + b
        chosen.append(experts)
    x = x[:length] if last is None else x[length - last:length]
    x = norm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return x @ params["lm_head"]["kernel"], balance, jnp.stack(chosen)


def weighted_loss(logits, targets, weights):
    """Mean over positions of ``w_i`` x -log softmax(logits_i)[target_i],
    position-aligned."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -(weights * picked).mean()


def logits_loss_and_choices(params, noisy, clean, weights, config,
                            forced=None, last=None):
    """The comparison's unit: the logits of the noisy half (of its last
    ``last`` positions; every layer still runs all 2 L), the loss (the
    weighted cross-entropy over the compared positions plus the
    weighted balance loss) and the experts each position's router chose
    in each layer, over ALL experts.

    Top-k is discontinuous, so the comparison has two parts
    (``check.py``): ``forced`` applies the experts another
    implementation chose, with this reference's own gates for them; the
    returned choices, and the balance loss's counts, are always this
    reference's own."""
    with jax.default_matmul_precision("highest"):
        to_f32 = lambda tree: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), tree)
        logits, balance, chosen = forward(
            to_f32(params), noisy, clean, config, forced, last)
        if last is not None:
            clean, weights = clean[-last:], weights[-last:]
        loss = (
            weighted_loss(logits, clean, weights)
            + config["assumed"]["router_aux_loss_coef"] * balance
        )
        return logits, loss, chosen
