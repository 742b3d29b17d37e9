"""Plain reference of the decoder the ``lfm2-8b-a1b-1chip`` configuration
trains (LiquidAI/LFM2-8B-A1B, ``model_type`` ``lfm2_moe``): forward
pass, loss and gradients in straightforward ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``. No kernel, no sort,
no grouped matmul, no flax; it imports nothing from ``elasticdl_tpu``.
It reads the same parameter tree the system trains (names below), so
seeded weights feed both sides.

The model, written down from the ``lfm2_moe`` implementation the source
names (``RMSNorm(x) = x rsqrt(mean(x^2) + eps) w``, eps ``norm_eps``).
Layer ``l`` is of kind ``layer_types[l]``:

    h = x + Mixer(RMSNorm(x)) ;  out = h + FFN(RMSNorm(h))

    Mixer ``conv`` (``in_proj`` (d, 3 d), ``conv_kernel`` (K, d),
    ``proj_out`` (d, d); no bias anywhere):
        B | C | X = u W_in
        z        = B * X
        c[t]     = sum_{j < K} w[j] * z[t - (K - 1) + j],   z[< 0] = 0
                   (depthwise: channel by channel; K = conv_L_cache = 3)
        y        = C * c ;   out = y W_out
    Mixer ``full_attention`` (``query`` (d, H, D), ``key`` / ``value``
    (d, Hkv, D), ``out_proj`` (H, D, d), ``q_norm`` / ``k_norm`` (D,)):
        q, k     = RMSNorm_D(u W_q), RMSNorm_D(u W_k)   over a head's
                   D = 64 lanes, one scale shared by the heads
        q, k     = rot(q), rot(k): the whole head rotates by halves
                   (lane i with lane i + D / 2) at theta ``rope_theta``
        s_ij     = q_i . k_j / sqrt(D)   for j <= i ; query head h reads
                   kv head h // (H / Hkv)
        out      = (softmax_j(s) v) W_o
    FFN, layers below ``num_dense_layers``:  (silu(h W_g) * (h W_u)) W_d
    FFN, the others: s = sigmoid(h W_r) over ALL experts; the k with the
        largest s + b (``expert_bias``: chooses, never weighs);
        g_e = routed_scaling_factor s_e / (sum_chosen s + 1e-6);
        y = sum over the chosen experts HELD here of g_e E_e(h); the
        absent experts add nothing; no shared expert
    logits = RMSNorm(x_L) E^T   (the head is the embedding: tied)
             or RMSNorm(x_L) W_head where the tree has an ``lm_head``
    loss   = mean_i CE(logits_i, t_(i+1)) over the held slice of the
             vocabulary

Where the system departs from a published code the reference follows
the system and the configuration says so (``departs``): rotary by
halves. ``expert_layer(..., held=(0, all))`` with all the experts'
kernels is the uncut layer: the test that adds the shares up calls it.

Memory, not mathematics: each block runs under ``jax.checkpoint``, the
query heads one at a time (``lax.map``), ``QUERY_BLOCK`` queries at a
time against a dense mask over all the keys, the held experts one at a
time (every held expert computes every position and a 0 / gate mask
keeps what the router chose).
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048
KINDS = ("conv", "full_attention")
GATE_EPS = 1e-6


def rms_norm(x, scale, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def rotary(x, theta):
    """x: (S, D); pairs (i, i + D / 2) rotate by pos * theta^(-2i / D)."""
    half = x.shape[1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def head_attention(q, k, v):
    """One head: q (S, D) over k, v (S, D), ``QUERY_BLOCK`` queries at a
    time; query i sees the keys j <= i."""
    seq, dim = q.shape
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq

    @jax.checkpoint
    def queries(args):
        q_b, start = args
        q_pos = (start + jnp.arange(block))[:, None]
        scores = (q_b @ k.T) / jnp.sqrt(jnp.float32(dim))
        scores = jnp.where(
            jnp.arange(seq)[None, :] <= q_pos, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    return jax.lax.map(
        queries,
        (q.reshape(seq // block, block, dim), jnp.arange(0, seq, block)),
    ).reshape(seq, v.shape[1])


def attention(x, p, config):
    """x: (S, d) -> (S, d): grouped-query softmax attention with a norm
    over the lanes of every q and k head."""
    eps, theta = config["norm_eps"], float(config["rope_theta"])
    heads, dim = config["num_attention_heads"], config["head_dim"]
    kv_heads = config["num_key_value_heads"]
    shape = (x.shape[1], heads, dim)
    if p["query"]["kernel"].shape != shape:
        raise ValueError(
            "the config gives W_q %r, the tree has %r"
            % (shape, p["query"]["kernel"].shape))
    if p["key"]["kernel"].shape[1] != kv_heads:
        raise ValueError("the tree has not the config's kv heads")
    k = jnp.einsum("sd,dhk->hsk", x, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    k = jax.vmap(
        lambda t: rotary(rms_norm(t, p["k_norm"]["scale"], eps), theta))(k)
    group = heads // kv_heads

    @jax.checkpoint
    def head(args):
        w_q, index = args
        q = rotary(rms_norm(x @ w_q, p["q_norm"]["scale"], eps), theta)
        return head_attention(q, k[index // group], v[index // group])

    out = jax.lax.map(
        head, (p["query"]["kernel"].transpose(1, 0, 2), jnp.arange(heads)))
    return jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


def short_conv(x, p, config):
    """x: (S, d) -> (S, d): the double-gated short convolution, the
    convolution as an explicit sum over K shifted copies."""
    taps, width = config["conv_L_cache"], x.shape[1]
    w = p["conv_kernel"]
    if w.shape != (taps, width) or config["conv_bias"]:
        raise ValueError(
            "the config gives %d taps over %d channels and no bias; the "
            "tree has %r" % (taps, width, w.shape))
    bcx = x @ p["in_proj"]["kernel"]
    b, c, u = bcx[:, :width], bcx[:, width:2 * width], bcx[:, 2 * width:]
    z = b * u
    conv = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j
        # the copy of z that lies ``back`` positions earlier
        shifted = jnp.concatenate(
            [jnp.zeros((back, width), z.dtype), z[:z.shape[0] - back]])
        conv = conv + w[j][None, :] * shifted
    return (c * conv) @ p["proj_out"]["kernel"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, p, bias, config, forced=None):
    """(gates (S, k), the experts applied (S, k), the experts this
    router would choose (S, k)), over ALL experts. The last two are the
    same unless ``forced`` names the experts to apply; the gates are
    always this router's own scores of the applied experts."""
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + bias, config["num_experts_per_tok"])
    applied = chosen if forced is None else forced
    gates = jnp.take_along_axis(scores, applied, axis=-1)
    gates = gates / (gates.sum(axis=-1, keepdims=True) + GATE_EPS)
    return gates * config["routed_scaling_factor"], applied, chosen


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


def expert_layer(x, p, bias, config, held, forced=None):
    """(this share's part of the layer's output, the experts its router
    chose). ``held`` = (first, count): ``p``'s ``w_gate / w_up /
    w_down`` are those experts' kernels."""
    gates, applied, chosen = route(x, p, bias, config, forced)
    y = held_experts_mlp(
        x, (p["w_gate"], p["w_up"], p["w_down"]), gates, applied, held[0])
    return y, chosen


def block(x, p, bias, forced, kind, config):
    """(x after the block, the experts its router chose (S, k) or None
    for a dense block)."""
    eps = config["norm_eps"]
    mixer = short_conv if kind == "conv" else attention
    x = x + mixer(rms_norm(x, p["ln_attn"]["scale"], eps), p["attn"], config)
    h = rms_norm(x, p["ln_mlp"]["scale"], eps)
    if "moe_mlp" not in p:
        return x + swiglu(h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                          p["mlp_down"]["kernel"]), None
    y, chosen = expert_layer(
        h, p["moe_mlp"], bias, config, config["held_experts"], forced)
    return x + y, chosen


def forward(params, biases, tokens, config, forced=None, last=None):
    """tokens: (S,) int32 -> (logits (S, V), or of the ``last``
    positions; the experts every expert layer's router chose (layers,
    S, k)). ``biases``: {block name: (E,)} of the expert layers;
    ``forced`` (layers, S, k): the experts to apply instead."""
    x = params["wte"]["embedding"][tokens]
    chosen = []
    for i in range(config["num_hidden_layers"]):
        name, kind = "block_%d" % i, config["layer_types"][i]
        if kind not in KINDS:
            raise ValueError("layer_types[%d]=%r" % (i, kind))
        if ("moe_mlp" in params[name]) != (i >= config["num_dense_layers"]):
            raise ValueError(
                "layer %d: the first num_dense_layers=%d have a dense "
                "MLP, the others experts" % (i, config["num_dense_layers"]))
        pick = None if forced is None else forced[len(chosen)]
        x, experts = jax.checkpoint(functools.partial(
            block, kind=kind, config=config,
        ))(x, params[name], biases.get(name), pick)
        if experts is not None:
            chosen.append(experts)
    if last is not None:
        x = x[-last:]
    x = rms_norm(x, params["ln_f"]["scale"], config["norm_eps"])
    head = (params["lm_head"]["kernel"] if "lm_head" in params
            else params["wte"]["embedding"].T)
    return x @ head, jnp.stack(chosen)


def cross_entropy(logits, targets):
    """Mean over positions of -log softmax(logits)[target]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


def logits_loss_and_choices(params, biases, tokens, config, forced=None,
                            last=None):
    """The comparison's unit: the logits (of the last ``last``
    positions; every layer still mixes and routes over the whole
    context), the loss (cross-entropy of predicting each compared
    position's successor; the final position has none) and the experts
    each token's router chose in each expert layer, over ALL experts.

    Top-k is discontinuous, so the comparison has two parts
    (``check.py``): ``forced`` applies the experts another
    implementation chose, with this reference's own gates for them; the
    returned choices are always this reference's own."""
    with jax.default_matmul_precision("highest"):
        to_f32 = lambda tree: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), tree)
        logits, chosen = forward(
            to_f32(params), to_f32(biases), tokens, config, forced, last)
        targets = tokens if last is None else tokens[-last:]
        return logits, cross_entropy(logits[:-1], targets[1:]), chosen
