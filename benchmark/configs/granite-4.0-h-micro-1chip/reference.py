"""Plain reference of the decoder the ``granite-4.0-h-micro-1chip``
configuration trains (ibm-granite/granite-4.0-h-micro, ``model_type``
``granitemoehybrid``; the Mamba-2 mixer of arXiv:2405.21060): forward
pass, loss and gradients in straightforward ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``. No kernel, no
chunked scan, no flax; it imports nothing from ``elasticdl_tpu``. It
reads the same parameter tree the system trains (names below), so
seeded weights feed both sides.

``h_0 = embedding_multiplier E[token]``; ``num_hidden_layers`` blocks
``a = x + r Mixer(norm(x)); y = a + r MLP(norm(a))`` with ``r`` the
``residual_multiplier`` and ``norm(x) = x rsqrt(mean(x^2) + eps) w``; a
final norm; ``logits = (h E^T) / logits_scaling`` (the head IS the
embedding). Layer ``i`` is a Mamba-2 layer or an attention layer as
``layer_types[i]`` says; ``MLP(u) = (silu(u W_gate) * (u W_up)) W_down``
(``shared_intermediate_size`` wide; ``num_local_experts`` is 0: no
router, no experts).

- The Mamba-2 mixer (``GraniteMoeHybridMambaLayer``), H =
  ``mamba_n_heads`` heads of P = ``mamba_d_head`` lanes over a state of
  N = ``mamba_d_state``, ``mamba_n_groups`` groups: ``z | xBC | dt = u
  W_in`` (H P, H P + 2 groups N, H; no bias); ``xBC = silu(conv(xBC) +
  b)``, a causal depthwise convolution over ``mamba_d_conv`` tokens
  with zeros before the sequence's start, then ``x | B | C`` its three
  parts; ``dt = softplus(dt + dt_bias)``, ``a = -exp(A_log) dt``; per
  head, ONE TOKEN A STEP, the state ``S`` (P x N) zero at the start:
  ``S = exp(a_t) S + dt_t x_t B_t^T; y_t = S C_t + D x_t`` (head h
  reads group ``h // (H / groups)``'s B and C); ``y = rmsnorm(y
  silu(z)) w`` (the gate BEFORE the norm, the norm over a group's lanes:
  all H P with one group) and the output projection.
- The attention layer (``GraniteMoeHybridAttention``):
  ``num_attention_heads`` query heads over ``num_key_value_heads`` key
  / value heads of ``hidden_size / num_attention_heads`` lanes, no
  bias, NOTHING rotated (``position_embedding_type`` ``nope``;
  ``rotate`` is there for the variant that has to fail), ``o = causal
  softmax(attention_multiplier q k^T) v``, ``W_o``.

The loss is the cross-entropy of the next token over the rows held.

Where the system departs from the source the reference follows the
system and the configuration says so (``departs``): the MLP's input
projection is two kernels (``mlp_gate`` | ``mlp_up``: the published
``input_linear`` is one matrix whose two halves they are), the
attention's kernels are (d, heads, lanes).

Memory, not mathematics: each block runs under ``jax.checkpoint``; the
per-token loop is a scan over blocks of ``SCAN_BLOCK`` tokens, each
under ``jax.checkpoint``, so the backward holds a state a block and a
block's own (8,192 states of 64 x 64 x 128 floats would be 17 GB); the
MLP runs ``ROW_BLOCK`` rows at a time; attention is computed a head and
``QUERY_BLOCK`` queries at a time.
"""

import functools

import jax
import jax.numpy as jnp

SCAN_BLOCK = 128
QUERY_BLOCK = 2048
ROW_BLOCK = 2048


def rms_norm(x, scale, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def selective_scan(x, dt, a, b, c, skip):
    """All heads, one token a step. x: (S, H, P); dt, a: (S, H); b, c:
    (S, H, N), already a head's own; skip: (H,) -> y (S, H, P)."""
    seq, heads, dim = x.shape
    block = SCAN_BLOCK if seq % SCAN_BLOCK == 0 else seq

    def token(state, xs):
        x_t, dt_t, a_t, b_t, c_t = xs
        state = jnp.exp(a_t)[:, None, None] * state + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, (
            jnp.einsum("hpn,hn->hp", state, c_t) + skip[:, None] * x_t)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = lambda t: t.reshape((seq // block, block) + t.shape[1:])
    _, y = jax.lax.scan(
        tokens, jnp.zeros((heads, dim, b.shape[-1]), jnp.float32),
        tuple(map(blocks, (x, dt, a, b, c))))
    return y.reshape(seq, heads, dim)


def mamba2_mixer(u, p, config, gate_after_norm=False, norm_lanes=None,
                 conv_bias=True, skip=True):
    """u: (S, d). Kernels: in_proj (d, 2 H P + 2 G N + H), conv_kernel
    (taps, H P + 2 G N), conv_bias (H P + 2 G N,), A_log, dt_bias, D
    (H,), out_norm_scale (H P,), out_proj (H, P, d). The keywords are
    the variants that have to fail: the gate applied AFTER the norm
    (Gated DeltaNet's order), the norm over ``norm_lanes`` lanes (a
    head's 64) in place of a group's, the convolution without its bias,
    the recurrence without ``D``'s skip."""
    heads, dim = config["mamba_n_heads"], config["mamba_d_head"]
    state, groups = config["mamba_d_state"], config["mamba_n_groups"]
    taps, eps = config["mamba_d_conv"], config["rms_norm_eps"]
    seq, inner = u.shape[0], heads * dim
    conv_dim = inner + 2 * groups * state
    zxbcdt = u @ p["in_proj"]["kernel"]
    z, xbc = zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = sum(p["conv_kernel"][j] * padded[j:j + seq] for j in range(taps))
    if conv_bias:
        conv = conv + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(seq, heads, dim)
    # a head's own B and C: its group's
    own = lambda t: jnp.repeat(
        t.reshape(seq, groups, state), heads // groups, axis=1)
    b = own(xbc[:, inner:inner + groups * state])
    c = own(xbc[:, inner + groups * state:])
    dt = jax.nn.softplus(zxbcdt[:, inner + conv_dim:] + p["dt_bias"])
    a = -jnp.exp(p["A_log"]) * dt
    y = selective_scan(
        x, dt, a, b, c, p["D"] if skip else jnp.zeros_like(p["D"]))
    y = y.reshape(seq, inner)
    lanes = norm_lanes or inner // groups
    normed = lambda t: rms_norm(
        t.reshape(seq, -1, lanes), 1.0, eps).reshape(seq, inner)
    if gate_after_norm:
        y = normed(y) * p["out_norm_scale"] * jax.nn.silu(z)
    else:
        y = normed(y * jax.nn.silu(z)) * p["out_norm_scale"]
    return jnp.einsum(
        "shp,hpd->sd", y.reshape(seq, heads, dim), p["out_proj"]["kernel"])


def rotary(x, base):
    """x: (S, D). Pairs (i, i + D/2) rotate by pos * base^(-i / (D/2)).
    Used by no layer of this model: the variant that must fail."""
    seq, dim = x.shape
    half = dim // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def head_attention(q, k, v, scale):
    """One head: q, k, v (S, D) -> (S, D), causal softmax of ``scale x
    q k^T``, ``QUERY_BLOCK`` queries at a time."""
    seq, dim = q.shape
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq

    @jax.checkpoint
    def queries(args):
        q_b, start = args
        scores = (q_b @ k.T) * scale
        allowed = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        scores = jnp.where(allowed, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    return jax.lax.map(
        queries,
        (q.reshape(seq // block, block, dim), jnp.arange(0, seq, block)),
    ).reshape(seq, dim)


def attention(u, p, config, rotate=False, scale=None):
    """u: (S, d). Kernels: query (d, H, D), key and value (d, Hkv, D),
    out_proj (H, D, d). ``rotate``: q and k rotated at ``rope_theta``,
    which this model does NOT do; ``scale``: another softmax scale than
    ``attention_multiplier``. Both are the variants that have to
    fail."""
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    q = jnp.einsum("sd,dhk->hsk", u, p["query"]["kernel"])
    k = jnp.einsum("sd,dhk->hsk", u, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", u, p["value"]["kernel"])
    if rotate:
        turn = jax.vmap(functools.partial(
            rotary, base=float(config["rope_theta"])))
        q, k = turn(q), turn(k)
    scale = config["attention_multiplier"] if scale is None else scale
    # query head h reads key / value head h // group
    k, v = (jnp.repeat(t, group, axis=0) for t in (k, v))
    out = jax.lax.map(
        lambda args: head_attention(*args, scale), (q, k, v))
    return jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def dense_mlp(x, w_gate, w_up, w_down):
    """``swiglu`` over ``ROW_BLOCK`` rows at a time, each block under a
    checkpoint of its own: three (S, 8192) float32 arrays are 0.8 GB at
    8,192 tokens."""
    seq = x.shape[0]
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    return jax.lax.map(
        jax.checkpoint(lambda block: swiglu(block, w_gate, w_up, w_down)),
        x.reshape(seq // rows, rows, -1)).reshape(seq, -1)


def is_mamba(i, config):
    """Whether layer ``i`` (0-indexed) is a Mamba-2 one."""
    kind = config["layer_types"][i]
    if kind not in ("mamba", "attention"):
        raise ValueError("layer_types[%d]=%r" % (i, kind))
    return kind == "mamba"


def block(x, p, i, config, variant=None, residual=None):
    """x after block ``i``. ``variant``: keyword arguments of the mixer
    for a variant that has to fail; ``residual``: another multiplier
    than ``residual_multiplier`` (1: the multiplier left out)."""
    eps = config["rms_norm_eps"]
    scale = config["residual_multiplier"] if residual is None else residual
    mixer = mamba2_mixer if is_mamba(i, config) else attention
    x = x + scale * mixer(
        rms_norm(x, p["ln_attn"]["scale"], eps), p["attn"], config,
        **(variant or {}))
    h = rms_norm(x, p["ln_mlp"]["scale"], eps)
    return x + scale * dense_mlp(
        h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
        p["mlp_down"]["kernel"])


def forward(params, tokens, config, last=None, variants=None):
    """tokens: (S,) int32 -> logits (S, V), or of the ``last``
    positions. ``variants``: {"mamba" / "full": mixer keywords,
    "residual": a multiplier}."""
    variants = variants or {}
    embedding = params["wte"]["embedding"]
    x = config["embedding_multiplier"] * embedding[tokens]
    for i in range(config["num_hidden_layers"]):
        variant = variants.get("mamba" if is_mamba(i, config) else "full")
        x = jax.checkpoint(functools.partial(
            block, i=i, config=config, variant=variant,
            residual=variants.get("residual")))(x, params["block_%d" % i])
    if last is not None:
        x = x[-last:]
    x = rms_norm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return (x @ embedding.T) / config["logits_scaling"]


def next_token_loss(logits, targets):
    """Mean over positions of -log softmax(logits)[target]; ``logits``
    at position t predict ``targets[t]`` (already shifted)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)
    return -picked.mean()


def logits_and_loss(params, tokens, config, last=None, variants=None):
    """The comparison's unit: the logits (of the last ``last``
    positions; every layer still mixes over the whole context) and the
    loss (cross-entropy of predicting each compared position's
    successor; the final position has none)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        logits = forward(params, tokens, config, last, variants)
        targets = tokens if last is None else tokens[-last:]
        return logits, next_token_loss(logits[:-1], targets[1:])
