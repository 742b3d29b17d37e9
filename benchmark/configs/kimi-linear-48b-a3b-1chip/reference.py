"""Plain reference of the decoder the ``kimi-linear-48b-a3b-1chip``
configuration trains (moonshotai/Kimi-Linear-48B-A3B-Instruct,
``model_type`` ``kimi_linear``; arXiv:2510.26692): forward pass, loss
and gradients in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no chunked
rule, no sort, no grouped matmul, no flax; it imports nothing from
``elasticdl_tpu``. It reads the same parameter tree the system trains
(names below), so seeded weights feed both sides.

Token embedding, ``num_hidden_layers`` blocks ``a = x + mixer(norm(x));
y = a + F(norm(a))``, a final norm and an untied head; ``norm(x) = x
rsqrt(mean(x^2) + eps) w``. Layer ``i`` (1-indexed) is a Kimi Delta
Attention layer if ``i`` is in ``linear_attn_config.kda_layers``, a
latent-attention layer if in ``full_attn_layers``; ``F`` is a dense
SwiGLU of ``intermediate_size`` in the first ``first_k_dense_replace``
layers and the expert layer in every other.

- Kimi Delta Attention, H = ``num_heads`` heads of D = ``head_dim``
  lanes: ``q | k | v = x W_qkv`` (H D each); each through a causal
  depthwise convolution of ``short_conv_kernel_size`` taps (no bias)
  and SiLU; q and k l2-normalised over a head's lanes (eps 1e-6), q
  scaled by ``D^-1/2``; ``g = -exp(A_log_h) softplus(W_fb (W_fa x) +
  dt_bias)``, the log decay of each of a head's D key channels; ``beta
  = sigmoid(x W_b)``; per head, ONE TOKEN A STEP, the state ``S`` (D x
  D) zero at the start: ``S = Diag(exp(g_t)) S; u = beta_t (v_t - S^T
  k_t); S = S + k_t u^T; o_t = S^T q_t``; then ``o = rmsnorm(o) w
  sigmoid(W_gb (W_ga x))`` per head and the output projection.
- Latent attention without positions: ``q = x W_q`` (H heads of nope +
  rope lanes); ``c = x W_kva`` (rank | rope); ``c_kv = rmsnorm(c[:rank])``;
  ``k_nope | v = c_kv W_kvb``; ``k = [k_nope | c[rank:]]``, the last
  ``rope`` lanes ONE head shared by all H; with ``mla_use_nope``
  NOTHING is rotated (``rotary`` is there for the variant that has to
  fail); ``o = causal softmax(q k^T (nope + rope)^-1/2) v``; ``W_o``.
- Expert layer: ``s = sigmoid(h W_r)`` over ALL ``published.
  num_experts``; the ``num_experts_per_token`` with the largest ``s +
  bias``; gates ``s`` of the chosen over their sum, times
  ``routed_scaling_factor``; of the chosen experts THOSE THIS CHIP
  HOLDS (``held_experts``: a first index and a count) each a SwiGLU
  MLP, nothing for the absent ones; plus the shared expert on every
  token. ``expert_layer(..., held=(0, all))`` with all the experts'
  kernels is the uncut layer: the test that adds the shares up calls it.

The loss is cross-entropy + ``aux_loss_alpha`` x the sequence-wise
balance loss summed over the expert layers: ``sum_e f_e P_e`` with
``f_e = count_e E / (k S)`` and ``P_e`` the mean of the scores divided
by their sum over the experts.

Where the system departs from the source the reference follows the
system and the configuration says so (``departs``): the columns of
``in_proj_qkv`` lie q | k | v and the three convolutions are one array
of taps over them (the published module has three projections and three
convolutions: with seeded weights the same function).

Memory, not mathematics: each block runs under ``jax.checkpoint``; the
per-token loop is a scan over blocks of ``SCAN_BLOCK`` tokens, each
under ``jax.checkpoint``, so the backward holds a state a block and a
block's own (32,768 states of 32 x 128 x 128 floats would be 68 GB);
a Kimi Delta Attention layer runs ``HEAD_GROUP`` heads at a time from
its input to their part of its output, each group under
``jax.checkpoint`` (q, k, v and g of all 32 heads are 0.5 GB an array in
float32 at 32,768 tokens, and a backward held seventeen such), and the
dense MLP over ``ROW_BLOCK`` rows at a time;
attention is computed a head and ``QUERY_BLOCK`` queries at a time, and
the held experts one at a time (every held expert computes every token
and a 0 / gate mask keeps what the router chose).
"""

import functools

import jax
import jax.numpy as jnp

SCAN_BLOCK = 128
QUERY_BLOCK = 2048
HEAD_GROUP = 8
ROW_BLOCK = 8192


def rms_norm(x, scale, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def delta_rule(q, k, v, g, beta):
    """One head, one token a step. q, k, g: (S, D); v: (S, Dv); beta:
    (S,) -> o (S, Dv). ``g`` is the log decay of each key channel: the
    state's ROWS decay, each by its own."""
    seq = q.shape[0]
    block = SCAN_BLOCK if seq % SCAN_BLOCK == 0 else seq

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, None] * state
        u = b_t * (v_t - state.T @ k_t)
        state = state + jnp.outer(k_t, u)
        return state, state.T @ q_t

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = lambda x: x.reshape((seq // block, block) + x.shape[1:])
    _, o = jax.lax.scan(
        tokens, jnp.zeros((q.shape[1], v.shape[1]), jnp.float32),
        tuple(map(blocks, (q, k, v, g, beta))))
    return o.reshape(seq, v.shape[1])


def kda_heads(x, low, p, config, gate):
    """A group of a Kimi Delta Attention layer's heads, from the
    layer's input to the group's part of its output (S, d). ``p``: the
    group's columns of every kernel (``kimi_delta_attention`` cuts
    them); ``low``: the two gates' low-rank latents (S, r) each, which
    all heads share."""
    linear = config["linear_attn_config"]
    dim, taps = linear["head_dim"], linear["short_conv_kernel_size"]
    seq = x.shape[0]
    split = lambda t: t.reshape(seq, -1, dim).transpose(1, 0, 2)

    def conv_silu(kernel, part_taps):
        """One of q, k, v: projection, causal depthwise convolution,
        SiLU, heads first."""
        padded = jnp.pad(x @ kernel, ((taps - 1, 0), (0, 0)))
        return split(jax.nn.silu(sum(
            part_taps[j] * padded[j:j + seq] for j in range(taps))))

    l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q, k, v = (conv_silu(p["in_proj_qkv"][:, i], p["conv_kernel"][:, i])
               for i in range(3))
    q, k = l2(q) * dim ** -0.5, l2(k)
    g = -jnp.exp(p["A_log"])[:, None, None] * split(jax.nn.softplus(
        low[0] @ p["f_up"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(x @ p["b_proj"]).T
    o = jax.vmap(delta_rule)(q, k, v, g, beta)
    o = rms_norm(o, p["out_norm"], config["rms_norm_eps"])
    return jnp.einsum(
        "hsv,hvd->sd", o * gate(split(low[1] @ p["g_up"])), p["out_proj"])


def kimi_delta_attention(x, p, config, gate=jax.nn.sigmoid):
    """x: (S, d). Kernels: in_proj_qkv (d, 3 H D), conv_kernel (taps, 3
    H D), f_down (d, r), f_up (r, H D), g_down, g_up alike, b_proj (d,
    H), A_log (H,), dt_bias (H D,), out_norm scale (D,), out_proj (H,
    D, d). ``gate``: the output gate's activation (a sigmoid; SiLU is
    the variant that has to fail). The heads run ``HEAD_GROUP`` at a
    time, each group under ``jax.checkpoint``, their outputs summed
    (``kda_heads``): memory, not mathematics."""
    linear = config["linear_attn_config"]
    heads, dim = linear["num_heads"], linear["head_dim"]
    size = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    groups = heads // size
    # the columns of a kernel by group of heads, the groups first
    columns = lambda t, parts=1: jnp.moveaxis(
        t.reshape(t.shape[:-1] + (parts, groups, size * dim)), -2, 0)
    by_group = {
        "in_proj_qkv": columns(p["in_proj_qkv"]["kernel"], 3),
        "conv_kernel": columns(p["conv_kernel"], 3),
        "f_up": columns(p["f_up"]["kernel"])[..., 0, :],
        "g_up": columns(p["g_up"]["kernel"])[..., 0, :],
        "dt_bias": p["dt_bias"].reshape(groups, size * dim),
        "A_log": p["A_log"].reshape(groups, size),
        "b_proj": jnp.moveaxis(
            p["b_proj"]["kernel"].reshape(-1, groups, size), 1, 0),
        "out_proj": p["out_proj"]["kernel"].reshape(
            (groups, size) + p["out_proj"]["kernel"].shape[1:]),
    }
    low = (x @ p["f_down"]["kernel"], x @ p["g_down"]["kernel"])
    run = jax.checkpoint(functools.partial(
        kda_heads, config=config, gate=gate))

    def add(total, group):
        group = dict(group, out_norm=p["out_norm"]["scale"])
        return total + run(x, low, group), None

    return jax.lax.scan(add, jnp.zeros_like(x), by_group)[0]


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


def head_attention(q, k, v):
    """One head: q, k (S, nope + rope), v (S, v) -> (S, v), causal,
    ``QUERY_BLOCK`` queries at a time."""
    seq, dim = q.shape
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq

    @jax.checkpoint
    def queries(args):
        q_b, start = args
        scores = (q_b @ k.T) / jnp.sqrt(jnp.float32(dim))
        allowed = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        scores = jnp.where(allowed, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    return jax.lax.map(
        queries,
        (q.reshape(seq // block, block, dim), jnp.arange(0, seq, block)),
    ).reshape(seq, v.shape[1])


def latent_attention(x, p, config, rotate=False):
    """x: (S, d). Kernels: q_proj (d, H, nope + rope), kv_down (d, rank
    + rope), kv_norm scale (rank,), kv_up (rank, H, nope + v), out_proj
    (H, v, d). ``rotate``: the rope lanes rotated at ``rope_theta``,
    which this model does NOT do."""
    eps = config["rms_norm_eps"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    q = jnp.einsum("sd,dhk->hsk", x, p["q_proj"]["kernel"])
    c = x @ p["kv_down"]["kernel"]
    c_kv = rms_norm(c[:, :rank], p["kv_norm"]["scale"], eps)
    kv = jnp.einsum("sr,rhk->hsk", c_kv, p["kv_up"]["kernel"])
    k_rope = c[:, rank:]
    if rotate:
        turn = functools.partial(rotary, base=float(config["rope_theta"]))
        k_rope = turn(k_rope)
        q = jnp.concatenate(
            [q[..., :nope], jax.vmap(turn)(q[..., nope:])], axis=-1)
    k = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(k_rope, (kv.shape[0],) + k_rope.shape),
    ], axis=-1)
    out = jax.lax.map(
        lambda args: head_attention(*args), (q, k, kv[..., nope:]))
    return jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def dense_mlp(x, w_gate, w_up, w_down):
    """``swiglu`` over ``ROW_BLOCK`` rows at a time, each block under a
    checkpoint of its own: three (S, intermediate) float32 arrays are
    3.4 GB at 32,768 tokens."""
    seq = x.shape[0]
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    return jax.lax.map(
        jax.checkpoint(lambda block: swiglu(block, w_gate, w_up, w_down)),
        x.reshape(seq // rows, rows, -1)).reshape(seq, -1)


def route(x, p, bias, config, forced=None):
    """(normalised scores (S, E), gates (S, k), the experts applied (S,
    k), the experts this router would choose (S, k)), over ALL experts.
    The last two are the same unless ``forced`` names the experts to
    apply; the gates are always this router's own scores of the applied
    experts."""
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + bias, config["num_experts_per_token"])
    applied = chosen if forced is None else forced
    gates = jnp.take_along_axis(scores, applied, axis=-1)
    if config["moe_renormalize"]:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    gates = gates * config["routed_scaling_factor"]
    return scores / scores.sum(axis=-1, keepdims=True), gates, applied, chosen


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


def shared_expert(x, p):
    return swiglu(x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                  p["shared_down"]["kernel"])


def sequence_balance(probs, chosen):
    """sum_e f_e P_e of one sequence, over all experts."""
    tokens, num_experts = probs.shape
    counts = (
        chosen[:, :, None] == jnp.arange(num_experts)[None, None, :]
    ).sum(axis=(0, 1))
    share = counts * (num_experts / (chosen.shape[1] * tokens))
    return jnp.sum(share * probs.mean(axis=0))


def expert_layer(x, p, bias, config, held, forced=None):
    """(this share's routed part of the layer's output, the layer's
    balance loss, the experts its router chose). ``held`` = (first,
    count): ``p``'s ``w_gate / w_up / w_down`` are those experts'
    kernels. The shared expert is every share's alike:
    ``shared_expert``. The loss counts this router's own choices,
    forced or not."""
    probs, gates, applied, chosen = route(x, p, bias, config, forced)
    y = held_experts_mlp(
        x, (p["w_gate"], p["w_up"], p["w_down"]), gates, applied, held[0])
    return y, sequence_balance(probs, chosen), chosen


def is_kda(i, config):
    """Whether layer ``i`` (0-indexed) is a Kimi Delta Attention one."""
    return i + 1 in config["linear_attn_config"]["kda_layers"]


def block(x, p, bias, forced, i, config, variant=None):
    """(x after the block, the layer's balance loss or 0, the experts
    its router chose (S, k) or None for a dense block). ``variant``:
    keyword arguments of the mixer for a variant that has to fail."""
    eps = config["rms_norm_eps"]
    mixer = kimi_delta_attention if is_kda(i, config) else latent_attention
    x = x + mixer(rms_norm(x, p["ln_attn"]["scale"], eps), p["attn"],
                  config, **(variant or {}))
    h = rms_norm(x, p["ln_mlp"]["scale"], eps)
    if "moe_mlp" not in p:
        return x + dense_mlp(
            h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
            p["mlp_down"]["kernel"]), 0.0, None
    y, balance, chosen = expert_layer(
        h, p["moe_mlp"], bias, config, config["held_experts"], forced)
    return x + y + shared_expert(h, p["moe_mlp"]), balance, chosen


def forward(params, biases, tokens, config, forced=None, last=None,
            variants=None):
    """tokens: (S,) int32 -> (logits (S, V), or of the ``last``
    positions; the summed balance loss; the experts every expert
    layer's router chose (L_moe, S, k)). ``biases``: {block name: (E,)}
    of the expert layers; ``forced`` (L_moe, S, k): the experts to
    apply instead; ``variants``: {"kda" / "full": mixer keywords}."""
    x = params["wte"]["embedding"][tokens]
    balance, chosen = 0.0, []
    for i in range(config["num_hidden_layers"]):
        name = "block_%d" % i
        variant = (variants or {}).get("kda" if is_kda(i, config) else "full")
        x, b, experts = jax.checkpoint(functools.partial(
            block, i=i, config=config, variant=variant))(
                x, params[name], biases.get(name),
                None if forced is None or "moe_mlp" not in params[name]
                else forced[len(chosen)])
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
                            last=None, variants=None):
    """The comparison's unit: the logits (of the last ``last``
    positions; every layer still mixes over the whole context), the
    loss (cross-entropy of predicting each compared position's
    successor, the final position has none; plus the weighted balance
    loss) and the experts each token's router chose in each expert
    layer, over ALL experts.

    Top-k is discontinuous, so the comparison has two parts
    (``check.py``): ``forced`` applies the experts another
    implementation chose, with this reference's own gates for them; the
    returned choices, and the balance loss's counts, are always this
    reference's own."""
    with jax.default_matmul_precision("highest"):
        to_f32 = lambda tree: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), tree)
        logits, balance, chosen = forward(
            to_f32(params), to_f32(biases), tokens, config, forced, last,
            variants)
        targets = tokens if last is None else tokens[-last:]
        loss = (
            next_token_loss(logits[:-1], targets[1:])
            + config["assumed"]["aux_loss_alpha"] * balance
        )
        return logits, loss, chosen
