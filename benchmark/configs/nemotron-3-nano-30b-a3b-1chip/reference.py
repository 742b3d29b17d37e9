"""Plain reference of the decoder the ``nemotron-3-nano-30b-a3b-1chip``
configuration trains (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type`` ``nemotron_h``; the Mamba-2 mixer of arXiv:2405.21060):
forward pass, loss and gradients in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. No kernel,
no chunked scan, no sort, no grouped matmul, no flax; it imports nothing
from ``elasticdl_tpu``. It reads the same parameter tree the system
trains (names below), so seeded weights feed both sides.

Token embedding; ``num_hidden_layers`` layers of ONE sublayer each, ``x
<- x + f(norm(x))`` with ``norm(x) = x rsqrt(mean(x^2) + eps) w`` (the
layer's ``ln``) and ``f`` by the layer's letter in
``hybrid_override_pattern``; a final norm and an untied head.

- ``M``, the Mamba-2 mixer (``NemotronHMamba2Mixer``), H =
  ``mamba_num_heads`` heads of P = ``mamba_head_dim`` lanes over a state
  of N = ``ssm_state_size``, ``n_groups`` groups: ``z | xBC | dt = u
  W_in`` (H P, H P + 2 groups N, H; no bias); ``xBC = silu(conv(xBC) +
  b)``, a causal depthwise convolution over ``conv_kernel`` tokens with
  zeros before the sequence's start, then ``x | B | C`` its three
  parts; ``dt = softplus(dt + dt_bias)``, ``a = -exp(A_log) dt``; per
  head, ONE TOKEN A STEP, the state ``S`` (P x N) zero at the start:
  ``S = exp(a_t) S + dt_t x_t B_t^T; y_t = S C_t + D x_t`` (head h
  reads group ``h // (H / groups)``'s B and C); ``y = rmsnorm(y
  silu(z)) w`` (the gate BEFORE the norm, the norm over a group's H P /
  groups lanes) and the output projection.
- ``*``, attention (``NemotronHAttention``): ``num_attention_heads``
  query heads over ``num_key_value_heads`` key / value heads of
  ``head_dim`` lanes, no bias, NOTHING rotated (``rotate`` is there for
  the variant that has to fail), ``o = causal softmax(q k^T /
  sqrt(head_dim)) v``, query head h reading key / value head ``h //
  (heads / kv heads)``, ``W_o``.
- ``E``, the expert layer (``NemotronHMOE``): ``s = sigmoid(h W_r)``
  over ALL ``published.n_routed_experts``; the ``num_experts_per_tok``
  with the largest ``s + bias`` (one group: ``n_group`` 1); gates ``s``
  of the chosen over their sum (+ 1e-20), times
  ``routed_scaling_factor``; of the chosen experts THOSE THIS CHIP HOLDS
  (``held_experts``: a first index and a count) each ``relu(h W_up)^2
  W_down``, nothing for the absent ones; plus the shared expert
  ``relu(h U_up)^2 U_down`` on every token. ``expert_layer(...,
  held=(0, all))`` with all the experts' kernels is the uncut layer: the
  test that adds the shares up calls it.

The loss is cross-entropy + ``aux_loss_alpha`` x the load-balancing
loss summed over the expert layers: ``E sum_e f_e P_e`` with ``f_e =
count_e / S`` and ``P_e`` the mean of the scores divided by their sum
over the experts.

Where the system departs from the source the reference follows the
system and the configuration says so (``departs``): the attention's
kernels are (d, heads, lanes).

Memory, not mathematics: each layer runs under ``jax.checkpoint``; the
per-token loop is a scan over blocks of ``SCAN_BLOCK`` tokens, each
under ``jax.checkpoint``, so the backward holds a state a block and a
block's own (8,192 states of 64 x 64 x 128 floats would be 17 GB); the
shared expert runs ``ROW_BLOCK`` rows at a time; attention is computed
a head and ``QUERY_BLOCK`` queries at a time; the held experts one at a
time over all the tokens, masked.
"""

import functools

import jax
import jax.numpy as jnp

SCAN_BLOCK = 128
QUERY_BLOCK = 2048
ROW_BLOCK = 2048
KINDS = {"M": "mamba", "E": "experts", "*": "full"}


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
                 groups=None):
    """u: (S, d). Kernels: in_proj (d, 2 H P + 2 G N + H), conv_kernel
    (taps, H P + 2 G N), conv_bias (H P + 2 G N,), A_log, dt_bias, D
    (H,), out_norm_scale (H P,), out_proj (H, P, d). The keywords are
    the variants that have to fail: the gate applied AFTER the norm
    (Gated DeltaNet's order), the norm over ``norm_lanes`` lanes (all H
    P) in place of a group's, B and C of group 0 read by ALL the heads
    (``groups=1``: one group's sharing)."""
    heads, dim = config["mamba_num_heads"], config["mamba_head_dim"]
    state, n_groups = config["ssm_state_size"], config["n_groups"]
    taps, eps = config["conv_kernel"], config["layer_norm_epsilon"]
    seq, inner = u.shape[0], heads * dim
    conv_dim = inner + 2 * n_groups * state
    zxbcdt = u @ p["in_proj"]["kernel"]
    z, xbc = zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = sum(p["conv_kernel"][j] * padded[j:j + seq] for j in range(taps))
    xbc = jax.nn.silu(conv + p["conv_bias"])
    x = xbc[:, :inner].reshape(seq, heads, dim)

    def own(t):
        """A head's own B or C: its group's."""
        t = t.reshape(seq, n_groups, state)
        if groups == 1:
            t = jnp.broadcast_to(t[:, :1], t.shape)
        return jnp.repeat(t, heads // n_groups, axis=1)

    b = own(xbc[:, inner:inner + n_groups * state])
    c = own(xbc[:, inner + n_groups * state:])
    dt = jax.nn.softplus(zxbcdt[:, inner + conv_dim:] + p["dt_bias"])
    a = -jnp.exp(p["A_log"]) * dt
    y = selective_scan(x, dt, a, b, c, p["D"]).reshape(seq, inner)
    lanes = norm_lanes or inner // n_groups
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


def head_attention(q, k, v):
    """One head: q, k, v (S, D) -> (S, D), causal softmax of ``q k^T /
    sqrt(D)``, ``QUERY_BLOCK`` queries at a time."""
    seq, dim = q.shape
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq

    @jax.checkpoint
    def queries(args):
        q_b, start = args
        scores = (q_b @ k.T) * dim ** -0.5
        allowed = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        scores = jnp.where(allowed, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    return jax.lax.map(
        queries,
        (q.reshape(seq // block, block, dim), jnp.arange(0, seq, block)),
    ).reshape(seq, dim)


def attention(u, p, config, rotate=False, group=None):
    """u: (S, d). Kernels: query (d, H, D), key and value (d, Hkv, D),
    out_proj (H, D, d). ``rotate``: q and k rotated at ``rope_theta``,
    which this model does NOT do; ``group``: query head h reads key /
    value head ``(h // group) mod Hkv`` in place of ``h // (H / Hkv)``.
    Both are the variants that have to fail."""
    heads, kv_heads = (
        config["num_attention_heads"], config["num_key_value_heads"])
    q = jnp.einsum("sd,dhk->hsk", u, p["query"]["kernel"])
    k = jnp.einsum("sd,dhk->hsk", u, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", u, p["value"]["kernel"])
    if rotate:
        turn = jax.vmap(functools.partial(
            rotary, base=float(config["rope_theta"])))
        q, k = turn(q), turn(k)
    # query head h reads key / value head h // (heads / kv heads)
    reads = (jnp.arange(heads) // (group or heads // kv_heads)) % kv_heads
    out = jax.lax.map(
        lambda args: head_attention(*args), (q, k[reads], v[reads]))
    return jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


def relu2_mlp(x, w_up, w_down, act="relu2", w_gate=None):
    """``relu(x W_up)^2 W_down``. ``act``: the variants that have to
    fail, a plain ReLU or a SwiGLU whose gate is ``w_gate``."""
    hidden = x @ w_up
    if act == "relu2":
        hidden = jnp.square(jax.nn.relu(hidden))
    elif act == "relu":
        hidden = jax.nn.relu(hidden)
    elif act == "swiglu":
        hidden = jax.nn.silu(x @ w_gate) * hidden
    else:
        raise ValueError("act=%r" % (act,))
    return hidden @ w_down


def route(x, p, bias, config, forced=None, renormalise=True, scale=None,
          use_bias=True):
    """(normalised scores (S, E), gates (S, k), the experts applied (S,
    k), the experts this router would choose (S, k)), over ALL experts.
    The last two are the same unless ``forced`` names the experts to
    apply; the gates are always this router's own scores of the applied
    experts. The keywords are the variants that have to fail: gates not
    renormalised, another scale than ``routed_scaling_factor``, the
    selection by ``s`` without the bias."""
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(
        scores + bias if use_bias else scores, config["num_experts_per_tok"])
    applied = chosen if forced is None else forced
    gates = jnp.take_along_axis(scores, applied, axis=-1)
    if renormalise:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    gates = gates * (
        config["routed_scaling_factor"] if scale is None else scale)
    return (scores / (scores.sum(axis=-1, keepdims=True) + 1e-20), gates,
            applied, chosen)


def held_experts_mlp(x, w_up, w_down, gates, experts, first, act="relu2"):
    """sum over the choices j whose expert is one of ``w_up``'s
    (experts ``first`` on): gates[t, j] expert(x[t]); by a loop over
    those experts and a mask."""
    ids = first + jnp.arange(w_up.shape[0])
    weight = (
        gates[:, :, None] * (experts[:, :, None] == ids[None, None, :])
    ).sum(axis=1)

    def term(total, args):
        w_u, w_d, column = args
        # (a SwiGLU expert has no gate kernel here: the variant gates
        # by its own up-projection)
        return total + column[:, None] * relu2_mlp(
            x, w_u, w_d, act, w_gate=w_u), None

    total, _ = jax.lax.scan(
        jax.checkpoint(term), jnp.zeros_like(x), (w_up, w_down, weight.T))
    return total


def shared_expert(x, p, act="relu2"):
    """The shared expert over ``ROW_BLOCK`` rows at a time, each block
    under a checkpoint of its own."""
    seq = x.shape[0]
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    w_up, w_down = p["shared_up"]["kernel"], p["shared_down"]["kernel"]
    return jax.lax.map(
        jax.checkpoint(lambda block: relu2_mlp(
            block, w_up, w_down, act, w_gate=w_up)),
        x.reshape(seq // rows, rows, -1)).reshape(seq, -1)


def load_balancing(probs, chosen):
    """E sum_e f_e P_e over all experts: f_e the share of the tokens
    that chose e among their k, P_e the mean normalised score."""
    tokens, num_experts = probs.shape
    counts = (
        chosen[:, :, None] == jnp.arange(num_experts)[None, None, :]
    ).sum(axis=(0, 1))
    return num_experts * jnp.sum((counts / tokens) * probs.mean(axis=0))


def expert_layer(x, p, bias, config, held, forced=None, act="relu2",
                 **routing):
    """(this share's routed part of the layer's output, the layer's
    balance loss, the experts its router chose). ``held`` = (first,
    count): ``p``'s ``w_up / w_down`` are those experts' kernels. The
    shared expert is every share's alike: ``shared_expert``. The loss
    counts this router's own choices, forced or not. ``act`` and
    ``routing`` (``route``'s keywords): the variants that have to
    fail."""
    probs, gates, applied, chosen = route(
        x, p, bias, config, forced, **routing)
    y = held_experts_mlp(
        x, p["w_up"], p["w_down"], gates, applied, held[0], act)
    return y, load_balancing(probs, chosen), chosen


def kind_of(i, config):
    """Layer ``i``'s kind (0-indexed): ``mamba``, ``experts`` or
    ``full``, by its letter in ``hybrid_override_pattern``."""
    return KINDS[config["hybrid_override_pattern"][i]]


def layer(x, p, bias, forced, i, config, variant=None):
    """(x after layer ``i``, the layer's balance loss or 0, the experts
    its router chose (S, k) or None). ``variant``: keyword arguments of
    the layer's function for a variant that has to fail; an expert
    layer's may also hold ``shared`` (how many times the shared expert
    is added: 0 and 2 have to fail)."""
    variant = dict(variant or {})
    h = rms_norm(x, p["ln"]["scale"], config["layer_norm_epsilon"])
    kind = kind_of(i, config)
    if kind == "mamba":
        return x + mamba2_mixer(h, p["attn"], config, **variant), 0.0, None
    if kind == "full":
        return x + attention(h, p["attn"], config, **variant), 0.0, None
    shared = variant.pop("shared", 1)
    act = variant.get("act", "relu2")
    y, balance, chosen = expert_layer(
        h, p["moe_mlp"], bias, config, config["held_experts"], forced,
        **variant)
    return (x + y + shared * shared_expert(h, p["moe_mlp"], act),
            balance, chosen)


def forward(params, biases, tokens, config, forced=None, last=None,
            variants=None):
    """tokens: (S,) int32 -> (logits (S, V), or of the ``last``
    positions; the summed balance loss; the experts every expert
    layer's router chose (L_moe, S, k)). ``biases``: {block name: (E,)}
    of the expert layers; ``forced`` (L_moe, S, k): the experts to
    apply instead; ``variants``: {"mamba" / "full" / "experts": the
    layer's keywords}."""
    x = params["wte"]["embedding"][tokens]
    balance, chosen = 0.0, []
    for i in range(config["num_hidden_layers"]):
        name = "block_%d" % i
        experts_layer = kind_of(i, config) == "experts"
        x, b, experts = jax.checkpoint(functools.partial(
            layer, i=i, config=config,
            variant=(variants or {}).get(kind_of(i, config))))(
                x, params[name], biases.get(name),
                None if forced is None or not experts_layer
                else forced[len(chosen)])
        balance = balance + b
        if experts is not None:
            chosen.append(experts)
    if last is not None:
        x = x[-last:]
    x = rms_norm(x, params["ln_f"]["scale"], config["layer_norm_epsilon"])
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
