"""Plain reference of the decoder the ``laguna-xs.2-1chip``
configuration trains (poolside/Laguna-XS.2, ``model_type`` ``laguna``):
forward pass, loss and gradients in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. No kernel,
no sort, no grouped matmul, no flax; it imports nothing from
``elasticdl_tpu``. It reads the same parameter tree the system trains
(names below), so seeded weights feed both sides.

The model, written down (``RMSNorm(x) = x rsqrt(mean(x^2) + eps) w``).
Layer ``l`` is of kind ``t(l)`` = ``layer_types[l]``, full or sliding,
with ``H_t`` = ``num_attention_heads_per_layer[l]`` query heads (48 /
64), 8 kv heads of d = 128, query head h reading kv head h // (H_t / 8):

    h        = RMSNorm(x)
    [q ; g]  = h W_qg          (S, H_t, 2 d): a query and its gate a head
    k, v     = h W_k, h W_v    (S, 8, d)
    q, k     = rot_t(q), rot_t(k), by ``rope_parameters[t]``:
      full:    the first d x partial_rotary_factor = 64 lanes rotate (by
               halves: lane i with lane i + 32), 64 pass through; pair
               i turns by pos x f_i, f the YaRN blend over those 64
               lanes at theta 500,000 (factor 64 over 4096, beta_fast
               64, beta_slow 1: ``yarn_frequencies``); cos and sin
               times ``attention_factor``; the lanes that pass through
               are not scaled
      sliding: all 128 lanes rotate, theta 10,000, no scaling
    s_ij     = q_i . k_j / sqrt(d)   for j <= i                 (full)
                                     for j <= i and i - j < W   (sliding;
               W = ``sliding_window`` = 512: a query sees itself and
               the 511 keys before it)
    o        = softmax_j(s) v ;  a = o * sigmoid(g) ;  x = x + a W_o
    MLP, ``dense``:   x = x + (silu(h' W_g) * (h' W_u)) W_d    h' = RMSNorm(x)
    MLP, ``sparse``:  p = sigmoid(h' W_r) over ALL experts; the k with
               the largest p + b (the balancing bias chooses, p weighs);
               w_e = moe_routed_scaling_factor p_e / sum_chosen p;
               x = x + sum over the chosen experts HELD here of
               w_e E_e(h') + E_shared(h'); the absent experts add nothing
    logits = RMSNorm(x_L) W_head
    loss   = mean_i CE(logits_i, t_(i+1)) over the held slice of the
             vocabulary (no balance loss: the config names none)

Where the system departs from a published code the reference follows
the system and the configuration says so (``departs``): rotary by
halves. ``expert_layer(..., held=(0, all))`` with all the experts'
kernels is the uncut layer: the test that adds the shares up calls it.

Memory, not mathematics: each block runs under ``jax.checkpoint``, the
query heads one at a time (``lax.map``: a head's query and gate from
its own columns of ``W_qg``, so that the (S, H_t, 2 d) projection never
stands whole), ``QUERY_BLOCK`` queries at a time
against a dense mask over the keys (a sliding layer's block reads only
the ``QUERY_BLOCK + W`` keys its rows can see, the mask still computed
position by position), the held experts one at a time (every held
expert computes every position and a 0 / gate mask keeps what the
router chose).
"""

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048
KINDS = ("full_attention", "sliding_attention")


def rms_norm(x, scale, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def yarn_frequencies(dim, rope):
    """The dim / 2 rotary frequencies of a ``yarn`` table over ``dim``
    lanes: pair i rotates by ``theta^(-2i / dim)`` where i is below
    ``low``, by that over ``factor`` where it is above ``high``, by the
    linear blend between: ``low`` / ``high`` the floor / ceiling of the
    dimension that makes ``beta_fast`` / ``beta_slow`` turns over the
    original context."""
    base = float(rope["rope_theta"])

    def turns(rotations):
        return dim * math.log(
            rope["original_max_position_embeddings"]
            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns(rope["beta_fast"])), 0)
    high = min(math.ceil(turns(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pairs = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = base ** (-2.0 * pairs / dim)
    ramp = jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / rope["factor"] * ramp


def rotary_table(rope, head_dim):
    """(lanes that rotate, their frequencies, what multiplies cos and
    sin) of one kind's ``rope_parameters``."""
    lanes = int(head_dim * rope["partial_rotary_factor"])
    if rope["rope_type"] == "yarn":
        return lanes, yarn_frequencies(lanes, rope), rope["attention_factor"]
    pairs = jnp.arange(lanes // 2, dtype=jnp.float32)
    return lanes, float(rope["rope_theta"]) ** (-2.0 * pairs / lanes), 1.0


def rotary(x, table):
    """x: (S, D). Of the first ``lanes`` lanes, pairs (i, i + lanes/2)
    rotate by pos * freqs[i], cos and sin times ``amplitude``; the
    other lanes pass through."""
    lanes, freqs, amplitude = table
    half = lanes // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    a, b = x[:, :half], x[:, half:lanes]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[:, lanes:]], axis=-1)


def head_attention(q, k, v, window=None):
    """One head: q (S, D) over k, v (S, D), ``QUERY_BLOCK`` queries at
    a time. ``window`` None: query i sees the keys j <= i. Else: j <= i
    and i - j < window, and a block of queries is given only the
    ``block + window`` keys that end at its last row (zeros stand
    before position 0 and the mask drops them)."""
    seq, dim = q.shape
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    span = seq if window is None else min(seq, block + window)
    if window is not None:
        pad = ((span - block, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    @jax.checkpoint
    def queries(args):
        q_b, start = args
        q_pos = (start + jnp.arange(block))[:, None]
        if window is None:
            keys, values, k_pos = k, v, jnp.arange(seq)[None, :]
            allowed = k_pos <= q_pos
        else:
            # rows start .. start + block - 1 of the padded arrays are
            # positions start - (span - block) .. start + block - 1
            keys = jax.lax.dynamic_slice_in_dim(k, start, span)
            values = jax.lax.dynamic_slice_in_dim(v, start, span)
            k_pos = (start - (span - block) + jnp.arange(span))[None, :]
            allowed = (k_pos <= q_pos) & (q_pos - k_pos < window) & (
                k_pos >= 0)
        scores = (q_b @ keys.T) / jnp.sqrt(jnp.float32(dim))
        scores = jnp.where(allowed, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ values

    return jax.lax.map(
        queries,
        (q.reshape(seq // block, block, dim), jnp.arange(0, seq, block)),
    ).reshape(seq, v.shape[1])


def gated_attention(x, p, kind, heads, config):
    """x: (S, d). Kernels: query (d, H_t, 2 D: a query and a gate a
    head), key, value (d, 8, D), out_proj (H_t, D, d); a tree with
    another count of heads than the config gives the layer is not this
    model's."""
    dim = config["head_dim"]
    shape = (x.shape[1], heads, 2 * dim)
    if p["query"]["kernel"].shape != shape:
        raise ValueError(
            "%s layer: the config gives W_qg %r, the tree has %r"
            % (kind, shape, p["query"]["kernel"].shape))
    table = rotary_table(config["rope_parameters"][kind], dim)
    window = config["sliding_window"] if kind == "sliding_attention" else None
    k = jnp.einsum("sd,dhk->hsk", x, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    k = jax.vmap(lambda t: rotary(t, table))(k)
    group = heads // k.shape[0]

    @jax.checkpoint
    def head(args):
        """One query head, from its own columns of W_qg: its gated
        output (S, D)."""
        w_qg, index = args
        qg = x @ w_qg
        q, gate = rotary(qg[:, :dim], table), qg[:, dim:]
        out = head_attention(
            q, k[index // group], v[index // group], window=window)
        return out * jax.nn.sigmoid(gate)

    out = jax.lax.map(
        head, (p["query"]["kernel"].transpose(1, 0, 2), jnp.arange(heads)))
    return jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


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
    gates = gates / gates.sum(axis=-1, keepdims=True)
    return gates * config["moe_routed_scaling_factor"], applied, chosen


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


def expert_layer(x, p, bias, config, held, forced=None):
    """(this share's routed part of the layer's output, the experts
    its router chose). ``held`` = (first, count): ``p``'s ``w_gate /
    w_up / w_down`` are those experts' kernels. The shared expert is
    every share's alike: ``shared_expert``."""
    gates, applied, chosen = route(x, p, bias, config, forced)
    y = held_experts_mlp(
        x, (p["w_gate"], p["w_up"], p["w_down"]), gates, applied, held[0])
    return y, chosen


def block(x, p, bias, forced, kind, heads, config):
    """(x after the block, the experts its router chose (S, k) or None
    for a dense block)."""
    eps = config["rms_norm_eps"]
    x = x + gated_attention(
        rms_norm(x, p["ln_attn"]["scale"], eps), p["attn"], kind, heads,
        config)
    h = rms_norm(x, p["ln_mlp"]["scale"], eps)
    if "moe_mlp" not in p:
        return x + swiglu(h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                          p["mlp_down"]["kernel"]), None
    y, chosen = expert_layer(
        h, p["moe_mlp"], bias, config, config["held_experts"], forced)
    return x + y + shared_expert(h, p["moe_mlp"]), chosen


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
        pick = None if forced is None else forced[len(chosen)]
        x, experts = jax.checkpoint(functools.partial(
            block, kind=kind, config=config,
            heads=config["num_attention_heads_per_layer"][i],
        ))(x, params[name], biases.get(name), pick)
        if experts is not None:
            chosen.append(experts)
    if last is not None:
        x = x[-last:]
    x = rms_norm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return x @ params["lm_head"]["kernel"], jnp.stack(chosen)


def cross_entropy(logits, targets):
    """Mean over positions of -log softmax(logits)[target]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


def logits_loss_and_choices(params, biases, tokens, config, forced=None,
                            last=None):
    """The comparison's unit: the logits (of the last ``last``
    positions; every layer still attends and routes over the whole
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
