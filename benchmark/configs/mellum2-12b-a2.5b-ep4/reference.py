"""Plain reference of the decoder the ``mellum2-12b-a2.5b-ep4``
configuration trains (JetBrains/Mellum2-12B-A2.5B-Instruct,
``model_type`` ``mellum``): forward pass, loss and gradients in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sort, no
grouped matmul, no mesh, no exchange, no flax; it imports nothing from
``elasticdl_tpu``. All 64 experts of a layer are here, on whatever
device runs this: where the system spreads them over four chips and
carries rows between them, the reference computes every expert for
every token and keeps what the router chose. It reads the same
parameter tree the system trains (names below), so seeded weights feed
both sides.

The model, written down (``RMSNorm(x) = x rsqrt(mean(x^2) + eps) w``,
eps 1e-6, no bias anywhere). Layer ``l`` is of kind ``k`` =
``layer_types[l]``, sliding or full, three to one:

    h = x + Attn_k(RMSNorm(x))
    y = h + MoE(RMSNorm(h))

    Attn_k:  q = n W_q (S, 32, 128); k, v = n W_k, n W_v (S, 4, 128);
             query head j reads kv head j // 8
      q, k = rot_k(q), rot_k(k): the whole head rotates (by halves:
             lane i with lane i + 64), pair i by pos x f_i
        sliding: f_i = theta^(-2 i / 128), theta 500,000
        full:    the YaRN blend of f_i and f_i / 16 over the original
                 context of 8192 (beta_fast 32, beta_slow 1:
                 ``yarn_frequencies``); cos and sin times
                 ``attention_factor`` 1.27726
      s_ij = q_i . k_j / sqrt(128)   for j <= i                (full)
                                     for j <= i and i - j < W  (sliding;
             W = ``sliding_window`` = 1024: a query sees itself and the
             1023 keys before it)
      o = softmax_j(s) v ;  Attn = o W_o

    MoE:     p = softmax(n W_r) over the 64 experts; the 8 largest;
             g_e = p_e / sum over the 8 chosen;
             MoE = sum_e g_e W_down,e (silu(W_gate,e n) * W_up,e n)
             at width 896

    logits = RMSNorm(x_L) W_head     (an untied head)
    loss   = mean over the sequences of mean_i CE(logits_i, t_(i+1))
             + router_aux_loss_coef x sum over the layers of
               E sum_e f_e P_e
             (f_e: the share of ALL the batch's tokens that chose
             expert e among their 8, P_e: the mean of p_e over them)

Where the system departs from a published code the reference follows
the system and the configuration says so (``departs``): rotary by
halves.

Memory, not mathematics: each block runs under ``jax.checkpoint``, the
query heads one at a time (``lax.map``), ``QUERY_BLOCK`` queries at a
time against a dense mask over the keys (a sliding layer's block reads
only the ``QUERY_BLOCK + W`` keys its rows can see, the mask still
computed position by position), the experts one at a time (every
expert computes every position and a 0 / gate mask keeps what the
router chose), the sequences of a batch one after another
(``jax.vmap`` over what is written for one).
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
    """(the pairs' frequencies, what multiplies cos and sin) of one
    kind's ``rope_parameters``; the whole head rotates."""
    if rope["rope_type"] == "yarn":
        return yarn_frequencies(head_dim, rope), rope["attention_factor"]
    pairs = jnp.arange(head_dim // 2, dtype=jnp.float32)
    return float(rope["rope_theta"]) ** (-2.0 * pairs / head_dim), 1.0


def rotary(x, table):
    """x: (S, D). Pairs (i, i + D / 2) rotate by pos * freqs[i], cos
    and sin times ``amplitude``."""
    freqs, amplitude = table
    half = x.shape[1] // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


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


def attention(x, p, kind, config):
    """x: (S, d). Kernels: query (d, 32, D), key, value (d, 4, D),
    out_proj (32, D, d)."""
    dim = config["head_dim"]
    table = rotary_table(config["rope_parameters"][kind], dim)
    window = config["sliding_window"] if kind == "sliding_attention" else None
    k = jnp.einsum("sd,dhk->hsk", x, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    k = jax.vmap(lambda t: rotary(t, table))(k)
    heads = p["query"]["kernel"].shape[1]
    group = heads // k.shape[0]

    @jax.checkpoint
    def head(args):
        w_q, index = args
        q = rotary(x @ w_q, table)
        return head_attention(
            q, k[index // group], v[index // group], window=window)

    out = jax.lax.map(
        head, (p["query"]["kernel"].transpose(1, 0, 2), jnp.arange(heads)))
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


def experts_mlp(x, weights, gates, experts):
    """sum over the choices j: gates[t, j] expert_(experts[t, j])(x[t]);
    by a loop over ALL the experts and a mask."""
    w_gate, w_up, w_down = weights
    ids = jnp.arange(w_gate.shape[0])
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
    """E sum_e f_e P_e over all experts and all the tokens given:
    probs (..., E), chosen (..., k) over the same leading axes."""
    num_experts = probs.shape[-1]
    probs = probs.reshape(-1, num_experts)
    chosen = chosen.reshape(probs.shape[0], -1)
    counts = (
        chosen[:, :, None] == jnp.arange(num_experts)[None, None, :]
    ).sum(axis=(0, 1))
    return num_experts * jnp.sum(
        counts / probs.shape[0] * probs.mean(axis=0))


def block(x, p, forced, kind, config):
    """(x after the block, the router's probabilities (S, E), the
    experts it chose (S, k))."""
    eps = config["rms_norm_eps"]
    x = x + attention(
        rms_norm(x, p["ln_attn"]["scale"], eps), p["attn"], kind, config)
    n = rms_norm(x, p["ln_mlp"]["scale"], eps)
    moe = p["moe_mlp"]
    probs, gates, applied, chosen = route(n, moe, config, forced)
    y = experts_mlp(
        n, (moe["w_gate"], moe["w_up"], moe["w_down"]), gates, applied)
    return x + y, probs, chosen


def forward(params, tokens, config, forced=None, last=None):
    """tokens: (S,) int32, ONE sequence -> (logits (S, V), or of the
    ``last`` positions; every layer's router's probabilities (layers,
    S, E); the experts it chose (layers, S, k)). ``forced`` (layers, S,
    k): the experts to apply instead."""
    x = params["wte"]["embedding"][tokens]
    probs, chosen = [], []
    for i in range(config["num_hidden_layers"]):
        kind = config["layer_types"][i]
        if kind not in KINDS or config["mlp_layer_types"][i] != "sparse":
            raise ValueError(
                "layer %d: %r, %r" % (i, kind, config["mlp_layer_types"][i]))
        x, p, experts = jax.checkpoint(functools.partial(
            block, kind=kind, config=config,
        ))(x, params["block_%d" % i], None if forced is None else forced[i])
        probs.append(p)
        chosen.append(experts)
    if last is not None:
        x = x[-last:]
    x = rms_norm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return (x @ params["lm_head"]["kernel"], jnp.stack(probs),
            jnp.stack(chosen))


def cross_entropy(logits, targets):
    """Mean over positions of -log softmax(logits)[target]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


def logits_loss_and_choices(params, tokens, config, forced=None, last=None):
    """The comparison's unit, for a BATCH ``tokens`` (B, S): the logits
    (B, S or ``last``, V) (every layer still attends and routes over the
    whole context), the loss (the mean over the sequences of the
    cross-entropy of predicting each compared position's successor, the
    final position has none; plus the weighted balance loss over all B
    x S tokens, summed over the layers) and the experts each token's
    router chose in each layer (layers, B, S, k).

    Top-k is discontinuous, so the comparison has two parts
    (``check.py``): ``forced`` (layers, B, S, k) applies the experts
    another implementation chose, with this reference's own gates for
    them; the returned choices, and the balance loss's counts, are
    always this reference's own."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        one = functools.partial(forward, config=config, last=last)
        if forced is None:
            logits, probs, chosen = jax.vmap(
                lambda t: one(params, t))(tokens)
        else:
            logits, probs, chosen = jax.vmap(
                lambda t, f: one(params, t, forced=f), in_axes=(0, 1))(
                    tokens, forced)
        # (B, layers, ...) -> (layers, B, ...)
        probs, chosen = probs.swapaxes(0, 1), chosen.swapaxes(0, 1)
        targets = tokens if last is None else tokens[:, -last:]
        ce = jax.vmap(cross_entropy)(logits[:, :-1], targets[:, 1:]).mean()
        balance = sum(
            balance_loss(probs[i], chosen[i])
            for i in range(probs.shape[0]))
        loss = ce + config["assumed"]["router_aux_loss_coef"] * balance
        return logits, loss, chosen
