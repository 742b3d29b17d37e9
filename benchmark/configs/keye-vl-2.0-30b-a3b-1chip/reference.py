"""Plain reference of the training step the ``keye-vl-2.0-30b-a3b-1chip``
configuration runs (Kwai-Keye/Keye-VL-2.0-30B-A3B's language model,
``model_type`` ``KeyeVL2``, on text): forward pass, loss (all three
terms) and gradients in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no tiles, no
bisection, no mask in memory, no sort of pairs, no grouped matmul,
no flax; it imports nothing from ``elasticdl_tpu``. It reads the same
parameter tree the system trains (names below), so seeded weights feed
both sides.

The layer, written down for its normed input ``x`` (S positions; J
indexer heads of Di; ``xd = stop_gradient(x)``):

    indexer:  qI = RoPE(xd W_qI)  (S, J, Di);  kI = RoPE(LayerNorm(xd W_kI))
              (S, Di), one key a position;  w = (xd W_w) J^-1/2 Di^-1/2
              I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]),   s <= t
    select:   S_t = the min(topk, t + 1) positions s <= t of the largest
              I[t, s], ties to the lower position (jax.lax.top_k)
    attend:   A_h[t, s] = softmax_{s in S_t}(q_h[t] . k_g(h)[s] / sqrt(D)),
              q = RoPE(RMSNorm_head(x W_q)), k likewise, head h reads kv
              head h // group;  o_h[t] = sum_{s in S_t} A_h[t, s] v_g(h)[s]
    term:     p[t, s] = stop_gradient(mean_h A_h[t, s]);
              L_I = mean_t KL(p[t, .] || softmax_{s in S_t} I[t, s])
    layer:    h = u + W_o o(RMSNorm(u));  y = h + sum_{e in top-k of
              softmax(h' W_r), gates renormalised, e HELD here} g_e
              W_down,e(silu(h' W_gate,e) * h' W_up,e),  h' = RMSNorm(h)
    loss:     CE(lm_head(RMSNorm(y_i)), x_(i+1)) over the compared
              positions + router_aux_loss_coef x balance
              + indexer_loss_coef x sum_layers L_I

``RMSNorm(x) = x rsqrt(mean(x^2) + eps) w``; ``LayerNorm`` has scale and
bias. The balance loss is ``E sum_e f_e P_e`` over ALL experts and all
positions, summed over the layers. ``expert_layer(..., held=(0, all))``
is the uncut layer: the test that adds the shares up calls it.
``kept_set`` is the selection, one function.

Memory, not mathematics: each block runs under ``jax.checkpoint``;
scores, ``top_k`` and the dense masked softmax of all heads are formed
``QUERY_BLOCK`` queries at a time, and the experts one at a time (every
held expert computes every position and a 0 / gate mask keeps what the
router chose). A kept set travels 8 keys a byte (``pack`` / ``unpack``).
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128
# the stated block of queries whose scores and kept sets are compared
TAIL_QUERIES = 512


def norm(x, w, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def rotary(x, positions, base):
    """x: (S, D). Pairs (i, i + D/2) rotate by position * base^(-i /
    (D/2))."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def pack(keep):
    """(..., S) bool -> (..., S / 8) uint8, the first key the high
    bit."""
    return jnp.packbits(keep, axis=-1)


def unpack(bits):
    return jnp.unpackbits(bits, axis=-1).astype(bool)


def indexer_operands(x, p, positions, config):
    """(qI (J, S, Di), kI (S, Di), w (S, J)) from the DETACHED input."""
    sa, base = config["sa_config"], float(config["rope_theta"])
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    x = jax.lax.stop_gradient(x)
    qi = jnp.einsum("sd,djk->jsk", x, p["indexer_q"]["kernel"])
    ki = layer_norm(
        x @ p["indexer_k"]["kernel"], p["indexer_k_norm"]["scale"],
        p["indexer_k_norm"]["bias"], config["rms_norm_eps"])
    qi = jax.vmap(lambda t: rotary(t, positions, base))(qi)
    ki = rotary(ki, positions, base)
    w = (x @ p["indexer_w"]["kernel"]) * (heads ** -0.5 * dim ** -0.5)
    return qi, ki, w


def scores_of(qi, ki, w, q_pos):
    """I (R, S) of the queries at ``q_pos``: qi (J, R, Di) theirs;
    -inf where a key lies after its query."""
    r = jnp.einsum("jtd,sd->jts", qi, ki)
    scores = jnp.einsum("jts,tj->ts", jax.nn.relu(r), w)
    seen = q_pos[:, None] >= jnp.arange(ki.shape[0])[None, :]
    return jnp.where(seen, scores, -jnp.inf)


def kept_set(scores, topk):
    """The selection: (R, S) bool, the ``min(topk, t + 1)`` largest of a
    row's causal scores, ties to the lower position."""
    rows, seq = scores.shape
    _, index = jax.lax.top_k(scores, min(topk, seq))
    keep = jnp.zeros((rows, seq), bool).at[
        jnp.arange(rows)[:, None], index].set(True)
    return keep & (scores > -jnp.inf)


def attention(x, p, positions, config, forced=None):
    """x: (S, d) -> (the mixer's output (S, d), L_I, this reference's
    own kept sets in bits (S, S / 8), I of the last ``TAIL_QUERIES``
    queries). Kernels: query (d, H, D), key, value (d, Hkv, D), q_norm,
    k_norm scale (D,), out_proj (H, D, d), indexer_q (d, J, Di),
    indexer_k (d, Di), indexer_k_norm scale / bias (Di,), indexer_w (d,
    J). ``forced`` (S, S / 8): the kept sets to attend over instead."""
    eps, base = config["rms_norm_eps"], float(config["rope_theta"])
    topk = config["sa_config"]["topk"]
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    seq = x.shape[0]
    q = jnp.einsum("sd,dhk->hsk", x, p["query"]["kernel"])
    k = jnp.einsum("sd,dhk->hsk", x, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    q = norm(q, p["q_norm"]["scale"], eps)
    k = norm(k, p["k_norm"]["scale"], eps)
    turn = jax.vmap(lambda t: rotary(t, positions, base))
    q, k = turn(q), turn(k)
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    qi, ki, w = indexer_operands(x, p, positions, config)
    rows = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    blocks = seq // rows
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    @jax.checkpoint
    def queries(args):
        q_b, qi_b, w_b, start, forced_b = args
        scores = scores_of(qi_b, ki, w_b, start + jnp.arange(rows))
        own = jax.lax.stop_gradient(kept_set(scores, topk))
        keep = own if forced_b is None else unpack(forced_b)
        s = jnp.einsum("htd,hsd->hts", q_b, k) * scale
        probs = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("hts,hsd->htd", probs, v)
        target = jax.lax.stop_gradient(probs.mean(axis=0))
        log_q = jax.nn.log_softmax(
            jnp.where(keep, scores, -jnp.inf), axis=-1)
        live = keep & (target > 0)
        safe = jnp.where(live, target, 1.0)
        kl = jnp.where(live, safe * (jnp.log(safe) - log_q), 0.0).sum(-1)
        return out, kl, pack(own)

    split = lambda t, axis: jnp.moveaxis(
        t.reshape(t.shape[:axis] + (blocks, rows) + t.shape[axis + 1:]),
        axis, 0)
    out, kl, own = jax.lax.map(queries, (
        split(q, 1), split(qi, 1), split(w, 0), jnp.arange(0, seq, rows),
        None if forced is None else split(forced, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
    tail = min(TAIL_QUERIES, seq)
    tail_scores = scores_of(
        qi[:, seq - tail:], ki, w[seq - tail:], jnp.arange(seq - tail, seq))
    return (jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"]),
            kl.reshape(seq).mean(), own.reshape(seq, -1),
            jax.lax.stop_gradient(tail_scores))


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


def block(x, p, forced_experts, forced_keys, positions, config):
    eps = config["rms_norm_eps"]
    mixed, kl, kept, tail_scores = attention(
        norm(x, p["ln_attn"]["scale"], eps), p["attn"], positions, config,
        forced_keys)
    x = x + mixed
    y, balance, chosen = expert_layer(
        norm(x, p["ln_mlp"]["scale"], eps), p["moe_mlp"], config,
        config["held_experts"], forced_experts)
    return x + y, (balance, kl, chosen, kept, tail_scores)


def forward(params, tokens, config, forced_experts=None, forced_keys=None,
            last=None):
    """tokens: (S,) int32 -> (logits (S, V), or of the ``last``
    positions; the summed balance loss; the summed L_I; the experts
    every layer's router chose (layers, S, k); every layer's kept sets
    in bits (layers, S, S / 8); I of the last queries (layers, tail,
    S)). ``forced_experts`` (layers, S, k) and ``forced_keys`` (layers,
    S, S / 8): what to apply and attend over instead."""
    x = params["wte"]["embedding"][tokens]
    positions = jnp.arange(tokens.shape[0])
    balance = indexer = 0.0
    chosen, kept, tails = [], [], []
    pick = lambda forced, i: None if forced is None else forced[i]
    for i in range(config["num_hidden_layers"]):
        x, (b, kl, experts, keys, tail) = jax.checkpoint(
            functools.partial(block, positions=positions, config=config)
        )(x, params["block_%d" % i], pick(forced_experts, i),
          pick(forced_keys, i))
        balance, indexer = balance + b, indexer + kl
        chosen.append(experts)
        kept.append(keys)
        tails.append(tail)
    if last is not None:
        x = x[-last:]
    x = norm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return (x @ params["lm_head"]["kernel"], balance, indexer,
            jnp.stack(chosen), jnp.stack(kept), jnp.stack(tails))


def cross_entropy(logits, targets):
    """Mean over positions of -log softmax(logits)[target]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


def logits_losses_and_choices(params, tokens, config, forced_experts=None,
                              forced_keys=None, last=None):
    """The comparison's unit: ``(logits, loss, indexer_loss, experts,
    kept sets in bits, scores of the last queries)``: the logits (of the
    last ``last`` positions; every layer still scores, selects, attends
    and routes over the whole context), the loss (the cross-entropy of
    predicting each compared position's successor, the final position
    has none, plus the weighted balance loss plus ``indexer_loss_coef``
    x the indexer's term) and that term alone, unweighted.

    Top-k is discontinuous, twice over, so the comparison has two parts
    (``check.py``): ``forced_experts`` and ``forced_keys`` apply the
    experts and attend over the keys another implementation chose, with
    this reference's own gates, probabilities and scores for them; the
    returned choices, kept sets and scores, and the balance loss's
    counts, are always this reference's own."""
    with jax.default_matmul_precision("highest"):
        to_f32 = lambda tree: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), tree)
        logits, balance, indexer, chosen, kept, tails = forward(
            to_f32(params), tokens, config, forced_experts, forced_keys,
            last)
        targets = tokens if last is None else tokens[-last:]
        assumed = config["assumed"]
        loss = (
            cross_entropy(logits[:-1], targets[1:])
            + assumed["router_aux_loss_coef"] * balance
            + assumed["indexer_loss_coef"] * indexer
        )
        return logits, loss, indexer, chosen, kept, tails
