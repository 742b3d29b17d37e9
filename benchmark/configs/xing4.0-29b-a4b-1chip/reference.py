"""Plain reference of the decoder the ``xing4.0-29b-a4b-1chip``
configuration trains (XingChen-AGI/Xing4.0-29B-A4B, ``model_type``
``xing4_0``): forward pass, both losses and gradients in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sort, no
grouped matmul, no flax; it imports nothing from ``elasticdl_tpu``. It
reads the same parameter tree the system trains (names below), so
seeded weights feed both sides.

The model, written down (n = ``hc_mult`` streams of C = ``hidden_size``
lanes a token; ``RMSNorm(x) = x rsqrt(mean(x^2) + eps) w``):

    X_0 = Emb(t) copied to the n streams
    each block, for each of its two sublayers F (latent attention, then
    the dense SwiGLU MLP or the expert layer), on a token's X (n x C):
      x~      = vec(X) rsqrt(mean(vec(X)^2) + hc_eps)      n C lanes, no weight
      H~_pre  = a_pre  (x~ P_pre)  + b_pre                 (n)
      H~_post = a_post (x~ P_post) + b_post                (n)
      H~_res  = a_res  mat(x~ P_res) + b_res               (n x n)
      H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
      H_res = Sinkhorn(exp(clip(H~_res, clamp_min, clamp_max))):
              hc_sinkhorn_iters times rows / (row sum + hc_eps), then columns
      u = H_pre X;   y = F(RMSNorm(u));   X' = H_res X + H_post^T y
    h = sum of the n streams;  logits = RMSNorm(h) W_head

    latent attention: c_q = RMSNorm(x W_qa); q = c_q W_qb (H heads of
      nope | rope); c = x W_kva (rank | rope); c_kv = RMSNorm(c[:rank]);
      k_nope | v = c_kv W_kvb; rotary over the rope lanes of q and of
      the ONE key head c[rank:], by YaRN's frequencies (``yarn_
      frequencies``); o = causal softmax(q k^T scale) v, scale = (nope +
      rope)^-1/2 (0.1 mscale_all_dim ln(factor) + 1)^2; then W_o
    expert layer: s = sigmoid(h W_r) over ALL experts; the k with the
      largest s + b; gates s of the chosen over their sum, times
      routed_scaling_factor; y = sum over the chosen experts HELD here
      of g_e expert_e(h) + shared(h); the absent experts add nothing
    prediction module (num_nextn_predict_layers 1), position i:
      h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_(i+1)))] W_eh, copied to the n
      streams, one more expert block, summed, RMSNorm, the same W_head;
      the last position has no successor and is given t_0 (the roll's
      wrap): it is causal, so nothing of it reaches a loss
    loss = mean_i CE(logits_i, t_(i+1)) + mtp_loss_weight x mean_i
      CE(mtp logits_i, t_(i+2)), both over the positions that have both
      targets; no balance loss (the config has no seq_aux)

Where the system departs from the source the reference follows the
system and the configuration says so (``departs``): rotary rotates the
two HALVES of the rope lanes where the published code rotates
interleaved pairs. ``expert_layer(..., held=(0, all))`` with all the
experts' kernels is the uncut layer: the test that adds the shares up
calls it.

Memory, not mathematics: each block runs under ``jax.checkpoint``, the
heads one at a time (``lax.map``), the held experts one at a time
(every held expert computes every position and a 0 / gate mask keeps
what the router chose), the Sinkhorn iterations in a ``fori_loop``.
"""

import functools
import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def yarn_frequencies(dim, base, scaling):
    """The dim / 2 rotary frequencies: pair i rotates by ``base^(-2i /
    dim)`` where i is below ``low``, by that over ``factor`` where it is
    above ``high``, by the linear blend between: ``low`` / ``high`` the
    floor / ceiling of the dimension that makes ``beta_fast`` /
    ``beta_slow`` turns over the original context."""
    def turns(rotations):
        return dim * math.log(
            scaling["original_max_position_embeddings"]
            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pairs = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = base ** (-2.0 * pairs / dim)
    ramp = jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / scaling["factor"] * ramp


def softmax_scale(width, scaling):
    factor, all_dim = scaling["factor"], scaling["mscale_all_dim"]
    mscale = 0.1 * all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return width ** -0.5 * mscale * mscale


def rotary(x, freqs):
    """x: (S, D). Pairs (i, i + D/2) rotate by pos * freqs[i]."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def head_attention(args, scale):
    """One head: q, k (S, nope + rope), v (S, v) -> (S, v)."""
    q, k, v = args
    seq = q.shape[0]
    scores = (q @ k.T) * scale
    causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def latent_attention(x, p, config):
    """x: (S, d). Kernels: q_down (d, q_rank), q_norm scale, q_proj
    (q_rank, H, nope + rope), kv_down (d, rank + rope), kv_norm scale,
    kv_up (rank, H, nope + v), out_proj (H, v, d)."""
    eps, scaling = config["rms_norm_eps"], config["rope_scaling"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope = config["qk_rope_head_dim"]
    freqs = yarn_frequencies(rope, float(config["rope_theta"]), scaling)
    c_q = rms_norm(x @ p["q_down"]["kernel"], p["q_norm"]["scale"], eps)
    q = jnp.einsum("sr,rhk->hsk", c_q, p["q_proj"]["kernel"])
    c = x @ p["kv_down"]["kernel"]
    c_kv = rms_norm(c[:, :rank], p["kv_norm"]["scale"], eps)
    kv = jnp.einsum("sr,rhk->hsk", c_kv, p["kv_up"]["kernel"])
    k_rope = rotary(c[:, rank:], freqs)
    q = jnp.concatenate([
        q[..., :nope], jax.vmap(lambda t: rotary(t, freqs))(q[..., nope:]),
    ], axis=-1)
    k = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(k_rope, (kv.shape[0],) + k_rope.shape),
    ], axis=-1)
    out = jax.lax.map(
        jax.checkpoint(functools.partial(
            head_attention, scale=softmax_scale(nope + rope, scaling))),
        (q, k, kv[..., nope:]))
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
    if config["norm_topk_prob"]:
        gates = gates / gates.sum(axis=-1, keepdims=True)
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


def sinkhorn(matrix, iters, eps):
    """matrix (S, n, n), positive: ``iters`` times rows over their sum
    + eps, then columns."""
    def step(_, m):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        return m / (m.sum(axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, step, matrix)


def hyper_coefficients(streams, p, config):
    """streams (S, n, C) -> H_pre (S, n), H_post (S, n), H_res (S, n,
    n). ``p``: ``p_pre`` / ``p_post`` (n, C, n), ``p_res`` (n, C, n n)
    (stream m's rows of the (n C)-row matrix are ``p[m]``), the gates
    ``a_*`` and the biases ``b_*``."""
    seq, n, dim = streams.shape
    flat = streams.reshape(seq, n * dim)
    flat = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True)
                           + config["hc_eps"])
    project = lambda name: flat @ p[name].reshape(n * dim, -1)
    h_pre = jax.nn.sigmoid(p["a_pre"] * project("p_pre") + p["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(
        p["a_post"] * project("p_post") + p["b_post"])
    raw = p["a_res"] * project("p_res").reshape(seq, n, n) + p["b_res"]
    raw = jnp.clip(
        raw, config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"])
    h_res = sinkhorn(
        jnp.exp(raw), config["hc_sinkhorn_iters"], config["hc_eps"])
    return h_pre, h_post, h_res


def hyper_connected(streams, p, config, sublayer):
    """``X' = H_res X + H_post^T F(H_pre X)`` for ``sublayer`` = F with
    its norm; F may return ``(y, more)``. Returns (X', H_res, more)."""
    h_pre, h_post, h_res = hyper_coefficients(streams, p, config)
    y = sublayer(jnp.einsum("sn,snc->sc", h_pre, streams))
    y, more = y if isinstance(y, tuple) else (y, None)
    mixed = (jnp.einsum("smn,snc->smc", h_res, streams)
             + h_post[:, :, None] * y[:, None, :])
    return mixed, h_res, more


def block(streams, p, bias, forced, config):
    """(streams after the block, the experts its router chose (S, k)
    or None for a dense block, H_res of its two sublayers)."""
    eps = config["rms_norm_eps"]
    streams, res_attn, _ = hyper_connected(
        streams, p["hc_attn"], config, lambda u: latent_attention(
            rms_norm(u, p["ln_attn"]["scale"], eps), p["attn"], config))

    def mlp(u):
        h = rms_norm(u, p["ln_mlp"]["scale"], eps)
        if "moe_mlp" not in p:
            return swiglu(h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                          p["mlp_down"]["kernel"])
        y, chosen = expert_layer(
            h, p["moe_mlp"], bias, config, config["held_experts"], forced)
        return y + shared_expert(h, p["moe_mlp"]), chosen

    streams, res_mlp, chosen = hyper_connected(
        streams, p["hc_mlp"], config, mlp)
    return streams, chosen, (res_attn, res_mlp)


def copies(x, config):
    return jnp.broadcast_to(
        x[:, None, :], (x.shape[0], config["hc_mult"], x.shape[1]))


def forward(params, biases, tokens, config, forced=None, last=None):
    """tokens: (S,) int32 -> a dict: ``logits`` (S, V), or of the
    ``last`` positions; ``mtp_logits`` the prediction module's, the
    same positions (None without a module); ``chosen`` the experts
    every expert layer's router chose, (layers, S, k), the module's
    block last; ``h_res`` {"first", "last"}: the (S, n, n) coefficients
    of block 0's attention sublayer and of the last main block's MLP
    sublayer. ``biases``: {block name: (E,)} of the expert layers;
    ``forced`` (layers, S, k): the experts to apply instead."""
    eps = config["rms_norm_eps"]
    embedding = params["wte"]["embedding"]
    run = jax.checkpoint(functools.partial(block, config=config))
    streams = copies(embedding[tokens], config)
    chosen, h_res = [], {}

    def expert_block(streams, name):
        pick = None if forced is None else forced[len(chosen)]
        streams, experts, res = run(
            streams, params[name], biases.get(name), pick)
        if experts is not None:
            chosen.append(experts)
        return streams, res

    layers = config["num_hidden_layers"]
    for i in range(layers):
        streams, res = expert_block(streams, "block_%d" % i)
        if i == 0:
            h_res["first"] = res[0]
        if i == layers - 1:
            h_res["last"] = res[1]
    hidden = streams.sum(axis=1)
    tail = (lambda x: x) if last is None else (lambda x: x[-last:])
    head = params["lm_head"]["kernel"]
    out = {"logits": rms_norm(tail(hidden), params["ln_f"]["scale"], eps)
           @ head, "mtp_logits": None}
    if config["num_nextn_predict_layers"]:
        merged = jnp.concatenate([
            rms_norm(hidden, params["mtp_hnorm"]["scale"], eps),
            rms_norm(embedding[jnp.roll(tokens, -1)],
                     params["mtp_enorm"]["scale"], eps),
        ], axis=-1) @ params["mtp_proj"]["kernel"]
        streams, _ = expert_block(copies(merged, config), "mtp_block")
        out["mtp_logits"] = rms_norm(
            tail(streams.sum(axis=1)), params["mtp_norm"]["scale"], eps
        ) @ head
    out["chosen"] = jnp.stack(chosen)
    out["h_res"] = h_res
    return out


def cross_entropy(logits, targets):
    """Mean over positions of -log softmax(logits)[target]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


def logits_losses_and_choices(params, biases, tokens, config, forced=None,
                              last=None):
    """The comparison's unit: ``forward``'s dict (the logits of the
    last ``last`` positions; every layer still attends, mixes and
    routes over the whole context) with ``loss`` and ``mtp_loss``: over
    the compared positions that have both targets, the cross-entropy of
    predicting each position's successor + ``mtp_loss_weight`` x the
    module's cross-entropy of predicting the one after (without a
    module: every position but the last, its successor).

    Top-k is discontinuous, so the comparison has two parts
    (``check.py``): ``forced`` applies the experts another
    implementation chose, with this reference's own gates for them; the
    returned choices are always this reference's own."""
    with jax.default_matmul_precision("highest"):
        to_f32 = lambda tree: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), tree)
        out = forward(
            to_f32(params), to_f32(biases), tokens, config, forced, last)
        targets = tokens if last is None else tokens[-last:]
        if out["mtp_logits"] is None:
            out["mtp_loss"] = jnp.float32(0.0)
            out["loss"] = cross_entropy(out["logits"][:-1], targets[1:])
            return out
        out["mtp_loss"] = cross_entropy(out["mtp_logits"][:-2], targets[2:])
        out["loss"] = (
            cross_entropy(out["logits"][:-2], targets[1:-1])
            + config["assumed"]["mtp_loss_weight"] * out["mtp_loss"])
        return out
