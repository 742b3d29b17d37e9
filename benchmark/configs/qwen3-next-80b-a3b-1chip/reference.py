"""Plain reference of the decoder the ``qwen3-next-80b-a3b-1chip``
configuration trains (Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type``
``qwen3_next``): forward pass, loss and gradients in straightforward
``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no chunked
rule, no sort, no grouped matmul, no flax; it imports nothing from
``elasticdl_tpu``. It reads the same parameter tree the system trains
(names below), so seeded weights feed both sides.

Token embedding, ``num_hidden_layers`` blocks ``x = x + mixer(norm(x));
x = x + moe(norm(x))``, a final norm and an untied head; ``norm(x) = x
rsqrt(mean(x^2) + eps) (1 + w)`` (zero-centred). Layer ``i`` is a
linear-attention layer if ``(i + 1) % full_attention_interval`` else a
full one.

- Gated DeltaNet mixer: ``q | k | v | z = x W_qkvz``, ``b | a = x
  W_ba``; ``[q | k | v]`` pass a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps (no bias) and SiLU; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q and k
  l2-normalised over their lanes (eps 1e-6), q scaled by
  ``linear_key_head_dim^-1/2``; value head ``h`` reads key head ``h //
  (value heads / key heads)``; per value head, ONE TOKEN A STEP, with
  the state ``S`` zero at the start: ``S = exp(g_t) S; u = beta_t (v_t -
  S^T k_t); S = S + k_t u^T; o_t = S^T q_t``; then ``o =
  rmsnorm(o) w silu(z)`` per head and the output projection.
- Gated attention mixer: ``q_proj`` gives every head a query and a gate
  of ``head_dim`` each; ``num_key_value_heads`` key and value heads; the
  zero-centred norm over the lanes of every query and key head; rotary
  (base ``rope_theta``) on the first ``partial_rotary_factor x
  head_dim`` lanes; causal softmax attention at ``head_dim^-1/2``,
  query head ``h`` reading kv head ``h // group``; ``o = o
  sigmoid(gate)``; the output projection.
- Expert layer: float32 softmax over the router's logits (all
  ``published.num_experts``), the ``num_experts_per_tok`` largest, their
  weights divided by their sum; of the chosen experts THOSE THIS CHIP
  HOLDS (``held_experts``: a first index and a count) each a SwiGLU MLP,
  nothing for the absent ones; plus ``sigmoid(x . w_sg) swiglu(x)``,
  the shared expert behind its gate. ``expert_layer(...,
  held=(0, all))`` is the uncut layer: the test that adds the shares up
  calls it.

The loss is cross-entropy + ``router_aux_loss_coef`` x the balance loss
summed over the layers: ``E sum_e f_e P_e`` over ALL experts, ``f_e``
the share of the tokens that chose e among their k, ``P_e`` the mean
router probability of e.

Where the system departs from the source the reference follows the
system and the configuration says so (``departs``): the columns of
``in_proj_qkvz`` lie q | k | v | z.

Memory, not mathematics: each block runs under ``jax.checkpoint``; the
per-token loop is a scan over blocks of ``SCAN_BLOCK`` tokens, each
under ``jax.checkpoint``, so the backward holds a state a block and a
block's own (32,768 states of 32 x 128 x 128 floats would be 68 GB);
attention is computed a head and ``QUERY_BLOCK`` queries at a time, and
the experts one at a time (every expert computes every token and a 0 /
gate mask keeps what the router chose).
"""

import functools

import jax
import jax.numpy as jnp

SCAN_BLOCK = 128
QUERY_BLOCK = 2048


def norm(x, w, eps):
    """Zero-centred RMSNorm: the scale is ``1 + w``."""
    var = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def rotary(x, base):
    """x: (S, D). Pairs (i, i + D/2) rotate by pos * base^(-i / (D/2))."""
    seq, dim = x.shape
    half = dim // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def delta_rule(q, k, v, g, beta):
    """One value head, one token a step. q, k: (S, Dk); v: (S, Dv); g,
    beta: (S,) -> o (S, Dv)."""
    seq = q.shape[0]
    block = SCAN_BLOCK if seq % SCAN_BLOCK == 0 else seq

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t) * state
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


def gated_delta_net(x, p, config):
    """x: (S, d). Kernels: in_proj_qkvz (d, 2 Hk Dk + 2 Hv Dv),
    in_proj_ba (d, 2 Hv), conv_kernel (taps, 2 Hk Dk + Hv Dv), A_log,
    dt_bias (Hv,), out_norm scale (Dv,), out_proj (Hv, Dv, d)."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    taps = config["linear_conv_kernel_dim"]
    seq = x.shape[0]
    qkvz = x @ p["in_proj_qkvz"]["kernel"]
    ba = x @ p["in_proj_ba"]["kernel"]
    conv_dim = 2 * hk * dk + hv * dv
    padded = jnp.pad(qkvz[:, :conv_dim], ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        p["conv_kernel"][j] * padded[j:j + seq] for j in range(taps)))
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    heads = lambda t, num, width: t.reshape(seq, num, width).transpose(
        1, 0, 2)
    q = l2(heads(qkv[:, :hk * dk], hk, dk)) * dk ** -0.5
    k = l2(heads(qkv[:, hk * dk:2 * hk * dk], hk, dk))
    v = heads(qkv[:, 2 * hk * dk:], hv, dv)
    rep = hv // hk
    o = jax.vmap(delta_rule, in_axes=(0, 0, 0, 1, 1))(
        jnp.repeat(q, rep, axis=0), jnp.repeat(k, rep, axis=0), v, g, beta)
    var = (o * o).mean(-1, keepdims=True)
    o = o / jnp.sqrt(var + config["rms_norm_eps"]) * p["out_norm"]["scale"]
    z = heads(qkvz[:, conv_dim:], hv, dv)
    return jnp.einsum("hsv,hvd->sd", o * jax.nn.silu(z),
                      p["out_proj"]["kernel"])


def head_attention(q, k, v):
    """One head: q (S, D) over k, v (S, D), causal, ``QUERY_BLOCK``
    queries at a time."""
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
        (q.reshape(seq // block, block, dim),
         jnp.arange(0, seq, block)),
    ).reshape(seq, v.shape[1])


def gated_attention(x, p, config):
    """x: (S, d). Kernels: query (d, H, 2 D: a query and a gate a head),
    key, value (d, Hkv, D), q_norm, k_norm scale (D,), out_proj (H, D,
    d)."""
    eps, base = config["rms_norm_eps"], float(config["rope_theta"])
    dim = config["head_dim"]
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    lanes = int(dim * config["partial_rotary_factor"])
    qg = jnp.einsum("sd,dhk->hsk", x, p["query"]["kernel"])
    q, gate = qg[..., :dim], qg[..., dim:]
    k = jnp.einsum("sd,dhk->hsk", x, p["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    q = norm(q, p["q_norm"]["scale"], eps)
    k = norm(k, p["k_norm"]["scale"], eps)
    turn = jax.vmap(lambda t: jnp.concatenate(
        [rotary(t[:, :lanes], base), t[:, lanes:]], axis=-1))
    q, k = turn(q), turn(k)
    out = jax.lax.map(
        lambda args: head_attention(*args),
        (q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)))
    return jnp.einsum("hsv,hvd->sd", out * jax.nn.sigmoid(gate),
                      p["out_proj"]["kernel"])


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


def shared_expert(x, p):
    return jax.nn.sigmoid(x @ p["shared_expert_gate"]["kernel"]) * swiglu(
        x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"])


def balance_loss(probs, chosen):
    """E sum_e f_e P_e over all experts."""
    tokens, num_experts = probs.shape
    counts = (
        chosen[:, :, None] == jnp.arange(num_experts)[None, None, :]
    ).sum(axis=(0, 1))
    return num_experts * jnp.sum(counts / tokens * probs.mean(axis=0))


def expert_layer(x, p, config, held, forced=None, shared=True):
    """(this share's part of the layer's output, its balance loss, the
    experts its router chose). ``held`` = (first, count): ``p``'s
    ``w_gate / w_up / w_down`` are those experts' kernels. ``shared``:
    whether the shared expert, which every chip computes alike, is
    added."""
    probs, gates, applied, chosen = route(x, p, config, forced)
    y = held_experts_mlp(
        x, (p["w_gate"], p["w_up"], p["w_down"]), gates, applied, held[0])
    if shared:
        y = y + shared_expert(x, p)
    return y, balance_loss(probs, chosen), chosen


def is_linear(i, config):
    return (i + 1) % config["full_attention_interval"] != 0


def block(x, p, forced, i, config):
    eps = config["rms_norm_eps"]
    mixer = gated_delta_net if is_linear(i, config) else gated_attention
    x = x + mixer(norm(x, p["ln_attn"]["scale"], eps), p["attn"], config)
    y, balance, chosen = expert_layer(
        norm(x, p["ln_mlp"]["scale"], eps), p["moe_mlp"], config,
        config["held_experts"], forced)
    return x + y, balance, chosen


def forward(params, tokens, config, forced=None, last=None):
    """tokens: (S,) int32 -> (logits (S, V), or of the ``last``
    positions; the summed balance loss; the experts every layer's
    router chose (L, S, k)). ``forced`` (L, S, k): the experts to apply
    instead."""
    x = params["wte"]["embedding"][tokens]
    balance, chosen = 0.0, []
    for i in range(config["num_hidden_layers"]):
        x, b, experts = jax.checkpoint(
            functools.partial(block, i=i, config=config)
        )(x, params["block_%d" % i], None if forced is None else forced[i])
        balance = balance + b
        chosen.append(experts)
    if last is not None:
        x = x[-last:]
    x = norm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return x @ params["lm_head"]["kernel"], balance, jnp.stack(chosen)


def next_token_loss(logits, targets):
    """Mean over positions of -log softmax(logits)[target]; ``logits``
    at position t predict ``targets[t]`` (already shifted)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)
    return -picked.mean()


def logits_loss_and_choices(params, tokens, config, forced=None, last=None):
    """The comparison's unit: the logits (of the last ``last``
    positions; every layer still mixes over the whole context), the
    loss (cross-entropy of predicting each compared position's
    successor, the final position has none; plus the weighted balance
    loss) and the experts each token's router chose in each layer, over
    ALL experts.

    Top-k is discontinuous, so the comparison has two parts
    (``check.py``): ``forced`` applies the experts another
    implementation chose, with this reference's own gates for them; the
    returned choices, and the balance loss's counts, are always this
    reference's own."""
    with jax.default_matmul_precision("highest"):
        to_f32 = lambda tree: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), tree)
        logits, balance, chosen = forward(
            to_f32(params), tokens, config, forced, last)
        targets = tokens if last is None else tokens[-last:]
        loss = (
            next_token_loss(logits[:-1], targets[1:])
            + config["assumed"]["router_aux_loss_coef"] * balance
        )
        return logits, loss, chosen
