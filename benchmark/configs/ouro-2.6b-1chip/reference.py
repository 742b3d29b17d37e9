"""Plain reference of the training step the ``ouro-2.6b-1chip``
configuration runs (ByteDance/Ouro-2.6B, ``model_type`` ``ouro``;
arXiv:2510.25741): forward pass, the Stage I loss and its gradients in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no scan over
passes, no flax; it imports nothing from
``elasticdl_tpu``. It reads the same parameter tree the system trains
(names below), so seeded weights feed both sides.

The model, written down (S positions, d wide, T = ``total_ut_steps``
passes over ``num_hidden_layers`` blocks; ``N(x) = x rsqrt(mean(x^2) +
eps) w``, each norm its own ``w``):

    block:   a = x + N2(Attn(N1(x))),  y = a + N4(SwiGLU(N3(a)))
             Attn: H heads of D, no grouping, no bias, rotary over the
             whole head at ``rope_theta`` (pairs (i, i + D/2)), causal
             softmax at scale 1 / sqrt(D);  SwiGLU(h) = W_down(silu(W_gate
             h) * (W_up h))
             (N1 ``ln_attn``, N2 ``ln_attn_out``, N3 ``ln_mlp``, N4
             ``ln_mlp_out``)
    loop:    h_0 = Embed(tokens);  h_t = N_f(Blocks(h_(t-1))), t = 1..T:
             the SAME blocks and the SAME ``ln_f`` every pass
    gate:    lambda_t = sigmoid(h_t w_g + b_g), t < T (one gate, with
             bias, shared by the passes)
    exits:   p_t = lambda_t prod_(j<t)(1 - lambda_j) for t < T,
             p_T = prod_(j<T)(1 - lambda_j): the four sum to 1
    loss:    CE_t = -log softmax(h_t W_head)[next token]; a position's
             loss is sum_t p_t CE_t - beta H(p), H(p) = -sum_t p_t log
             p_t; the loss is the mean over the compared positions that
             have a next token

Departures from ``modeling_ouro.py`` (as recalled: no network here),
the same as ``config.json``'s ``departs`` that touch the equations:
``lambda_T`` is not computed (the implementation evaluates its gate on
the last pass too and does not use it under the remainder rule); the
distribution is formed by log-sigmoids, not by a running product.

Memory, not mathematics: with ``remat`` each block application runs
under ``jax.checkpoint``, its softmax ``QUERY_BLOCK`` queries at a
time (a 16,384-square score matrix of 16 heads is 17 GB) and its MLP
``QUERY_BLOCK`` rows at a time; the logits of
an exit are formed whole for the ``last`` positions alone, and
``QUERY_BLOCK`` positions at a time where the loss runs over all of
them (``span_losses``).
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


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


def attention(x, p, config, remat):
    """x: (S, d) -> (S, d). Kernels: query, key, value (d, H, D),
    out_proj (H, D, d)."""
    seq = x.shape[0]
    positions = jnp.arange(seq)
    base = float(config["rope_theta"])
    turn = jax.vmap(lambda t: rotary(t, positions, base))
    q = turn(jnp.einsum("sd,dhk->hsk", x, p["query"]["kernel"]))
    k = turn(jnp.einsum("sd,dhk->hsk", x, p["key"]["kernel"]))
    v = jnp.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    def queries(q_b, start):
        s = jnp.einsum("htd,hsd->hts", q_b, k) * scale
        seen = (start + jnp.arange(q_b.shape[1]))[:, None] >= positions[None]
        probs = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,hsd->htd", probs, v)

    if remat and seq % QUERY_BLOCK == 0 and seq > QUERY_BLOCK:
        blocks = seq // QUERY_BLOCK
        q_blocks = jnp.moveaxis(
            q.reshape(q.shape[0], blocks, QUERY_BLOCK, q.shape[-1]), 1, 0)
        out = jax.lax.map(
            lambda args: jax.checkpoint(queries)(*args),
            (q_blocks, jnp.arange(0, seq, QUERY_BLOCK)))
        out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
    else:
        out = queries(q, 0)
    return jnp.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


def swiglu(x, p):
    return (jax.nn.silu(x @ p["mlp_gate"]["kernel"]) * (
        x @ p["mlp_up"]["kernel"])) @ p["mlp_down"]["kernel"]


def block(x, p, config, remat=False):
    eps = config["rms_norm_eps"]
    mixed = attention(norm(x, p["ln_attn"]["scale"], eps), p["attn"],
                      config, remat)
    a = x + norm(mixed, p["ln_attn_out"]["scale"], eps)

    def mlp(rows):
        return norm(
            swiglu(norm(rows, p["ln_mlp"]["scale"], eps), p),
            p["ln_mlp_out"]["scale"], eps)

    seq = x.shape[0]
    if not (remat and seq % QUERY_BLOCK == 0 and seq > QUERY_BLOCK):
        return a + mlp(a)
    # every row is on its own: QUERY_BLOCK of them at a time (the
    # compiler runs several blocks' recomputation at once, and the three
    # (S, 5632) float32 intermediates of each were 4 GB of the
    # program's 11.4 GB of temporaries at the cell)
    return a + jax.lax.map(
        jax.checkpoint(mlp),
        a.reshape(-1, QUERY_BLOCK, a.shape[-1])).reshape(a.shape)


def exits_and_gates(params, tokens, config, remat=False):
    """tokens: (S,) int32 -> (the T exits' states, each (S, d); the
    gate's logits of the first T - 1, each (S,))."""
    eps = config["rms_norm_eps"]
    run = functools.partial(block, config=config, remat=remat)
    if remat:
        run = jax.checkpoint(run)
    h = params["wte"]["embedding"][tokens]
    gate = params.get("early_exit_gate")
    exits, gates = [], []
    for t in range(config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            h = run(h, params["block_%d" % i])
        h = norm(h, params["ln_f"]["scale"], eps)
        exits.append(h)
        if t < config["total_ut_steps"] - 1:
            gates.append((h @ gate["kernel"])[:, 0] + gate["bias"][0])
    return exits, gates


def exit_distribution(gates, seq):
    """``p`` (T, S) from the T - 1 gates' logits: ``p_t = lambda_t
    prod_(j<t)(1 - lambda_j)``, the last exit the remainder."""
    stay, p = jnp.ones((seq,), jnp.float32), []
    for g in gates:
        lam = jax.nn.sigmoid(g)
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


def cross_entropy(logits, targets):
    """-log softmax(logits)[target] a position."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def stage_one(ce, p, beta):
    """``(loss, {name: term})`` from the T exits' cross-entropies and
    the exit distribution, (T, L) each, over L positions that have a
    next token: the means over positions of ``sum_t p_t CE_t``
    (``expected_ce``), ``H(p)`` (``exit_entropy``) and each ``CE_t``
    (``ce_exit_<t>``); the loss is the first less ``beta`` x the
    second."""
    terms = {"expected_ce": (p * ce).sum(axis=0).mean(),
             "exit_entropy": -(p * jnp.log(p)).sum(axis=0).mean()}
    terms.update(("ce_exit_%d" % t, ce[t].mean()) for t in range(len(ce)))
    return terms["expected_ce"] - beta * terms["exit_entropy"], terms


def exits_and_probs(params, tokens, config, remat=False):
    """``(the T exits' states (S, d) each, p (T, S))`` of the whole
    sequence."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        exits, gates = exits_and_gates(params, tokens, config, remat)
        return exits, exit_distribution(gates, tokens.shape[0])


def logits_and_losses(exits, p, kernel, tokens, config, last=None):
    """The comparison's unit: ``(logits (T, L, V), p (T, L), loss,
    {name: term})`` over the last ``last`` positions (all of them when
    None; every layer attended over the whole context): each exit's
    logits, whole, the exit distribution, the loss (a mean over the
    compared positions that have a next token: all but the final one)
    and its named parts."""
    with jax.default_matmul_precision("highest"):
        first = 0 if last is None else tokens.shape[0] - last
        kernel = kernel.astype(jnp.float32)
        logits = jnp.stack([h[first:] @ kernel for h in exits])
        ce = jnp.stack([
            cross_entropy(exit_logits[:-1], tokens[first + 1:])
            for exit_logits in logits])
        loss, terms = stage_one(
            ce, p[:, first:-1], config["assumed"]["beta"])
        return logits, p[:, first:], loss, terms


def span_losses(exits, p, kernel, tokens, config, remat=False):
    """The same objective over ALL positions: ``(loss, CE_t of every
    position that has a next token (T, S - 1))``. Memory, not
    mathematics: with ``remat`` an exit's logits are formed
    ``QUERY_BLOCK`` positions at a time under ``jax.checkpoint`` (one
    exit's 16,384 x 49,152 are 3.2 GB)."""
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[0]
        kernel = kernel.astype(jnp.float32)
        # the final position has no next token: any, dropped below
        targets = jnp.roll(tokens, -1)

        def of_block(h, t):
            return cross_entropy(h @ kernel, t)

        def of_exit(h):
            if not (remat and seq % QUERY_BLOCK == 0 and seq > QUERY_BLOCK):
                return of_block(h, targets)
            return jax.lax.map(
                lambda args: jax.checkpoint(of_block)(*args),
                (h.reshape(-1, QUERY_BLOCK, h.shape[-1]),
                 targets.reshape(-1, QUERY_BLOCK))).reshape(seq)

        ce = jnp.stack([of_exit(h) for h in exits])[:, :-1]
        return stage_one(ce, p[:, :-1], config["assumed"]["beta"])[0], ce
