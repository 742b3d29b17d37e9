"""The exits of a looped stack (``MoeTransformerLM.looped``; Ouro's
Stage I objective, arXiv:2510.25741): the distribution a gate gives a
position over the ``T`` passes' exits, and the expected cross-entropy
over them.

    lambda_t = sigmoid(gate(h_t)),  t < T
    p_t = lambda_t prod_(j<t) (1 - lambda_j),  p_T = prod_(j<T) (1 - lambda_j)
    loss = mean over positions of  sum_t p_t CE_t - beta H(p)

``CE_t`` is the next-token cross-entropy of ONE head applied to pass
``t``'s exit. ``T`` exits of ``S x V`` logits do not fit beside a step
(four of 16,384 x 49,152 are 12.9 GB in float32), so the head and its
log-sum-exp run ``EXIT_CHUNK`` positions at a time (the ``T`` exits of
them one after the other) under ``jax.checkpoint``: the forward keeps a
chunk's ``(T, B, chunk)`` cross-entropies and nothing ``V`` wide, the
backward forms a chunk's logits again. The head's kernel enters the chunks as a float32
copy that each chunk rounds back to the exits' dtype, so the kernel's
gradient is added up over chunks and exits in float32.
"""

import jax
import jax.numpy as jnp

# positions a chunk: an exit's 512 x 49,152 float32 logits are 101 MB.
# Measured on the chip at the Ouro cell's shape, seven layers, the
# passes still unrolled (PR 55, by a scratch script that timed the step
# alone and is not kept; PERF.md Section 6): a step takes 2.548 s at
# 256, 2.452 at 512, 2.508 at 1,024 and 2.518 at 2,048, and the
# compiler's peak is the same at all four (it lies in the passes'
# backward, not here)
EXIT_CHUNK = 512


def exit_distribution(gate_logits):
    """``log p`` (T, ...) from the gate's logits of the first ``T - 1``
    passes (T - 1, ...): the last exit takes what is left, so the ``T``
    sum to 1. In float32, by log-sigmoids."""
    g = gate_logits.astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-g)
    # what stayed BEFORE pass t; with no gate at all (T = 1) the sum
    # over no passes is 0: all mass on the one exit
    before = jnp.cumsum(stay, axis=0) - stay
    return jnp.concatenate([
        jax.nn.log_sigmoid(g) + before, stay.sum(axis=0, keepdims=True)])


def exit_cross_entropies(exits, kernel, targets):
    """``CE_t`` of every position, (T, B, S) float32: ``exits``, the T
    passes' states (B, S, D) each, through the head ``kernel`` (D, V)
    against ``targets`` (B, S), a chunk of positions at a time. The
    exits stay T arrays (a chunk of a batch of one is a view of each)
    behind an optimization barrier: the loop wants its operands in a
    layout of its own, and without the barrier XLA fills that copy by
    running the whole residual path again from every sublayer's
    float32 output, all of them kept to the end of the forward pass
    (6 GB at 16 block applications of 16,384 positions: PERF.md
    Section 6, PR 55)."""
    exits = jax.lax.optimization_barrier(tuple(exits))
    batch, seq, dim = exits[0].shape
    chunk = min(EXIT_CHUNK, seq)
    pad = -seq % chunk
    chunks = (seq + pad) // chunk

    def by_chunk(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(
            x.reshape((batch, chunks, chunk) + x.shape[2:]), 1, 0)

    wide = kernel.astype(jnp.float32)

    @jax.checkpoint
    def of_chunk(wide, states, t):
        def of_exit(h):
            logits = jnp.einsum(
                "bcd,dv->bcv", h, wide.astype(h.dtype),
                preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(
                logits, t[..., None], axis=-1)[..., 0]
            return jax.nn.logsumexp(logits, axis=-1) - picked

        return jnp.stack([of_exit(h) for h in states])

    ce = jax.lax.map(
        lambda args: of_chunk(wide, *args),
        (tuple(by_chunk(h) for h in exits), by_chunk(targets)))
    return jnp.moveaxis(ce, 0, 2).reshape(
        len(exits), batch, seq + pad)[..., :seq]


def expected_loss(labels, exits, kernel, log_p, beta):
    """``(per-sample losses (B,), {name: per-sample term})``: ``exits``
    the T passes' states (B, S, D) each; position ``i`` of every exit
    predicts ``labels[i + 1]``, the last position has no target;
    ``log_p`` (T, B, S) from ``exit_distribution``. The
    terms: ``expected_ce`` (sum_t p_t CE_t), ``exit_entropy`` (H(p),
    unweighted: the loss holds it at ``-beta``) and ``ce_exit_<t>``."""
    with jax.named_scope("exit/head"):
        seq = labels.shape[-1]
        targets = jnp.roll(labels.astype(jnp.int32), -1, axis=-1)
        ce = exit_cross_entropies(exits, kernel, targets)
        has_target = (jnp.arange(seq) < seq - 1).astype(jnp.float32)
        mean = lambda x: (x * has_target).sum(axis=-1) / (seq - 1)
        p = jnp.exp(log_p)
        expected = mean((p * ce).sum(axis=0))
        entropy = mean(-(p * log_p).sum(axis=0))
        terms = {"expected_ce": expected, "exit_entropy": entropy}
        terms.update(
            ("ce_exit_%d" % t, mean(ce[t])) for t in range(ce.shape[0]))
        return expected - beta * entropy, terms
