"""Mixture-of-experts routing and dispatch: two formulations.

No reference counterpart (SURVEY.md §2.12: expert parallelism is absent
from the reference); this is a new TPU-first capability.

**One-hot, with a static capacity** (``top_k_routing``, ``moe_dispatch``,
``moe_combine``): the GShard/Switch formulation expressed entirely as
static-shape einsums so XLA can lay expert compute out over an ``ep``
mesh axis and insert the all-to-alls itself:

- every token picks its top-k experts from router logits;
- each expert has a fixed per-group capacity C (static shape!), tokens
  beyond capacity are dropped (their combine weight is zero, the residual
  stream carries them through);
- dispatch/combine are (G, S, E, C) tensors contracted against the token
  stream, so "send token to expert" is an einsum — exactly the shape
  GSPMD turns into an all-to-all when tokens are dp-sharded and experts
  ep-sharded. Those einsums cost (2/3) x (S / expert width) of the
  expert layer's own FLOPs, which is why the other formulation exists.

**Sorted, dropless** (``route_top_k``, ``sort_by_expert``,
``dispatch_sorted``, ``grouped_matmul``, ``combine_sorted``): no
capacity and no one-hot. The (token, choice) pairs are ordered by
expert, the rows gathered in that order, each expert multiplies its own
contiguous group of rows (a grouped matmul over ragged groups), and the
rows are gathered back and summed under their gates. Every token
reaches exactly ``k`` experts. Dispatch and combine are permutations,
so their backward passes are gathers too (custom VJPs below): nothing
on this path is a scatter, and nothing costs a matmul FLOP that the
experts themselves do not need. On one device, and on a mesh whose
``ep`` is 1, the sort is over all the tokens the call sees. On a mesh
with ``ep > 1`` the experts are spread over the ranks of ``ep`` and the
path runs in a region manual over the mesh (``exchange_plan``,
``regroup_plan``, ``exchange_rows``, ``permute_rows``;
``MoeMlp._sorted_over_ep``): each rank sorts its own pairs, the rows
travel to the ranks that hold their experts in one ragged all-to-all,
are regrouped there by expert (a gather that runs the chunks of the
receive buffer that carry a pair, not the buffer), multiplied, and come
back the way they went; still nothing is dropped and no backward is a
scatter. A layer
may also hold a stated share of the experts WITHOUT an exchange
(``sort_held``, ``dispatch_held``, ``combine_held``; one chip's part of
a deployment that a benchmark cell cuts out): it routes over all of
them, gives only the pairs of its own experts a row (in a buffer of a
static size, of which a step runs the rows that carry a pair and no
more) and returns its own experts' part of the result; nothing stands
in for the absent chips or their traffic.

Everything is shape-static and jit-friendly: k is a Python int, the
sorted path's only data-dependent quantity is ``group_sizes``, an
(E,) array that the grouped matmul takes as an operand.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# checkpoint_names of the sorted path: the routing (a few integers a
# token) and the outputs of the three grouped matmuls. A remat policy
# that saves matmul outputs names these, because a grouped matmul is no
# ``dot_general`` (models/transformer.py:remat_block).
MOE_ROUTE_NAME = "moe_route"
MOE_MATMUL_NAME = "moe_expert_matmul"
MOE_SAVE_NAMES = (MOE_ROUTE_NAME, MOE_MATMUL_NAME)


def expert_capacity(seq_len, num_experts, k=1, capacity_factor=1.25):
    """Static per-group expert capacity: ceil(S*k/E) * factor."""
    per_expert = (seq_len * k + num_experts - 1) // num_experts
    return max(1, int(per_expert * capacity_factor))


def top_k_routing(router_logits, k, capacity):
    """Compute dispatch/combine tensors for top-k token→expert routing.

    Args:
      router_logits: (G, S, E) — G token groups (batch rows), S tokens
        per group, E experts.
      k: experts per token (static Python int).
      capacity: per-(group, expert) token budget C (static Python int).

    Returns:
      combine: (G, S, E, C) float — weights for re-combining expert
        outputs back into the token stream (zero for dropped tokens).
      dispatch: (G, S, E, C) bool — one-hot token→(expert, slot)
        assignment.
      aux_loss: scalar — Switch-style load-balance loss, E * Σ_e f_e·p_e
        where f_e is the fraction of tokens whose FIRST choice is e and
        p_e the mean router probability of e.
    """
    num_experts = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gates, indices = jax.lax.top_k(probs, k)  # (G, S, k)
    # Renormalize the kept gates so combine weights sum to 1 per token.
    gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-9)

    # Load-balance aux loss over first choices (Switch Transformer eq. 4).
    first_choice = jax.nn.one_hot(indices[..., 0], num_experts)
    tokens_per_expert = first_choice.mean(axis=(0, 1))  # f_e
    prob_per_expert = probs.mean(axis=(0, 1))  # p_e
    aux_loss = num_experts * jnp.sum(tokens_per_expert * prob_per_expert)

    # Assign capacity slots choice-rank-major: all rank-0 choices get
    # priority over rank-1 choices, and within a rank, earlier tokens win
    # (cumsum order). `counts` carries per-expert occupancy across ranks.
    combine = jnp.zeros(
        router_logits.shape + (capacity,), dtype=jnp.float32
    )
    dispatch = jnp.zeros(
        router_logits.shape + (capacity,), dtype=jnp.bool_
    )
    counts = jnp.zeros(
        router_logits.shape[:1] + (num_experts,), dtype=jnp.int32
    )  # (G, E)
    for rank in range(k):
        choice = jax.nn.one_hot(
            indices[..., rank], num_experts, dtype=jnp.int32
        )  # (G, S, E)
        # Position of each token inside its chosen expert's buffer.
        position = (
            jnp.cumsum(choice, axis=1) - choice + counts[:, None, :]
        )  # (G, S, E)
        within = (position < capacity) & (choice > 0)
        slot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)
        dispatch_r = within[..., None] & (slot > 0)  # (G, S, E, C)
        combine = combine + gates[..., rank, None, None] * dispatch_r
        dispatch = dispatch | dispatch_r
        counts = counts + (choice * within).sum(axis=1)
    return combine, dispatch, aux_loss


def moe_dispatch(x, dispatch):
    """Token stream → per-expert buffers.

    x: (G, S, M); dispatch: (G, S, E, C) → (E, G, C, M).
    Under GSPMD (tokens g→dp-sharded, output e→ep-sharded) this einsum
    IS the all-to-all.
    """
    return jnp.einsum(
        "gsec,gsm->egcm", dispatch.astype(x.dtype), x
    )


def moe_combine(expert_out, combine):
    """Per-expert buffers → token stream (weighted by gate values).

    expert_out: (E, G, C, M); combine: (G, S, E, C) → (G, S, M).
    """
    return jnp.einsum(
        "gsec,egcm->gsm", combine.astype(expert_out.dtype), expert_out
    )


# ---------------------------------------------------------------------
# the sorted, dropless formulation


def route_top_k(router_logits, k, normalize=False, scoring="softmax",
                bias=None, scale=1.0):
    """Token-choice routing without a capacity.

    router_logits: (T, E), any float dtype. The scores and the top-k
    run in float32 whatever the logits' dtype (the published OLMoE
    implementation's ``softmax(..., dtype=float)``).

    ``scoring="softmax"`` (OLMoE): the k largest probabilities are the
    gates. ``scoring="sigmoid"`` (DeepSeek-V3's ``noaux_tc`` with one
    group, arXiv:2412.19437 eq. 12-16, Moonlight's): ``scores =
    sigmoid(logits)``; the experts are the k largest of ``scores +
    bias`` (``bias`` (E,): the balancing bias, which steers the
    SELECTION only and receives no gradient); the gates are the chosen
    experts' ``scores`` without it. Under ``normalize`` the gates are
    divided by their sum; ``scale`` (``routed_scaling_factor``)
    multiplies them last.

    Returns ``(gates, experts, probs)``: (T, k) float32 gate values,
    (T, k) int32 expert ids, (T, E) float32 scores: the softmax's
    probabilities, or the sigmoid's scores normalised to sum to one
    over the experts (what the sequence-wise balance loss and the
    entropy counter read)."""
    if scoring == "softmax":
        if bias is not None or scale != 1.0:
            raise ValueError(
                "a selection bias and a scaling factor belong to "
                "scoring=\"sigmoid\"")
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
        gates, experts = jax.lax.top_k(probs, k)
        if normalize:
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-9)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
        biased = scores if bias is None else (
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)))
        _, experts = jax.lax.top_k(biased, k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
        if normalize:
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
        gates = gates * scale
        probs = scores / (scores.sum(axis=-1, keepdims=True) + 1e-20)
    else:
        raise ValueError(
            "scoring must be 'softmax' or 'sigmoid', got %r" % (scoring,))
    gates, experts = checkpoint_name(
        (gates, experts.astype(jnp.int32)), MOE_ROUTE_NAME
    )
    return gates, experts, probs


def sort_by_expert(experts, num_experts):
    """The permutation that groups the (token, choice) pairs by expert.

    experts: (T, k) int32. Pair ``p = t * k + j`` is token t's j-th
    choice. Returns

    - ``order`` (T*k,): the pair at each sorted position (stable, so a
      group keeps its tokens in order);
    - ``inverse`` (T*k,): the sorted position of each pair;
    - ``group_sizes`` (E,) int32: pairs per expert, in expert order;
      they sum to T*k, whatever the routing."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    # the inverse of a permutation by a second sort: no scatter
    inverse = jnp.argsort(order).astype(jnp.int32)
    # a compare fused into its reduction, not a stored one-hot
    group_sizes = jnp.sum(
        flat[:, None] == jnp.arange(num_experts, dtype=flat.dtype)[None],
        axis=0, dtype=jnp.int32,
    )
    return checkpoint_name((order, inverse, group_sizes), MOE_ROUTE_NAME)


def sort_held(experts, num_experts, first, count, buffer_rows):
    """The sort of an expert layer that holds experts ``first`` to
    ``first + count - 1`` of ``num_experts`` (one chip's share under
    expert parallelism): only the (token, choice) pairs whose expert
    lives here get a row, in a buffer of the static size
    ``buffer_rows``.

    experts: (T, k) int32 over ALL experts. Returns

    - ``pairs`` (buffer_rows,): the pair at each row, held pairs first,
      grouped by expert, a group's tokens in order;
    - ``valid`` (buffer_rows,) bool: the row carries a held pair;
    - ``group_sizes`` (count,) int32: the rows of each held expert,
      summing to ``min(held, buffer_rows)``, the rows that carry a
      pair, and NOT to the buffer. The layer's work follows them: the
      grouped matmul visits no row tile past the last held row, and
      what a row past it holds is whatever the memory held (on a TPU;
      zeros from ``ragged_dot``). Every exit of the buffer selects
      with ``valid`` (``dispatch_held``'s transpose, ``combine_held``);
    - ``loads`` (num_experts,) int32: pairs per expert over all
      experts, what the balance loss and the counters read;
    - ``held``, ``dropped`` (int32 scalars): the pairs whose expert
      lives here, and those of them that found no row (0 unless the
      buffer is too small).

    A buffer larger than T x k (a short sequence under a configuration
    sized for a long one) is cut to it: no pair can lack a row then."""
    flat = experts.reshape(-1)
    buffer_rows = min(buffer_rows, flat.shape[0])
    loads = jnp.sum(
        flat[:, None] == jnp.arange(num_experts, dtype=flat.dtype)[None],
        axis=0, dtype=jnp.int32,
    )
    local = flat - first
    here = (local >= 0) & (local < count)
    # pairs of absent experts sort behind every held one
    pairs = jnp.argsort(
        jnp.where(here, local, count), stable=True
    ).astype(jnp.int32)[:buffer_rows]
    sizes = jax.lax.dynamic_slice_in_dim(loads, first, count)
    held = sizes.sum()
    ends = jnp.minimum(jnp.cumsum(sizes), buffer_rows)
    valid = jnp.arange(buffer_rows) < ends[-1]
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    dropped = jnp.maximum(held - buffer_rows, 0)
    return checkpoint_name(
        (pairs, valid, group_sizes, loads, held, dropped), MOE_ROUTE_NAME)


# What a held layer runs of its buffer follows the step's held pairs,
# each stage in the way it won alone on the chip (PERF.md, PR 36).
# The dispatch's gather runs chunks of this many rows, as many as hold a
# pair (a loop with a traced trip count: a gather costs its rows); so
# does the regrouping of a receive buffer under ``ep`` (``permute_rows``).
HELD_CHUNK_ROWS = 4096
# The two scatter-adds run a PREFIX of the buffer, the shortest of this
# many equal steps that holds every held pair (a ``lax.switch`` over the
# static lengths: XLA's scatter-add costs 0.6 ms a call before its first
# row, so chunks lost there): eight for the combine's, four for the
# dispatch's transpose. XLA's rematerialisation counts a conditional as
# if every branch ran (it sums the branches' peaks, their operands
# included), and in the backward of a rematerialised block, where a
# step's memory peaks, eight branches made it recompute 800 MB
# projections it had room for.
HELD_PREFIXES = 8
HELD_BACKWARD_PREFIXES = 4


def held_chunk_rows(buffer_rows):
    """The rows of one chunk of a ``buffer_rows`` buffer:
    ``HELD_CHUNK_ROWS`` where that divides it, else the buffer whole."""
    if buffer_rows % HELD_CHUNK_ROWS:
        return buffer_rows
    return HELD_CHUNK_ROWS


def held_prefixes(buffer_rows, steps):
    """The static prefix lengths of a ``buffer_rows`` buffer: ``steps``
    equal steps up to the buffer, or the buffer alone where ``steps``
    does not divide it."""
    if buffer_rows % steps:
        return (buffer_rows,)
    step = buffer_rows // steps
    return tuple(range(step, buffer_rows + 1, step))


def _prefix_index(filled, lengths):
    """Which of ``lengths`` (``held_prefixes``) holds ``filled`` rows
    (the first where there are none)."""
    return jnp.clip(-(-filled // lengths[0]) - 1, 0, len(lengths) - 1)


def rows_run(held, buffer_rows):
    """The rows of a ``buffer_rows`` buffer that a layer with ``held``
    pairs runs: the prefix its combine's scatter-add visits, an eighth
    of the buffer a step (the dispatch's gather stops at the last chunk
    of ``held_chunk_rows`` that holds a pair, the grouped matmuls at
    the last row tile, the dispatch's transpose at the next quarter of
    the buffer; the combine's backward gathers the whole buffer)."""
    lengths = held_prefixes(buffer_rows, HELD_PREFIXES)
    return jnp.asarray(lengths, jnp.int32)[_prefix_index(held, lengths)]


def _over_held_prefix(valid, lengths, fn, *operands):
    """``fn(n, *operands)`` at the one of the prefix ``lengths`` that
    holds every row with a pair: one branch a length, the step picks."""
    return jax.lax.switch(
        _prefix_index(jnp.sum(valid, dtype=jnp.int32), lengths),
        [functools.partial(fn, n) for n in lengths], *operands)


# Each of the four permutes is jitted on its own: the layers of a model
# share its trace and its lowering (a scatter-add's branches are traced
# once a shape and not once a layer); always inside the step's own
# trace, where the recompile sentinel's host bookkeeping cannot run.
# What they read of this module's constants reaches them as a static
# argument.


def _gather_chunks(x, index, divisor, filled, chunk):
    """``take(x, index // divisor)`` into a zeroed buffer, in chunks of
    ``chunk`` of the index up to the last that holds one of its first
    ``filled`` entries: a loop whose trip count the step decides (a
    gather costs its rows). Nothing differentiates through it: its
    callers are custom VJPs' forwards and backwards."""
    def gather(i, buffer):
        at = jax.lax.dynamic_slice_in_dim(index, i * chunk, chunk)
        if divisor != 1:
            at = at // divisor
        return jax.lax.dynamic_update_slice_in_dim(
            buffer, jnp.take(x, at, axis=0), i * chunk, 0)

    return jax.lax.fori_loop(
        0, -(-filled // chunk), gather,
        jnp.zeros((index.shape[0], x.shape[1]), x.dtype))


@functools.partial(  # edlint: disable=obs-bare-jit
    jax.jit, static_argnums=(3, 4))
def _gather_held(x, pairs, valid, k, chunk):
    return _gather_chunks(
        x, pairs, k, jnp.sum(valid, dtype=jnp.int32), chunk)


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnums=(3, 4, 5))
def _scatter_held(d_rows, pairs, valid, k, tokens, lengths):
    def scatter(n, d_rows, pairs, valid):
        kept = jnp.where(valid[:n, None], d_rows[:n], 0)
        return jnp.zeros((tokens, d_rows.shape[1]), d_rows.dtype).at[
            pairs[:n] // k].add(kept)

    return _over_held_prefix(valid, lengths, scatter, d_rows, pairs, valid)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch_held(x, pairs, valid, k):
    """x (T, M) -> (buffer_rows, M): row r is the token of pair
    ``pairs[r]``. A gather that stops at the last chunk that holds a
    pair (the rows past it are zeros; a spare row before it holds the
    token of some pair of an absent expert). Its transpose is a
    scatter-add of the rows ``valid`` selects, over the prefix (in
    quarters of the buffer) that holds them: the incoming gradient's
    spare rows may hold anything (the grouped matmuls write no row
    past the last held tile)."""
    return _gather_held(x, pairs, valid, k, held_chunk_rows(pairs.shape[0]))


def _dispatch_held_fwd(x, pairs, valid, k):
    return dispatch_held(x, pairs, valid, k), (pairs, valid, x.shape[0])


def _dispatch_held_bwd(k, res, d_rows):
    pairs, valid, tokens = res
    lengths = held_prefixes(pairs.shape[0], HELD_BACKWARD_PREFIXES)
    return _scatter_held(d_rows, pairs, valid, k, tokens, lengths), None, None


dispatch_held.defvjp(_dispatch_held_fwd, _dispatch_held_bwd)


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnums=(4,))
def _combine_held(rows, gates, pairs, valid, lengths):
    tokens, k = gates.shape

    def scatter(n, rows, flat, pairs, valid):
        weighted = (
            rows[:n].astype(jnp.float32) * jnp.take(flat, pairs[:n])[:, None])
        kept = jnp.where(valid[:n, None], weighted, 0)
        return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[
            pairs[:n] // k].add(kept)

    y = _over_held_prefix(
        valid, lengths, scatter, rows, gates.reshape(-1), pairs, valid)
    return y.astype(rows.dtype)


@jax.jit  # edlint: disable=obs-bare-jit (as above)
def _combine_held_grads(rows, gates, pairs, valid, dy):
    """d_rows[r] = gate(pairs[r]) x dy[token(pairs[r])] and d_gates at
    pair ``pairs[r]`` = <dy[token], rows[r]>, both from ONE bfloat16
    gather of ``dy`` over the whole buffer, in float32 as autodiff of
    the forward has them (which gathers a float32 ``dy``, at twice the
    time). ``valid`` selects: a spare row of ``rows`` may hold a NaN."""
    k = gates.shape[1]
    flat = gates.reshape(-1)
    back = jnp.take(dy, pairs // k, axis=0).astype(jnp.float32)
    d_rows = jnp.where(
        valid[:, None], back * jnp.take(flat, pairs)[:, None], 0)
    d_gate = jnp.where(
        valid, jnp.sum(back * rows.astype(jnp.float32), axis=-1), 0)
    # a row's pair is its own: nothing is added twice
    d_flat = jnp.zeros_like(flat).at[pairs].add(d_gate.astype(flat.dtype))
    return d_rows.astype(rows.dtype), d_flat.reshape(gates.shape)


@jax.custom_vjp
def combine_held(rows, gates, pairs, valid):
    """rows (buffer_rows, M), gates (T, k) -> (T, M): each token's held
    experts' outputs under their gates, summed in float32 and rounded
    once; nothing for a pair whose expert lives elsewhere, and nothing
    from a row that carries no pair: ``valid`` SELECTS the products (a
    spare row may hold a NaN, and 0 x NaN is NaN), over the prefix of
    the buffer that holds the pairs. The rows' gradient is zero in
    every spare row (``_combine_held_grads``)."""
    return _combine_held(
        rows, gates, pairs, valid,
        held_prefixes(rows.shape[0], HELD_PREFIXES))


def _combine_held_fwd(rows, gates, pairs, valid):
    return combine_held(rows, gates, pairs, valid), (rows, gates, pairs, valid)


def _combine_held_bwd(res, dy):
    return _combine_held_grads(*res, dy) + (None, None)


combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


@jax.custom_vjp
def dispatch_sorted(x, order, inverse):
    """x (T, M) -> (T*k, M): row s is the token of the pair at sorted
    position s, so expert e's rows are contiguous."""
    k = order.shape[0] // x.shape[0]
    return jnp.take(x, order // k, axis=0)


def _dispatch_sorted_fwd(x, order, inverse):
    return dispatch_sorted(x, order, inverse), (inverse, x.shape[0])


def _dispatch_sorted_bwd(res, d_rows):
    """dx[t] = sum_j d_rows[inverse[t*k + j]]: a gather through the
    inverse permutation, where plain autodiff of ``take`` would emit a
    scatter-add."""
    inverse, tokens = res
    k = inverse.shape[0] // tokens
    back = jnp.take(d_rows, inverse, axis=0)
    dx = back.reshape(tokens, k, -1).astype(jnp.float32).sum(axis=1)
    return dx.astype(d_rows.dtype), None, None


dispatch_sorted.defvjp(_dispatch_sorted_fwd, _dispatch_sorted_bwd)


def _gathered_back(rows, inverse, tokens):
    return jnp.take(rows, inverse, axis=0).reshape(tokens, -1, rows.shape[-1])


@jax.custom_vjp
def combine_sorted(rows, gates, order, inverse):
    """rows (T*k, M) in sorted order, gates (T, k) -> (T, M): each
    token's k expert outputs, weighted by their gates and summed (in
    float32, rounded once)."""
    back = _gathered_back(rows, inverse, gates.shape[0])
    y = (back.astype(jnp.float32) * gates[..., None]).sum(axis=1)
    return y.astype(rows.dtype)


def _combine_sorted_fwd(rows, gates, order, inverse):
    return (
        combine_sorted(rows, gates, order, inverse),
        (rows, gates, order, inverse),
    )


def _combine_sorted_bwd(res, dy):
    """Both cotangents are gathers: d_rows[s] = gate(order[s]) x
    dy[token(order[s])], and d_gates[t, j] = <dy[t], rows[inverse[t*k +
    j]]> over a re-gather of the forward rows (cheaper than keeping the
    (T, k, M) copy alive)."""
    rows, gates, order, inverse = res
    tokens, k = gates.shape
    back = _gathered_back(rows, inverse, tokens)
    d_gates = jnp.einsum(
        "tkm,tm->tk", back.astype(jnp.float32), dy.astype(jnp.float32)
    ).astype(gates.dtype)
    gate_of = jnp.take(gates.reshape(-1), order)
    d_rows = (
        jnp.take(dy, order // k, axis=0).astype(jnp.float32)
        * gate_of[:, None]
    ).astype(rows.dtype)
    return d_rows, d_gates, None, None


combine_sorted.defvjp(_combine_sorted_fwd, _combine_sorted_bwd)


# ---------------------------------------------------------------------
# the exchange: the sorted path with its experts spread over ``ep``
#
# Inside a region manual over the data axes every rank sorts its own
# (token, choice) pairs by expert, so the pairs of one destination rank
# are one contiguous chunk of its sorted rows. The ranks all-gather
# their (E,) group sizes (``ranks x E`` integers a layer), and from
# that one table every rank knows the whole exchange: what each rank
# sends to each (``exchange_plan``), where it lands, and how the rows a
# rank received, which lie sender by sender, regroup by its own experts
# (``regroup_plan``). The rows travel by ``exchange_rows`` and come
# back the way they went; its transpose is the exchange reversed, and
# the regrouping's is a gather through the inverse permutation, so no
# backward on this path is a scatter either.
#
# The regrouping's two index vectors are as long as the receive buffer
# and are built again in the rematerialised forward, so they are built
# without a lookup by position: each is the position plus a shift that
# only changes at the ``ranks x held`` (sender, expert) runs' ends, a
# compare and a select fused into one sum over those ends. A gather
# costs ~11 ns a position on a v5e whatever the table's size: four
# lookups a plan in tables of 64 entries were 47 ms a step at 131,072
# positions, the compares 0.3 (PERF.md, PR 48).

EXCHANGE_SCOPE = "moe/exchange"


def resolve_exchange():
    """``"ragged_all_to_all"`` on a TPU: one collective that moves each
    chunk's true rows and no padding. XLA's CPU backend has no such
    operation (``UNIMPLEMENTED: HLO opcode ragged-all-to-all``), so
    there, for the tests, ``"all_gather"``: the same offsets and sizes
    read out of the gathered operands."""
    return (
        "ragged_all_to_all" if jax.default_backend() == "tpu"
        else "all_gather")


def exchange_plan(counts, me, buffer_rows):
    """Who sends what where, from ``counts`` (ranks, E): every rank's
    pairs per expert, rank r holding experts ``r E / ranks`` onward.
    ``me``: this rank's index along the axis. ``buffer_rows``: the rows
    a rank can receive. A destination's buffer fills sender by sender;
    what finds no row is not sent (the tail of the last senders'
    chunks, their highest experts first) and is counted in
    ``dropped``, 0 unless the buffer is too small for the step's
    routing, and always 0 at ``ranks x`` a rank's pairs.

    Returns a dict: ``there`` and ``back``, the two directions' (offset
    in the sender's rows, rows sent, offset in the receiver's rows,
    rows received), each (ranks,) and one entry a peer; ``received``
    (ranks, E / ranks): this rank's received rows by sender and held
    expert; ``sent`` (ranks, ranks): rows by sender and destination,
    the same on every rank; ``dropped``: over all ranks."""
    ranks = counts.shape[0]
    by_dest = counts.reshape(ranks, ranks, -1)  # sender, dest, expert
    want = by_dest.sum(-1)
    ends = jnp.minimum(jnp.cumsum(want, axis=0), buffer_rows)
    sent = jnp.diff(ends, axis=0, prepend=0)
    lands = ends - sent  # in the destination's buffer
    lies = jnp.cumsum(want, axis=1) - want  # in the sender's sorted rows
    kept = jnp.diff(
        jnp.minimum(jnp.cumsum(by_dest, axis=-1), sent[..., None]),
        axis=-1, prepend=0)
    return {
        "there": (lies[me], sent[me], lands[me], sent[:, me]),
        "back": (lands[:, me], sent[:, me], lies[:, me], sent[me]),
        "received": kept[:, me],
        "sent": sent,
        "dropped": (want - sent).sum(),
    }


def regroup_plan(received, buffer_rows):
    """The permutation between a receive buffer's order (sender by
    sender, each sender's rows by expert) and the grouped matmul's
    (expert by expert), from ``received`` (ranks, held experts).
    Returns ``(by_expert, by_sender, group_sizes, carried)``:
    ``take(buffer, by_expert)`` groups the rows by expert, ``take(rows,
    by_sender)`` puts them back, ``group_sizes`` (held experts,) sum to
    ``carried``, the rows that carry a pair. The rows past those map to
    themselves: whatever they hold stays among them, and
    ``permute_rows`` runs none past the last chunk that carries one.

    Each vector is the position plus a shift that is constant on every
    (sender, expert) run, so it is built with no lookup by position (a
    gather costs ~11 ns a position on a v5e whatever the table's
    size): ``p + shift[0] + sum_j where(p >= ends[j], step[j], 0)``
    over the ``ranks x held`` runs' ends, ``step`` the change of the
    shift from a run to the next and, after the last, back to 0. An
    empty run's end is its predecessor's, so both steps engage
    together; past ``carried`` all engage and the position stands."""
    ranks, held = received.shape
    received = received.astype(jnp.int32)
    by_sender_sizes = received.reshape(-1)
    by_expert_sizes = received.T.reshape(-1)
    sender_ends = jnp.cumsum(by_sender_sizes)
    expert_ends = jnp.cumsum(by_expert_sizes)
    # a run's place in the buffer less its place among the grouped rows
    shifts = (
        (sender_ends - by_sender_sizes).reshape(ranks, held).T
        - (expert_ends - by_expert_sizes).reshape(held, ranks))
    at = jnp.arange(buffer_rows, dtype=jnp.int32)

    def shifted(ends, shifts):
        # a compare and a select fused into their reduction over the
        # runs' ends
        steps = jnp.diff(shifts, append=0)
        return at + shifts[0] + jnp.sum(
            jnp.where(at[:, None] >= ends[None], steps[None], 0),
            axis=1, dtype=jnp.int32)

    return checkpoint_name(
        (shifted(expert_ends, shifts.reshape(-1)),
         shifted(sender_ends, -shifts.T.reshape(-1)),
         received.sum(axis=0), sender_ends[-1]),
        MOE_ROUTE_NAME)


def received_rows_run(carried, buffer_rows):
    """The rows of a ``buffer_rows`` receive buffer that a rank with
    ``carried`` received rows permutes: whole chunks of
    ``held_chunk_rows`` up to the last that carries a pair."""
    chunk = held_chunk_rows(buffer_rows)
    return -(-carried // chunk) * chunk


@functools.partial(  # edlint: disable=obs-bare-jit (as the held permutes)
    jax.jit, static_argnums=(3,))
def _gather_carried(rows, index, carried, chunk):
    return _gather_chunks(rows, index, 1, carried, chunk)


@jax.custom_vjp
def permute_rows(rows, index, inverse, carried):
    """``take(rows, index)`` for a permutation ``index`` of a receive
    buffer's rows with its ``inverse`` (``regroup_plan``), of which the
    first ``carried`` carry a pair and the others map to themselves: a
    gather in chunks of ``held_chunk_rows`` that stops at the last
    chunk that carries one, the rows past it zeros (nothing reads
    them: the grouped matmuls stop at the group sizes' sum and the
    exchange sends from offsets below ``carried``). The transpose is
    the same through the inverse."""
    return _gather_carried(
        rows, index, carried, held_chunk_rows(rows.shape[0]))


def _permute_rows_fwd(rows, index, inverse, carried):
    return permute_rows(rows, index, inverse, carried), (inverse, carried)


def _permute_rows_bwd(res, d_rows):
    inverse, carried = res
    back = _gather_carried(
        d_rows, inverse, carried, held_chunk_rows(d_rows.shape[0]))
    return back, None, None, None


permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _gathered_all_to_all(rows, out_rows, route, axis):
    """``ragged_all_to_all``'s result into a zeroed buffer, read out of
    the gathered operands: row r of the result is the row of the sender
    whose chunk covers r."""
    me = jax.lax.axis_index(axis)
    everyone = jax.lax.all_gather(rows, axis)
    lies, sent, lands, _ = (
        jax.lax.all_gather(part, axis)[:, me] for part in route)
    at = jnp.arange(out_rows, dtype=jnp.int32)
    covers = (at[None] >= lands[:, None]) & (at[None] < (lands + sent)[:, None])
    sender = jnp.argmax(covers, axis=0)
    source = jnp.clip(
        lies[sender] + at - lands[sender], 0, rows.shape[0] - 1)
    return jnp.where(
        covers.any(axis=0)[:, None], everyone[sender, source], 0
    ).astype(rows.dtype)


def _all_to_all_rows(rows, out_rows, route, axis):
    """Chunk ``j`` of this rank's ``rows`` (``route``'s offset and
    size) to rank ``j``'s buffer of ``out_rows`` rows at the offset
    ``route`` names there; the rows nothing is written to are zeros."""
    with jax.named_scope(EXCHANGE_SCOPE):
        if resolve_exchange() == "all_gather":
            return _gathered_all_to_all(rows, out_rows, route, axis)
        return jax.lax.ragged_all_to_all(
            rows, jnp.zeros((out_rows,) + rows.shape[1:], rows.dtype),
            *route, axis_name=axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def exchange_rows(rows, route, back, out_rows, axis):
    """The all-to-all over ``axis``: ``rows`` (N, M), this rank's rows
    with each peer's chunk contiguous, -> (out_rows, M), what the peers
    sent here. ``route`` and ``back`` are a plan's two directions
    (``exchange_plan``), whichever way this call goes. Its transpose is
    the exchange reversed: the cotangent's chunks travel ``back`` to
    the rows they came from, and a row that was not sent gets zero."""
    return _all_to_all_rows(rows, out_rows, route, axis)


def _exchange_rows_fwd(rows, route, back, out_rows, axis):
    return exchange_rows(rows, route, back, out_rows, axis), (
        back, rows.shape[0])


def _exchange_rows_bwd(out_rows, axis, res, d_out):
    back, in_rows = res
    return _all_to_all_rows(d_out, in_rows, back, axis), None, None


exchange_rows.defvjp(_exchange_rows_fwd, _exchange_rows_bwd)


# The Pallas grouped matmul's tiles (tm rows, tk of K, tn of N), chosen
# for each call from that call's own shapes (``gmm_tiles``).
# Rows: 512 (1024 are refused by the TPU compiler at any K / N tile
# worth having; 256 halve a weight tile's reuse and lost in both expert
# cells' ``gmm`` calls, PERF.md, PR 30). ``tgmm`` alone may fall back to
# 256: it contracts over its rows, so a step's FLOPs a byte are tk x tn
# / (tk + tn) whatever the row tile, and the row tile gives way where
# that lets the output tile grow. K and N: 1024 where 1024 divides the
# dimension (in the OLMoE step (512, 1024, 1024) ran 3% ahead of (512,
# 512, 1024), PERF.md, PR 25), never under 512 (a weight tile's reuse
# then falls under the v5e's ridge), always whole lane tiles.
GMM_ROW_TILE = 512
TGMM_ROW_TILES = (512, 256)
GMM_TILE = 1024
GMM_MIN_TILE = 512
_LANES = 128
# what the TPU compiler grants one kernel's buffers (scoped VMEM, v5e)
GMM_VMEM_BYTES = 16 * 2**20
GMM_CALLS = ("gmm", "gmm_transposed", "tgmm")


def gmm_vmem_bytes(call, tiles, dtype):
    """Bytes of VMEM one grid step of the backend's ``gmm`` (plain or
    with a transposed rhs) or ``tgmm`` holds at ``tiles``: the two
    operand tiles and the output tile, double-buffered by the pipeline,
    the float32 accumulator (output-tile sized), and the kernel's own
    copy of its lhs tile (cast, masked, and in ``tgmm`` transposed),
    counted at 1.2 of its bytes. Fitted to what the compiler says when
    it refuses a tile ("Scoped allocation with size 16.79M and limit
    16.00M": 60 tiles compiled for a described v5e, PR 30): it reads
    1.04-1.2 of that copy at row tiles up to 512 (all the rule asks
    for; more at 768 and 1024), less where ``gmm`` has a single K tile,
    so the count errs high, never low.
    ``tests/test_moe_tpu_compile.py`` holds it against the compiler."""
    tm, tk, tn = tiles
    itemsize = jnp.dtype(dtype).itemsize
    lhs = tm * tk
    if call == "tgmm":  # (rows, k)^T (rows, n) -> (k, n)
        rhs, out = tm * tn, tk * tn
    else:  # (rows, k) (k, n) -> (rows, n)
        rhs, out = tk * tn, tm * tn
    return int(
        2 * itemsize * (lhs + rhs + out) + 4 * out + 1.2 * itemsize * lhs)


def _covered(dim, tile):
    """What ``dim`` costs in whole tiles."""
    return -(-dim // tile) * tile


def gmm_fill(k, n, tiles):
    """The share of a call's tile work that is needed work."""
    _, tk, tn = tiles
    return k * n / (_covered(k, tk) * _covered(n, tn))


def _tile_candidates(dim):
    if dim % GMM_TILE == 0:
        return (GMM_TILE, GMM_MIN_TILE)
    whole = _covered(dim, _LANES)
    return tuple(range(whole, min(whole, GMM_MIN_TILE) - 1, -_LANES))


def gmm_tiles(rows, k, n, dtype, call):
    """(tm, tk, tn) for one call of the backend's grouped matmul, from
    the call's own shapes. ``call`` is one of ``GMM_CALLS``: ``"gmm"``
    (rows (rows, k) x weights (E, k, n)), ``"gmm_transposed"`` (the
    same product through weights stored (E, n, k): the rows' gradient)
    or ``"tgmm"`` ((rows, k)^T x (rows, n) -> (E, k, n): the weights'
    gradient).

    A dimension that 1024 divides keeps 1024 (512 where that alone
    fits). Any other takes the multiple of 128 that covers it with the
    least padded work: the whole dimension where the call's buffers fit
    the compiler's 16 MiB (``gmm_vmem_bytes``), else the best of the
    smaller ones; among tiles of equal padded work, the largest (K x
    N), and among those the most rows. The MXU computes every tile in
    full, so Moonlight's 1408 under tiles of 1024 did 2048 / 1408 =
    1.45 times the work it needed."""
    if call not in GMM_CALLS:
        raise ValueError("call must be one of %r, got %r" % (GMM_CALLS, call))
    if rows % GMM_ROW_TILE:
        raise ValueError(
            "the Pallas grouped matmul takes whole row tiles of %d, got "
            "%d rows" % (GMM_ROW_TILE, rows))
    row_tiles = TGMM_ROW_TILES if call == "tgmm" else (GMM_ROW_TILE,)
    fitting = [
        (tm, tk, tn) for tm in row_tiles
        for tk in _tile_candidates(k) for tn in _tile_candidates(n)
        if gmm_vmem_bytes(call, (tm, tk, tn), dtype) <= GMM_VMEM_BYTES
    ]
    if not fitting:
        raise ValueError(
            "no tile of the %s (%d x %d, %s) fits %d bytes of VMEM"
            % (call, k, n, jnp.dtype(dtype).name, GMM_VMEM_BYTES))
    return min(
        fitting,
        key=lambda t: (_covered(k, t[1]) * _covered(n, t[2]), -t[1] * t[2],
                       -t[0]),
    )


def projection_tiles(rows, k, n, dtype):
    """The three calls of one expert projection (rows, k) x (E, k, n):
    ``{"fwd", "d_rows", "d_weights"}`` -> (tm, tk, tn). The rows'
    gradient contracts over n; the weights' gradient is (k, n)."""
    return {
        "fwd": gmm_tiles(rows, k, n, dtype, "gmm"),
        "d_rows": gmm_tiles(rows, n, k, dtype, "gmm_transposed"),
        "d_weights": gmm_tiles(rows, k, n, dtype, "tgmm"),
    }


def projection_fill(k, n, tiles):
    """Needed work over tile work of a projection's three calls."""
    spent = (
        1 / gmm_fill(k, n, tiles["fwd"])
        + 1 / gmm_fill(n, k, tiles["d_rows"])
        + 1 / gmm_fill(k, n, tiles["d_weights"])
    )
    return 3 / spent


def resolve_grouped_matmul(num_rows, dtype, one_device=True):
    """``"pallas_gmm"`` or ``"ragged_dot"`` for a grouped matmul over
    ``num_rows`` rows of ``dtype``. The Pallas kernel takes bfloat16
    rows in whole row tiles on a TPU, and like every ``pallas_call`` it
    cannot be partitioned automatically: ``one_device`` says that the
    call sees one device's rows, on one device or inside a region
    manual over the mesh (the exchange's, ``MoeMlp._sorted_over_ep``).
    A global sort under GSPMD (a mesh whose ``ep`` is 1) keeps
    ``ragged_dot``."""
    fits = (
        one_device
        and jax.default_backend() == "tpu"
        and dtype == jnp.bfloat16
        and num_rows % GMM_ROW_TILE == 0
    )
    return "pallas_gmm" if fits else "ragged_dot"


def _gmm_backend():
    # the package's ``gmm`` attribute is its one-tiling custom VJP; the
    # kernels are the module of the same name
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _pallas_forward(rows, weights, group_sizes, interpret):
    tiles = projection_tiles(
        rows.shape[0], weights.shape[1], weights.shape[2], rows.dtype)
    return _gmm_backend().gmm(
        rows, weights, group_sizes, rows.dtype, tiles["fwd"],
        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def pallas_grouped_matmul(rows, weights, group_sizes, interpret=False):
    """``grouped_matmul`` through the Pallas kernels that ship with jax
    (``megablox``'s ``gmm``, and ``tgmm`` for the weights' gradient),
    each of the three calls at tiles of its own (``projection_tiles``).
    The package's custom VJP hands ONE tiling to all three, whose K and
    N are swapped against each other. Same residuals as that one: the
    rows, the weights, the group sizes."""
    return _pallas_forward(rows, weights, group_sizes, interpret)


def _pallas_grouped_matmul_fwd(rows, weights, group_sizes, interpret):
    out = _pallas_forward(rows, weights, group_sizes, interpret)
    return out, (rows, weights, group_sizes)


def _pallas_grouped_matmul_bwd(interpret, res, d_out):
    rows, weights, group_sizes = res
    backend = _gmm_backend()
    tiles = projection_tiles(
        rows.shape[0], weights.shape[1], weights.shape[2], rows.dtype)
    d_rows = backend.gmm(
        d_out, weights, group_sizes, rows.dtype, tiles["d_rows"],
        transpose_rhs=True, interpret=interpret)
    # ``tgmm`` takes its lhs (k, rows) and swaps it back before the
    # kernel: the pair cancels, the kernel transposes a tile
    d_weights = backend.tgmm(
        rows.swapaxes(0, 1), d_out, group_sizes, weights.dtype,
        tiles["d_weights"], num_actual_groups=weights.shape[0],
        interpret=interpret)
    return d_rows, d_weights, None


pallas_grouped_matmul.defvjp(
    _pallas_grouped_matmul_fwd, _pallas_grouped_matmul_bwd)


def grouped_matmul(rows, weights, group_sizes, one_device=True):
    """rows (N, K) grouped contiguously, weights (E, K, F),
    group_sizes (E,) summing to N -> (N, F): group e of the rows times
    ``weights[e]``.

    Two implementations, chosen from what the call can see. On one TPU
    device, for bfloat16 rows in whole tiles: ``pallas_grouped_matmul``.
    Otherwise ``jax.lax.ragged_dot``, which XLA compiles to Mosaic
    kernels of its own on a TPU and to plain dots on the CPU. Timed in
    the OLMoE cell's step on a v5e, not alone: 31.9 samples/s against
    29.1 (PERF.md, PR 25)."""
    impl = resolve_grouped_matmul(rows.shape[0], rows.dtype, one_device)
    if impl == "pallas_gmm":
        out = pallas_grouped_matmul(rows, weights, group_sizes)
    else:
        out = jax.lax.ragged_dot(rows, weights, group_sizes)
    return checkpoint_name(out, MOE_MATMUL_NAME)


def load_balancing_loss(probs, group_sizes, axes=()):
    """E x sum_e f_e P_e (OLMoE, arXiv:2409.02060 eq. 3; Switch's form
    over all k choices): f_e the share of the tokens that chose expert
    e among their k (so the f sum to k), P_e the mean router
    probability of e. 1 x k for a uniform router. ``axes``: inside a
    manual region, the mesh axes whose ranks' tokens are one batch;
    ``group_sizes`` are then the loads summed over them and ``probs``
    this rank's."""
    tokens, num_experts = probs.shape
    if axes:
        # jax's own psum / pmean here and below: the region is
        # differentiated from OUTSIDE (``MoeMlp._sorted_over_ep``'s
        # unchecked ``shard_map``), whose transpose expects them
        # (``parallel/collectives.py`` pins the other convention, for a
        # vjp taken inside a region)
        tokens = tokens * jax.lax.psum(1, axes)
    share = group_sizes.astype(jnp.float32) / tokens
    mean = probs.mean(axis=0)
    if axes:
        mean = jax.lax.pmean(mean, axes)
    return num_experts * jnp.sum(share * mean)


def sequence_balance_loss(probs, experts, num_sequences, axes=()):
    """DeepSeek-V3's complementary sequence-wise balance loss
    (arXiv:2412.19437 eq. 17-20), the mean over the sequences of
    ``sum_e f_e P_e``: within ONE sequence of T tokens ``f_e = E / (k
    T) x`` the (token, choice) pairs that chose expert e and ``P_e`` the
    mean over its tokens of the normalised scores. 1 for a uniform
    router. probs (B*T, E) float32, experts (B*T, k). ``axes`` as
    ``load_balancing_loss`` takes them: the mean is then over all
    those ranks' sequences, as many on each."""
    num_experts, k = probs.shape[-1], experts.shape[-1]
    probs = probs.reshape(num_sequences, -1, num_experts)
    tokens = probs.shape[1]
    chosen = experts.reshape(num_sequences, tokens * k)
    # a compare fused into its reduction, as ``sort_by_expert`` counts
    counts = jnp.sum(
        chosen[:, :, None]
        == jnp.arange(num_experts, dtype=chosen.dtype)[None, None],
        axis=1, dtype=jnp.float32,
    )
    share = counts * (num_experts / (k * tokens))
    loss = jnp.sum(share * probs.mean(axis=1), axis=-1).mean()
    return jax.lax.pmean(loss, axes) if axes else loss


def balancing_bias_update(bias, group_sizes, speed):
    """DeepSeek-V3's auxiliary-loss-free balancing (arXiv:2412.19437
    2.1.2; arXiv:2408.15664): after a step's routing, an overloaded
    expert's selection bias falls by ``speed`` and an underloaded one's
    rises, ``bias += speed x sign(mean_load - load_e)``. No gradient
    reaches it; the optimizer never sees it."""
    load = group_sizes.astype(jnp.float32)
    return bias + speed * jnp.sign(load.mean() - load)


def router_z_loss(router_logits):
    """Mean over tokens of logsumexp(logits)^2 (ST-MoE, arXiv:2202.08906
    eq. 5; OLMoE eq. 4), in float32."""
    z = jax.nn.logsumexp(router_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(jnp.square(z))


def routing_stats(probs, group_sizes, k, held=None, dropped=None,
                  buffer_rows=None, axes=()):
    """What the ``moe_routing`` journal event reports of one expert
    layer, as device scalars: pairs per expert (largest and mean, over
    ALL experts), the router's mean entropy in nats, and the pairs that
    reached no expert (counted from the group sizes; the sorted path
    drops none). A layer that holds a share of the experts
    (``sort_held``) also reports ``held``, the pairs whose expert lives
    here, its ``dropped`` are those of them its buffer had no row for,
    and of the ``rows_buffer`` rows of its buffer (``buffer_rows``) the
    ``rows_run`` that this step ran (``rows_run``: the prefix that
    holds the pairs). ``axes`` as ``load_balancing_loss`` takes them:
    ``group_sizes`` and ``dropped`` are then over all those ranks."""
    tokens = probs.shape[0]
    entropy = -jnp.sum(probs * jnp.log(probs + 1e-30), axis=-1).mean()
    if axes:
        entropy = jax.lax.pmean(entropy, axes)
    stats = {
        "load_max": group_sizes.max().astype(jnp.float32),
        "load_mean": group_sizes.astype(jnp.float32).mean(),
        "entropy": entropy,
        "dropped": (
            tokens * k - group_sizes.sum() if dropped is None else dropped
        ).astype(jnp.float32),
    }
    if held is not None:
        stats["held"] = held.astype(jnp.float32)
        stats["rows_run"] = rows_run(held, buffer_rows).astype(jnp.float32)
        stats["rows_buffer"] = jnp.float32(buffer_rows)
    return stats



def exchange_stats(sent, row_bytes, buffer_rows, axes=()):
    """What the ``moe_routing`` event reports of one expert layer's
    exchange, from a plan's ``sent`` (ranks, ranks; ``exchange_plan``):
    ``sent`` the pairs a rank sent to OTHER ranks, ``received_max`` and
    ``received_mean`` the rows a rank received, its own pairs among
    them (what its grouped matmuls run), all three by rank: the mean,
    the largest and the mean; ``exchange_bytes``: what one rank sends a
    step in this layer's four passes (the dispatch and the combine,
    forward and backward), ``sent`` x ``row_bytes`` x 4;
    ``received_run`` of ``received_buffer``: the rows of its
    ``buffer_rows`` receive buffer that the busiest rank's regrouping
    ran (``received_rows_run``; the ranks meet at every collective, so
    the busiest sets the pace). ``axes``: the data axes besides the
    exchange's own, over whose expert groups the ranks are counted."""
    sent = sent.astype(jnp.float32)
    received = sent.sum(axis=0)
    left = (sent.sum(axis=1) - jnp.diagonal(sent)).mean()
    stats = {
        "sent": left,
        "received_max": received.max(),
        "received_mean": received.mean(),
        "exchange_bytes": left * (4.0 * row_bytes),
    }
    if axes:
        stats = {
            name: (jax.lax.pmax if name == "received_max"
                   else jax.lax.pmean)(value, axes)
            for name, value in stats.items()}
    stats["received_run"] = received_rows_run(
        stats["received_max"], buffer_rows)
    stats["received_buffer"] = jnp.float32(buffer_rows)
    return stats
