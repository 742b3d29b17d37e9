"""Attention over keys that a learned indexer picks (DeepSeek Sparse
Attention, the DeepSeek-V3.2-Exp report): a mask that is DATA.

Every other softmax mixer attends under a static layout
(``ops/flash_attention.py``: ``keep`` is arithmetic on positions). Here
a light indexer scores every (query, key) pair of the causal prefix,

    I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])        s <= t,

a query keeps the ``min(topk, t + 1)`` keys of the largest ``I``, ties
to the lower position (``jax.lax.top_k``'s order), and the main
attention is a softmax over the kept keys alone, forward and backward.
The indexer learns from a term of its own,

    L_I = mean_t KL( p[t, .] || softmax_{s in S_t} I[t, s] ),
    p[t, s] = stop_gradient( mean_h A_h[t, s] ),

and from nothing else: the selection passes no gradient, and the caller
hands the indexer a detached input.

``dsa_attention`` is the op a model calls. Two implementations of the
same equations:

- ``"xla"``: ``jax.numpy`` lines on dense ``(S, S)`` scores
  (``scores_reference``, ``select_reference``, a dense masked softmax),
  differentiated by ``jax.grad``. The CPU's path, small shapes', and
  the kernels' oracle.
- ``"pallas"``: five Mosaic kernels on the causal rectangle of tiles,
  none of which ever holds an ``(S, S)`` float:

  ``dsa_select``         a block of queries' scores into VMEM as
                         sortable integers, and the k-th largest of each
                         row by bisection on their bits (32 counting
                         passes; then, only where a row's threshold
                         value occurs more often than it may be kept,
                         the cut-off position among the equals by
                         bisection on positions): ``(threshold, tie
                         position)`` a query. Exact, no sort.
  ``dsa_mask``           the scores again, a tile at a time, compared
                         with the query's pair: the kept set as BITS,
                         eight keys a byte by planes (``_planes``: bit
                         ``b`` of byte ``[t, j]`` is the pair ``(t, b *
                         S / 8 + j)``, an int8 ``(S, S / 8)``, 134 MB at
                         32,768, which a remat policy saves by name so
                         that a block's backward makes nothing of the
                         selection again), with the rows' statistics
                         (``lse`` of ``I`` over the kept set, its
                         entropy, kept keys, kept keys among the nearest
                         ``topk``).
  ``flash_sparse_fwd``   the flash forward and the fused backward of
  ``flash_sparse_bwd``   ``ops/flash_attention.py`` with the tile's mask
                         READ, not computed (a tile's bytes are one
                         aligned block of the packed array, its bit the
                         plane its k-block lies in): they walk every
                         tile of the causal prefix (a seeded indexer's
                         picks lie spread over it, so no tile is empty;
                         a tile list for a trained indexer's clustered
                         picks is ROADMAP M16's).
  ``dsa_indexer_loss``   per tile: all heads' probabilities from
                         ``lse`` (their mean is ``p``), the scores again,
                         ``softmax(I) - p`` (the scores' cotangent), the
                         row's KL, and the cotangents of ``qI``, ``kI``
                         and ``w``: the term AND its gradient in one
                         pass, attached by an identity-primal
                         ``custom_vjp`` as ``flash_attention._attach``
                         attaches its backward.

``select`` is ONE function from scores to the kept set (the reading of
``q_chunk_size`` / ``kv_chunk_size`` not taken, a selection shared by a
chunk of queries over chunks of keys, would change it and
``_select_rows``, nothing else). A sequence no longer than ``topk``
keeps every query's whole prefix: the call then IS the causal one
(``dot_product_attention``), bit for bit.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.ops import flash_attention as _flash
from elasticdl_tpu.ops.attention import dot_product_attention

logger = _logger_factory("elasticdl_tpu.ops.sparse_attention")

NEG_INF = _flash.NEG_INF
_INT_MIN = -2**31
# checkpoint_name labels: the kept set in bits and the indexer's term
# with its cotangents. A remat policy that saves them
# (``models/transformer.py:remat_block``: "flash", "dots") re-runs
# neither the selection's two kernels nor the term's in a block's
# backward.
DSA_SELECT_NAME = "dsa_select"
DSA_LOSS_NAME = "dsa_indexer_loss"
DSA_SAVE_NAMES = (DSA_SELECT_NAME, DSA_LOSS_NAME)
# queries a grid step of ``dsa_select`` holds (their row of sortable
# scores is ``rows x S x 4`` bytes of VMEM: 16 MiB at 32,768), and the
# keys a chunk of its passes reads
_SELECT_ROWS = 128
_SELECT_CHUNK = 512
_VMEM_BYTES = 100 * 2**20
# the stated block of queries whose scores a probe hands out: the last
PROBE_QUERIES = 512


# ---------------------------------------------------------------------------
# The equations as jax.numpy lines
# ---------------------------------------------------------------------------


def _scores_of(qi, ki, w, q_pos):
    """``I`` (B, T, S) float32 of the queries at ``q_pos`` (T,), theirs
    ``qi`` (B, J, T, D) and ``w`` (B, T, J); ``NEG_INF`` where a key
    lies after its query."""
    r = jnp.einsum(
        "bjtd,bsd->bjts", qi, ki, preferred_element_type=jnp.float32)
    scores = jnp.einsum("bjts,btj->bts", jnp.maximum(r, 0.0), w)
    seen = q_pos[:, None] >= jnp.arange(ki.shape[1])[None, :]
    return jnp.where(seen, scores, NEG_INF)


def scores_reference(qi, ki, w):
    """``I`` (B, S, S) float32, ``NEG_INF`` above the diagonal. ``qi``
    (B, J, S, D), ``ki`` (B, S, D), ``w`` (B, S, J) float32."""
    return _scores_of(qi, ki, w, jnp.arange(ki.shape[1]))


def select_reference(scores, topk):
    """The kept set (B, T, S) bool of causal ``scores`` (``NEG_INF``
    where a key lies after its query, rows ``t = 0 .. T - 1``): the
    ``min(topk, t + 1)`` largest of a row, ties to the lower position,
    by ``jax.lax.top_k`` itself."""
    batch, rows, seq = scores.shape
    _, index = jax.lax.top_k(scores, min(topk, seq))
    keep = jnp.zeros((batch, rows, seq), bool)
    keep = keep.at[
        jnp.arange(batch)[:, None, None], jnp.arange(rows)[None, :, None],
        index].set(True)
    return keep & (scores > NEG_INF / 2)


def _sortable(scores):
    """float32 -> int32 of the same TOTAL order (-0.0 below +0.0, as
    ``jax.lax.top_k``'s comparison reads them)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def select(scores, topk):
    """``select_reference``'s set without a sort: the k-th largest of a
    row by bisection on the scores' bits, then the cut-off position
    among the threshold's equals. The arithmetic of ``dsa_select`` and
    ``dsa_mask`` as ``jax.numpy`` lines (``_select_rows`` + ``_kept``);
    the tests hold all three to ``select_reference``."""
    rows, seq = scores.shape[-2:]
    q_pos = jnp.arange(rows)[:, None]
    k_pos = jnp.arange(seq)[None, :]
    key = jnp.where(k_pos <= q_pos, _sortable(scores), _INT_MIN)
    threshold, tie = _select_rows(
        key, jnp.minimum(topk, q_pos + 1), k_pos, seq)
    return _kept(key, threshold, tie, q_pos, k_pos)


def _select_rows(key, k_row, k_pos, seq, count=None):
    """``(threshold, tie)`` (rows, 1) int32 of sortable ``key`` (...,
    rows, S): the largest T with ``count(key >= T) >= k_row``, and the
    position of the last of T's equals that is kept. ``count(pred)``
    sums a predicate of ``(key, k_pos)`` over a row (the kernel's walks
    its chunks)."""
    if count is None:
        count = lambda pred: jnp.sum(
            pred(key, k_pos).astype(jnp.int32), axis=-1, keepdims=True)
    at_least = lambda t: count(lambda k, _: k >= t)
    threshold = jnp.where(at_least(0) >= k_row, 0, _INT_MIN)

    def bit(i, threshold):
        cand = threshold | jax.lax.shift_left(jnp.int32(1), 30 - i)
        return jnp.where(at_least(cand) >= k_row, cand, threshold)

    threshold = jax.lax.fori_loop(0, 31, bit, threshold)
    need = k_row - count(lambda k, _: k > threshold)
    equals = count(lambda k, _: k == threshold)

    def position(i, first):
        cand = first | jax.lax.shift_left(
            jnp.int32(1), (seq - 1).bit_length() - 1 - i)
        before = count(lambda k, p: (k == threshold) & (p < cand))
        return jnp.where(before < need, cand, first)

    # only a row whose threshold has more equals than it may keep needs
    # the second bisection; every other keeps them all
    tie = jax.lax.cond(
        jnp.max(equals - need) > 0,
        lambda: jax.lax.fori_loop(
            0, (seq - 1).bit_length(), position, jnp.zeros_like(need)),
        lambda: jnp.full_like(need, seq))
    return threshold, tie


def _kept(key, threshold, tie, q_pos, k_pos):
    """The kept set from a query's pair: above the threshold, or equal
    to it no later than the tie position; never after the query."""
    return (k_pos <= q_pos) & (
        (key > threshold) | ((key == threshold) & (k_pos <= tie)))


def pack_planes(keep, planes):
    """``keep`` (..., S) bool as int8 (..., S / planes): bit ``b`` of
    byte ``j`` is key ``b * S / planes + j``. What ``dsa_mask`` writes
    as ``jax.numpy`` lines; one plane is a byte a key."""
    width = keep.shape[-1] // planes
    bits = keep.reshape(keep.shape[:-1] + (planes, width)).astype(jnp.uint8)
    shifts = jnp.arange(planes, dtype=jnp.uint8)[:, None]
    return jax.lax.bitcast_convert_type(
        jnp.sum(bits << shifts, axis=-2, dtype=jnp.uint8), jnp.int8)


def unpack_planes(packed, planes):
    """``pack_planes`` back: (..., S / planes) int8 to (..., S) bool."""
    bits = jax.lax.bitcast_convert_type(packed, jnp.uint8)[..., None, :]
    shifts = jnp.arange(planes, dtype=jnp.uint8)[:, None]
    return (((bits >> shifts) & 1) != 0).reshape(
        packed.shape[:-1] + (planes * packed.shape[-1],))


def indexer_kl(probs_mean, scores, keep):
    """``KL(p || softmax_kept(I))`` a query, (B, S): ``probs_mean`` is
    ``p`` (it sums to 1 over the kept set), ``scores`` ``I``."""
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, NEG_INF), axis=-1)
    live = keep & (probs_mean > 0)
    safe = jnp.where(live, probs_mean, 1.0)
    return jnp.sum(
        jnp.where(live, safe * (jnp.log(safe) - log_q), 0.0), axis=-1)


def select_facts(scores, keep, topk):
    """What the ``dsa_select`` event says of a layer, from dense scores
    and the kept set: kept keys a query (mean), the entropy of
    ``softmax(I)`` over the kept set (mean, nats), and the share of the
    kept keys that lie among the query's nearest ``topk``."""
    seq = scores.shape[-1]
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, NEG_INF), axis=-1)
    entropy = -jnp.sum(jnp.where(keep, jnp.exp(log_q) * log_q, 0.0), -1)
    near = (jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]) < topk
    kept = keep.sum(-1)
    return {
        "kept_mean": kept.mean().astype(jnp.float32),
        "entropy": entropy.mean(),
        "near_share": (keep & near).sum() / jnp.maximum(kept.sum(), 1),
    }


def _probe(keep, after, qi, ki, w):
    """What a reference check asks of a layer beside its facts
    (``dsa_attention``'s ``probe``): the kept set, 8 keys a byte
    (``jnp.packbits`` along the keys); the entries kept AFTER their
    query (0); and ``I`` of the last ``PROBE_QUERIES`` queries from the
    call's own operands, 128 queries at a time."""
    seq = keep.shape[-1]
    tail = min(PROBE_QUERIES, seq)
    rows = min(128, tail)
    q_pos = jnp.arange(seq - tail, seq).reshape(-1, rows)
    stop = jax.lax.stop_gradient
    qi_tail = stop(qi)[:, :, seq - tail:].reshape(
        qi.shape[:2] + (-1, rows, qi.shape[-1]))
    w_tail = stop(w)[:, seq - tail:].reshape(
        w.shape[0], -1, rows, w.shape[-1])

    scores = jax.lax.map(
        lambda args: _scores_of(args[0], stop(ki), args[1], args[2]),
        (jnp.moveaxis(qi_tail, 2, 0), jnp.moveaxis(w_tail, 1, 0), q_pos))
    return {
        "kept_bits": jnp.packbits(keep, axis=-1),
        "kept_after": after.astype(jnp.float32),
        "scores_tail": jnp.moveaxis(scores, 0, 1).reshape(
            keep.shape[0], tail, seq),
    }


def _dense(q, k, v, qi, ki, w, topk, sm_scale, probe=False):
    """``(out, kl (B,), facts)`` on dense scores."""
    scores = scores_reference(qi, ki, w)
    keep = jax.lax.stop_gradient(select_reference(scores, topk))
    group = q.shape[1] // k.shape[1]
    k_all, v_all = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k_all, preferred_element_type=jnp.float32
    ) * sm_scale
    probs = jax.nn.softmax(jnp.where(keep[:, None], s, NEG_INF), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v_all)
    kl = indexer_kl(
        jax.lax.stop_gradient(probs.mean(axis=1)), scores, keep)
    facts = select_facts(jax.lax.stop_gradient(scores), keep, topk)
    if probe:
        seq = keep.shape[-1]
        after = keep & (jnp.arange(seq)[:, None] < jnp.arange(seq)[None, :])
        facts.update(_probe(keep, after.sum(), qi, ki, w))
    return out, kl.mean(axis=-1), facts


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _score_tile(qi_ref, k_tile, w):
    """``I`` of a tile, float32 (rows, keys): ``qi_ref`` the block (1,
    J, rows, D), ``k_tile`` (keys, D), ``w`` (rows, J) float32. One
    order of operations for every kernel that needs a score, so that a
    pair's score is the same bits in each."""
    acc = None
    for j in range(qi_ref.shape[1]):
        r = jax.lax.dot_general(
            qi_ref[0, j], k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        term = w[:, j:j + 1] * jnp.maximum(r, 0.0)
        acc = term if acc is None else acc + term
    return acc


def _positions(q_block, k_start, rows, keys):
    q_pos = q_block * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
    return q_pos, k_pos


def _select_kernel(qi_ref, ki_ref, w_ref, thr_ref, tie_ref, key_ref,
                   thr_scr, tie_scr, *, topk, rows, chunk, seq):
    block = pl.program_id(1)
    # the chunks of keys some query of the block may see
    chunks = jax.lax.div((block + 1) * rows + chunk - 1, chunk)
    w = w_ref[0]

    def fill(c, carry):
        start = pl.multiple_of(c * chunk, chunk)
        scores = _score_tile(qi_ref, ki_ref[0, pl.ds(start, chunk), :], w)
        q_pos, k_pos = _positions(block, start, rows, chunk)
        key_ref[:, pl.ds(start, chunk)] = jnp.where(
            k_pos <= q_pos, _sortable(scores), _INT_MIN)
        return carry

    jax.lax.fori_loop(0, chunks, fill, 0)

    def count(pred):
        def body(c, acc):
            start = pl.multiple_of(c * chunk, chunk)
            _, k_pos = _positions(block, start, rows, chunk)
            hit = pred(key_ref[:, pl.ds(start, chunk)], k_pos)
            return acc + jnp.sum(
                hit.astype(jnp.int32), axis=1, keepdims=True)
        return jax.lax.fori_loop(
            0, chunks, body, jnp.zeros((rows, 1), jnp.int32))

    q_pos, _ = _positions(block, 0, rows, chunk)
    threshold, tie = _select_rows(
        None, jnp.minimum(topk, q_pos + 1), None, seq, count=count)
    thr_scr[:] = jnp.broadcast_to(threshold, thr_scr.shape)
    tie_scr[:] = jnp.broadcast_to(tie, tie_scr.shape)
    thr_ref[0, 0] = thr_scr[:, 0]
    tie_ref[0, 0] = tie_scr[:, 0]


def _select_call(qi, ki, w, topk, interpret):
    """``(threshold, tie)`` (B, 1, S) int32."""
    batch, heads, seq, dim = qi.shape
    rows = min(_SELECT_ROWS, seq)
    chunk = min(_SELECT_CHUNK, seq)
    kernel = functools.partial(
        _select_kernel, topk=topk, rows=rows, chunk=chunk, seq=seq)
    row_spec = pl.BlockSpec((1, 1, rows), lambda b, i: (b, 0, i))
    out = _flash._out_struct((batch, 1, seq), jnp.int32, qi, ki, w)
    return pl.pallas_call(
        kernel,
        grid=(batch, seq // rows),
        in_specs=[
            pl.BlockSpec((1, heads, rows, dim), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, seq, dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, rows, heads), lambda b, i: (b, i, 0)),
        ],
        out_specs=(row_spec, row_spec),
        scratch_shapes=[
            pltpu.VMEM((rows, seq), jnp.int32),
            pltpu.VMEM((rows, _flash._STATS_LANES), jnp.int32),
            pltpu.VMEM((rows, _flash._STATS_LANES), jnp.int32),
        ],
        out_shape=(out, out),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
        name="dsa_select",
    )(qi, ki, w)


def _tiles(seq):
    """(block_q, block_k) of ``dsa_mask`` and ``dsa_indexer_loss``,
    whose tiles hold sixteen heads' products of scores."""
    return _flash._auto_block(seq, 512), _flash._auto_block(seq, 512)


def _flash_tiles(seq, head_dim, dtype, backward=False):
    """The tiles of ``flash_sparse_fwd`` / ``flash_sparse_bwd``: the
    causal kernels' own rule (``flash_attention._blocks``: 1024 x 1024
    from 8,192 positions on)."""
    return _flash._blocks(
        seq, seq, head_dim, dtype, None, None, backward=backward)


def _planes(seq, head_dim, dtype):
    """The keys a byte of the kept set holds: 8 where an eighth of the
    sequence is whole tiles of the widest of the three readers, else 4,
    2 or 1 (a byte a key); 0 where the sequence is not whole tiles of
    it. A reader's tile is then ONE block of the packed ``(S, S /
    planes)`` array and one bit, its k-block's plane."""
    widest = max(
        _tiles(seq)[1], _flash_tiles(seq, head_dim, dtype)[1],
        _flash_tiles(seq, head_dim, dtype, backward=True)[1])
    return next((p for p in (8, 4, 2, 1) if seq % (p * widest) == 0), 0)


def _causal_maps(block_q, block_k, num_q, k_outer=False):
    return _flash._index_maps(
        _flash.CAUSAL, block_q, block_k, num_q, k_outer=k_outer)


def _mask_kernel(qi_ref, ki_ref, w_ref, thr_ref, tie_ref,
                 mask_ref, lse_ref, ent_ref, kept_ref, near_ref,
                 m_scr, l_scr, e_scr, kept_scr, near_scr,
                 *, topk, block_q, block_k):
    """``mask_ref`` is the q-block's whole packed row block (1,
    block_q, S / planes), resident over the k-blocks: zeroed at the
    first, and a tile ORs its kept set into its columns at its plane's
    bit. The k-blocks come in the order of their positions, so a row's
    statistics sum in one order whatever the planes."""
    q_block = pl.program_id(1)
    k_block = pl.program_id(2)
    steps = pl.num_programs(2)
    last_k, _, _ = _flash._causal_pair(q_block, k_block, block_q, block_k)
    per_plane = mask_ref.shape[2] // block_k

    @pl.when(k_block == 0)
    def _init():
        mask_ref[...] = jnp.zeros_like(mask_ref)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        for ref in (l_scr, e_scr, kept_scr, near_scr):
            ref[:] = jnp.zeros_like(ref)

    @pl.when(k_block <= last_k)
    def _tile():
        scores = _score_tile(qi_ref, ki_ref[0], w_ref[0])
        q_pos, k_pos = _positions(q_block, k_block * block_k,
                                  block_q, block_k)
        key = jnp.where(k_pos <= q_pos, _sortable(scores), _INT_MIN)
        keep = _kept(key, thr_ref[0, 0][:, None], tie_ref[0, 0][:, None],
                     q_pos, k_pos)
        cols = pl.ds(pl.multiple_of(
            jax.lax.rem(k_block, per_plane) * block_k, block_k), block_k)
        bits = jax.lax.shift_left(
            keep.astype(jnp.int32), jax.lax.div(k_block, per_plane))
        mask_ref[0, :, cols] = (
            mask_ref[0, :, cols].astype(jnp.int32) | bits).astype(jnp.int8)
        kept = keep.astype(jnp.float32)
        near = jnp.where(q_pos - k_pos < topk, kept, 0.0)
        s = jnp.where(keep, scores, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        wide = lambda x: jnp.broadcast_to(x, m_scr.shape)
        l_scr[:] = wide(l_scr[:, :1] * correction + jnp.sum(
            p, axis=1, keepdims=True))
        e_scr[:] = wide(e_scr[:, :1] * correction + jnp.sum(
            p * jnp.where(keep, scores, 0.0), axis=1, keepdims=True))
        kept_scr[:] = wide(
            kept_scr[:, :1] + jnp.sum(kept, axis=1, keepdims=True))
        near_scr[:] = wide(
            near_scr[:, :1] + jnp.sum(near, axis=1, keepdims=True))
        m_scr[:] = wide(m_new)

    @pl.when(k_block == steps - 1)
    def _finalize():
        l_final = jnp.maximum(l_scr[:, 0], 1e-30)
        lse = m_scr[:, 0] + jnp.log(l_final)
        lse_ref[0, 0] = lse
        # H = lse - E[I] under softmax(I) over the kept set
        ent_ref[0, 0] = lse - e_scr[:, 0] / l_final
        kept_ref[0, 0] = kept_scr[:, 0]
        near_ref[0, 0] = near_scr[:, 0]


def _mask_call(qi, ki, w, threshold, tie, topk, planes, interpret):
    """``(kept set (B, S, S / planes) int8 by planes, lse_I, entropy,
    kept, near)``, the last four (B, 1, S) float32. A tile above the
    diagonal computes nothing and fetches nothing; its bits stay 0."""
    batch, heads, seq, dim = qi.shape
    block_q, block_k = _tiles(seq)
    num_q = seq // block_q
    width = seq // planes
    q_idx, k_idx, stat_idx = _causal_maps(block_q, block_k, num_q)
    operands = (qi, ki, w, threshold, tie)
    stat = _flash._out_struct((batch, 1, seq), jnp.float32, *operands)
    stat_spec = pl.BlockSpec((1, 1, block_q), stat_idx)
    scratch = pltpu.VMEM((block_q, _flash._STATS_LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _mask_kernel, topk=topk, block_q=block_q, block_k=block_k),
        grid=(batch, num_q, seq // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, heads, block_q, dim), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_k, dim), k_idx),
            pl.BlockSpec((1, block_q, heads), q_idx),
            stat_spec, stat_spec,
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0)),
            stat_spec, stat_spec, stat_spec, stat_spec,
        ),
        scratch_shapes=[scratch] * 5,
        out_shape=(
            _flash._out_struct((batch, seq, width), jnp.int8, *operands),
            stat, stat, stat, stat,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="dsa_mask",
    )(*operands)


def _kept_tile(mask_ref, k_block, per_plane):
    """The tile's kept set, bool, from its block of the packed array:
    the bit of the plane that ``k_block`` lies in."""
    bit = jax.lax.shift_left(1, jax.lax.div(k_block, per_plane))
    return (mask_ref[0].astype(jnp.int32) & bit) != 0


def _masked(s, mask_ref, k_block, per_plane):
    return jnp.where(_kept_tile(mask_ref, k_block, per_plane), s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, block_q, block_k,
                per_plane):
    """``flash_attention._fwd_kernel`` on the causal rectangle, the
    tile's mask read from ``mask_ref``."""
    q_block = pl.program_id(1)
    k_block = pl.program_id(2)
    steps = pl.num_programs(2)
    last_k, _, _ = _flash._causal_pair(q_block, k_block, block_q, block_k)

    @pl.when(k_block == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(k_block <= last_k)
    def _tile():
        v = v_ref[0]
        s = _masked(jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale,
            mask_ref, k_block, per_plane)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_ref[:, :1] * correction + jnp.sum(
            p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(k_block == steps - 1)
    def _finalize():
        l_final = l_ref[:, :1]
        safe_l = jnp.where(l_final > 0.0, l_final, 1.0)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (
            m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30)))


def _mask_index_map(idx, heads, per_plane):
    """A mask tile's index map from the q-ish / k-ish ones of merged
    head ``b``: the batch is ``b // heads`` (``heads`` 1 on a grid over
    the batch), the columns the k-block's place in its plane."""
    q_idx, k_idx = idx

    def tile(b, outer, inner):
        return (jax.lax.div(b, heads), q_idx(b, outer, inner)[1],
                jax.lax.rem(k_idx(b, outer, inner)[1], per_plane))

    return tile


def _fwd_call(q, k, v, mask, sm_scale, interpret):
    bh, seq, head_dim = q.shape
    heads = bh // mask.shape[0]
    block_q, block_k = _flash_tiles(seq, head_dim, q.dtype)
    num_q = seq // block_q
    per_plane = mask.shape[2] // block_k
    q_idx, k_idx, stat_idx = _causal_maps(block_q, block_k, num_q)
    kv_idx = _flash._kv_index_map(k_idx, bh // k.shape[0])
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, block_q=block_q,
            block_k=block_k, per_plane=per_plane),
        grid=(bh, num_q, seq // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, block_k, head_dim), kv_idx),
            pl.BlockSpec((1, block_k, head_dim), kv_idx),
            pl.BlockSpec(
                (1, block_q, block_k),
                _mask_index_map((q_idx, k_idx), heads, per_plane)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, 1, block_q), stat_idx),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, _flash._STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, _flash._STATS_LANES), jnp.float32),
        ],
        out_shape=(
            _flash._out_struct((bh, seq, head_dim), q.dtype, q, k, v),
            _flash._out_struct((bh, 1, seq), jnp.float32, q, k, v),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_sparse_fwd",
    )(q, k, v, mask)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc,
                *, sm_scale, block_q, block_k, per_plane):
    """``flash_attention._dkv_kernel(with_dq=True)`` on the causal
    rectangle, the tile's mask read: five score-sized products a tile,
    dq's accumulator one head's whole ``(S, d)`` in VMEM."""
    k_block = pl.program_id(1)
    q_block = pl.program_id(2)
    grid_k = pl.num_programs(1)
    steps = pl.num_programs(2)
    _, first_q, _ = _flash._causal_pair(q_block, k_block, block_q, block_k)
    rows = pl.ds(pl.multiple_of(q_block * block_q, block_q), block_q)

    @pl.when(q_block == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(k_block == 0)
    def _init_dq():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    @pl.when(q_block >= first_q)
    def _tile():
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        s = _masked(jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale,
            mask_ref, k_block, per_plane)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0][:, None]) * sm_scale).astype(
            q.dtype)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[rows, :] += jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(q_block == steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(k_block == grid_k - 1)
    def _finalize_dq():
        dq_ref[0, rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, mask, sm_scale, interpret):
    bh, seq, head_dim = q.shape
    heads = bh // mask.shape[0]
    group = bh // k.shape[0]
    block_q, block_k = _flash_tiles(seq, head_dim, q.dtype, backward=True)
    num_q = seq // block_q
    per_plane = mask.shape[2] // block_k
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )[:, None, :]
    q_idx, k_idx, stat_idx = _causal_maps(
        block_q, block_k, num_q, k_outer=True)
    kv_idx = _flash._kv_index_map(k_idx, group)
    operands = (q, k, v, do, lse, delta, mask)
    kv_dtype = k.dtype if group == 1 else jnp.float32
    kv_struct = _flash._out_struct(
        (bh, seq, head_dim), kv_dtype, *operands)
    dk, dv, dq = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, block_q=block_q,
            block_k=block_k, per_plane=per_plane),
        grid=(bh, seq // block_k, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, block_k, head_dim), kv_idx),
            pl.BlockSpec((1, block_k, head_dim), kv_idx),
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, 1, block_q), stat_idx),
            pl.BlockSpec((1, 1, block_q), stat_idx),
            pl.BlockSpec(
                (1, block_q, block_k),
                _mask_index_map((q_idx, k_idx), heads, per_plane)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, head_dim), k_idx),
            pl.BlockSpec((1, block_k, head_dim), k_idx),
            pl.BlockSpec((1, seq, head_dim), lambda b, j, i: (b, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((seq, head_dim), jnp.float32),
        ],
        out_shape=(
            kv_struct, kv_struct,
            _flash._out_struct(q.shape, q.dtype, *operands),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_flash._FUSED_VMEM_BYTES,
        ),
        interpret=interpret,
        name="flash_sparse_bwd",
    )(*operands)
    if group > 1:
        # a kv head's gradient is its group's sum (``flash_attention
        # ._bwd``: after the kernel, in float32, rounded once)
        dk, dv = (
            d.reshape((-1, group) + d.shape[1:]).sum(axis=1).astype(x.dtype)
            for d, x in ((dk, k), (dv, v)))
    return dq, dk, dv


def _loss_kernel(q_ref, k_ref, lse_ref, mask_ref, qi_ref, ki_ref, w_ref,
                 lsei_ref, kl_ref, dqi_ref, dw_ref, dki_ref,
                 kl_acc, dqi_acc, dw_acc, dki_acc,
                 *, sm_scale, group, block_q, block_k, per_plane):
    """The indexer's term and its gradient on one tile, grid (batch,
    q-block, k-block). ``p`` is the mean over the heads of ``exp(s_h -
    lse_h)`` on the kept entries; the scores' cotangent (before the
    mean over queries) ``softmax(I) - p``; dqi and dw sum over the
    k-blocks of a q-block, dki over the q-blocks of a k-block, so its
    accumulator is the batch row's whole ``(S, D)`` in VMEM as
    ``flash_bwd``'s dq is."""
    q_block = pl.program_id(1)
    k_block = pl.program_id(2)
    grid_q = pl.num_programs(1)
    steps = pl.num_programs(2)
    last_k, _, _ = _flash._causal_pair(q_block, k_block, block_q, block_k)
    keys = pl.ds(pl.multiple_of(k_block * block_k, block_k), block_k)
    heads = q_ref.shape[1]

    @pl.when(k_block == 0)
    def _init():
        for ref in (kl_acc, dqi_acc, dw_acc):
            ref[:] = jnp.zeros_like(ref)

    @pl.when(q_block == 0)
    def _init_dki():
        dki_acc[keys, :] = jnp.zeros((block_k, dki_acc.shape[1]), jnp.float32)

    @pl.when(k_block <= last_k)
    def _tile():
        keep = _kept_tile(mask_ref, k_block, per_plane)

        def head(h, total):
            s = jax.lax.dot_general(
                q_ref[0, h], k_ref[0, jax.lax.div(h, group)],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            return total + jnp.exp(s - lse_ref[0, h, 0][:, None])

        total = jax.lax.fori_loop(
            0, heads, head, jnp.zeros((block_q, block_k), jnp.float32))
        p = jnp.where(keep, total * (1.0 / heads), 0.0)
        ki = ki_ref[0]
        w = w_ref[0]
        scores = _score_tile(qi_ref, ki, w)
        log_q = scores - lsei_ref[0, 0][:, None]
        live = keep & (p > 0.0)
        safe = jnp.where(live, p, 1.0)
        kl_acc[:] += jnp.broadcast_to(jnp.sum(
            jnp.where(live, safe * (jnp.log(safe) - log_q), 0.0),
            axis=1, keepdims=True), kl_acc.shape)
        d_scores = jnp.where(keep, jnp.exp(log_q), 0.0) - p
        for j in range(qi_ref.shape[1]):
            qi = qi_ref[0, j]
            r = jax.lax.dot_general(
                qi, ki, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dw_acc[:, j:j + 1] += jnp.sum(
                d_scores * jnp.maximum(r, 0.0), axis=1, keepdims=True)
            dr = jnp.where(r > 0.0, d_scores * w[:, j:j + 1], 0.0).astype(
                qi.dtype)
            dqi_acc[j] += jnp.dot(
                dr, ki, preferred_element_type=jnp.float32)
            dki_acc[keys, :] += jax.lax.dot_general(
                dr, qi, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(k_block == steps - 1)
    def _finalize():
        kl_ref[0, 0] = kl_acc[:, 0]
        dqi_ref[0] = dqi_acc[:].astype(dqi_ref.dtype)
        dw_ref[0] = dw_acc[:]

    @pl.when(q_block == grid_q - 1)
    def _finalize_dki():
        dki_ref[0, keys, :] = dki_acc[keys, :].astype(dki_ref.dtype)


def _loss_call(q, k, lse, mask, qi, ki, w, lse_i, sm_scale, interpret):
    """``(kl (B, 1, S), dqi, dw, dki)``: a query's KL and the cotangents
    of ``sum_t kl[t]`` to the indexer's three operands."""
    batch, heads, seq, head_dim = q.shape
    kv_heads = k.shape[1]
    idx_heads, idx_dim = qi.shape[1], qi.shape[3]
    block_q, block_k = _tiles(seq)
    num_q = seq // block_q
    per_plane = mask.shape[2] // block_k
    q_idx, k_idx, stat_idx = _causal_maps(block_q, block_k, num_q)
    moving = lambda b, i, j: k_idx(b, i, j)[1]
    operands = (q, k, lse, mask, qi, ki, w, lse_i)
    struct = lambda shape, dtype: _flash._out_struct(shape, dtype, *operands)
    return pl.pallas_call(
        functools.partial(
            _loss_kernel, sm_scale=sm_scale, group=heads // kv_heads,
            block_q=block_q, block_k=block_k, per_plane=per_plane),
        grid=(batch, num_q, seq // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, heads, block_q, head_dim), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec(
                (1, kv_heads, block_k, head_dim),
                lambda b, i, j: (b, 0, moving(b, i, j), 0)),
            pl.BlockSpec(
                (1, heads, 1, block_q), lambda b, i, j: (b, 0, 0, i)),
            pl.BlockSpec(
                (1, block_q, block_k),
                _mask_index_map((q_idx, k_idx), 1, per_plane)),
            pl.BlockSpec(
                (1, idx_heads, block_q, idx_dim),
                lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_k, idx_dim), k_idx),
            pl.BlockSpec((1, block_q, idx_heads), q_idx),
            pl.BlockSpec((1, 1, block_q), stat_idx),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q), stat_idx),
            pl.BlockSpec(
                (1, idx_heads, block_q, idx_dim),
                lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_q, idx_heads), q_idx),
            pl.BlockSpec((1, seq, idx_dim), lambda b, i, j: (b, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _flash._STATS_LANES), jnp.float32),
            pltpu.VMEM((idx_heads, block_q, idx_dim), jnp.float32),
            pltpu.VMEM((block_q, idx_heads), jnp.float32),
            pltpu.VMEM((seq, idx_dim), jnp.float32),
        ],
        out_shape=(
            struct((batch, 1, seq), jnp.float32),
            struct(qi.shape, qi.dtype),
            struct(w.shape, jnp.float32),
            struct(ki.shape, ki.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            # dki gathers over the q-blocks: only the batch is parallel
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
        name="dsa_indexer_loss",
    )(*operands)


# ---------------------------------------------------------------------------
# The two gradients, each attached to values that are already there
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _attach(q, k, v, o, lse, mask, sm_scale, interpret):
    return o


def _attach_fwd(q, k, v, o, lse, mask, sm_scale, interpret):
    return o, (q, k, v, o, lse, mask)


def _attach_bwd(sm_scale, interpret, res, do):
    q, k, v, o, lse, mask = res
    dq, dk, dv = _bwd_call(q, k, v, o, lse, do, mask, sm_scale, interpret)
    return (dq, dk, dv, jnp.zeros_like(o), jnp.zeros_like(lse),
            jnp.zeros(mask.shape, jax.dtypes.float0))


_attach.defvjp(_attach_fwd, _attach_bwd)


@jax.custom_vjp
def _attach_loss(qi, ki, w, kl, dqi, dki, dw):
    return kl


def _attach_loss_fwd(qi, ki, w, kl, dqi, dki, dw):
    return kl, (dqi, dki, dw)


def _attach_loss_bwd(res, g):
    dqi, dki, dw = res
    scale = lambda d: (
        g.reshape((-1,) + (1,) * (d.ndim - 1)) * d.astype(jnp.float32)
    ).astype(d.dtype)
    return (scale(dqi), scale(dki), scale(dw), jnp.zeros_like(g),
            jnp.zeros_like(dqi), jnp.zeros_like(dki), jnp.zeros_like(dw))


_attach_loss.defvjp(_attach_loss_fwd, _attach_loss_bwd)


def _by_kernels(q, k, v, qi, ki, w, topk, sm_scale, interpret,
                probe=False):
    """``(out, kl (B,), facts)`` by the kernels."""
    batch, heads, seq, head_dim = q.shape
    stop = jax.lax.stop_gradient
    qi_, ki_, w_ = stop(qi), stop(ki), stop(w)
    planes = _planes(seq, head_dim, q.dtype)
    with jax.named_scope("dsa/select"):
        threshold, tie = _select_call(qi_, ki_, w_, topk, interpret)
    with jax.named_scope("dsa/scores"):
        mask, lse_i, entropy, kept, near = _mask_call(
            qi_, ki_, w_, threshold, tie, topk, planes, interpret)
        mask = checkpoint_name(mask, DSA_SELECT_NAME)
    merge = lambda t: t.reshape((-1,) + t.shape[2:])
    with jax.named_scope("dsa/attend"):
        o, lse = _fwd_call(
            merge(stop(q)), merge(stop(k)), merge(stop(v)), mask, sm_scale,
            interpret)
        o = checkpoint_name(o, _flash.FLASH_OUT_NAME)
        lse = checkpoint_name(lse, _flash.FLASH_LSE_NAME)
        out = _attach(
            merge(q), merge(k), merge(v), o, lse, mask, sm_scale, interpret
        ).reshape(q.shape)
    with jax.named_scope("dsa/indexer_loss"):
        kl, dqi, dw, dki = _loss_call(
            stop(q), stop(k), lse.reshape(batch, heads, 1, seq), mask,
            qi_, ki_, w_, lse_i, sm_scale, interpret)
        # a sample's term is the mean over its queries
        share = lambda d: (d.astype(jnp.float32) * (1.0 / seq)).astype(
            d.dtype)
        kl, dqi, dki, dw = (
            checkpoint_name(x, DSA_LOSS_NAME) for x in (
                kl[:, 0].mean(axis=-1), share(dqi), share(dki), share(dw)))
        kl = _attach_loss(qi, ki, w, kl, dqi, dki, dw)
    kept_total = jnp.maximum(kept.sum(), 1.0)
    facts = {
        "kept_mean": kept.mean(),
        "entropy": entropy.mean(),
        "near_share": near.sum() / kept_total,
    }
    if probe:
        # what the mask keeps after a query is counted, not cut away
        q_pos = jnp.arange(seq)[:, None]
        k_pos = jnp.arange(seq)[None, :]
        kept_any = unpack_planes(mask, planes)
        facts.update(_probe(
            kept_any & (k_pos <= q_pos), (kept_any & (k_pos > q_pos)).sum(),
            qi, ki, w))
    return out, kl, facts


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------


def tiles_facts(seq, topk, head_dim=128, dtype=jnp.bfloat16):
    """What the attention line says of a call, from shapes: of one
    head's forward grid and of its backward one, ``(run, masked,
    skipped, block_q, block_k)`` (the first version runs every tile of
    the causal prefix, each under the mask); the kept entries a head,
    ``sum_t min(topk, t + 1)``; and those over the entries of the tiles
    that run, the forward's two score-sized products and the backward's
    five each over their own tiles (``sparse_attn_fill``)."""
    def grid(backward):
        blocks = _flash_tiles(seq, head_dim, dtype, backward)
        run, _, skipped = _flash.causal_pairs(
            seq, seq, *blocks, causal=True, k_outer=backward)
        return (run, run, skipped) + blocks

    full = min(seq, topk)
    kept = full * (full + 1) // 2 + (seq - full) * topk
    forward, backward = grid(False), grid(True)
    computed = sum(
        products * run * block_q * block_k
        for products, (run, _, _, block_q, block_k) in (
            (2, forward), (5, backward)))
    return {"forward": forward, "backward": backward, "kept": kept,
            "fill": 7.0 * kept / computed}


def _refusal(q):
    """Why the kernels cannot take ``q`` (B, H, S, d); "" when they
    can."""
    seq = q.shape[2]
    # no planes: some reader's tiles do not divide the sequence
    if not _planes(seq, q.shape[-1], q.dtype) or seq % min(
            _SELECT_ROWS, seq) or seq % min(_SELECT_CHUNK, seq) or seq < 128:
        return "seq %d is not whole tiles (%d, %d)" % ((seq,) + _tiles(seq))
    # ``flash_sparse_bwd`` holds dq's whole-head block in the pipeline's
    # two buffers: a shape the dense kernel fits only with one is over
    if _flash.fused_dq_buffers(seq, seq, q.shape[-1], q.dtype) != 2:
        return "dq's accumulator at (%d, %d) %s is over the VMEM budget" % (
            seq, q.shape[-1], q.dtype.name)
    return ""


@functools.lru_cache(maxsize=None)
def _log_once(impl, backend, reason, q_shape, q_dtype, kv_heads, idx, topk):
    seq = q_shape[2]
    facts = tiles_facts(seq, topk, q_shape[3], jnp.dtype(q_dtype))
    pairs = "run=%d masked=%d skipped=%d blocks=%dx%d"
    held = "dense"
    if impl == "pallas":
        planes = _planes(seq, q_shape[3], jnp.dtype(q_dtype))
        held = "bits planes=%d saved_bytes=%d" % (
            planes, q_shape[0] * seq * seq // planes)
    logger.info(
        "attention impl=auto resolved to %s (backend=%s, q=%s %s%s, "
        "kv_heads=%d group=%d, indexer heads=%d dim=%d, flash "
        "backward=fused, mask=selected(%d) pairs " + pairs + " (backward "
        + pairs + ") kept=%d fill=%.4f kept_set=%s)",
        impl, backend, q_shape, q_dtype,
        ", reason: %s" % reason if reason else "", kv_heads,
        q_shape[1] // kv_heads, idx[0], idx[1], topk, *facts["forward"],
        *facts["backward"], facts["kept"], facts["fill"], held)


def dsa_attention(q, k, v, qi, ki, w, topk, sm_scale=None, impl="auto",
                  interpret=False, probe=False):
    """``(out (B, H, S, d), kl (B,), facts)``: attention of ``q`` (B, H,
    S, d) over the keys of ``k``, ``v`` (B, Hk, S, d) that the indexer's
    ``qi`` (B, J, S, D), ``ki`` (B, S, D) and ``w`` (B, S, J) float32
    pick, ``topk`` a query; a sample's ``L_I`` (its mean over the
    queries); and the layer's facts for the ``dsa_select`` event.
    ``out``'s gradient reaches q, k and v alone, ``kl``'s qi, ki and w
    alone. ``impl``: "auto" (the kernels on a TPU where the shapes are
    whole tiles, else the ``jax.numpy`` lines), "pallas", "xla".
    ``probe``: ``facts`` also carries what a reference check compares
    (``_probe``: the kept set in bits, the entries kept after their
    query, the scores of the last ``PROBE_QUERIES`` queries)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    seq = q.shape[2]
    if impl == "auto":
        backend = jax.default_backend()
        reason = (
            _refusal(q) if backend == "tpu"
            else "the Pallas kernels need a TPU backend")
        impl = "xla" if reason else "pallas"
        _log_once(
            impl, backend, reason, tuple(q.shape), q.dtype.name, k.shape[1],
            (qi.shape[1], qi.shape[3]), topk)
    if impl not in ("xla", "pallas"):
        raise ValueError("unknown sparse attention impl %r" % (impl,))
    if seq <= topk:
        # every query keeps its whole prefix: the causal call itself
        with jax.named_scope("dsa/attend"):
            out = dot_product_attention(
                q, k, v, causal=True, sm_scale=sm_scale, impl=impl,
                interpret=interpret)
        with jax.named_scope("dsa/indexer_loss"):
            _, kl, facts = _dense(
                *(jax.lax.stop_gradient(t) for t in (q, k, v)), qi, ki, w,
                topk, sm_scale, probe)
        return out, kl, facts
    if impl == "xla":
        return _dense(q, k, v, qi, ki, w, topk, sm_scale, probe)
    return _by_kernels(
        q, k, v, qi, ki, w, topk, sm_scale, interpret, probe)
