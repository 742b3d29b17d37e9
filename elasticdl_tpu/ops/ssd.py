"""The selective state-space scan of Mamba-2 in chunked form (state
space duality, "Transformers are SSMs", arXiv:2405.21060): the mixer of
granite-4.0-h's, Bamba's and Nemotron-H's ``mamba`` layers.

Per head, with a state ``S`` (head width P x state width N, zero at the
sequence's start unless one is handed in), for each token ``t``::

    S = exp(a_t) S + dt_t x_t B_t^T;   y_t = S C_t + D x_t

(``a_t = dt_t A <= 0`` the head's log decay a token, ``dt_t > 0`` its
step, ``B_t`` and ``C_t`` (N) shared by the ``H / groups`` heads of a
group, ``D`` one number a head). ``ssd_recurrence`` below is that loop,
one token a step: the oracle of the tests, never the timed path.

``ssd_scan`` computes the same thing a chunk of ``chunk`` tokens at a
time. With ``G_i`` the log decay cumulated from the chunk's first token
through token ``i`` (float32)::

    M[i, j]  = C_i . B_j                    (i >= j; one matrix a chunk and
                                             GROUP, shared by its heads)
    Y_in[i]  = sum_(j<=i) M[i, j] exp(G_i - G_j) dt_j x_j
    Y_out[i] = exp(G_i) S C_i                   (the entering state)
    S       <- exp(G_last) S + sum_j exp(G_last - G_j) dt_j x_j B_j^T
    y        = Y_in + Y_out + D x

Every exponent is ``<= 0`` as written (the pairs ``i < j`` are masked
IN the exponent, with ``-inf``), so nothing overflows however negative
``a`` is; the factorised form ``(C e^G)(B e^-G)^T`` does and is not
used. Checked against the per-token loop in float64: equal to 1e-13,
at decays of -50 a token too (``tests/test_ssd_scan.py``).

``ops/gated_delta.py`` computes ANOTHER recurrence: the delta rule's
state is corrected by ``(I - beta k k^T)`` before it is written, which
costs a triangular inverse a chunk and a state-dependent ``V' = U - W
S``. This one has neither (its chunk is two masked matmuls and a
rank-``chunk`` update), so that module's operands and kernels have
nothing to give it; what is shared is the plumbing: ``segments_of``,
the segments under ``jax.checkpoint`` with the state carried in float32
between them, and the log-once line.

Precision: ``dt``, ``a``, ``G`` and the carried state are float32; the
matmuls take their operands in the compute dtype (``x``'s) and
accumulate in float32, the decay mask ``exp(G_i - G_j)`` is made in
float32 and rounded with ``M`` once, the state is rounded where it is a
matmul operand (as the published Triton kernels round it).

Memory: a head's decay mask is ``chunk x chunk`` float32 a (head,
chunk): 537 MB a layer at 64 heads and 8,192 tokens. A sequence longer
than ``segment`` chunks therefore runs a segment at a time, each under
``jax.checkpoint``, the state carried between them: the backward holds
one segment's masks and their cotangents, not the sequence's.

What is a kernel and what is not: nothing is a kernel yet. ``scan_impl``
says ``xla`` everywhere; the lines below are what XLA fuses. The log's
``ssd scan ... impl=xla`` line says so once a distinct call.
"""

import functools

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.ops.gated_delta import segments_of

logger = _logger_factory("elasticdl_tpu.ops.ssd")

# the published chunk (``mamba_chunk_size``) and the chunks of a
# checkpointed segment (2,048 tokens: a quarter of the masks of an
# 8,192-token layer alive in its backward)
DEFAULT_CHUNK = 256
DEFAULT_SEGMENT = 8


def scan_impl(dtype, head_dim, state, chunk, mesh=None):
    """What runs the chunks: ``"xla"``, the lines of this module, on
    every backend, dtype, shape and mesh. The chooser is where a kernel
    pair (``ssd_*``: the benchmark's trace reader charges such a name
    to ``mamba/scan``) will be decided from what it can observe, as
    ``ops/gated_delta.py:scan_impl`` decides the delta rules'."""
    del dtype, head_dim, state, chunk, mesh
    return "xla"


@functools.lru_cache(maxsize=None)
def _log_once(heads, head_dim, state, groups, chunk, impl, segments, tokens):
    """One line per distinct call of the scan (this runs at trace
    time), beside the attention line of ``ops/attention.py``."""
    logger.info(
        "ssd scan heads=%dx%d state=%d groups=%d chunk=%d impl=%s "
        "segments=%d (tokens=%d)", heads, head_dim, state, groups, chunk,
        impl, segments, tokens)


def _einsum(spec, a, b, dtype):
    """``einsum`` with both operands in ``dtype``, float32 out (float64
    for float64 operands: the tests' exact comparison)."""
    return jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.promote_types(dtype, jnp.float32))


def _segment(state, xs, skip, decay_dtype):
    """The chunks of one segment from the state ``state`` (B, G, R, P,
    N), in the dtype it is carried in: -> (the state after them, y (B,
    n, Q, G, R, P) in ``x``'s dtype). ``xs``: x (B, n, Q, G, R, P), dt
    and a (B, n, Q, G, R) float32, b and c (B, n, Q, G, N); ``skip``
    (G, R)."""
    x, dt, a, b, c = xs
    dtype, wide = x.dtype, dt.dtype
    chunk = x.shape[2]
    # (B, n, Q, G, R): G_i, the decay from the chunk's first token on
    cum = jnp.cumsum(a.astype(decay_dtype), axis=2).astype(wide)
    total = cum[:, :, -1]  # (B, n, G, R): the chunk's whole decay
    # the queries' and the keys' products once a group
    scores = _einsum("bnigs,bnjgs->bngij", c, b, dtype)
    # exp(G_i - G_j) a head, the tokens in the lanes; the mask is on
    # the EXPONENT: a pair above the diagonal is exp(-inf), never an
    # overflow times zero, and its gradient is zero, not nan
    lanes = cum.transpose(0, 1, 3, 4, 2)  # (B, n, G, R, Q)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(
        row >= col, lanes[..., :, None] - lanes[..., None, :], -jnp.inf))
    masked = (scores[:, :, :, None] * decay).astype(dtype)
    x_wide = x.astype(wide)
    y = _einsum(
        "bngrij,bnjgrp->bnigrp", masked, x_wide * dt[..., None], dtype)
    # what each chunk adds to the state that leaves it
    to_end = jnp.exp(total[:, :, None] - cum)  # (B, n, Q, G, R), <= 1
    local = _einsum(
        "bnjgrp,bnjgs->bngrps", x_wide * (dt * to_end)[..., None], b, dtype)

    def carry(state, xs):
        local_n, total_n = xs
        entering = state
        state = (
            jnp.exp(total_n)[..., None, None] * state + local_n
        ).astype(state.dtype)
        return state, entering

    chunks_first = lambda t: jnp.moveaxis(t, 1, 0)
    # elementwise steps over a few chunks: unrolled, they fuse
    state, entering = jax.lax.scan(
        carry, state, (chunks_first(local), chunks_first(total)),
        unroll=min(x.shape[1], 16))
    entering = jnp.moveaxis(entering, 0, 1)  # (B, n, G, R, P, N)
    y = y + jnp.exp(cum)[..., None] * _einsum(
        "bnigs,bngrps->bnigrp", c, entering, dtype)
    return state, (y + skip[..., None] * x_wide).astype(dtype)


def ssd_scan(x, dt, a, b, c, skip, chunk=DEFAULT_CHUNK, state=None,
             segment=DEFAULT_SEGMENT, return_state=False, state_dtype=None,
             decay_dtype=None, mesh=None):
    """x: (B, S, H, P) in the compute dtype; dt: (B, S, H) float32, the
    step after its softplus; a: (B, S, H) float32, the log decay a token
    ``dt A <= 0``; b, c: (B, S, groups, N), head ``h`` reads group ``h
    // (H / groups)``; skip: (H,), ``D``; state: (B, H, P, N), the state
    the sequence starts from (None: zero). Returns y (B, S, H, P) in
    ``x``'s dtype, and with ``return_state`` the pair (y, the state
    after the last token, float32).

    A sequence longer than ``segment`` chunks runs a segment at a time,
    each under ``jax.checkpoint`` (module docstring). A length that
    ``chunk`` (past one segment: the segment) does not divide is padded
    with tokens that write nothing and decay nothing (``dt = 0``, ``a =
    0``) and cut again. The result does not depend on ``chunk`` or
    ``segment``. ``state_dtype``, ``decay_dtype``: what the state is
    carried in and what the decay is cumulated in (None: float32);
    anything else is for the tests and the benchmark's precision
    experiment (``scripts/granite_precision.py``). ``mesh``: the mesh
    the caller's step is sharded over, if any (``scan_impl``); the scan
    itself places nothing and leaves its layout to GSPMD."""
    batch, seq, heads, dim = x.shape
    groups, width = b.shape[2], b.shape[3]
    if heads % groups or c.shape != b.shape:
        raise ValueError(
            "b and c are (B, S, groups, N) alike and the %d heads divide "
            "over the groups; got %s and %s" % (heads, b.shape, c.shape))
    if dt.shape != x.shape[:3] or a.shape != dt.shape:
        raise ValueError(
            "dt and a are one number a head and token, %s; got %s and %s"
            % (x.shape[:3], dt.shape, a.shape))
    rep = heads // groups
    wide = jnp.promote_types(x.dtype, jnp.float32)
    state_dtype = state_dtype or wide
    decay_dtype = decay_dtype or wide
    pad, segments = segments_of(seq, chunk, segment)
    impl = scan_impl(x.dtype, dim, width, chunk, mesh)
    _log_once(heads, dim, width, groups, chunk, impl, segments, batch * seq)
    if pad:
        widen = lambda t: jnp.pad(
            t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, a, b, c = map(widen, (x, dt, a, b, c))
    num = (seq + pad) // (segments * chunk)  # chunks a segment
    # segments first: (segments, B, n, Q, ...)
    split = lambda t, *rest: jnp.moveaxis(
        t.reshape((batch, segments, num, chunk) + rest), 1, 0)
    xs = (
        split(x, groups, rep, dim),
        split(dt.astype(wide), groups, rep),
        split(a.astype(wide), groups, rep),
        split(b, groups, width), split(c, groups, width),
    )
    if state is None:
        state = jnp.zeros((batch, heads, dim, width), state_dtype)
    state = state.astype(state_dtype).reshape(
        batch, groups, rep, dim, width)
    run = lambda state, xs: _segment(
        state, xs, skip.astype(wide).reshape(groups, rep), decay_dtype)
    if segments == 1:
        state, y = run(state, tuple(t[0] for t in xs))
    else:
        state, y = jax.lax.scan(jax.checkpoint(run), state, xs)
        y = jnp.moveaxis(y, 0, 1)  # (B, segments, n, Q, G, R, P)
    y = y.reshape(batch, seq + pad, heads, dim)[:, :seq]
    if not return_state:
        return y
    return y, state.astype(wide).reshape(batch, heads, dim, width)


def ssd_recurrence(x, dt, a, b, c, skip, state=None):
    """The scan one token a step, in the inputs' dtype: the definition
    the chunked form is tested against. Shapes as ``ssd_scan``; returns
    (y, the state after the last token)."""
    heads, rep = x.shape[2], x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(t, rep, axis=2) for t in (b, c))

    def step(state, xs):
        x_t, dt_t, a_t, b_t, c_t = xs  # (B, H, P), (B, H), (B, H), (B, H, N)
        state = jnp.exp(a_t)[..., None, None] * state + (
            (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, (
            jnp.einsum("bhpn,bhn->bhp", state, c_t)
            + skip[:, None] * x_t)

    tokens_first = lambda t: jnp.moveaxis(t, 1, 0)
    if state is None:
        state = jnp.zeros(
            (x.shape[0], heads, x.shape[3], b.shape[3]), x.dtype)
    state, y = jax.lax.scan(
        step, state, tuple(map(tokens_first, (x, dt, a, b, c))))
    return jnp.moveaxis(y, 0, 1), state
