"""The gated delta rule in chunked form (Gated DeltaNet, arXiv:2412.06464;
the linear-attention layers of Qwen3-Next).

Per value head, with a state ``S`` (key width x value width, zero at the
sequence's start), for each token ``t``::

    S = exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S = S + k_t u^T;
    o_t = S^T q_t

(``g_t <= 0`` the log decay, ``beta_t`` in (0, 1) the write strength).
``gated_delta_recurrence`` below is that loop, one token a step: the
oracle of the tests, never the timed path (32,768 sequential steps).

``gated_delta_rule`` computes the same thing a chunk of ``chunk`` tokens
at a time (the WY form of the delta rule, arXiv:2406.06484, with the
decay folded in). Inside a chunk, with ``G`` the decay cumulated from
the chunk's first token, ``D[i, j] = exp(G_i - G_j)`` for ``i >= j`` and
``A = strictly_lower(diag(beta) K K^T . D)``::

    T  = (I + A)^-1
    U  = T (beta V)              W = T (beta K . exp(G))
    V' = U - W S                 O = (Q . exp(G)) S + lower(Q K^T . D) V'
    S <- exp(G_last) S + (K . exp(G_last - G))^T V'

Everything but ``V'`` and ``S`` is independent of the state, so it is
batched matmuls over all chunks at once; a ``lax.scan`` over the chunks
carries ``S`` and runs two small matmuls a step; ``O`` is batched again
from the states the scan hands back. Every decay factor is an ``exp`` of
a difference that is ``<= 0``, so nothing overflows however negative
``g`` is. Checked against the per-token loop in float64: equal to 1e-15.

Precision: the cumulated decay, ``T`` and ``S`` are float32; the
matmuls take their operands in the compute dtype (``q``'s) and
accumulate in float32, the state too when it is an operand (as the
published Triton kernels do). The inverse is the product form, exact
because ``A`` is nilpotent (strictly lower, ``A^chunk = 0``)::

    (I + A)^-1 = (I - A)(I + A^2)(I + A^4) ... (I + A^(chunk/2))

in float32 at matmul precision "highest" (a default-precision float32
matmul on the TPU rounds its operands to bfloat16): log2(chunk) - 1
squarings and log2(chunk) products of chunk x chunk matrices, 0.3% of
the layer's FLOPs, where a triangular solve would be ``chunk``
dependent steps.

What is a kernel and what is not. The inverse and its VJP are Pallas
TPU kernels, ``gdn_inverse_fwd`` and ``gdn_inverse_bwd`` (PR 32): XLA
runs each of the ten products as one batched matmul over all of a
segment's matrices and writes every square and every partial product
to HBM, 64 lanes padded to 128; the kernel reads a block of ``A``,
keeps it in VMEM through the same ten products at the same precision
and writes ``T``. Two 64 x 64 matrices lie side by side on the 128
lanes and meet a block-diagonal right operand, so a pass fills the
MXU's depth (each output element stays the same sum of the same
products; the other matrix's lanes meet zeros, so a nan or inf in
one matrix reaches its lane neighbour's result too, where XLA kept it
to its own: the step's health check sees either); ``inverse`` and
``power`` of one span, which share their right operand, are stacked on
the rows (``inverse + inverse P`` and ``P P`` are ``[inverse; P] @ P``);
and ``_CHAINS`` independent pairs are interleaved in one loop body,
because one pair's five dependent spans alone leave the MXU waiting
(3.9 ms for 4,096 matrices where eight chains take 2.1 and XLA 6.6;
PERF.md Section 6, PR 32). The kernel's VJP runs ``T^T (dT T^T)``
where the XLA path runs ``(T^T dT) T^T``: the same two products at the
same precision in the other association, equal to float32 rounding and
not bit for bit. ``inverse_impl`` decides from the backend, the dtype,
the chunk and the mesh which runs, with no switch for a user: a TPU,
float32, chunk 64 or 128, one device -> the kernels (``impl=pallas``
on the rule's linear-attention line); the CPU, float64, another chunk
or a mesh of several devices -> the XLA product form below
(``impl=xla``). A ``pallas_call`` has no GSPMD partitioning rule
(``ops/attention.py:_shard_over_mesh``), and the rule opens no
``shard_map`` of its own yet, so on a mesh it stays what GSPMD can
partition. Everything else of the rule is XLA: building ``A``,
applying ``T``, the chunk-to-chunk scan.

Memory: autodiff through the scan keeps one state a chunk (in the
compute dtype, as its matmul operand) and the chunk's ``W``, ``V'``,
decayed keys and C x C matrices (float32 ones, which the TPU pads from
64 to 128 lanes): about 4 GB a layer at 32,768 tokens, 32 heads of 128 x
128 and chunk 64, too much beside 10 GB of optimizer state. So a
sequence runs in segments of ``segment`` chunks, each under
``jax.checkpoint``, with the state carried from one to the next: the
backward rebuilds one segment's forward at a time and holds a
``1 / segments`` part of that (a recompute by groups of chunks; its cost
is one more forward of the rule, 0.5% of the cell's FLOPs). The inverse
has a VJP of its own from ``T`` alone. The output carries
``checkpoint_name`` ``GDN_OUT_NAME`` so that a remat policy can name it
as it names flash's (today's policies do not: the backward rebuilds the
segments' residuals whether or not ``o`` was kept).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory

logger = _logger_factory("elasticdl_tpu.ops.gated_delta")

# checkpoint_name of the rule's output (models/transformer.py:
# remat_block's policies name it beside flash's)
GDN_OUT_NAME = "gdn_out"
DEFAULT_CHUNK = 64
# chunks a segment: 8192 tokens at chunk 64
DEFAULT_SEGMENT = 128
# the chunks the inverse kernels take: a 128-lane row holds two
# matrices of 64 or one of 128
_KERNEL_CHUNKS = (64, 128)
_LANES = 128
# VMEM a grid step's blocks may take (every operand and the result,
# double-buffered), and the limit the kernels' pallas_calls state: the
# v5e compiler's default, which the blocks and the loop body's spilled
# chains (about 3 MiB) stay under
_INVERSE_BLOCK_BYTES = 8 * 2**20
_INVERSE_VMEM_LIMIT = 16 * 2**20
# independent lane rows (pairs of 64 x 64 matrices) a loop iteration
# interleaves
_CHAINS = 8


def _matmul(a, b, dtype):
    """Batched ``a @ b``, operands in ``dtype``, float32 out (float64
    for float64 operands: the tests' exact comparison)."""
    return jnp.matmul(
        a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.promote_types(dtype, jnp.float32))


def _inverse_product(a):
    size = a.shape[-1]
    eye = jnp.eye(size, dtype=a.dtype)
    inverse, power = eye - a, a
    span = 2
    while span < size:
        power = _exact(power, power)  # a^span
        inverse = _exact(inverse, eye + power)
        span *= 2
    return inverse


def _exact(x, y):
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


def inverse_impl(dtype, size, mesh=None):
    """``"pallas"`` or ``"xla"``: what runs the inverse of ``size`` x
    ``size`` matrices of ``dtype`` in a step sharded over ``mesh``
    (None: one device). Like every ``pallas_call`` the kernels cannot be
    partitioned automatically, so they run where there is nothing to
    partition: on one device, or inside a region that is already manual
    over the whole mesh (module docstring)."""
    one_device = (
        mesh is None or mesh.size == 1 or jax_compat.manual_over(mesh))
    fits = (
        one_device
        and jax.default_backend() == "tpu"
        and dtype == jnp.float32
        and size in _KERNEL_CHUNKS
    )
    return "pallas" if fits else "xla"


def inverse_block(count, size, arrays):
    """Matrices a grid step takes of ``count`` ``size`` x ``size``
    float32 ones: as many as ``arrays`` double-buffered blocks (operands
    and result; a matrix holds whole 128-lane rows in VMEM) fit in
    ``_INVERSE_BLOCK_BYTES``, in whole loop iterations of ``_CHAINS``
    lane rows, and no more than ``count`` needs."""
    unit = _CHAINS * (_LANES // size)
    fit = _INVERSE_BLOCK_BYTES // inverse_vmem_bytes(1, size, arrays)
    return max(unit, min(fit, count + -count % unit) // unit * unit)


def inverse_vmem_bytes(block, size, arrays):
    """VMEM of ``arrays`` double-buffered blocks of ``block``
    matrices."""
    return 2 * arrays * block * size * _LANES * 4


def _dot(x, y, contract=((1,), (0,))):
    return jax.lax.dot_general(
        x, y, (contract, ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _lane_row(ref, first, pack):
    """``pack`` matrices of ``ref`` from ``first`` on, side by side on
    the lanes: (size, pack x size)."""
    if pack == 1:
        return ref[first]
    return jnp.concatenate([ref[first + p] for p in range(pack)], axis=1)


def _block_diagonal(x, size):
    """The matrices of a lane row ``x`` (size, pack x size) on the
    diagonal of a (pack x size, pack x size) right operand: the lane
    row times it is, matrix by matrix, the products."""
    pack = x.shape[1] // size
    if pack == 1:
        return x
    block = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // size
    return jnp.concatenate(
        [jnp.where(block == p, x, 0.0) for p in range(pack)], axis=0)


def _inverse_fwd_kernel(a_ref, t_ref, *, size):
    pack = _LANES // size
    row = jax.lax.broadcasted_iota(jnp.int32, (size, _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (size, _LANES), 1)
    eye = (col % size == row).astype(jnp.float32)

    def body(step, carry):
        first = [(step * _CHAINS + c) * pack for c in range(_CHAINS)]
        powers = [_lane_row(a_ref, f, pack) for f in first]
        inverses = [eye - a for a in powers]
        powers = [_dot(a, _block_diagonal(a, size)) for a in powers]  # a^2
        span = 2
        while 2 * span < size:
            # [inverse; a^span] @ a^span: inverse (I + a^span) less
            # inverse, and a^(2 span)
            both = [
                _dot(jnp.concatenate([i, p], axis=0),
                     _block_diagonal(p, size))
                for i, p in zip(inverses, powers)]
            inverses = [i + b[:size] for i, b in zip(inverses, both)]
            powers = [b[size:] for b in both]
            span *= 2
        for f, i, p in zip(first, inverses, powers):
            i = i + _dot(i, _block_diagonal(p, size))
            for m in range(pack):
                t_ref[f + m] = i[:, m * size:(m + 1) * size]
        return carry

    jax.lax.fori_loop(0, a_ref.shape[0] // (_CHAINS * pack), body, 0)


def _inverse_bwd_kernel(t_ref, d_ref, da_ref, *, size):
    pack = _LANES // size

    def body(step, carry):
        first = [(step * _CHAINS + c) * pack for c in range(_CHAINS)]
        ts = [_lane_row(t_ref, f, pack) for f in first]
        # T dT^T, then (T dT^T) T = -(da)^T, matrix by matrix
        ys = [
            _dot(t, _block_diagonal(_lane_row(d_ref, f, pack), size),
                 ((1,), (1,)))
            for f, t in zip(first, ts)]
        ys = [_dot(y, _block_diagonal(t, size)) for y, t in zip(ys, ts)]
        for f, y in zip(first, ys):
            if pack > 1:
                # rows padded to the lanes: one square transpose lays
                # matrix m's own transpose on rows m x size.., lanes
                # 0..size
                y = jnp.concatenate(
                    [y, jnp.zeros((_LANES - size, _LANES), y.dtype)], axis=0)
            da = -y.T
            for m in range(pack):
                da_ref[f + m] = da[m * size:(m + 1) * size, :size]
        return carry

    jax.lax.fori_loop(0, t_ref.shape[0] // (_CHAINS * pack), body, 0)


def _inverse_call(kernel, name, operands, interpret):
    """``kernel`` over blocks of the (M, C, C) float32 ``operands``,
    one (M, C, C) result. M is padded with zero matrices to whole
    blocks (their inverse is I, their gradient 0) and cut again."""
    count, size, _ = operands[0].shape
    block = inverse_block(count, size, len(operands) + 1)
    pad = -count % block
    if pad:
        operands = [
            jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in operands]
    spec = pl.BlockSpec((block, size, size), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(kernel, size=size),
        grid=((count + pad) // block,),
        in_specs=[spec] * len(operands),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(
            (count + pad, size, size), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_INVERSE_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
    )(*operands)
    return out[:count] if pad else out


# jitted so that every layer and segment of a model shares one trace of
# the kernel's body; always inside the step's own trace, where the
# recompile sentinel's host bookkeeping cannot run
@functools.partial(  # edlint: disable=obs-bare-jit
    jax.jit, static_argnames=("interpret",))
def gdn_inverse_fwd(a, interpret=False):
    """``(I + a)^-1`` of strictly lower ``a`` (M, C, C) float32, C 64
    or 128, by the product form at matmul precision highest, a block
    of matrices held in VMEM through all of its products."""
    return _inverse_call(
        _inverse_fwd_kernel, "gdn_inverse_fwd", [a], interpret)


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnames=("interpret",))
def gdn_inverse_bwd(inverse, d_inverse, interpret=False):
    """``-T^T dT T^T`` for ``T = inverse``, both (M, C, C) float32."""
    return _inverse_call(
        _inverse_bwd_kernel, "gdn_inverse_bwd", [inverse, d_inverse],
        interpret)


def _flat(x):
    return x.reshape((-1,) + x.shape[-2:])


def unit_lower_inverse(a, mesh=None):
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., C, C),
    C a power of two, by the product form (module docstring), run by
    what ``inverse_impl`` says for ``a`` in a step over ``mesh``. Its
    gradient is the inverse's own, ``da = -T^T dT T^T``, from ``T``
    alone: autodiff of the product would keep every square and every
    partial product, ten C x C float32 matrices a chunk and head where
    this keeps one."""
    return _inverse(a, inverse_impl(a.dtype, a.shape[-1], mesh))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _inverse(a, impl):
    """``unit_lower_inverse`` by ``impl``, as ``inverse_impl`` gave it
    for ``a``."""
    size = a.shape[-1]
    if size & (size - 1):
        raise ValueError("the chunk must be a power of two, got %d" % size)
    if impl == "pallas":
        return gdn_inverse_fwd(_flat(a)).reshape(a.shape)
    return _inverse_product(a)


def _inverse_vjp_fwd(a, impl):
    inverse = _inverse(a, impl)
    return inverse, inverse


def _inverse_vjp_bwd(impl, inverse, d_inverse):
    if impl == "pallas":
        return (gdn_inverse_bwd(_flat(inverse), _flat(d_inverse)).reshape(
            inverse.shape),)
    t = jnp.swapaxes(inverse, -1, -2)
    return (-_exact(_exact(t, d_inverse), t),)


_inverse.defvjp(_inverse_vjp_fwd, _inverse_vjp_bwd)


@functools.lru_cache(maxsize=None)
def _log_once(hk, hv, dk, chunk, impl, tokens):
    """One line per distinct call of the rule (this runs at trace time),
    beside the attention line of ``ops/attention.py``, from where the
    path is chosen. ``impl``: what runs the chunks' inverses, ``pallas``
    (the ``gdn_inverse_*`` kernels) or ``xla``."""
    logger.info(
        "linear attention heads k=%d v=%d dim=%d chunk=%d impl=%s "
        "(tokens=%d)", hk, hv, dk, chunk, impl, tokens)


def _chunks(state, q, k, v, g, beta, state_dtype, decay_dtype, impl):
    """The rule over whole chunks from the state ``state``: q, k (B, Hk,
    1, N, C, Dk), v (B, Hk, R, N, C, Dv), g, beta (B, Hk, R, N, C)
    float32 -> (the state after them, o (B, Hk, R, N, C, Dv) float32).
    ``impl``: what runs the inverses."""
    dtype, chunk = q.dtype, q.shape[-2]
    # G, from the chunk's first token
    cum = jnp.cumsum(g.astype(decay_dtype), axis=-1).astype(g.dtype)
    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    # exp of a masked difference: above the diagonal the difference is
    # positive and may overflow, and inf x 0 would poison the gradient
    decay = jnp.exp(jnp.where(
        row >= col, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    into = jnp.exp(cum)  # what reaches token i of the entering state
    last = cum[..., -1:]
    onto = jnp.exp(last - cum)  # what is left of token i at the end

    swap = lambda x: jnp.swapaxes(x, -1, -2)
    kk = _matmul(k, swap(k), dtype)  # (B, Hk, 1, N, C, C)
    a = jnp.where(row > col, kk * beta[..., :, None] * decay, 0.0)
    t = _inverse(a, impl)
    u = _matmul(t, beta[..., None] * v, dtype)
    # matmul operands only from here on: kept in the compute dtype
    w = _matmul(t, (beta * into)[..., None] * k, dtype).astype(dtype)
    k_onto = (onto[..., None] * k).astype(dtype)  # (B, Hk, R, N, C, Dk)
    q_into = into[..., None] * q
    attn = jnp.where(row >= col, _matmul(q, swap(k), dtype) * decay, 0.0)

    def step(state, xs):
        u_n, w_n, k_n, end = xs
        new_v = u_n - _matmul(w_n, state, dtype)
        held = state
        state = (
            jnp.exp(end)[..., None] * state
            + _matmul(swap(k_n), new_v, dtype)
        ).astype(state_dtype)
        return state, (new_v.astype(dtype), held.astype(dtype))

    chunks_first = lambda x: jnp.moveaxis(x, 3, 0)
    state, (new_v, states) = jax.lax.scan(
        step, state, tuple(map(chunks_first, (u, w, k_onto, last))))
    new_v = jnp.moveaxis(new_v, 0, 3)
    states = jnp.moveaxis(states, 0, 3)  # (B, Hk, R, N, Dk, Dv)
    return state, (
        _matmul(q_into, states, dtype) + _matmul(attn, new_v, dtype))


def gated_delta_rule(q, k, v, g, beta, chunk=DEFAULT_CHUNK,
                     segment=DEFAULT_SEGMENT, state_dtype=None,
                     decay_dtype=None, mesh=None):
    """q, k: (B, Hk, S, Dk), already normalised and scaled; v: (B, Hv,
    S, Dv) with ``Hv`` a multiple of ``Hk`` (value head ``h`` reads key
    head ``h // (Hv / Hk)``; q and k are never repeated in memory); g,
    beta: (B, Hv, S) float32. Returns o (B, Hv, S, Dv) in ``v``'s dtype.

    A sequence longer than ``segment`` chunks runs a segment at a time,
    each under ``jax.checkpoint``, the state carried between them: the
    backward then holds one segment's chunk matrices and states, not
    the sequence's (module docstring). A length that ``chunk`` (or, past
    one segment, the segment) does not divide is padded with tokens that
    write nothing and decay nothing (``beta = 0``, ``g = 0``) and cut
    again. ``state_dtype``, ``decay_dtype``: what the scan carries
    ``S`` in and what the decay is cumulated in (None: float32);
    anything else is for the tests and the benchmark's precision
    experiment (``scripts/gdn_precision.py``). ``mesh``: the mesh the
    caller's step is sharded over, if any (``inverse_impl``: the
    inverses' kernels run on one device); the rule itself places
    nothing and leaves its layout over the mesh to GSPMD."""
    batch, hk, seq, dk = q.shape
    hv, dv = v.shape[1], v.shape[3]
    if hv % hk:
        raise ValueError(
            "%d value heads do not divide over %d key heads" % (hv, hk))
    rep = hv // hk
    wide = jnp.promote_types(q.dtype, jnp.float32)
    state_dtype = state_dtype or wide
    decay_dtype = decay_dtype or wide
    # the chunks' matrices are ``wide`` whatever q is
    impl = inverse_impl(wide, chunk, mesh)
    _log_once(hk, hv, dk, chunk, impl, batch * seq)
    span = chunk if seq <= chunk * segment else chunk * segment
    pad = -seq % span
    if pad:
        widen = lambda x: jnp.pad(
            x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    segments = max(1, (seq + pad) // (chunk * segment))
    num = (seq + pad) // (segments * chunk)  # chunks a segment
    # segments first; key-like (B, Hk, 1, N, C, Dk), value-like (B, Hk,
    # R, N, C, ...)
    split = lambda x, heads, *rest: jnp.moveaxis(
        x.reshape((batch,) + heads + (segments, num, chunk) + rest),
        1 + len(heads), 0)
    xs = (
        split(q, (hk, 1), dk), split(k, (hk, 1), dk),
        split(v, (hk, rep), dv),
        split(g.astype(wide), (hk, rep)), split(beta.astype(wide), (hk, rep)),
    )
    run = lambda state, xs: _chunks(
        state, *xs, state_dtype, decay_dtype, impl)
    state0 = jnp.zeros((batch, hk, rep, dk, dv), state_dtype)
    if segments == 1:
        _, o = run(state0, tuple(x[0] for x in xs))
    else:
        _, o = jax.lax.scan(jax.checkpoint(run), state0, xs)
        o = jnp.moveaxis(o, 0, 3)  # (B, Hk, R, segments, N, C, Dv)
    o = o.reshape(batch, hv, seq + pad, dv)[:, :, :seq]
    return checkpoint_name(o.astype(v.dtype), GDN_OUT_NAME)


def gated_delta_recurrence(q, k, v, g, beta):
    """The rule one token a step, in the inputs' dtype: the definition
    the chunked form is tested against. Shapes as
    ``gated_delta_rule``."""
    rep = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(x, rep, axis=1) for x in (q, k))

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (B, H, D) and (B, H)
        state = jnp.exp(g_t)[..., None, None] * state
        u = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    tokens_first = lambda x: jnp.moveaxis(x, 2, 0)
    state0 = jnp.zeros(v.shape[:2] + (q.shape[-1], v.shape[-1]), v.dtype)
    _, o = jax.lax.scan(
        step, state0, tuple(map(tokens_first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2)
