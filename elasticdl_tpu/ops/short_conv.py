"""The double-gated short convolution (LFM2's ``conv`` mixer, between
its two projections): for one channel and position t, with the input
projection's columns lying ``B | C | X``,

    z[t] = B[t] X[t]
    c[t] = sum_{j < K} w[j] z[t - (K - 1) + j]        z[< 0] = 0
    y[t] = C[t] c[t]

causal, depthwise (every channel its own K taps), no bias and no
activation; K = ``conv_L_cache`` = 3 in LFM2-8B-A1B, so a position reads
itself and the two before it. Every batch row is its own sequence.

The passes are bound by the bytes they move: a forward needs ``3 C``
elements read and ``C`` written a token, a backward ``4 C`` read and
``3 C`` written (``benchmark/flops/conv_moe_decoder.py:gate_need``).
``gated_short_conv`` runs them one of two ways, and ``conv_impl``
chooses with no switch for a user:

- ``impl=xla`` (``gated_short_conv_xla``, the plain reference of the
  tests): ``jax.numpy`` lines, K shifted multiply-adds over the
  sequence axis (``ops/qkv_conv.py:conv_silu_xla``'s form; a
  ``conv_general_dilated`` with one group a channel lowers to a
  convolution the TPU runs on the MXU at a channel a pass). The call
  is a ``jax.checkpoint``: what its backward keeps is its two operands,
  and it forms ``z`` and ``c`` again where a plain trace would keep
  both in float32, 0.5 GB a layer at 32,768 x 2048. On a TPU XLA turns
  the lines (three slices of the projection, two shifts through a
  ``jnp.pad``) into several fusions that each read and write whole
  (S, C) arrays: 53.0 ms a step in the LFM2 cell for 10.8 ms of needed
  traffic (PR 49's trace). It runs on the CPU, at the tests' 64
  channels, under a mesh that is not manual and wherever else
  ``conv_impl`` refuses.
- ``impl=pallas``: two Pallas TPU kernels under one ``custom_vjp``,
  after ``ops/qkv_conv.py`` (PR 44). ``short_conv_fwd``: a grid step
  takes a tile of whole rows of one sequence and reads B, C and X
  WHERE THE PROJECTION WROTE THEM: the same array through three
  ``BlockSpec``s at column blocks 0, 1, 2 of ``bcx`` (no slice, no
  copy), plus the ``K - 1`` rows of B and X before the tile from a
  second, 16-row view (zeros before a sequence's first tile); one
  write of ``y``.
  ``short_conv_bwd``: the same tile with the rows before it (``z`` and
  the convolution are formed again, as the lines' checkpoint does) and
  the rows AFTER it of C and ``dy`` (the transposed convolution looks
  forward: ``dz[t] = sum_j w[j] (C dy)[t + (K - 1) - j]``, zero past
  the sequence's end); writes ``dB | dC | dX`` once, in the
  projection's layout, so a grid step takes whole rows, and a tile's
  share of the taps' gradient (B, tiles, K, C) float32, which XLA
  sums. Residuals: ``bcx`` and the taps, alive or recomputed anyway;
  no ``jax.checkpoint`` is needed. A kernel's name starts with
  ``short_conv``: ``benchmark/lib/conv_trace.py`` charges a Mosaic
  kernel of that name to ``short_conv/gate`` (``SCOPE``), and both
  calls sit under the scope, the backward's inside the VJP.

**The same work.** Either way: float32 arithmetic from the loads on
under narrower operands, ``y`` rounded once, ``dbcx`` rounded once, the
taps' gradient summed in float32 (the kernels: by tile, then over
tiles) and rounded to the taps' dtype, every position and channel
computed.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.ops.qkv_conv import (
    _HALO,
    _LANES,
    _SUB,
    _TILES,
    _by_sublane,
    _conv,
    _params,
    _shifted,
)

logger = _logger_factory("elasticdl_tpu.ops.short_conv")

# the scope of ``models/transformer.py:ShortConv`` both kernels are
# counted under (``benchmark/lib/conv_trace.py``)
SCOPE = "short_conv/gate"
# an iteration of a kernel's loop over its tile takes whole rows, the
# most of these whose array is ``_CHUNK`` elements (32 float32
# registers, ``qkv_conv``'s 256 x 128; ``conv_impl``'s table), a packed
# tile of sublanes at least
_CHUNK = 256 * 128
_LOOP_ROWS = (256, 128, 64, 32, 16)
# what a grid step's double-buffered blocks may take of ``_params``'
# VMEM limit; the backward's seven (B, C, X, dy in, dB | dC | dX out)
# decide
_BLOCK_BYTES = 40 * 2**20
_BWD_BLOCKS = 7


def row_tile(seq, channels, itemsize):
    """Rows a grid step takes: the most that divides ``seq`` and whose
    backward blocks, whole rows of ``channels``, fit the VMEM budget;
    None where none does."""
    return next(
        (t for t in _TILES
         if seq % t == 0
         and 2 * _BWD_BLOCKS * t * channels * itemsize <= _BLOCK_BYTES),
        None)


def loop_rows(tile, channels):
    """Rows an iteration of a kernel's loop over a tile of ``tile``
    whole rows takes."""
    rows = next(
        (r for r in _LOOP_ROWS if r * channels <= _CHUNK), _LOOP_ROWS[-1])
    return min(rows, tile)


def conv_impl(dtype, channels, seq, taps, mesh=None):
    """``"pallas"`` or ``"xla"``: what runs the gates and the
    convolution of a layer whose projection is ``dtype``, over
    ``channels`` channels, ``seq`` tokens and ``taps`` taps, in a step
    sharded over ``mesh`` (None: one device). The kernels: a TPU with
    nothing to partition (one device, or a region already manual over
    the mesh), bfloat16 or float32, channels in whole 128-lane rows, a
    row tile that divides the sequence (``row_tile``), at most 9 taps.
    Everything else (the CPU, the tests' 64 channels) runs the module's
    lines.

    On one v5e (``scripts/short_conv.py``, PR 50, 1 x 32,768 x 6,144
    bfloat16, 2,048 channels, 3 taps; ms a call, GB/s over the bytes a
    call NEEDS: 537 MB forward, 940 MB backward; in brackets the
    seconds a kernel takes to trace and lower on the chip's host)::

        rows a step, rows a loop      forward             backward
        XLA's lines                   2.37 (227)          8.25 (10.62 with
                                                          the forward)
        256, 16                       0.86 (621) [0.04]   1.59 (590) [0.05]
        256, 64                       0.86 (623) [0.03]   1.58 (594) [0.05]
        512, 16   <- chosen           0.85 (629) [0.03]   1.54 (610) [0.05]
        512, 32                       0.86 (627) [0.02]   1.53 (615) [0.04]
        512, 64                       0.85 (631) [0.03]   1.54 (608) [0.05]
        1024, 16                      0.84 (636) [0.03]   1.53 (614) [0.05]
        1024, 64                      0.85 (634) [0.03]   1.54 (609) [0.06]
        first form*, 512 rows a step:
        256 x 128 a loop              0.85 (628) [0.19]   1.54 (612) [0.52]
        64 x 512 a loop               0.86 (626) [0.07]   1.55 (608) [0.14]
        32 x 1024 a loop              0.85 (634) [0.04]   1.57 (599) [0.08]

    (*) the first form walked a tile by 128-lane groups, unrolled in
    the body, 256 rows an iteration (``qkv_conv``'s shape), and its
    forward could take a block of channels a grid step (512, 1,024 or
    all 2,048: 0.83-0.91 ms over 33 rows of the table, nothing
    between them). Nothing decides the TIME: every form, tile and loop
    reads 0.83-0.91 ms forward and 1.52-1.59 backward, where
    ``qkv_conv``'s rows a loop iteration halved its time (that kernel's
    lane sums and transcendentals had to hide under the next rows'
    multiply-adds; these kernels have neither and wait for the HBM at
    any shape). What the shape decides is the seconds to TRACE: sixteen
    unrolled lane groups cost the cell's step +2.6 s of tracing and
    +0.9 s of lowering on the chip's host, paid again by the reference
    check (``setup_s`` 111.5 -> 122.3 s warm, against its 10% bound),
    whole rows cost nothing (107.4). So an iteration takes WHOLE rows,
    32 float32 registers an array (``loop_rows``: 16 rows of 2,048
    channels), the forward takes whole rows a grid step as the backward
    must, and the rows a step are the most whose backward blocks fit
    ``_BLOCK_BYTES`` (1,024 ran too, 56 of the 64 MiB the compiler is
    given). The pair through its VJP, the taps' sum included: 2.33 ms,
    XLA's 10.62. In the cell's step (traced): ``short_conv_fwd`` 0.78
    ms a call, ``short_conv_bwd`` 1.44.
    """
    dtype = jnp.dtype(dtype)
    fits = (
        jax_compat.kernels_can_run(mesh)
        and dtype in (jnp.bfloat16, jnp.float32)
        and channels % _LANES == 0
        and row_tile(seq, channels, dtype.itemsize) is not None
        and 1 <= taps <= _SUB + 1
    )
    return "pallas" if fits else "xla"


def conv_choice(dtype, channels, seq, taps, mesh=None):
    """(``conv_impl``'s answer, the rows a grid step takes or None):
    what the log's line and the journal's ``mixer_kinds`` event say."""
    impl = conv_impl(dtype, channels, seq, taps, mesh)
    tile = (row_tile(seq, channels, jnp.dtype(dtype).itemsize)
            if impl == "pallas" else None)
    return impl, tile


@functools.lru_cache(maxsize=None)
def log_choice(channels, taps, impl, tokens, tile):
    """One line per distinct layer shape (this runs at trace time)."""
    logger.info(
        "short conv channels=%d taps=%d impl=%s (tokens=%d tile=%s; B | C "
        "| X one projection, float32 arithmetic, one rounding)",
        channels, taps, impl, tokens, tile)


# --------------------------------------------------- the module's lines

def causal_depthwise_conv(z, taps):
    """``c[t] = sum_j taps[j] z[t - (K - 1) + j]`` over axis -2 of ``z``
    (..., S, C) with ``taps`` (K, C); positions before the first are 0."""
    seq, k = z.shape[-2], taps.shape[0]
    padded = jnp.pad(z, [(0, 0)] * (z.ndim - 2) + [(k - 1, 0), (0, 0)])
    return sum(
        taps[j] * jax.lax.slice_in_dim(padded, j, j + seq, axis=z.ndim - 2)
        for j in range(k))


def _check_width(bcx, taps):
    channels = taps.shape[1]
    if bcx.shape[-1] != 3 * channels:
        raise ValueError(
            "the projection is B | C | X, three times the taps' %d "
            "channels wide; got %d" % (channels, bcx.shape[-1]))
    return channels


@jax.checkpoint
def gated_short_conv_xla(bcx, taps):
    """``C * conv(B * X)`` as XLA runs it: ``bcx`` (..., S, 3 C) the
    input projection's result, ``taps`` (K, C); returns (..., S, C) in
    ``bcx``'s dtype."""
    channels = _check_width(bcx, taps)
    wide = jnp.promote_types(bcx.dtype, jnp.float32)
    b, c, x = (
        bcx[..., i * channels:(i + 1) * channels].astype(wide)
        for i in range(3))
    conv = causal_depthwise_conv(b * x, taps.astype(wide))
    return (c * conv).astype(bcx.dtype)


# ------------------------------------------------------- in the tile

def _wide(ref, rows):
    return ref[0, rows, :].astype(jnp.float32)


def _z_before(b_before_ref, x_before_ref):
    """``B X`` of the _SUB rows before the tile; zeros before a
    sequence's first tile."""
    every = slice(None)
    z = (_wide(b_before_ref, every) * _wide(x_before_ref, every))[-_SUB:]
    return jnp.where(pl.program_id(1) == 0, 0.0, z)


def _fwd_kernel(b_ref, c_ref, x_ref, b_before_ref, x_before_ref, w_ref,
                y_ref, *, chunk):
    """One tile of whole rows: ``b_ref``, ``c_ref``, ``x_ref``
    (1, T, C) the three column blocks of ``bcx``, ``*_before_ref`` the
    _HALO rows before the tile, ``w_ref`` (K, C) float32; writes
    ``y_ref`` (1, T, C)."""
    tile, dtype = y_ref.shape[1], y_ref.dtype
    w = w_ref[...]
    taps = w.shape[0]

    def step(r, before):
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        z = _wide(b_ref, rows) * _wide(x_ref, rows)
        conv = _conv(_shifted(jnp.concatenate([before, z]), taps, chunk), w)
        y_ref[0, rows, :] = (_wide(c_ref, rows) * conv).astype(dtype)
        return z[-_SUB:]

    jax.lax.fori_loop(
        0, tile // chunk, step, _z_before(b_before_ref, x_before_ref))


def _bwd_kernel(b_ref, c_ref, x_ref, b_before_ref, x_before_ref,
                c_after_ref, w_ref, dy_ref, dy_after_ref, dbcx_ref, dw_ref,
                *, chunk):
    """One tile of whole rows, walked from its last chunk to its first:
    the three column blocks of ``bcx`` (1, T, C) with the _HALO rows of
    B and X before and of C after it, ``dy_ref`` (1, T, C) with the
    _HALO rows after it; writes ``dbcx_ref`` (1, T, 3 C), ``dB | dC |
    dX``, and the tile's share of the taps' gradient ``dw_ref``
    (1, 1, K, C) float32."""
    last_tile = pl.program_id(1) == pl.num_programs(1) - 1
    tile, channels = dy_ref.shape[1], dy_ref.shape[2]
    dtype = dbcx_ref.dtype
    w = w_ref[...]
    taps = w.shape[0]
    chunks = tile // chunk
    every = slice(None)
    halo = _z_before(b_before_ref, x_before_ref)
    # g = C dy of the _SUB rows after the tile
    g_after = (_wide(c_after_ref, every) * _wide(dy_after_ref, every))[:_SUB]
    g_after = jnp.where(last_tile, 0.0, g_after)

    def step(i, carry):
        g_after, dw = carry
        r = chunks - 1 - i
        start = pl.multiple_of(r * chunk, chunk)
        rows = pl.ds(start, chunk)
        b, x, dy = _wide(b_ref, rows), _wide(x_ref, rows), _wide(dy_ref, rows)
        above = pl.ds(pl.multiple_of(
            jnp.maximum(start - _HALO, 0), _HALO), _HALO)
        before = (_wide(b_ref, above) * _wide(x_ref, above))[-_SUB:]
        views = _shifted(jnp.concatenate(
            [jnp.where(r == 0, halo, before), b * x]), taps, chunk)
        g = _wide(c_ref, rows) * dy
        # dz_t = sum_j w[j] g_(t + (taps - 1) - j)
        ahead = jnp.concatenate([g, g_after])
        dz = sum(
            w[j:j + 1] * (
                pltpu.roll(ahead, chunk + _SUB - (taps - 1 - j), 0)
                if j < taps - 1 else ahead)[:chunk]
            for j in range(taps))
        for k, grad in enumerate((dz * x, dy * _conv(views, w), dz * b)):
            dbcx_ref[0, rows, k * channels:(k + 1) * channels] = (
                grad.astype(dtype))
        dw = tuple(dw[j] + _by_sublane(g * views[j]) for j in range(taps))
        return g[:_SUB], dw

    zero = jnp.zeros((_SUB, channels), jnp.float32)
    _, dw = jax.lax.fori_loop(0, chunks, step, (g_after, (zero,) * taps))
    for j in range(taps):
        dw_ref[0, 0, j:j + 1, :] = jnp.sum(dw[j], axis=0, keepdims=True)


# ------------------------------------------------------- their calls

def _rows(bcx):
    """``bcx`` (..., S, 3 C) as (B, S, 3 C): every row of the leading
    axes its own sequence."""
    return bcx.reshape((-1,) + bcx.shape[-2:])


def _specs(seq, tile, channels):
    """The BlockSpecs of a tile of whole rows over the grid (batch,
    tiles, 1): ``column(part)``, ``tile`` rows of column block ``part``
    of an array ``channels`` a block wide (B, C, X of ``bcx``; 0 of
    ``y`` or ``dy``), and ``before(part)`` / ``after(part)``, the _HALO
    rows that end where the tile starts / start where it ends (the
    sequence's own first / last where there are none: the kernels put
    zeros there)."""
    per_tile = tile // _HALO
    spec = lambda rows, at, part: pl.BlockSpec(
        (1, rows, channels), lambda b, i, g: (b, at(i), part))
    return (
        functools.partial(spec, tile, lambda i: i),
        functools.partial(
            spec, _HALO, lambda i: jnp.maximum(i * per_tile - 1, 0)),
        functools.partial(
            spec, _HALO,
            lambda i: jnp.minimum((i + 1) * per_tile, seq // _HALO - 1)))


# jitted so that every layer of a model shares one trace of a kernel's
# body; always inside the step's own trace, where the recompile
# sentinel's host bookkeeping cannot run
@functools.partial(  # edlint: disable=obs-bare-jit
    jax.jit, static_argnames=("tile", "chunk", "interpret"))
def short_conv_fwd(bcx, taps, tile=None, chunk=None, interpret=False):
    """``bcx`` (..., S, 3 C), ``taps`` (K, C) -> ``y`` (..., S, C) in
    ``bcx``'s dtype; ``tile``, ``chunk``: rows a grid step takes and
    rows an iteration of its loop takes (``row_tile``, ``loop_rows``)."""
    k, channels = taps.shape
    whole = _rows(bcx)
    batch, seq, _ = whole.shape
    tile = tile or row_tile(seq, channels, bcx.dtype.itemsize)
    column, before, _ = _specs(seq, tile, channels)
    y = pl.pallas_call(
        functools.partial(
            _fwd_kernel, chunk=chunk or loop_rows(tile, channels)),
        grid=(batch, seq // tile, 1),
        in_specs=[
            column(0), column(1), column(2), before(0), before(2),
            pl.BlockSpec((k, channels), lambda b, i, g: (0, 0)),
        ],
        out_specs=column(0),
        out_shape=jax_compat.out_struct(
            (batch, seq, channels), bcx.dtype, bcx, taps),
        compiler_params=_params(),
        interpret=interpret,
        name="short_conv_fwd",
    )(whole, whole, whole, whole, whole, taps.astype(jnp.float32))
    return y.reshape(bcx.shape[:-1] + (channels,))


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnames=("tile", "chunk", "interpret"))
def short_conv_bwd(bcx, taps, dy, tile=None, chunk=None, interpret=False):
    """The operands of ``short_conv_fwd`` and its result's cotangent
    (..., S, C) -> (``dbcx`` (..., S, 3 C) in ``bcx``'s dtype, the
    taps' gradient a tile (B, S / tile, K, C) float32)."""
    k, channels = taps.shape
    whole, grad = _rows(bcx), _rows(dy)
    batch, seq, _ = whole.shape
    tile = tile or row_tile(seq, channels, bcx.dtype.itemsize)
    column, before, after = _specs(seq, tile, channels)
    struct = lambda shape, dtype: jax_compat.out_struct(
        shape, dtype, bcx, taps, dy)
    dbcx, dw = pl.pallas_call(
        functools.partial(
            _bwd_kernel, chunk=chunk or loop_rows(tile, channels)),
        grid=(batch, seq // tile, 1),
        in_specs=[
            column(0), column(1), column(2), before(0), before(2), after(1),
            pl.BlockSpec((k, channels), lambda b, i, g: (0, 0)),
            column(0), after(0),
        ],
        out_specs=[
            pl.BlockSpec((1, tile, 3 * channels), lambda b, i, g: (b, i, 0)),
            pl.BlockSpec((1, 1, k, channels), lambda b, i, g: (b, i, 0, 0)),
        ],
        out_shape=[
            struct(whole.shape, bcx.dtype),
            struct((batch, seq // tile, k, channels), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="short_conv_bwd",
    )(whole, whole, whole, whole, whole, whole, taps.astype(jnp.float32),
      grad, grad)
    return dbcx.reshape(bcx.shape), dw


# ------------------------------------------------------- the pair

@jax.custom_vjp
def _gated_short_conv_pallas(bcx, taps):
    return short_conv_fwd(bcx, taps)


def _pallas_fwd(bcx, taps):
    return short_conv_fwd(bcx, taps), (bcx, taps)


def _pallas_bwd(residuals, dy):
    bcx, taps = residuals
    with jax.named_scope(SCOPE):
        dbcx, dw = short_conv_bwd(bcx, taps, dy)
        return dbcx, dw.sum(axis=(0, 1)).astype(taps.dtype)


_gated_short_conv_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def gated_short_conv(bcx, taps, mesh=None):
    """``C * conv(B * X)``: ``bcx`` (..., S, 3 C) the input projection's
    result, ``taps`` (K, C); returns (..., S, C) in ``bcx``'s dtype. By
    the kernel pair where ``conv_impl`` says so, from the backend, the
    dtype, the shapes and ``mesh``; by the module's lines elsewhere.
    The log's ``short conv ... impl=`` line says which."""
    channels, seq, k = _check_width(bcx, taps), bcx.shape[-2], taps.shape[0]
    impl, tile = conv_choice(bcx.dtype, channels, seq, k, mesh)
    log_choice(channels, k, impl, seq, tile)
    run = _gated_short_conv_pallas if impl == "pallas" else (
        gated_short_conv_xla)
    return run(bcx, taps)
