"""The gated output norm that ends a linear or a state-space mixer, as
two Pallas TPU kernels under one VJP (PR 65): an RMSNorm of the rule's
output over a head's (or a group's) lanes, times a learned scale, times
a gate that the layer's input projection wrote. Three forms, which
differ only in the gate and in its place (``FORMS``)::

    norm_silu     rmsnorm(x) w silu(z)       GatedDeltaNet (per head)
    norm_sigmoid  rmsnorm(x) w sigmoid(z)    KimiDeltaAttention (per head)
    silu_norm     rmsnorm(x silu(z)) w       Mamba2Mixer (per group)

with ``rmsnorm(u) = u / sqrt(mean_lanes(u^2) + eps)``. As XLA runs the
modules' lines (``models/transformer.py``, the ``*/out_norm`` scopes) a
pass costs 3 to 6 times what its bytes need: the delta rules'
``(B, H, S, D) -> (B, S, H, D)`` is a copy, the gate's columns are
sliced out of the projection's output, the selective scan's output is
copied to float32 and turned, the statistics are a pass of their own
and the backward is a dozen fusions over float32 copies. Here
(``impl=pallas``):

- ``gated_norm_fwd``: ONE read of ``x`` where the rule wrote it, one
  read of the gate IN PLACE in the projection's output (a BlockSpec at
  ``z_offset``, no slice), one write of the result the output
  projection consumes.
- ``gated_norm_bwd``: reads ``x``, the gate and the cotangent, rebuilds
  the statistics in VMEM (they need only what the backward reads
  anyway, so a saved ``rstd`` would add a stream and save none), writes
  ``dx`` in the rule's layout, ``dz`` and the scale's gradient in
  float32 parts that XLA sums. ``dz`` goes back into the projection's
  cotangent by a pad, which XLA fuses into the projection's backward
  matmuls as it does ``ops/qkv_conv.py``'s ``dX`` (read in the compiled
  steps, PR 65: the kernel's result is the matmul fusions' operand).

The one pair takes its operands one of two ways, as the CALLER's rule
leaves them in memory (``gated_norm``'s ``rows``), because a Mosaic
kernel's operands lie row-major and XLA copies whatever does not:

- **by heads** (the delta rules, whose own kernels write
  (B, H, S, D) row-major): a grid step takes a row tile of several
  heads, the transposition to (B, S, H D) is the block's index (head
  ``h`` written to lanes ``h D ...``), the norm is a sum over a head's
  128 lanes. ``x`` is read, and ``dx`` written, by the rule's
  segments (``qkv_conv.rule_segments``), so the rule's own turn of its
  output back to (B, H, S, D) folds away as q's, k's and v's does.
- **by columns** (the selective scan): XLA lays EVERY array of the
  Mamba-2 mixer with the sequence in the lanes (the projection's
  output, the convolution, the scan's chunks (C, 256 rows), the output
  projection's operand), so the kernels take ``x`` (B, S / R, C, R) a
  chunk at a time, the gate and the result (B, ., S): all three are
  views of what is in memory. The norm then runs DOWN a block: sums of
  registers, no sum over the lanes. Read by rows instead (the first
  form of this PR, measured: ``granite4h-micro-s8k`` 2.3073 -> 2.2257
  samples/s) the pair itself ran at 600 GB/s and XLA put 33 ms a step
  of transposing copies around it (the scan's output, the result for
  the output projection, the projection's output for the
  convolution).

Residuals: ``x``, the projection's output and the scale, all alive
anyway. Neither kernel's name holds ``gdn`` or ``kda`` or starts with
``ssd`` (``benchmark/lib/gdn_trace.py``, ``kda_trace.py`` and
``ssm_trace.py`` charge a Mosaic kernel so named to the SCAN); both
calls sit under the caller's ``*/out_norm`` scope, the backward's
inside the VJP (``scope``), and ``observability/scopes.py:KERNELS`` has
no entry for them: one kernel pair, three scopes, the ``op_name``
decides.

**The same work.** Float32 from the loads on: the statistics, every
product and both gates, as the lines' are; operands as they arrive, ONE
rounding at each result (the delta rules' lines round the norm's result
to the parameters' dtype before the gate where the scale is bfloat16: a
TPU fusion may keep it in float32, these kernels always do, as
``ops/qkv_conv.py``'s). The sigmoid is ``(1 + tanh(z / 2)) / 2``
(``qkv_conv._sigmoid``), no approximate reciprocal anywhere. Against
the lines, forward and VJP, the pair is within one rounding of the
result's dtype (a sum's order differs and a float32 product is formed
in another order): ``tests/test_gated_norm_kernels.py`` and
``scripts/gated_norm.py`` count the unequal elements.

``gated_norm_impl`` chooses with no switch for a user.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.ops.qkv_conv import (
    _LANES, _SUB, _TILES, _by_segment, _by_sublane, _params, _sigmoid)

logger = _logger_factory("elasticdl_tpu.ops.gated_norm")

# form -> (the gate, whether it is applied BEFORE the norm)
FORMS = {
    "norm_silu": ("silu", False),
    "norm_sigmoid": ("sigmoid", False),
    "silu_norm": ("silu", True),
}
# lanes a grid step takes at most, where a normed segment is narrower
# (``gated_norm_impl``'s table): segments of it are unrolled in the body
_WIDE = 512
# elements of one array an iteration of a kernel's loop takes by heads
# (its rows times the step's lanes); by columns the channels of a
# group it takes: what the cells' paired runs of PR 65 were made with
# (alone, 128 read a third less: ``gated_norm_impl``'s table)
_CHUNK = 256 * 512
_CHANNELS = 32
# what a grid step's double-buffered blocks may take of ``_params``'
# VMEM limit: the backward's five arrays
_BLOCK_BYTES = 24 * 2**20


# ------------------------------------------------------- the choice

def step_block(seq, lanes, heads, itemsize, z_offset=0):
    """By heads, (rows a grid step takes, heads it takes): the most
    heads of ``lanes`` lanes, a divisor of ``heads``, within ``_WIDE``
    lanes whose width divides ``z_offset`` (the gate's block index is a
    whole one), then the most rows that divide ``seq`` whose
    double-buffered blocks (the backward's five arrays) fit
    ``_BLOCK_BYTES``; None where there is none."""
    count = next((
        c for c in range(max(_WIDE // lanes, 1), 0, -1)
        if heads % c == 0 and z_offset % (c * lanes) == 0), None)
    if count is None:
        return None
    tile = next((
        t for t in _TILES if seq % t == 0
        and 2 * 5 * t * count * lanes * itemsize <= _BLOCK_BYTES), None)
    return None if tile is None else (tile, count)


def column_block(lanes, groups, rows, itemsize, z_offset=0):
    """By columns, the groups of ``lanes`` channels a grid step takes
    beside the ``rows`` rows of one chunk: the most, a divisor of
    ``groups``, whose channels divide ``z_offset`` and whose
    double-buffered blocks fit ``_BLOCK_BYTES``; None where not one
    group does."""
    return next((
        c for c in range(groups, 0, -1)
        if groups % c == 0 and z_offset % (c * lanes) == 0
        and 2 * 5 * rows * c * lanes * itemsize <= _BLOCK_BYTES), None)


def gated_norm_impl(dtype, lanes, heads, seq, mesh=None, z_offset=0,
                    rows=None, segments=1):
    """``"pallas"`` or ``"xla"``: what runs the gated norm of a layer
    whose projection is ``dtype``, over ``heads`` segments (heads or
    groups) of ``lanes`` normed lanes each and ``seq`` tokens, the
    gate's columns ``z_offset`` lanes into their array, in a step
    sharded over ``mesh`` (None: one device); ``rows``: None where the
    rule hands its output by heads, (B, H, S, lanes), else the rows of
    a chunk where the scan hands it by chunks with a chunk's ROWS in
    the lanes (``gated_norm``); ``segments``: the equal runs of the
    sequence a delta rule writes its output by. The kernels: a TPU
    with nothing to partition (one device, or a region already manual
    over the mesh), bfloat16 or float32, the normed lanes whole
    128-lane rows, the gate's columns on a block's boundary, and by
    heads a row tile that divides a segment, by columns chunks of whole
    128-lane rows that divide the sequence. Everything else (the CPU,
    the tests' 16-wide heads, a mesh under GSPMD) runs the module's
    lines.

    On one v5e (``scripts/gated_norm.py``, PR 65, bfloat16, one layer's
    call; ms a call, in brackets GB/s over the bytes a call NEEDS:
    three arrays forward, five backward)::

                                    XLA's lines        the pair
        shape                       fwd     fwd+VJP    fwd          bwd
        by columns, 1 x 8,192 x 4,096, chunks of 256 rows
        1 group of 4,096 (granite)  0.68    1.90       0.505 (398)  0.836 (401)
        8 groups of 512 (nemotron)  2.24    3.35       0.516 (390)  0.850 (395)
        by heads, 1 x 32 x 32,768 x 128 read by 8 segments
        SiLU gate, z at column
          8,192 of 12,288 (qwen)    1.65    14.64      1.225 (657)  2.051 (654)
        sigmoid gate (kimi)         1.64    12.03      1.221 (659)  2.045 (656)

    Every form wins at every cell's shape, so every form is in. What
    decides the kernels' time is what ONE ITERATION of the loop takes
    (Mosaic does not overlap iterations: PR 44's lesson). By columns,
    a group's channels an iteration, granite's shape fwd / bwd: 16
    0.934 / 1.580, 32 0.510 / 0.843, 64 0.350 / 0.587, **128 0.335 /
    0.559**; nemotron's 8 groups a grid step 0.339 / 0.568 at 128,
    4 groups 0.353 / 0.575, 2 0.370 / 0.611, 1 0.399 / 0.649. THE
    CELLS RUN 32 (0.505 / 0.836 in the table's place: 398 GB/s): the
    sweep was read after their paired runs, no chip was to be had for
    a second set, and a constant is not changed under a claim without
    one; 128 (256 at nemotron's own chunks of 128 rows, which no run
    has made) is the next PR's first line. By
    heads, rows an iteration at 1,024 rows x 4 heads a step: 16 2.00 /
    3.48, 32 1.31 / 2.18, 64 1.34 / 2.08, 128 1.25 / 2.05, **256 1.22
    / 2.04**; 1 head a step at 256 rows 1.59 / 2.39, 2 heads 1.36 /
    2.15, 8 heads (128 rows) 1.21 / 2.05; 512 rows a step 1.42 / 2.16,
    256 rows 1.55 / 2.31. A kernel traces and lowers in 0.02-0.08 s.
    Against the lines the results are within ONE rounding of bfloat16:
    0.13-0.16% of a result's elements differ (44,763 of 33.6 M) and of
    a delta rule's ``dx`` 27%, by up to 1.55 roundings, where the
    LINES are the further from float32 (rms 2.35e-3 against the
    pair's 1.66e-3: their VJP rounds the norm's cotangent to bfloat16
    between the gate and the norm).
    """
    dtype = jnp.dtype(dtype)
    fits = (
        jax_compat.kernels_can_run(mesh)
        and dtype in (jnp.bfloat16, jnp.float32)
        and lanes % _LANES == 0
        and _block(lanes, heads, seq, dtype.itemsize, z_offset, rows,
                   segments) is not None
    )
    return "pallas" if fits else "xla"


def _block(lanes, heads, seq, itemsize, z_offset, rows, segments=1):
    """``step_block``'s answer by heads for one of ``segments`` runs of
    the sequence, by columns (``rows`` rows, ``column_block``'s
    groups); None where the kernels do not fit."""
    if rows is None:
        return None if seq % segments else step_block(
            seq // segments, lanes, heads, itemsize, z_offset)
    if rows % _LANES or seq % rows:
        return None
    count = column_block(lanes, heads, rows, itemsize, z_offset)
    return None if count is None else (rows, count)


@functools.lru_cache(maxsize=None)
def log_choice(form, lanes, heads, impl, tokens, tile):
    """One line per distinct layer shape (this runs at trace time),
    beside the rule's line."""
    logger.info(
        "gated norm form=%s lanes=%d heads=%d impl=%s tile=%s (tokens=%d)",
        form, lanes, heads, impl, tile, tokens)


def choose(form, x, z, lanes, heads, mesh=None, z_offset=0, rows=None,
           segments=1):
    """``gated_norm_impl``'s answer for a layer's operands ``x`` and
    ``z`` (B, S, W) (``xla`` where their dtypes differ: the parameters'
    float32 beside a bfloat16 input), with the log's line that says
    which it got and the rows a grid step takes."""
    seq, size = z.shape[1], z.dtype.itemsize
    impl = gated_norm_impl(
        z.dtype, lanes, heads, seq, mesh, z_offset, rows, segments
    ) if x.dtype == z.dtype else "xla"
    log_choice(form, lanes, heads, impl, seq, _block(
        lanes, heads, seq, size, z_offset, rows, segments)[0]
        if impl == "pallas" else None)
    return impl


# ------------------------------------------------------- in the tile

def _gates(z, kind):
    """(the gate, its derivative) of ``z`` float32."""
    sig = _sigmoid(z)
    if kind == "silu":
        return z * sig, sig * (1.0 + z * (1.0 - sig))
    return sig, sig * (1.0 - sig)


def _rstd(u, eps):
    return jax.lax.rsqrt(
        jnp.sum(u * u, axis=-1, keepdims=True) / u.shape[-1] + eps)


def _forward(x, z, w, form, eps):
    """The result (rows, lanes) float32 of one head: ``x``, ``z``
    (rows, lanes) and ``w`` (1, lanes) float32, in the lines' own order
    of products."""
    kind, gate_first = FORMS[form]
    gate, _ = _gates(z, kind)
    if gate_first:
        u = x * gate
        return (u * _rstd(u, eps)) * w
    return (x * (_rstd(x, eps) * w)) * gate


def _backward(x, z, w, grad, form, eps):
    """(``dx``, ``dz``, the scale's gradient a row) (rows, lanes)
    float32 from the result's cotangent ``grad``."""
    kind, gate_first = FORMS[form]
    gate, d_gate = _gates(z, kind)
    u = x * gate if gate_first else x
    inv = _rstd(u, eps)
    unit = u * inv
    # the cotangent of ``unit w``
    scaled = grad if gate_first else grad * gate
    by_w = scaled * w
    du = inv * (by_w - unit * (
        jnp.sum(by_w * unit, axis=-1, keepdims=True) / u.shape[-1]))
    if gate_first:
        return du * gate, du * x * d_gate, scaled * unit
    return du, grad * (unit * w) * d_gate, scaled * unit


def _loop(count, chunk, body, carry=0):
    """``body(slice, carry)`` over ``count`` slices of ``chunk``."""
    return jax.lax.fori_loop(0, count, lambda r, carry: body(
        pl.ds(pl.multiple_of(r * chunk, chunk), chunk), carry), carry)


def _fwd_kernel(x_ref, z_ref, w_ref, out_ref, *, form, lanes, eps, chunk):
    """By heads, one tile of rows of several heads: ``x_ref``
    (1, c, T, lanes), ``z_ref`` and ``out_ref`` (1, T, c lanes),
    ``w_ref`` (1, c lanes) float32."""
    tile, wide = out_ref.shape[1:]

    def body(rows, carry):
        for h in range(wide // lanes):
            cols = slice(h * lanes, (h + 1) * lanes)
            out_ref[0, rows, cols] = _forward(
                x_ref[0, h, rows, :].astype(jnp.float32),
                z_ref[0, rows, cols].astype(jnp.float32), w_ref[:, cols],
                form, eps).astype(out_ref.dtype)
        return carry

    _loop(tile // chunk, chunk, body)


def _bwd_kernel(x_ref, z_ref, w_ref, grad_ref, dx_ref, dz_ref, dw_ref, *,
                form, lanes, eps, chunk):
    """The same tile with the result's cotangent ``grad_ref``
    (1, T, c lanes): writes ``dx_ref`` (1, c, T, lanes), ``dz_ref``
    (1, T, c lanes) and the tile's share of the scale's gradient
    ``dw_ref`` (1, 1, _SUB, c lanes) float32, a sublane each."""
    tile, wide = dz_ref.shape[1:]
    dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    def body(rows, carry):
        for h in range(wide // lanes):
            cols = slice(h * lanes, (h + 1) * lanes)
            dx, dz, dw = _backward(
                x_ref[0, h, rows, :].astype(jnp.float32),
                z_ref[0, rows, cols].astype(jnp.float32), w_ref[:, cols],
                grad_ref[0, rows, cols].astype(jnp.float32), form, eps)
            dx_ref[0, h, rows, :] = dx.astype(dx_ref.dtype)
            dz_ref[0, rows, cols] = dz.astype(dz_ref.dtype)
            dw_ref[0, 0, :, cols] += _by_sublane(dw)
        return carry

    _loop(tile // chunk, chunk, body)


# By columns the norm runs DOWN a block: a group's channels lie on the
# sublanes and in the rows of registers, so a statistic is a sum of
# registers and one sum over 8 sublanes a chunk, no sum over the lanes
# at all. A group (4,096 channels x 256 rows is 1,024 registers) is
# walked twice, ``chunk`` channels at a time: the statistics first,
# the results after them from the same blocks in VMEM.

def _over_sublanes(acc, lanes):
    return jnp.sum(acc, axis=0, keepdims=True) / lanes


def _channels(group, channels, chunk):
    """The block's rows that hold ``channels`` of ``group``."""
    return pl.ds(pl.multiple_of(group.start + channels.start, chunk), chunk)


def _fwd_columns(x_ref, z_ref, w_ref, out_ref, *, form, lanes, eps, chunk):
    """One chunk's rows of several groups: ``x_ref`` (1, 1, c lanes, R),
    ``z_ref`` and ``out_ref`` (1, c lanes, R), ``w_ref`` (c lanes, 1)
    float32."""
    kind, gate_first = FORMS[form]
    wide, rows = out_ref.shape[1:]

    def group(at, carry):
        def load(channels):
            where = _channels(at, channels, chunk)
            x = x_ref[0, 0, where, :].astype(jnp.float32)
            gate, _ = _gates(z_ref[0, where, :].astype(jnp.float32), kind)
            return where, x, gate

        def stat(channels, acc):
            _, x, gate = load(channels)
            u = x * gate if gate_first else x
            return acc + _by_sublane(u * u)

        inv = jax.lax.rsqrt(_over_sublanes(_loop(
            lanes // chunk, chunk, stat,
            jnp.zeros((_SUB, rows), jnp.float32)), lanes) + eps)

        def write(channels, carry):
            where, x, gate = load(channels)
            w = w_ref[where, :]
            out = ((x * gate) * inv) * w if gate_first else (
                x * (inv * w)) * gate
            out_ref[0, where, :] = out.astype(out_ref.dtype)
            return carry

        return _loop(lanes // chunk, chunk, write, carry)

    _loop(wide // lanes, lanes, group)


def _bwd_columns(x_ref, z_ref, w_ref, grad_ref, dx_ref, dz_ref, dw_ref, *,
                 form, lanes, eps, chunk):
    """The same block with the result's cotangent ``grad_ref``
    (1, c lanes, R): writes ``dx_ref`` (1, 1, c lanes, R), ``dz_ref``
    (1, c lanes, R) and ADDS the block's share of the scale's gradient
    to ``dw_ref`` (1, c lanes, 128) float32, a lane each, which stays
    in VMEM while the grid walks the chunks (its innermost axis)."""
    kind, gate_first = FORMS[form]
    wide, rows = dz_ref.shape[1:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    def group(at, carry):
        def load(channels):
            where = _channels(at, channels, chunk)
            x = x_ref[0, 0, where, :].astype(jnp.float32)
            z = z_ref[0, where, :].astype(jnp.float32)
            gate, d_gate = _gates(z, kind)
            grad = grad_ref[0, where, :].astype(jnp.float32)
            if gate_first:
                return where, x, gate, d_gate, grad, x * gate, grad
            return where, x, gate, d_gate, grad, x, grad * gate

        def stat(channels, acc):
            where, _, _, _, _, u, scaled = load(channels)
            return (acc[0] + _by_sublane(u * u),
                    acc[1] + _by_sublane(scaled * w_ref[where, :] * u))

        zero = jnp.zeros((_SUB, rows), jnp.float32)
        squares, along = _loop(lanes // chunk, chunk, stat, (zero, zero))
        inv = jax.lax.rsqrt(_over_sublanes(squares, lanes) + eps)
        # ``unit mean(by_w unit)`` is ``u`` times this
        along = inv * inv * _over_sublanes(along, lanes)

        def write(channels, carry):
            where, x, gate, d_gate, grad, u, scaled = load(channels)
            w = w_ref[where, :]
            unit = u * inv
            du = inv * (scaled * w - u * along)
            if gate_first:
                dx, dz = du * gate, du * x * d_gate
            else:
                dx, dz = du, grad * (unit * w) * d_gate
            dx_ref[0, 0, where, :] = dx.astype(dx_ref.dtype)
            dz_ref[0, where, :] = dz.astype(dz_ref.dtype)
            dw = scaled * unit
            dw_ref[0, where, :] += sum(
                dw[:, lane:lane + _LANES] for lane in range(0, rows, _LANES))
            return carry

        return _loop(lanes // chunk, chunk, write, carry)

    _loop(wide // lanes, lanes, group)


# ------------------------------------------------------- their calls

def _by_heads(x, z, lanes, z_offset, tile, group):
    """(grid, the BlockSpecs of ``x`` (segments, B, H, S / segments,
    lanes), of the gate in ``z`` (B, S, W), of an array (B, S, C) and
    of the scale (1, C), the result's shape, rows a grid step takes) by
    heads: row block ``i`` of the sequence is block ``i % per`` of
    segment ``i // per``."""
    _, batch, heads, rows, _ = x.shape
    seq = z.shape[1]
    block = step_block(rows, lanes, heads, z.dtype.itemsize, z_offset)
    tile, count = tile or block[0], group or block[1]
    wide, per = count * lanes, rows // tile
    first = z_offset // wide
    return (
        (batch, seq // tile, heads // count),
        pl.BlockSpec(
            (None, 1, count, tile, lanes),
            lambda b, i, g: (i // per, b, g, i % per, 0)),
        pl.BlockSpec((1, tile, wide), lambda b, i, g: (b, i, first + g)),
        pl.BlockSpec((1, tile, wide), lambda b, i, g: (b, i, g)),
        pl.BlockSpec((1, wide), lambda b, i, g: (0, g)),
        (batch, seq, heads * lanes), tile)


def _by_columns(x, z, lanes, z_offset, group):
    """The same by columns: ``x`` (B, N, C, R), the gate in ``z``
    (B, W, S), an array (B, C, S), the scale (C, 1); the grid walks the
    chunks innermost."""
    batch, chunks, width, rows = x.shape
    count = group or column_block(
        lanes, width // lanes, rows, z.dtype.itemsize, z_offset)
    wide = count * lanes
    first = z_offset // wide
    return (
        (batch, width // wide, chunks),
        pl.BlockSpec((1, 1, wide, rows), lambda b, g, n: (b, n, g, 0)),
        pl.BlockSpec((1, wide, rows), lambda b, g, n: (b, first + g, n)),
        pl.BlockSpec((1, wide, rows), lambda b, g, n: (b, g, n)),
        pl.BlockSpec((wide, 1), lambda b, g, n: (g, 0)),
        (batch, width, chunks * rows), rows)


def _wide_scale(scale, width, columns):
    """``scale`` (lanes,), one for every segment, or (C,) as (1, C)
    float32, by columns (C, 1)."""
    scale = jnp.tile(scale.astype(jnp.float32), width // scale.shape[0])
    return scale[:, None] if columns else scale[None]


def _layout(x, z, lanes, z_offset, columns, tile, group, chunk):
    """``_by_columns``' or ``_by_heads``' answer with what an iteration
    of the kernel's loop takes: a group's channels, or rows."""
    if columns:
        return _by_columns(x, z, lanes, z_offset, group) + (
            chunk or _CHANNELS,)
    found = _by_heads(x, z, lanes, z_offset, tile, group)
    wide, tile = found[3].block_shape[-1], found[-1]
    return found + (min(chunk or _CHUNK // wide, tile),)


_STATIC = ("form", "lanes", "eps", "z_offset", "columns", "tile", "group",
           "chunk", "interpret")


# jitted so that every layer of a model shares one trace of a kernel's
# body; always inside the step's own trace, where the recompile
# sentinel's host bookkeeping cannot run
@functools.partial(  # edlint: disable=obs-bare-jit
    jax.jit, static_argnames=_STATIC)
def gated_norm_fwd(x, z, scale, form, lanes, eps, z_offset=0, columns=False,
                   tile=None, group=None, chunk=None, interpret=False):
    """By heads ``x`` (segments, B, H, S / segments, lanes), the gate's
    array ``z`` (B, S, W) whose columns ``[z_offset, z_offset + C)``
    gate, ``scale`` (lanes,) or (C,) -> the result (B, S, C) in ``z``'s
    dtype; by ``columns`` ``x`` (B, N, C, R), ``z`` (B, W, S) whose
    ROWS ``[z_offset, ...)`` gate -> (B, C, S). ``tile``, ``group``,
    ``chunk``: rows and heads (groups) a grid step takes
    (``step_block``, ``column_block``) and rows (a group's channels) an
    iteration of its loop takes."""
    grid, x_block, z_block, out_block, w_block, shape, _, chunk = _layout(
        x, z, lanes, z_offset, columns, tile, group, chunk)
    return pl.pallas_call(
        functools.partial(
            _fwd_columns if columns else _fwd_kernel, form=form,
            lanes=lanes, eps=eps, chunk=chunk),
        grid=grid,
        in_specs=[x_block, z_block, w_block],
        out_specs=out_block,
        out_shape=jax_compat.out_struct(shape, z.dtype, x, z, scale),
        compiler_params=_params(),
        interpret=interpret,
        name="gated_norm_fwd",
    )(x, z, _wide_scale(scale, shape[1 if columns else 2], columns))


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnames=_STATIC)
def gated_norm_bwd(x, z, scale, grad, form, lanes, eps, z_offset=0,
                   columns=False, tile=None, group=None, chunk=None,
                   interpret=False):
    """The operands of ``gated_norm_fwd`` and its result's cotangent ->
    (``dx`` as ``x``, ``dz`` as the result in ``z``'s dtype, the
    scale's gradient in parts, float32: by heads a tile and a sublane
    (B, S / tile, _SUB, C), by columns a lane (B, C, 128))."""
    struct = lambda shape, dtype: jax_compat.out_struct(
        shape, dtype, x, z, scale, grad)
    grid, x_block, z_block, out_block, w_block, shape, tile, chunk = _layout(
        x, z, lanes, z_offset, columns, tile, group, chunk)
    if columns:
        wide = out_block.block_shape[1]
        dw_block = pl.BlockSpec((1, wide, _LANES), lambda b, g, n: (b, g, 0))
        dw = struct((shape[0], shape[1], _LANES), jnp.float32)
    else:
        wide = out_block.block_shape[-1]
        dw_block = pl.BlockSpec(
            (1, 1, _SUB, wide), lambda b, i, g: (b, i, 0, g))
        dw = struct((shape[0], shape[1] // tile, _SUB, shape[2]), jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _bwd_columns if columns else _bwd_kernel, form=form,
            lanes=lanes, eps=eps, chunk=chunk),
        grid=grid,
        in_specs=[x_block, z_block, w_block, out_block],
        out_specs=[x_block, out_block, dw_block],
        out_shape=[struct(x.shape, x.dtype), struct(shape, z.dtype), dw],
        compiler_params=_params(),
        interpret=interpret,
        name="gated_norm_bwd",
    )(x, z, _wide_scale(scale, shape[1 if columns else 2], columns), grad)


# ------------------------------------------------------- the pair

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _pair(x, z, scale, form, lanes, eps, z_offset, columns, scope):
    return gated_norm_fwd(x, z, scale, form, lanes, eps, z_offset, columns)


def _pair_fwd(x, z, scale, form, lanes, eps, z_offset, columns, scope):
    return gated_norm_fwd(
        x, z, scale, form, lanes, eps, z_offset, columns), (x, z, scale)


def _pair_bwd(form, lanes, eps, z_offset, columns, scope, residuals, grad):
    x, z, scale = residuals
    with jax.named_scope(scope):
        dx, dz, dw = gated_norm_bwd(
            x, z, scale, grad, form, lanes, eps, z_offset, columns)
        axis = 1 if columns else 2
        pad = [(0, 0)] * 3
        pad[axis] = (z_offset, z.shape[axis] - z_offset - dz.shape[axis])
        dw = dw.sum(axis=(0, 2) if columns else (0, 1, 2))
        return (
            dx, jnp.pad(dz, pad),
            dw.reshape(-1, scale.shape[0]).sum(axis=0).astype(scale.dtype))


_pair.defvjp(_pair_fwd, _pair_bwd)


def gated_norm(x, z, scale, form, lanes, eps, z_offset, scope, rows=None,
               segments=1):
    """The gated norm (module docstring) of a mixer's output ``x``,
    gated by the columns ``[z_offset, z_offset + C)`` of ``z`` (B, S, W)
    and scaled by ``scale`` ((``lanes``,): every segment's; (C,): a
    lane's own) -> (B, S, C) in ``z``'s dtype; ``scope``: the named
    scope the backward's operations lie under (the caller's own holds
    the forward's). ``rows`` None: ``x`` (B, H, S, ``lanes``) as a
    delta rule's kernels write it, ``segments`` equal runs of the
    sequence first (``ops/qkv_conv.py:rule_segments``: read so, the
    rule's own turn back to (B, H, S, D) folds with this one to no
    copy, as for q, k and v), the norm a head's. ``rows`` R: ``x``
    (B, S, C) as the selective scan writes it, R-row chunk by chunk
    with a chunk's ROWS in the lanes, as XLA lays every array of that
    mixer (the sequence in the lanes); the kernels then take ``x``
    (B, S / R, C, R), the gate and the result (B, ., S), and every
    turn here is a view of what is in memory."""
    if rows is None:
        return _pair(_by_segment(x, segments), z, scale, form, lanes, eps,
                     z_offset, False, scope)
    batch, seq, width = x.shape
    by_chunks = jnp.swapaxes(x.reshape(batch, seq // rows, rows, width), 2, 3)
    return jnp.swapaxes(_pair(
        by_chunks, jnp.swapaxes(z, 1, 2), scale, form, lanes, eps, z_offset,
        True, scope), 1, 2)
