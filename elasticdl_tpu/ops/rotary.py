"""The rotary position embedding of a query and a key (RoPE; YaRN's
table where a model scales it): lanes ``i`` and ``i + half`` of the
``2 half`` lanes of a head that rotate turn by the angle ``position *
frequency_i``,

    out[i]        = x[i] cos - x[i + half] sin
    out[i + half] = x[i + half] cos + x[i] sin

and the lanes past them pass through. A pass is bound by the bytes it
moves: it reads q and k once and writes them once. ``rotate`` runs it
one of two ways, and ``rotary_impl`` chooses with no switch for a user:

- ``impl=xla`` (``rotary_embedding``, the plain reference of the
  tests): ``jax.numpy`` lines that form ``cos`` and ``sin`` where they
  are called, slice the head into halves, multiply in float32 and
  concatenate; a partial rotation concatenates once more. They run on
  the CPU, under a mesh that is not manual, at a 64-wide head and
  wherever else ``rotary_impl`` refuses, and theirs is the program
  every such model always had.
- ``impl=pallas``: two Pallas TPU kernels under one ``custom_vjp``,
  after ``ops/short_conv.py`` (PR 50). The table (``rotary_table``:
  the same lines' ``cos`` and ``sin``, arranged a lane of the head
  each, float32) is formed ONCE a call outside the kernels and handed
  in. ``rotary_fwd``: a grid step takes a tile of whole rows of several
  heads of q AND of k where the projections wrote them, (B, H, S, d);
  the grid walks the sequence outside the heads, so a table block is
  fetched once a row tile. In the tile: the rows to float32, ``x cos
  + swapped(x) sin`` with the halves swapped by ``pltpu.roll`` on the
  lanes (no slice, no concatenate), rounded once. The result is written
  over its operand (``input_output_aliases``): no second q-sized buffer
  exists, and where fewer lanes rotate than a head has, only the
  128-lane groups that hold them are read and written at all.
  ``rotary_bwd``: the rotation of the cotangent by the negated angles,
  the same body with the sign of ``sin`` flipped; the table is the
  only residual.

**The same work.** Either way: the configuration's ``cos`` and ``sin``
in float32, float32 products from narrower operands, one rounding to
the operand's dtype, every position and head. On the chip the kernels'
results are the lines' element for element, forward and VJP
(``scripts/rotary.py``: 0 unequal at every cell's shape). As traced,
the lines' VJP rounds each of a lane's two terms to the operand's dtype
and adds them there (the transposes of the two float32 widenings of one
slice); the TPU's compiler keeps the sum in float32, as the kernel
does, and the CPU's does not: there the kernel is the closer to the
float32 result, and equals the lines applied to the cotangent with the
positions negated (``tests/test_rotary_kernels.py``).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.ops.qkv_conv import _LANES, _TILES, _params

logger = _logger_factory("elasticdl_tpu.ops.rotary")

# an iteration of a kernel's loop over its tile takes the rows whose
# array is this many elements (32 float32 registers, ``qkv_conv``'s
# 256 x 128; ``rotary_impl``'s table)
_CHUNK = 256 * 128
# what a grid step's double-buffered blocks may take of ``_params``'
# VMEM limit: q and k, in and out, and the table's two
_BLOCK_BYTES = 24 * 2**20


# --------------------------------------------------- the module's lines

def yarn_mscale(factor, mscale):
    """``0.1 mscale ln(factor) + 1`` over a factor above 1, else 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim, base, scaling):
    """The ``dim // 2`` rotary frequencies under YaRN, as the published
    DeepSeek-V3 code builds them (``DeepseekV3YarnRotaryEmbedding``):
    pair i keeps ``base^(-2i/dim)`` below the correction dimension of
    ``beta_fast`` rotations over the original context, takes that over
    ``factor`` above the one of ``beta_slow``, and a linear blend of
    the two between them."""
    half = dim // 2

    def correction_dim(rotations):
        return dim * math.log(
            scaling.original_max_position_embeddings
            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extrapolated = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return extrapolated / scaling.factor * ramp + extrapolated * (1 - ramp)


def cos_sin(seq, dim, base, positions, scaling, shape):
    """``cos`` and ``sin`` of the angles ``seq`` positions turn the
    ``dim // 2`` lane pairs of ``dim`` lanes by, float32, each reshaped
    to ``shape``: ``base^(-2i/dim)`` a pair, YaRN's blended table
    (``yarn_frequencies``) and amplitude under ``scaling``, the rows'
    own indices where ``positions`` (S,) is None."""
    half = dim // 2
    if scaling is None:
        freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        amplitude = 1.0
    else:
        freqs = yarn_frequencies(dim, base, scaling)
        amplitude = (yarn_mscale(scaling.factor, scaling.mscale)
                     / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    positions = (
        jnp.arange(seq, dtype=jnp.float32) if positions is None
        else positions.astype(jnp.float32))
    angles = positions[:, None] * freqs[None, :]
    cos = jnp.cos(angles).reshape(shape)
    sin = jnp.sin(angles).reshape(shape)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    return cos, sin


def rotary_embedding(x, base=10000.0, seq_axis=2, positions=None,
                     scaling=None):
    """Apply RoPE; seq_axis=2 for (B, H, S, d), 1 for (B, S, H, d).
    ``positions`` (S,): the position each row rotates by where it is
    not its index (block diffusion's two copies of one sequence).
    ``scaling`` (``YarnScaling``): YaRN's frequency table in place of
    ``base^(-2i/d)``; cos and sin are multiplied by ``mscale`` over
    ``mscale_all_dim``'s (1 where the two are equal, DeepSeek-V3's)."""
    seq, dim = x.shape[seq_axis], x.shape[-1]
    half = dim // 2
    shape = [1] * x.ndim
    shape[seq_axis], shape[-1] = seq, half
    cos, sin = cos_sin(seq, dim, base, positions, scaling, shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def rotate_xla(t, rotary_dim=None, **rope):
    """``rotary_embedding`` on the first ``rotary_dim`` lanes of ``t``
    (B, H, S, d) (None: all of them), the rest passing through."""
    if rotary_dim is None:
        return rotary_embedding(t, **rope)
    return jnp.concatenate([
        rotary_embedding(t[..., :rotary_dim], **rope), t[..., rotary_dim:],
    ], axis=-1)


# ------------------------------------------------------- the choice

def lane_groups(rotary_dim):
    """Lanes of a head the kernels read and write: the whole 128-lane
    groups that hold the ``rotary_dim`` lanes that rotate."""
    return -(-rotary_dim // _LANES) * _LANES


def step_block(seq, heads, width, itemsize):
    """(rows a grid step takes, grid steps over the heads): the most
    rows that divide ``seq`` and the fewest steps that divide every
    count of ``heads`` (q's and k's: a step takes ``1 / steps`` of
    each) whose double-buffered blocks, operands in and out ``width``
    lanes wide and the table's two, fit ``_BLOCK_BYTES``; None where no
    tile divides the sequence."""
    shared = math.gcd(*heads)
    for tile in (t for t in _TILES if seq % t == 0):
        for steps in (s for s in range(1, shared + 1) if shared % s == 0):
            if 2 * tile * width * (
                    2 * sum(heads) // steps * itemsize + 2 * 4
            ) <= _BLOCK_BYTES:
                return tile, steps
    return None


def rotary_impl(dtype, head_dim, rotary_dim, seq, mesh=None):
    """``"pallas"`` or ``"xla"``: what rotates the q and k (B, H, S,
    ``head_dim``) of a layer, ``rotary_dim`` lanes of a head turning,
    over ``seq`` positions in a step sharded over ``mesh`` (None: one
    device). The kernels: a TPU with nothing to partition (one device,
    or a region already manual over the mesh), bfloat16 or float32, a
    head of whole 128-lane rows, an even count of lanes that rotate
    and a row tile that divides the sequence; with or without
    ``positions`` and YaRN, which only the table sees. Everything else
    (the CPU, a 64-wide head, a mesh under GSPMD, the tests' small
    widths) runs the module's lines.

    On one v5e (``scripts/rotary.py``, PR 56, bfloat16, q and k of one
    layer; ms a call, in brackets GB/s over the bytes a call NEEDS: the
    lane groups that rotate read once and written once)::

                                 XLA's lines        the pair
        q heads / kv x tokens    fwd     fwd+VJP    fwd          fwd+VJP*
        16/16 x 16,384 x 128     1.28    3.43       0.48 (560)   0.93 (580)
        8/8 x 16,384 x 256       0.60    1.88       0.49 (544)   0.92 (585)
        8/8 x 16,384 x 256,
          64 lanes rotate        1.53    2.36       0.29 (466)   0.53 (509)
        48/8 x 32,768 x 128,
          64 rotate, YaRN        10.60   20.28      1.51 (622)   3.02 (623)
        64/8 x 32,768 x 128      6.18    17.13      1.92 (631)   3.82 (633)
        32/4 x 16,384 x 128,
          positions given        1.52    4.00       0.53 (566)   1.02 (590)
        16/2 x 32,768 x 256,
          64 rotate              3.95    8.35       0.56 (537)   1.14 (528)
        8 x 16/16 x 4,096 x 128  2.86    7.60       0.91 (592)   1.69 (636)

    (*) through the ``custom_vjp``, the table's forming included. Every
    form wins at every cell's shape, so every form is in. The results
    are the lines' element for element, forward and VJP, at all eight
    shapes (0 unequal of up to 151 M). What the lines lose is not the
    transcendentals: handed ``cos`` and ``sin`` they read 1.25 ms for
    1.28. It is the halves: a slice at lane 64 (or 32) and a
    concatenation are shuffles of every register and passes of their
    own, 4 x the bytes' time at a whole head and 12 x where 64 lanes of
    128 rotate, and nothing at a whole 256-wide head, whose halves are
    whole registers (the second row: the Pythia cells'); the same lines
    written with ``jnp.roll`` and the kernels' table read 2.19 ms. What
    decides the KERNELS' time is the rows an iteration of the loop
    takes (16/16 x 16,384 x 128, 1,024 rows and 8 heads of each a grid
    step): 32 rows 1.55 ms, 64 0.82,
    128 0.52, **256 0.48**, 512 0.47, 1,024 0.47; Mosaic does not
    overlap iterations, so a short one waits for its own loads. Rows a
    grid step (256, 512, 1,024) and steps over the heads (1 to 16)
    move nothing at 256 rows an iteration (0.47-0.49; 16 steps of one
    head 0.48-0.68); q and k in one call or one each 0.48 against 0.50.
    A kernel takes 0.013-0.026 s to trace and lower. In
    ``ouro2.6b-s16k``'s step (traced): ``rotary_fwd`` 0.42 ms a call,
    ``rotary_bwd`` 0.43, where the lines took 1.39 a pass. The
    transposition to (B, H, S, d) stays outside: XLA writes it from the
    projection's matmul (1.014 ms against 0.981 for the (B, S, H, d)
    layout, where a pass of its own takes 0.24), so there is nothing
    for a kernel to take.
    """
    dtype = jnp.dtype(dtype)
    fits = (
        jax_compat.kernels_can_run(mesh)
        and dtype in (jnp.bfloat16, jnp.float32)
        and head_dim % _LANES == 0
        and 0 < rotary_dim <= head_dim and rotary_dim % 2 == 0
        and any(seq % tile == 0 for tile in _TILES)
    )
    return "pallas" if fits else "xla"


@functools.lru_cache(maxsize=None)
def log_choice(impl, heads, kv_heads, head_dim, rotary_dim, seq, positions,
               yarn):
    """One line per distinct call shape (this runs at trace time)."""
    logger.info(
        "rotary impl=%s heads=%d kv_heads=%d head=%d lanes=%d tokens=%d "
        "positions=%s yarn=%s transposition=outside (float32 arithmetic, "
        "one rounding)",
        impl, heads, kv_heads, head_dim, rotary_dim, seq,
        "given" if positions else "rows", "yes" if yarn else "no")


# ------------------------------------------------------- in the tile

def rotary_table(seq, rotary_dim, width, base=10000.0, positions=None,
                 scaling=None):
    """(``cos``, ``sin``) each (S, ``width``) float32, a lane of the
    kernels' lane groups each: ``cos | cos | 1`` and ``-sin | sin | 0``
    over the ``rotary_dim`` lanes that rotate and the rest, from
    ``cos_sin``'s values."""
    half = rotary_dim // 2
    cos, sin = cos_sin(seq, rotary_dim, base, positions, scaling, (seq, half))
    rest = (seq, width - rotary_dim)
    return (jnp.concatenate([cos, cos, jnp.ones(rest, cos.dtype)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros(rest, sin.dtype)], axis=-1))


def _turned(x, cos, sin, rotary_dim, sign):
    """``x`` (rows, W) float32 turned by the table's rows: ``x cos +
    sign swapped(x) sin``, the halves of the first ``rotary_dim`` lanes
    swapped by rolls on the lanes, the other lanes ``x``."""
    width, half = x.shape[-1], rotary_dim // 2
    swapped = pltpu.roll(x, half, 1)  # lane i reads lane i - half
    if rotary_dim < width:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        swapped = jnp.where(
            lane < half, pltpu.roll(x, width - half, 1), swapped)
    out = x * cos + swapped * sin if sign > 0 else x * cos - swapped * sin
    return out if rotary_dim == width else jnp.where(
        lane < rotary_dim, out, x)


def _kernel(cos_ref, sin_ref, *refs, rotary_dim, sign, chunk):
    """One tile of whole rows of several heads: ``cos_ref``,
    ``sin_ref`` (T, W) float32 the table's rows, then the operands'
    blocks (1, h, T, W) and, as many again, the results' (the same
    memory)."""
    ins, outs = refs[:len(refs) // 2], refs[len(refs) // 2:]

    def rows_step(r, carry):
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        cos, sin = cos_ref[rows, :], sin_ref[rows, :]
        for x_ref, out_ref in zip(ins, outs):

            def head_step(h, carry):
                x = x_ref[0, h, rows, :].astype(jnp.float32)
                out_ref[0, h, rows, :] = _turned(
                    x, cos, sin, rotary_dim, sign).astype(out_ref.dtype)
                return carry

            jax.lax.fori_loop(0, x_ref.shape[1], head_step, 0)
        return carry

    jax.lax.fori_loop(0, cos_ref.shape[0] // chunk, rows_step, 0)


# ------------------------------------------------------- their calls

def _call(name, sign, operands, cos, sin, rotary_dim, tile, steps, chunk,
          interpret):
    """The kernel ``name`` over ``operands``, arrays (B, H_i, S, d) of
    one dtype and sequence (a head count each), turned in place by the
    table ``cos``, ``sin`` (S, W): the grid walks the batch, the row
    tiles and, innermost, ``steps`` blocks of every operand's heads, so
    a table block is fetched once a row tile."""
    seq, width = cos.shape
    batch = operands[0].shape[0]
    block = step_block(
        seq, tuple(x.shape[1] for x in operands), width,
        operands[0].dtype.itemsize)
    tile, steps = tile or block[0], steps or block[1]
    table = pl.BlockSpec((tile, width), lambda b, i, h: (i, 0))
    blocks = [
        pl.BlockSpec(
            (1, x.shape[1] // steps, tile, width),
            lambda b, i, h: (b, h, i, 0))
        for x in operands]
    return pl.pallas_call(
        functools.partial(
            _kernel, rotary_dim=rotary_dim, sign=sign,
            chunk=min(chunk or _CHUNK // width, tile)),
        grid=(batch, seq // tile, steps),
        in_specs=[table, table] + blocks,
        out_specs=blocks,
        out_shape=[
            jax_compat.out_struct(x.shape, x.dtype, x, cos) for x in operands],
        input_output_aliases={2 + n: n for n in range(len(operands))},
        compiler_params=_params(),
        interpret=interpret,
        name=name,
    )(cos, sin, *operands)


# jitted so that every layer of a model shares one trace of a kernel's
# body; always inside the step's own trace, where the recompile
# sentinel's host bookkeeping cannot run
@functools.partial(  # edlint: disable=obs-bare-jit
    jax.jit,
    static_argnames=("rotary_dim", "tile", "steps", "chunk", "interpret"))
def rotary_fwd(operands, cos, sin, rotary_dim, tile=None, steps=None,
               chunk=None, interpret=False):
    """``operands`` (q, k or one of them) (B, H, S, d) turned by the
    table (``rotary_table``); ``tile``, ``steps``, ``chunk``: rows a
    grid step takes, grid steps over the heads (``step_block``) and
    rows an iteration of the kernel's loop takes."""
    return _call("rotary_fwd", 1, tuple(operands), cos, sin, rotary_dim,
                 tile, steps, chunk, interpret)


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit,
    static_argnames=("rotary_dim", "tile", "steps", "chunk", "interpret"))
def rotary_bwd(cotangents, cos, sin, rotary_dim, tile=None, steps=None,
               chunk=None, interpret=False):
    """The cotangents of ``rotary_fwd``'s results turned back: the
    rotation by the negated angles."""
    return _call("rotary_bwd", -1, tuple(cotangents), cos, sin, rotary_dim,
                 tile, steps, chunk, interpret)


# ------------------------------------------------------- the pair

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate_pallas(operands, cos, sin, rotary_dim):
    return tuple(rotary_fwd(operands, cos, sin, rotary_dim))


def _pallas_fwd(operands, cos, sin, rotary_dim):
    return tuple(rotary_fwd(operands, cos, sin, rotary_dim)), (cos, sin)


def _pallas_bwd(rotary_dim, table, cotangents):
    return tuple(rotary_bwd(cotangents, *table, rotary_dim)), None, None


_rotate_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def rotate(q, k, rotary_dim=None, base=10000.0, positions=None,
           scaling=None, mesh=None):
    """q (B, H, S, d) and k (B, Hk, S, d) with the first ``rotary_dim``
    lanes of every head (None: all) turned by their rows' positions.
    By the kernel pair where ``rotary_impl`` says so, from the backend,
    the dtype, the shapes and ``mesh``; by the module's lines
    elsewhere. The log's ``rotary impl=`` line says which."""
    (_, heads, seq, head_dim), kv_heads = q.shape, k.shape[1]
    lanes = rotary_dim or head_dim
    impl = rotary_impl(q.dtype, head_dim, lanes, seq, mesh)
    if k.dtype != q.dtype or k.shape[2:] != q.shape[2:] or step_block(
            seq, (heads, kv_heads), lane_groups(lanes),
            q.dtype.itemsize) is None:
        impl = "xla"
    log_choice(impl, heads, kv_heads, head_dim, lanes, seq,
               positions is not None, scaling is not None)
    rope = dict(base=base, positions=positions, scaling=scaling)
    if impl == "xla":
        return (rotate_xla(q, rotary_dim, **rope),
                rotate_xla(k, rotary_dim, **rope))
    cos, sin = rotary_table(seq, lanes, lane_groups(lanes), **rope)
    return _rotate_pallas((q, k), cos, sin, lanes)
