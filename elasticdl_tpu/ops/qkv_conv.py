"""What lies between a Gated DeltaNet layer's input projection and its
rule, for q, k and v, as two Pallas TPU kernels under one VJP (PR 44):
the causal depthwise convolution over the sequence, SiLU, the l2 norm
of q's and k's heads, q's scale, and the split into heads
``(B, S, H x D) -> (B, H, S, D)``.

For the projection's output ``X`` (B, S, W), whose first
``C = (2 Hk + Hv) D`` columns lie q | k | v (z's columns follow and are
never read), and taps ``w`` (K, C)::

    u_t = sum_j w[j] X[t - (K - 1) + j]        rows before the first: 0
    y   = silu(u)
    q   = y_q / sqrt(sum_D y_q^2 + eps) / sqrt(D);  k likewise, unscaled
    v   = y_v

As XLA runs the module's lines (``conv_silu_xla`` + ``split_heads_xla``,
``impl=xla``) a layer of the Qwen3-Next cell (1 x 32,768 x 12,288, 16 /
32 heads of 128) moves 26.6 GB a step in ~60 passes of 0.27-2.7 GB (the
compiled step, PR 44: the first columns are sliced out, 805 -> 537 MB, a
forward; the convolution runs with the SEQUENCE in the lanes, so the
array is turned on the way in and back; q's and k's lanes go to float32
copies of 268 MB each, the heads' transposition is a copy a head group;
the backward pads four shifted products into one sum, 2.7 GB, and
reduces the taps' gradient from a 2.1 GB product). Here
(``impl=pallas``):

- ``qkv_conv_fwd``: ONE read of the C columns where they lie in ``X``
  and one write of q, k, v where the rule reads them. A grid step takes
  ``tile`` rows of one group of ``c`` heads (``c D`` lanes, a head is
  whole 128-lane rows, so the transposition is the output's block
  index) and the ``K - 1`` rows before them from a second, 16-row view
  of the same array (zeros before the first tile). The three results
  share the grid: a result's block index stands still while another's
  groups pass, so each block is written once, when its group has run.
  They are written by the rule's segments, (segments, B, H, S /
  segments, D) (``rule_segments``: the block index again), and handed
  on as (B, H, S, D): that turn and the rule's own to segments-first
  fold to no copy, where a result written (B, H, S, D) cost the rule a
  copy of q, k and v a forward (XLA had fused its turn into the
  fusions that made them, and cannot into a ``pallas_call``).
- ``qkv_conv_bwd``: reads dq, dk, dv by segments too and the same
  tile of ``X`` with the rows before AND the 8 rows after it (the
  transposed convolution looks forward: ``dX`` of rows ``[a, b)`` needs
  ``du`` over ``[a, b + K - 1)``, rebuilt from the next tile's first
  rows; past the sequence's end it is 0). Rebuilds ``u`` in VMEM,
  applies the norm's and SiLU's derivatives, writes ``dX`` (B, S, C)
  once and a tile's share of the taps' gradient (B, tiles, K, C)
  float32, which XLA sums.

Residuals: ``X`` and the taps, both alive anyway. Neither kernel's name
holds ``gdn``: ``benchmark/lib/gdn_trace.py`` charges a Mosaic kernel
of that name to ``gdn/scan``; both calls sit under the scope
``gdn/conv`` (``SCOPE``), the backward's inside the VJP.

**The same work.** Float32 from the loads on (XLA's fusions compute
bfloat16 elementwise lines in float32 too and round where a fusion
ends). Rounding points are FEWER than the lines': q, k, v are rounded
once, on the way out, and ``dX`` once (the module's ``qkv`` is rounded
after SiLU, before the norm, and autodiff rounds the norm's cotangent:
a TPU fusion may keep either in float32, these kernels always do, both
directions alike). The norm's sums and the taps' gradient are float32;
the taps' gradient is summed by tile and then over tiles; the sigmoid
is ``(1 + tanh(u / 2)) / 2``, one transcendental where the quotient is
one and a division. On the chip, against the lines in float32 from the
same bfloat16 values (``scripts/qkv_conv.py``, PR 44, rms over rms): q
1.7e-3 (the lines 2.3e-3), v 1.7e-3 (1.7e-3), ``dX`` 1.7e-3 (2.9e-3),
the taps' gradient 1.6e-3 (1.7e-3).

``conv_impl`` chooses with no switch for a user.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.ops.gated_delta import DEFAULT_SEGMENT, segments_of

logger = _logger_factory("elasticdl_tpu.ops.qkv_conv")

# the scope of ``models/transformer.py:GatedDeltaNet`` both kernels are
# counted under (``benchmark/lib/gdn_trace.py``)
SCOPE = "gdn/conv"
NORM_EPS = 1e-6
_LANES = 128
# rows a float32 tile of sublanes holds: the most a tile may look back
# (K - 1) or ahead, and the rows of the carried pieces
_SUB = 8
# rows of a halo view: a whole packed tile of sublanes of either dtype
_HALO = 16
# rows an iteration of a kernel's loop over its tile takes
# (``conv_impl``'s table)
_CHUNK = 256
# heads a grid step takes, rows it takes (``scripts/qkv_conv.py``)
_GROUP_HEADS = (4, 2, 1)
_TILES = (1024, 512, 256, 128)
_VMEM_LIMIT = 64 * 2**20


def group_heads(hk, hv):
    """Heads a grid step takes: the most that divides both counts."""
    return next(c for c in _GROUP_HEADS if hk % c == 0 and hv % c == 0)


def row_tile(seq):
    """Rows a grid step takes, None where no tile divides ``seq``."""
    return next((t for t in _TILES if seq % t == 0), None)


def conv_impl(dtype, dk, dv, seq, taps, mesh=None):
    """``"pallas"`` or ``"xla"``: what runs the convolution, SiLU, norms
    and head split of a layer whose projection is ``dtype``, with key
    and value heads ``dk`` / ``dv`` wide, over ``seq`` tokens and
    ``taps`` taps, in a step sharded over ``mesh`` (None: one device).
    The kernels: a TPU with nothing to partition (one device, or a
    region already manual over the mesh), bfloat16 or float32, heads of
    one width in whole 128-lane rows, a sequence a row tile divides, at
    most 9 taps. Everything else (the CPU, the tests' 16-wide heads)
    runs the module's lines.

    On one v5e (``scripts/qkv_conv.py``, PR 44, 1 x 32,768 x 12,288
    bfloat16, 16 / 32 heads of 128, 4 taps; ms a call, GB/s over the
    bytes a call NEEDS: 1.07 GB forward, 1.61 GB backward)::

        rows x heads a step, rows a loop    forward        backward
        XLA's lines                         12.68           19.65 (32.33 with
                                                            the forward)
        1024 x 8, 64 (first form*)          4.04 (266)      7.46 (216)
        256 x 4, 64  (first form*)          4.38 (245)      8.77 (184)
        1024 x 8, 64                        3.18 (337)      4.76 (339)
        1024 x 8, 128                       2.30 (467)      3.92 (411)
        1024 x 8, 256                       1.94 (553)      3.63 (443)
        1024 x 8, 512                       2.06 (522)      3.98 (405)
        1024 x 4, 256   <- chosen           1.98 (544)      3.58 (450)
        512 x 4, 256                        2.16 (496)      3.93 (410)

    (*) the first form sliced its shifted rows between tiles, took the
    sigmoid as a quotient and rounded y to bfloat16 before the norm;
    rows and heads a step moved it 8% (256 x 4 to 1024 x 8), rows a
    LOOP ITERATION halve it: an iteration's lane sums (the norm: 1.6
    of the first form's 4.0 ms) and transcendentals hide under the
    next rows' multiply-adds only inside one iteration. 4 heads a step
    run as 8 do and trace in half the time (the heads and the three
    kinds are unrolled in the body: the step's trace +2.8 s for +8.6).
    The pair through its VJP, pad and taps' sum included: 7.6 ms,
    XLA's 32.3.
    """
    fits = (
        jax_compat.kernels_can_run(mesh)
        and dtype in (jnp.bfloat16, jnp.float32)
        and dk == dv
        and dk % _LANES == 0
        and row_tile(seq) is not None
        and 1 <= taps <= _SUB + 1
    )
    return "pallas" if fits else "xla"


@functools.lru_cache(maxsize=None)
def log_choice(hk, hv, dim, taps, impl, tokens, tile):
    """One line per distinct layer shape (this runs at trace time),
    beside the rule's ``linear attention`` line."""
    logger.info(
        "linear attention conv heads k=%d v=%d dim=%d taps=%d impl=%s "
        "(tokens=%d tile=%s)", hk, hv, dim, taps, impl, tokens, tile)


# --------------------------------------------------- the module's lines

def conv_silu_xla(qkvz, taps, conv_dim, bias=None):
    """``silu`` of the causal depthwise convolution (+ ``bias``, if any) of
    ``qkvz``'s first ``conv_dim`` columns with ``taps`` (K, conv_dim), as
    XLA runs it."""
    seq, k = qkvz.shape[1], taps.shape[0]
    padded = jnp.pad(qkvz[..., :conv_dim], ((0, 0), (k - 1, 0), (0, 0)))
    # y_t = sum_j taps[j] x_(t - (taps - 1) + j): four shifted
    # multiply-adds, one fusion; None: the sum every older layer has
    terms = (taps[j] * padded[:, j:j + seq] for j in range(k))
    return jax.nn.silu(sum(terms) if bias is None else sum(terms, bias))


def split_heads_xla(t, num, width, normalise=None):
    """(B, S, num x width) -> (B, num, S, width); with ``normalise``,
    l2-normalised over the lanes (float32, eps 1e-6) and scaled by
    it."""
    batch, seq, _ = t.shape
    t = t.reshape(batch, seq, num, width)
    if normalise is not None:
        lanes = t.astype(jnp.float32)
        t = (lanes * jax.lax.rsqrt(
            jnp.sum(lanes * lanes, axis=-1, keepdims=True) + NORM_EPS
        ) * normalise).astype(t.dtype)
    return t.transpose(0, 2, 1, 3)


def qkv_conv_xla(qkvz, taps, heads):
    """The module's lines whole: ``heads`` (Hk, Hv, D) -> q, k, v."""
    hk, hv, dim = heads
    key_dim = hk * dim
    qkv = conv_silu_xla(qkvz, taps, 2 * key_dim + hv * dim)
    return (
        split_heads_xla(qkv[..., :key_dim], hk, dim, dim ** -0.5),
        split_heads_xla(qkv[..., key_dim:2 * key_dim], hk, dim, 1.0),
        split_heads_xla(qkv[..., 2 * key_dim:], hv, dim))


# ------------------------------------------------------- in the tile

def _shifted(rows, taps, count):
    """The ``taps`` views of ``rows`` (_SUB + count, D) a tap reads:
    view j holds rows ``_SUB - (taps - 1) + j`` onward, ``count`` of
    them. A rotation over the sublanes and an aligned slice: a slice
    that starts between tiles costs a select a register more."""
    return [
        (pltpu.roll(rows, taps - 1 - j, 0) if j < taps - 1 else rows)[
            _SUB:_SUB + count]
        for j in range(taps)]


def _conv(views, w):
    """``u`` from the shifted views of the rows and ``w`` (K, D)."""
    return sum(w[j:j + 1] * view for j, view in enumerate(views))


def _sigmoid(u):
    """``1 / (1 + exp(-u))`` by the hyperbolic tangent: one pass of the
    transcendental unit where the quotient is one and a division."""
    return 0.5 * jnp.tanh(0.5 * u) + 0.5


def _normalised(y, scale):
    return y * (jax.lax.rsqrt(
        jnp.sum(y * y, axis=-1, keepdims=True) + NORM_EPS) * scale)


def _kinds(key_groups, dim):
    """(first group, one past the last, the norm's scale or None) of q,
    k and v: q's and k's ``key_groups`` groups each, then v's."""
    return ((0, key_groups, dim ** -0.5),
            (key_groups, 2 * key_groups, 1.0),
            (2 * key_groups, None, None))


def _when_kind(g, lo, hi):
    return (g >= lo) if hi is None else ((g >= lo) & (g < hi))


def _fwd_kernel(x_ref, before_ref, w_ref, q_ref, k_ref, v_ref, *,
                key_groups, dim, chunk):
    """One tile of rows of one group of heads: ``x_ref`` (1, T, c D),
    ``before_ref`` the _HALO rows before it, ``w_ref`` (K, c D)
    float32; of the three results (c, T, D) the group's own is
    written."""
    first_tile = pl.program_id(1) == 0
    g = pl.program_id(2)
    tile, dtype = x_ref.shape[1], x_ref.dtype
    heads = x_ref.shape[2] // dim
    taps = w_ref.shape[0]

    def run(out_ref, scale):
        for h in range(heads):
            lanes = slice(h * dim, (h + 1) * dim)
            w = w_ref[:, lanes]
            before = before_ref[0, :, lanes].astype(jnp.float32)[-_SUB:]
            before = jnp.where(first_tile, 0.0, before)

            def step(r, before):
                start = pl.multiple_of(r * chunk, chunk)
                x = x_ref[0, pl.ds(start, chunk), lanes].astype(
                    jnp.float32)
                u = _conv(_shifted(
                    jnp.concatenate([before, x]), taps, chunk), w)
                y = u * _sigmoid(u)
                if scale is not None:
                    y = _normalised(y, scale)
                out_ref[h, pl.ds(start, chunk), :] = y.astype(dtype)
                return x[-_SUB:]

            jax.lax.fori_loop(0, tile // chunk, step, before)

    for (lo, hi, scale), out_ref in zip(
            _kinds(key_groups, dim), (q_ref, k_ref, v_ref)):
        pl.when(_when_kind(g, lo, hi))(
            functools.partial(run, out_ref, scale))


def _d_silu(u, grad, scale):
    """``du`` from ``u`` and the cotangent ``grad`` of a head's result,
    float32; ``scale``: the norm's, None for v."""
    sig = _sigmoid(u)
    if scale is not None:
        y = u * sig
        inv = jax.lax.rsqrt(
            jnp.sum(y * y, axis=-1, keepdims=True) + NORM_EPS)
        along = jnp.sum(grad * y, axis=-1, keepdims=True)
        grad = (grad - y * (inv * inv * along)) * (inv * scale)
    return grad * (sig * (1.0 + u * (1.0 - sig)))


def _bwd_kernel(x_ref, before_ref, after_ref, w_ref, dq_ref, dk_ref,
                dv_ref, dq_after_ref, dk_after_ref, dv_after_ref, dx_ref,
                dw_ref, *, key_groups, dim, chunk):
    """One tile of rows of one group of heads, walked from its last
    chunk to its first: ``x_ref`` (1, T, c D) with the _HALO rows
    before and after it, the group's cotangent (c, T, D) with the
    _HALO rows after it; writes ``dx_ref`` (1, T, c D) and the tile's
    share of the taps' gradient ``dw_ref`` (1, 1, K, c D) float32."""
    first_tile = pl.program_id(1) == 0
    last_tile = pl.program_id(1) == pl.num_programs(1) - 1
    g = pl.program_id(2)
    tile, dtype = x_ref.shape[1], x_ref.dtype
    heads = x_ref.shape[2] // dim
    taps = w_ref.shape[0]
    chunks = tile // chunk

    def run(grad_ref, grad_after_ref, scale):
        for h in range(heads):
            lanes = slice(h * dim, (h + 1) * dim)
            w = w_ref[:, lanes]
            halo = before_ref[0, :, lanes].astype(jnp.float32)[-_SUB:]
            halo = jnp.where(first_tile, 0.0, halo)
            # du of the _SUB rows after the tile, from the tile's last
            # rows and the next tile's first
            rows = jnp.concatenate([
                x_ref[0, tile - _HALO:, lanes].astype(jnp.float32)[-_SUB:],
                after_ref[0, :, lanes].astype(jnp.float32)[:_SUB]])
            du_after = _d_silu(
                _conv(_shifted(rows, taps, _SUB), w),
                grad_after_ref[h].astype(jnp.float32)[:_SUB], scale)
            du_after = jnp.where(last_tile, 0.0, du_after)

            def step(i, carry):
                du_after, dw = carry
                r = chunks - 1 - i
                start = pl.multiple_of(r * chunk, chunk)
                x = x_ref[0, pl.ds(start, chunk), lanes].astype(
                    jnp.float32)
                above = pl.multiple_of(
                    jnp.maximum(start - _HALO, 0), _HALO)
                before = x_ref[0, pl.ds(above, _HALO), lanes].astype(
                    jnp.float32)[-_SUB:]
                views = _shifted(jnp.concatenate(
                    [jnp.where(r == 0, halo, before), x]), taps, chunk)
                du = _d_silu(
                    _conv(views, w),
                    grad_ref[h, pl.ds(start, chunk), :].astype(
                        jnp.float32), scale)
                # dx_t = sum_j w[j] du_(t + (taps - 1) - j)
                ahead = jnp.concatenate([du, du_after])
                dx = sum(
                    w[j:j + 1] * (
                        pltpu.roll(ahead, chunk + _SUB - (taps - 1 - j), 0)
                        if j < taps - 1 else ahead)[:chunk]
                    for j in range(taps))
                dx_ref[0, pl.ds(start, chunk), lanes] = dx.astype(dtype)
                dw = tuple(
                    dw[j] + _by_sublane(du * views[j])
                    for j in range(taps))
                return du[:_SUB], dw

            zero = jnp.zeros((_SUB, dim), jnp.float32)
            _, dw = jax.lax.fori_loop(
                0, chunks, step, (du_after, (zero,) * taps))
            for j in range(taps):
                dw_ref[0, 0, j:j + 1, lanes] = jnp.sum(
                    dw[j], axis=0, keepdims=True)

    for (lo, hi, scale), grad_ref, grad_after_ref in zip(
            _kinds(key_groups, dim), (dq_ref, dk_ref, dv_ref),
            (dq_after_ref, dk_after_ref, dv_after_ref)):
        pl.when(_when_kind(g, lo, hi))(
            functools.partial(run, grad_ref, grad_after_ref, scale))


def _by_sublane(x):
    """(rows, D) -> (_SUB, D): the rows summed a sublane, which is all
    the vector unit adds without a shuffle."""
    return x.reshape(-1, _SUB, x.shape[-1]).sum(axis=0)


# ------------------------------------------------------- their calls

def _layout(heads, group):
    """(c, q's groups (k's too), all groups) for ``heads`` (Hk, Hv, D)
    taken ``group`` (None: ``group_heads``) a grid step."""
    hk, hv, _ = heads
    c = group or group_heads(hk, hv)
    return c, hk // c, (2 * hk + hv) // c


def _head_index(key_groups):
    """A result's (or a cotangent's) group index at grid group ``g``,
    for q, k and v: its own while its groups pass, the nearest of them
    before and after, so that a block moves only when it is needed."""
    return (
        lambda g: jnp.minimum(g, key_groups - 1),
        lambda g: jnp.clip(g - key_groups, 0, key_groups - 1),
        lambda g: jnp.maximum(g - 2 * key_groups, 0),
    )


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def _by_segment(x, segments):
    """(B, H, S, D) -> (segments, B, H, S / segments, D)."""
    batch, num, seq, dim = x.shape
    return jnp.moveaxis(
        x.reshape(batch, num, segments, seq // segments, dim), 2, 0)


def _whole(x):
    """(segments, B, H, rows, D) -> (B, H, S, D): the turn back, which
    XLA folds with the rule's own turn into no copy at all."""
    segments, batch, num, rows, dim = x.shape
    return jnp.moveaxis(x, 0, 2).reshape(batch, num, segments * rows, dim)


def _head_blocks(c, rows, dim, row_block, per, ats):
    """The three results' (cotangents') BlockSpecs: ``rows`` rows of
    ``c`` heads at row block ``row_block(i)`` of the sequence, which
    is block ``row_block(i) % per`` of segment ``row_block(i) // per``
    in an array (segments, B, H, S / segments, D)."""
    return [
        pl.BlockSpec(
            (None, None, c, rows, dim),
            lambda b, i, g, at=at: (
                row_block(i) // per, b, at(g), row_block(i) % per, 0))
        for at in ats]


# jitted so that every layer of a model shares one trace of a kernel's
# body; always inside the step's own trace, where the recompile
# sentinel's host bookkeeping cannot run
@functools.partial(  # edlint: disable=obs-bare-jit
    jax.jit, static_argnames=(
        "heads", "segments", "tile", "group", "chunk", "interpret"))
def qkv_conv_fwd(qkvz, taps, heads, segments=1, tile=None, group=None,
                 chunk=None, interpret=False):
    """``qkvz`` (B, S, W), ``taps`` (K, C) in its dtype, ``heads``
    (Hk, Hv, D) -> q (B, Hk, S, D), k (B, Hk, S, D), v (B, Hv, S, D) in
    ``qkvz``'s dtype, written ``segments`` equal runs of the sequence
    first, (segments, B, H, S / segments, D), and turned back;
    ``tile``, ``group``, ``chunk``: rows and heads a grid step takes
    and rows an iteration of its loop takes (``row_tile``,
    ``group_heads``, ``_CHUNK``)."""
    batch, seq, _ = qkvz.shape
    hk, hv, dim = heads
    tile = tile or row_tile(seq // segments)
    c, key_groups, count = _layout(heads, group)
    wide = c * dim
    struct = lambda num: jax_compat.out_struct(
        (segments, batch, num, seq // segments, dim), qkvz.dtype, qkvz,
        taps)
    results = pl.pallas_call(
        functools.partial(
            _fwd_kernel, key_groups=key_groups, dim=dim,
            chunk=min(chunk or _CHUNK, tile)),
        grid=(batch, seq // tile, count),
        in_specs=[
            pl.BlockSpec((1, tile, wide), lambda b, i, g: (b, i, g)),
            pl.BlockSpec(
                (1, _HALO, wide), lambda b, i, g: (
                    b, jnp.maximum(i * (tile // _HALO) - 1, 0), g)),
            pl.BlockSpec((taps.shape[0], wide), lambda b, i, g: (0, g)),
        ],
        out_specs=_head_blocks(
            c, tile, dim, lambda i: i, seq // segments // tile,
            _head_index(key_groups)),
        out_shape=[struct(hk), struct(hk), struct(hv)],
        compiler_params=_params(),
        interpret=interpret,
        name="qkv_conv_fwd",
    )(qkvz, qkvz, taps.astype(jnp.float32))
    return [_whole(x) for x in results]


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnames=(
        "heads", "segments", "tile", "group", "chunk", "interpret"))
def qkv_conv_bwd(qkvz, taps, dq, dk, dv, heads, segments=1, tile=None,
                 group=None, chunk=None, interpret=False):
    """The operands of ``qkv_conv_fwd`` and its results' cotangents
    (B, H, S, D), read ``segments`` first as the results were written
    -> (``dX`` (B, S, C) in ``qkvz``'s dtype, the taps' gradient a tile
    (B, S / tile, K, C) float32)."""
    batch, seq, _ = qkvz.shape
    hk, hv, dim = heads
    tile = tile or row_tile(seq // segments)
    c, key_groups, count = _layout(heads, group)
    wide, k = c * dim, taps.shape[0]
    conv_dim = count * wide
    per_tile = tile // _HALO
    last = seq // _HALO - 1
    after = lambda i: jnp.minimum((i + 1) * per_tile, last)
    struct = lambda shape, dtype: jax_compat.out_struct(
        shape, dtype, qkvz, taps, dq, dk, dv)
    ats = _head_index(key_groups)
    rows = seq // segments
    grads = [_by_segment(x, segments) for x in (dq, dk, dv)]
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, key_groups=key_groups, dim=dim,
            chunk=min(chunk or _CHUNK, tile)),
        grid=(batch, seq // tile, count),
        in_specs=[
            pl.BlockSpec((1, tile, wide), lambda b, i, g: (b, i, g)),
            pl.BlockSpec(
                (1, _HALO, wide), lambda b, i, g: (
                    b, jnp.maximum(i * per_tile - 1, 0), g)),
            pl.BlockSpec(
                (1, _HALO, wide), lambda b, i, g: (b, after(i), g)),
            pl.BlockSpec((k, wide), lambda b, i, g: (0, g)),
        ] + _head_blocks(c, tile, dim, lambda i: i, rows // tile, ats)
        + _head_blocks(c, _HALO, dim, after, rows // _HALO, ats),
        out_specs=[
            pl.BlockSpec((1, tile, wide), lambda b, i, g: (b, i, g)),
            pl.BlockSpec((1, 1, k, wide), lambda b, i, g: (b, i, 0, g)),
        ],
        out_shape=[
            struct((batch, seq, conv_dim), qkvz.dtype),
            struct((batch, seq // tile, k, conv_dim), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="qkv_conv_bwd",
    )(qkvz, qkvz, qkvz, taps.astype(jnp.float32), *grads, *grads)


# ------------------------------------------------------- the pair

def rule_segments(seq, chunk, segment=DEFAULT_SEGMENT):
    """The equal runs of the sequence q, k, v are written by: the
    segments ``gated_delta_rule`` scans over at ``chunk`` and
    ``segment`` chunks a segment (whole 128-row tiles each), so that its
    turn of the sequence to segments-first is no copy (PR 44: 10 ms a
    step of copies under ``gdn/scan`` otherwise, which XLA's own
    producer had fused); 1 where the rule pads the sequence."""
    pad, segments = segments_of(seq, chunk, segment)
    return 1 if pad else segments


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def qkv_conv(qkvz, taps, heads, segments=1, scope=SCOPE):
    """q, k, v (B, H, S, D) of a Gated DeltaNet layer from its
    projection's output and its taps (module docstring); ``heads``
    (Hk, Hv, D), ``segments``: ``rule_segments``; ``scope``: the named
    scope the backward's operations lie under (the caller's own holds
    the forward's: a Kimi Delta Attention layer's is ``kda/conv``)."""
    return tuple(qkv_conv_fwd(qkvz, taps, heads, segments))


def _qkv_conv_fwd(qkvz, taps, heads, segments, scope):
    return tuple(qkv_conv_fwd(qkvz, taps, heads, segments)), (qkvz, taps)


def _qkv_conv_bwd(heads, segments, scope, residuals, cotangents):
    qkvz, taps = residuals
    with jax.named_scope(scope):
        dx, dw = qkv_conv_bwd(qkvz, taps, *cotangents, heads, segments)
        width = qkvz.shape[-1] - dx.shape[-1]
        return (
            jnp.pad(dx, ((0, 0), (0, 0), (0, width))),
            dw.sum(axis=(0, 1)).astype(taps.dtype))


qkv_conv.defvjp(_qkv_conv_fwd, _qkv_conv_bwd)
