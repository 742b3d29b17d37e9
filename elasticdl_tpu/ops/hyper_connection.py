"""A sublayer's manifold-constrained hyper-connection as Pallas TPU
kernels with a VJP of their own (PR 38): the equations of
``models/transformer.py:HyperConnection``, each direction passing over
the n x D streams as few times as the sublayer F between ``u`` and ``y``
allows.

For a token's streams X (n x D), a kernel ``P`` (n D x n (n + 2)),
gates ``a`` and biases ``b``::

    r       = rsqrt(mean(X^2) + eps)               over the n D lanes
    raw     = (vec(X) P) r                         n (n + 2) wide
    H_pre   = sigmoid(a_pre raw_pre + b_pre)
    H_post  = 2 sigmoid(a_post raw_post + b_post)
    H_res   = Sinkhorn(exp(clip(a_res raw_res + b_res)))
    u  = H_pre X;   y = F(norm(u));   X' = H_res X + H_post^T y

As XLA runs them (the module's own lines, ``impl=xla``) a sublayer
costs the time of about sixteen passes over X: the statistic, four
24-wide products, their 24 -> n D transpose in float32, the norm's
backward and 24 reductions over D are a pass each, and twenty Sinkhorn
iterations are a loop of launches. Here (``impl=pallas``):

- ``mhc_pre_fwd``: ONE read of X. A tile is ``_TILE`` tokens x all n
  streams x all D lanes, X where it lies in ``(B, n, S, D)``. The
  ``[K, D] x [D, T]`` products with a float32 accumulator put the
  tokens on the LANES (operands in the streams' dtype, as the module's
  einsum; the division by the RMS follows the product, as there), the
  sum of squares is float32, the gates, biases, sigmoids,
  ``exp(clip(.))`` and the Sinkhorn iterations run on ``(1, T)`` rows a
  coefficient, then ``u = H_pre X``. Writes ``u``, the coefficients
  ``(B, K, S)`` float32 (rows: ``H_pre`` n, ``H_post`` n, ``H_res``
  n x n row-major, then padding to whole sublane tiles) and ``raw`` in
  the same layout with the token's ``r`` in the first padding row.
- ``mhc_post_fwd``: reads X and ``y``, writes ``X'``.
- ``mhc_post_bwd``: reads ``dX'`` and ``y``, writes ``dy = H_post dX'``
  and ``dH_post = <dX'_i, y>``: what F's backward needs before
  everything else.
- ``mhc_pre_bwd``, after F's backward: reads X, ``dX'`` and ``du``
  once. In the tile: the n (n + 1) reductions over D (``dH_res``,
  ``dH_pre``), the Sinkhorn's backward from its recomputed iterates
  (VMEM, ``2 iters (n + 1) n`` floats a token), the sigmoids', gates'
  and norm's, the ``[T, K] x [K, n D]`` product, and ``dX = H_res^T
  dX' + H_pre^T du + (the coefficients' path)`` written ONCE; the
  kernel's gradient accumulates over the grid in a float32 block that
  stays in VMEM, the pre-activations' cotangents leave as ``(B, K,
  S)`` and XLA sums them into the nine small parameters' gradients.

**How the pair composes.** X has two consumers, ``pre`` and ``post``.
Two independent VJPs would each return a full-size cotangent for X and
JAX would add them: three more passes a sublayer and a second n x D
buffer. So ``pre`` hands X through: it returns ``(u, carrier, coef)``
with ``carrier`` the streams themselves, standing for the product
``R = H_res X`` that is never written; ``post(carrier, y, coef)``
evaluates ``R + H_post^T y`` in one kernel. The carrier's cotangent is
``R``'s, which is ``dX'`` itself: ``post``'s backward returns ``dX'``
in the carrier's place untouched (and zeros for ``H_res``: ``R``'s
dependence on it is ``pre``'s), and ``pre``'s backward applies the
adjoint of ``X -> H_res(X) X`` to it beside ``u``'s and the
coefficients' own. Neither half's VJP is a derivative alone; the
pair's is, for any number of ``post`` calls on one carrier (the
adjoint is linear in ``dX'``), and ``pre`` alone (nothing written) is
too (``dX'`` = 0). The carrier must reach nothing but ``post``:
``HyperConnection`` keeps it inside ``write``. The test is of the
pair around a sublayer (``tests/test_hyper_connection_kernels.py``),
and that X's cotangent is written once.

**The same work.** Float32 from the products' accumulators on,
float32 multiply-adds in both mixes and every reduction over D, the
streams' dtype in and out where the module's lines have it, the same
iterations, ``eps`` and clamp. What differs is rounding order: sums
over D and over the streams associate otherwise, a row's ``1 / (sum +
eps)`` is taken once and multiplied where the scan divides each entry,
and the coefficients' cotangent meets the MXU rounded to the streams'
dtype ONCE, where autodiff rounds per product.

``mix_impl`` chooses with no switch for a user: a TPU, bfloat16 or
float32 streams, D in whole 128-lane rows, a token count the tile
divides, at most ``_MAX_STREAMS`` streams, and one device or a region
already manual over the mesh (a ``pallas_call`` has no GSPMD rule) ->
the kernels; the CPU, float64, the tests' small shapes, any mesh ->
the module's lines. The choice is one log line beside the attention
line: ``hyper-connections streams=4 dim=3584 iters=20 impl=pallas
(tokens=4096)``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory

logger = _logger_factory("elasticdl_tpu.ops.hyper_connection")

_LANES = 128
# tokens a grid step takes
_TILE = 128
# tokens an iteration of a kernel's loops over its tile takes: a
# bfloat16 tile of sublanes, two float32 ones
_GROUP = 16
# the coefficients' rows are padded to whole bfloat16 sublane tiles
_ROW_PAD = 16
# n (n + 2) + 1 rows have to fit the 128 lanes of a transposed tile
_MAX_STREAMS = 8
# VMEM a grid step's blocks may take (double-buffered operands and
# results, scratch) and the limit the pallas_calls state; a v5e core
# has 128 MiB
_VMEM_BUDGET = 80 * 2**20
_VMEM_LIMIT = 100 * 2**20
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def coef_rows(streams):
    """(n (n + 2), the rows the kernels' coefficient arrays have: those
    and one for the token's rsqrt, in whole sublane tiles)."""
    rows = streams * (streams + 2)
    return rows, rows + 1 + -(rows + 1) % _ROW_PAD


def vmem_bytes(streams, dim, itemsize):
    """VMEM of ``mhc_pre_bwd``'s grid step, the largest of the four:
    X, ``dX'``, ``du`` and ``dX`` double-buffered, the float32 product
    and the kernel's gradient."""
    slab = _TILE * dim
    _, padded = coef_rows(streams)
    return (
        2 * (3 * streams + 1) * slab * itemsize
        + streams * slab * 4
        + 2 * streams * padded * dim * (itemsize + 4))


def mix_impl(dtype, streams, dim, tokens, mesh=None):
    """``"pallas"`` or ``"xla"``: what runs a hyper-connection over
    ``streams`` streams of ``dtype``, ``dim`` wide, ``tokens`` a
    sequence, in a step sharded over ``mesh`` (None: one device)."""
    fits = (
        jax_compat.kernels_can_run(mesh)
        and dtype in (jnp.bfloat16, jnp.float32)
        and 1 <= streams <= _MAX_STREAMS
        and dim % _LANES == 0
        and tokens % _TILE == 0
        and vmem_bytes(
            streams, dim, jnp.dtype(dtype).itemsize) <= _VMEM_BUDGET
    )
    return "pallas" if fits else "xla"


@functools.lru_cache(maxsize=None)
def log_choice(streams, dim, iters, impl, tokens):
    """One line per distinct hyper-connection (this runs at trace
    time), beside the attention line, from where ``mix_impl`` chose."""
    logger.info(
        "hyper-connections streams=%d dim=%d iters=%d impl=%s (tokens=%d)",
        streams, dim, iters, impl, tokens)


# ------------------------------------------------------- in the tile

def _row(ref, k):
    """Row ``k`` of a (rows, T) scratch: one coefficient over the
    tile's tokens, (1, T)."""
    return ref[pl.ds(k, 1), :]


def _to_columns(rows, col_scr):
    """(K, T), tokens on the lanes -> ``col_scr`` (T, 128), tokens on
    the sublanes: column ``k`` is row ``k``, what a mix multiplies a
    (tokens, D) slab by."""
    count, tile = rows.shape
    full = jnp.concatenate(
        [rows, jnp.zeros((_LANES - count, tile), rows.dtype)], axis=0)
    col_scr[...] = full.T


def _groups(tile, body):
    """``body(rows)`` for every group of ``_GROUP`` tokens of the tile."""
    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP))
        return carry

    jax.lax.fori_loop(0, tile // _GROUP, step, 0)


def _place(columns):
    """[(lane, (G, 1) value)] -> (G, 128) with each value on its lane
    and zeros elsewhere."""
    group = columns[0][1].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (group, _LANES), 1)
    out = jnp.zeros((group, _LANES), jnp.float32)
    for k, value in columns:
        out = jnp.where(lane == k, value, out)
    return out


def _activations(raw, gb_ref, n, clamp):
    """(K, T) ``raw`` -> (the gates' sigmoids: ``H_pre`` and ``H_post``
    on their rows; ``exp(clip(.))`` on every row; whether the clamp let
    the row's pre-activation through)."""
    act = gb_ref[0] * raw + gb_ref[1]
    row = jax.lax.broadcasted_iota(jnp.int32, raw.shape, 0)
    sig = jax.nn.sigmoid(act)
    gates = jnp.where(row < n, sig, 2.0 * sig)
    inside = jnp.logical_and(act >= clamp[0], act <= clamp[1])
    return gates, jnp.exp(jnp.clip(act, *clamp)), inside


def _sinkhorn(m, n, iters, eps, keep=None):
    """``iters`` times rows then columns over ``m``, n x n (1, T)
    entries; ``keep(half step, entries, the n reciprocals)`` sees every
    half step's result."""
    def step(it, m):
        m = [list(r) for r in m]
        inv = [1.0 / (sum(m[i][1:], m[i][0]) + eps) for i in range(n)]
        m = [[m[i][j] * inv[i] for j in range(n)] for i in range(n)]
        if keep is not None:
            keep(2 * it, m, inv)
        inv = [
            1.0 / (sum((m[i][j] for i in range(1, n)), m[0][j]) + eps)
            for j in range(n)]
        m = [[m[i][j] * inv[j] for j in range(n)] for i in range(n)]
        if keep is not None:
            keep(2 * it + 1, m, inv)
        return tuple(tuple(r) for r in m)

    return jax.lax.fori_loop(
        0, iters, step, tuple(tuple(r) for r in m))


# ------------------------------------------------------- the kernels

def _pre_fwd_kernel(x_ref, kt_ref, gb_ref, u_ref, coef_ref, raw_ref,
                    work_scr, col_scr, *, n, iters, eps, clamp):
    f32 = jnp.float32
    tile, dim = x_ref.shape[2:]
    count, _ = coef_rows(n)
    z = None
    for m in range(n):
        part = jax.lax.dot_general(
            kt_ref[m], x_ref[0, m], _NT, preferred_element_type=f32)
        z = part if z is None else z + part

    def squares(rows):
        total = None
        for m in range(n):
            wide = x_ref[0, m, rows, :].astype(f32)
            part = jnp.sum(wide * wide, axis=-1, keepdims=True)
            total = part if total is None else total + part
        col_scr[rows, :] = jnp.broadcast_to(total, (_GROUP, _LANES))

    _groups(tile, squares)
    square_sum = col_scr[...].T[:1]  # (1, T)
    r = jax.lax.rsqrt(square_sum / (n * dim) + eps)
    raw = z * r
    row = jax.lax.broadcasted_iota(jnp.int32, raw.shape, 0)
    raw_ref[0] = jnp.where(row == count, r, raw)
    gates, start, _ = _activations(raw, gb_ref, n, clamp)
    work_scr[...] = start
    coef_ref[0] = jnp.where(row < 2 * n, gates, 0.0)
    h_res = _sinkhorn(
        [[_row(work_scr, 2 * n + i * n + j) for j in range(n)]
         for i in range(n)], n, iters, eps)
    for i in range(n):
        for j in range(n):
            coef_ref[0, pl.ds(2 * n + i * n + j, 1), :] = h_res[i][j]
    _to_columns(coef_ref[0], col_scr)

    def mix(rows):
        cols = col_scr[rows, :]
        out = None
        for j in range(n):
            term = cols[:, j:j + 1] * x_ref[0, j, rows, :].astype(f32)
            out = term if out is None else out + term
        u_ref[0, rows, :] = out.astype(u_ref.dtype)

    _groups(tile, mix)


def _post_fwd_kernel(x_ref, y_ref, coef_ref, out_ref, col_scr, *, n):
    f32 = jnp.float32
    tile = x_ref.shape[2]
    _to_columns(coef_ref[0], col_scr)

    def mix(rows):
        cols = col_scr[rows, :]
        wide = [x_ref[0, j, rows, :].astype(f32) for j in range(n)]
        y_wide = y_ref[0, rows, :].astype(f32)
        for i in range(n):
            kept = None
            for j in range(n):
                k = 2 * n + i * n + j
                term = cols[:, k:k + 1] * wide[j]
                kept = term if kept is None else kept + term
            out_ref[0, i, rows, :] = (
                cols[:, n + i:n + i + 1] * y_wide + kept
            ).astype(out_ref.dtype)

    _groups(tile, mix)


def _post_bwd_kernel(dxo_ref, y_ref, coef_ref, dy_ref, dcoef_ref, col_scr,
                     red_scr, *, n):
    f32 = jnp.float32
    tile = dxo_ref.shape[2]
    _, padded = coef_rows(n)
    _to_columns(coef_ref[0], col_scr)

    def mix(rows):
        cols = col_scr[rows, :]
        y_wide = y_ref[0, rows, :].astype(f32)
        out, sums = None, []
        for i in range(n):
            wide = dxo_ref[0, i, rows, :].astype(f32)
            term = cols[:, n + i:n + i + 1] * wide
            out = term if out is None else out + term
            sums.append(
                (n + i, jnp.sum(wide * y_wide, axis=-1, keepdims=True)))
        dy_ref[0, rows, :] = out.astype(dy_ref.dtype)
        red_scr[rows, :] = _place(sums)

    _groups(tile, mix)
    dcoef_ref[0] = red_scr[...].T[:padded]


def _pre_bwd_kernel(x_ref, dxo_ref, du_ref, kt_ref, gb_ref, coef_ref,
                    raw_ref, dcoef_ref, dx_ref, dkt_ref, dact_ref,
                    red_scr, col_scr, work_scr, grad_scr, iter_scr,
                    prod_scr, *, n, iters, eps, clamp):
    f32 = jnp.float32
    tile, dim = x_ref.shape[2:]
    count, padded = coef_rows(n)
    res = lambda i, j: 2 * n + i * n + j

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0))
    def _():
        dkt_ref[...] = jnp.zeros_like(dkt_ref)

    # the n (n + 1) reductions over D, a group of tokens at a time
    def reduce(rows):
        wide = [x_ref[0, j, rows, :].astype(f32) for j in range(n)]
        du_wide = du_ref[0, rows, :].astype(f32)
        sums = [
            (j, jnp.sum(du_wide * wide[j], axis=-1, keepdims=True))
            for j in range(n)]
        for i in range(n):
            d_wide = dxo_ref[0, i, rows, :].astype(f32)
            sums += [
                (res(i, j),
                 jnp.sum(d_wide * wide[j], axis=-1, keepdims=True))
                for j in range(n)]
        red_scr[rows, :] = _place(sums)

    _groups(tile, reduce)
    # the coefficients' cotangents, tokens on the lanes; what reached
    # the coefficients from elsewhere (dH_post from ``post``) joins
    grad_scr[...] = red_scr[...].T[:padded] + dcoef_ref[0]

    raw = raw_ref[0]
    r = raw[count:count + 1]
    gates, start, inside = _activations(raw, gb_ref, n, clamp)
    work_scr[...] = start
    row = jax.lax.broadcasted_iota(jnp.int32, raw.shape, 0)

    # the Sinkhorn's forward again, every half step kept
    def keep(half, m, inv):
        for i in range(n):
            for j in range(n):
                iter_scr[half, pl.ds(i * n + j, 1), :] = m[i][j]
            iter_scr[half, pl.ds(n * n + i, 1), :] = inv[i]

    _sinkhorn(
        [[_row(work_scr, res(i, j)) for j in range(n)] for i in range(n)],
        n, iters, eps, keep)

    # and backward: for N = M inv, dM = inv (dN - sum(dN N)) over the
    # normalised axis, columns then rows
    def back(it, d):
        it = iters - 1 - it
        d = [list(r) for r in d]
        for half, by_column in ((2 * it + 1, True), (2 * it, False)):
            out = lambda i, j: iter_scr[half, pl.ds(i * n + j, 1), :]
            inv = lambda a: iter_scr[half, pl.ds(n * n + a, 1), :]
            if by_column:
                dots = [
                    sum((d[i][j] * out(i, j) for i in range(1, n)),
                        d[0][j] * out(0, j)) for j in range(n)]
                d = [[inv(j) * (d[i][j] - dots[j]) for j in range(n)]
                     for i in range(n)]
            else:
                dots = [
                    sum((d[i][j] * out(i, j) for j in range(1, n)),
                        d[i][0] * out(i, 0)) for i in range(n)]
                d = [[inv(i) * (d[i][j] - dots[i]) for j in range(n)]
                     for i in range(n)]
        return tuple(tuple(r) for r in d)

    d_start = jax.lax.fori_loop(
        0, iters, back,
        tuple(tuple(_row(grad_scr, res(i, j)) for j in range(n))
              for i in range(n)))
    for i in range(n):
        for j in range(n):
            grad_scr[pl.ds(res(i, j), 1), :] = d_start[i][j]

    # through exp(clip(.)) and the sigmoids to the pre-activations
    grad = grad_scr[...]
    slope = jnp.where(
        row < n, gates * (1.0 - gates), gates * (1.0 - 0.5 * gates))
    d_act = jnp.where(
        row < 2 * n, grad * slope,
        jnp.where(inside, grad * work_scr[...], 0.0))
    d_act = jnp.where(row < count, d_act, 0.0)
    dact_ref[0] = d_act
    d_raw = gb_ref[0] * d_act
    # raw = z r: dz = d_raw r, and r's own cotangent back to X through
    # the mean square, 2 ds X = scale X
    dz = (d_raw * r).astype(x_ref.dtype)
    scale = (
        -jnp.sum(d_raw * raw, axis=0, keepdims=True) * r * r / (n * dim))
    _to_columns(jnp.where(row == count, scale, coef_ref[0]), col_scr)
    for m in range(n):
        dkt_ref[m] += jnp.dot(
            dz, x_ref[0, m], preferred_element_type=f32)
        prod_scr[m] = jax.lax.dot_general(
            dz, kt_ref[m], _TN, preferred_element_type=f32)

    def mix(rows):
        cols = col_scr[rows, :]
        du_wide = du_ref[0, rows, :].astype(f32)
        d_wide = [dxo_ref[0, i, rows, :].astype(f32) for i in range(n)]
        for j in range(n):
            out = None
            for i in range(n):
                k = res(i, j)
                term = cols[:, k:k + 1] * d_wide[i]
                out = term if out is None else out + term
            out = out + cols[:, j:j + 1] * du_wide
            out = out + prod_scr[j, rows, :]
            out = out + cols[:, count:count + 1] * x_ref[
                0, j, rows, :].astype(f32)
            dx_ref[0, j, rows, :] = out.astype(dx_ref.dtype)

    _groups(tile, mix)


# ------------------------------------------------------- their calls

def _slab(n, tile, dim):
    return pl.BlockSpec((1, n, tile, dim), lambda b, s: (b, 0, s, 0))


def _one(tile, dim):
    return pl.BlockSpec((1, tile, dim), lambda b, s: (b, s, 0))


def _rows_spec(padded, tile):
    return pl.BlockSpec((1, padded, tile), lambda b, s: (b, 0, s))


def _whole(shape):
    return pl.BlockSpec(shape, lambda b, s: (0,) * len(shape))


def _params(parallel):
    return pltpu.CompilerParams(
        dimension_semantics=(
            ("parallel", "parallel") if parallel
            else ("arbitrary", "arbitrary")),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def _static(dims):
    return dict(n=dims[0], iters=dims[1], eps=dims[2], clamp=dims[3])


# jitted so that every sublayer of a model shares one trace of a
# kernel's body; always inside the step's own trace, where the
# recompile sentinel's host bookkeeping cannot run
@functools.partial(  # edlint: disable=obs-bare-jit
    jax.jit, static_argnames=("dims", "interpret"))
def mhc_pre_fwd(x, kt, gb, dims, interpret=False):
    """X (B, n, S, D), the kernel transposed ``kt`` (n, K, D) in X's
    dtype, gates and biases a row ``gb`` (2, K, 1) float32; ``dims``
    (n, iters, eps, (clamp low, high)) -> (u (B, S, D) in X's dtype,
    the coefficients (B, K, S) float32, ``raw`` (B, K, S) float32 with
    the token's rsqrt in row n (n + 2))."""
    batch, n, seq, dim = x.shape
    _, padded = coef_rows(n)
    struct = lambda shape, dtype: jax_compat.out_struct(
        shape, dtype, x, kt, gb)
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, **_static(dims)),
        grid=(batch, seq // _TILE),
        in_specs=[_slab(n, _TILE, dim), _whole(kt.shape),
                  _whole(gb.shape)],
        out_specs=[_one(_TILE, dim), _rows_spec(padded, _TILE),
                   _rows_spec(padded, _TILE)],
        out_shape=[struct((batch, seq, dim), x.dtype),
                   struct((batch, padded, seq), jnp.float32),
                   struct((batch, padded, seq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((padded, _TILE), jnp.float32),
                        pltpu.VMEM((_TILE, _LANES), jnp.float32)],
        compiler_params=_params(True),
        interpret=interpret,
        name="mhc_pre_fwd",
    )(x, kt, gb)


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnames=("interpret",))
def mhc_post_fwd(x, y, coef, interpret=False):
    """``X' = H_res X + H_post^T y``: X (B, n, S, D), y (B, S, D), the
    coefficients (B, K, S) -> (B, n, S, D) in X's dtype."""
    batch, n, seq, dim = x.shape
    padded = coef.shape[1]
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, n=n),
        grid=(batch, seq // _TILE),
        in_specs=[_slab(n, _TILE, dim), _one(_TILE, dim),
                  _rows_spec(padded, _TILE)],
        out_specs=_slab(n, _TILE, dim),
        out_shape=jax_compat.out_struct(x.shape, x.dtype, x, y, coef),
        scratch_shapes=[pltpu.VMEM((_TILE, _LANES), jnp.float32)],
        compiler_params=_params(True),
        interpret=interpret,
        name="mhc_post_fwd",
    )(x, y, coef)


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnames=("interpret",))
def mhc_post_bwd(dxo, y, coef, interpret=False):
    """``dX'`` (B, n, S, D), y, the coefficients -> (dy = H_post dX' in
    y's dtype, (B, K, S) float32 with ``dH_post`` on its rows and zeros
    on the others)."""
    batch, n, seq, dim = dxo.shape
    padded = coef.shape[1]
    struct = lambda shape, dtype: jax_compat.out_struct(
        shape, dtype, dxo, y, coef)
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n),
        grid=(batch, seq // _TILE),
        in_specs=[_slab(n, _TILE, dim), _one(_TILE, dim),
                  _rows_spec(padded, _TILE)],
        out_specs=[_one(_TILE, dim), _rows_spec(padded, _TILE)],
        out_shape=[struct(y.shape, y.dtype),
                   struct(coef.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_TILE, _LANES), jnp.float32)] * 2,
        compiler_params=_params(True),
        interpret=interpret,
        name="mhc_post_bwd",
    )(dxo, y, coef)


@functools.partial(  # edlint: disable=obs-bare-jit (as above)
    jax.jit, static_argnames=("dims", "interpret"))
def mhc_pre_bwd(x, dxo, du, kt, gb, coef, raw, dcoef, dims,
                interpret=False):
    """The sublayer's backward after F's: X, ``dX'`` (B, n, S, D), du
    (B, S, D), ``mhc_pre_fwd``'s operands and results, ``dcoef`` (B, K,
    S) float32 what reached the coefficients from elsewhere -> (dX in
    X's dtype, the transposed kernel's gradient (n, K, D) float32, the
    pre-activations' cotangents (B, K, S) float32)."""
    batch, n, seq, dim = x.shape
    padded = coef.shape[1]
    iters = dims[1]
    struct = lambda shape, dtype: jax_compat.out_struct(
        shape, dtype, x, dxo, du, kt, gb, coef, raw, dcoef)
    rows = _rows_spec(padded, _TILE)
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, **_static(dims)),
        grid=(batch, seq // _TILE),
        in_specs=[_slab(n, _TILE, dim), _slab(n, _TILE, dim),
                  _one(_TILE, dim), _whole(kt.shape), _whole(gb.shape),
                  rows, rows, rows],
        out_specs=[_slab(n, _TILE, dim), _whole(kt.shape), rows],
        out_shape=[struct(x.shape, x.dtype),
                   struct(kt.shape, jnp.float32),
                   struct(coef.shape, jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((_TILE, _LANES), jnp.float32),
            pltpu.VMEM((_TILE, _LANES), jnp.float32),
            pltpu.VMEM((padded, _TILE), jnp.float32),
            pltpu.VMEM((padded, _TILE), jnp.float32),
            pltpu.VMEM((2 * iters, n * n + n + -(n * n + n) % 8, _TILE),
                       jnp.float32),
            pltpu.VMEM((n, _TILE, dim), jnp.float32),
        ],
        compiler_params=_params(False),
        interpret=interpret,
        name="mhc_pre_bwd",
    )(x, dxo, du, kt, gb, coef, raw, dcoef)


# ------------------------------------------------------- the pair

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def pre(x, kt, gb, dims):
    """-> (u, the carrier, the coefficients (B, K, S)); module
    docstring. ``kt`` (n, K, D) in X's dtype and ``gb`` (2, K, 1)
    float32 as ``operands`` builds them."""
    u, coef, _ = mhc_pre_fwd(x, kt, gb, dims)
    return u, x, coef


def _pre_fwd(x, kt, gb, dims):
    u, coef, raw = mhc_pre_fwd(x, kt, gb, dims)
    return (u, x, coef), (x, kt, gb, coef, raw)


def _pre_bwd(dims, residuals, cotangents):
    x, kt, gb, coef, raw = residuals
    du, dxo, dcoef = cotangents
    dx, dkt, dact = mhc_pre_bwd(x, dxo, du, kt, gb, coef, raw, dcoef, dims)
    # a gate's and a bias's gradient a row, summed over the tokens
    dgb = jnp.stack([
        jnp.sum(dact * raw, axis=(0, 2)), jnp.sum(dact, axis=(0, 2))
    ])[..., None]
    return dx, dkt.astype(kt.dtype), dgb


pre.defvjp(_pre_fwd, _pre_bwd)


@jax.custom_vjp
def post(carrier, y, coef):
    """``X' = H_res X + H_post^T y`` from ``pre``'s carrier and
    coefficients; module docstring for what its VJP returns."""
    return mhc_post_fwd(carrier, y, coef)


def _post_fwd(carrier, y, coef):
    return mhc_post_fwd(carrier, y, coef), (y, coef)


def _post_bwd(residuals, dxo):
    y, coef = residuals
    dy, dcoef = mhc_post_bwd(dxo, y, coef)
    return dxo, dy, dcoef


post.defvjp(_post_fwd, _post_bwd)


def operands(kernel, gates, biases):
    """The kernels' two small operands from the module's parameters:
    ``kernel`` (n, D, n (n + 2)) in the streams' dtype -> (n, K, D),
    zero rows after the first n (n + 2); ``gates`` (a_pre, a_post,
    a_res) and ``biases`` (b_pre (n), b_post (n), b_res (n, n)) float32
    -> (2, K, 1): a row's gate, a row's bias."""
    n = kernel.shape[0]
    count, padded = coef_rows(n)
    kt = jnp.pad(
        kernel.transpose(0, 2, 1), ((0, 0), (0, padded - count), (0, 0)))
    gate = jnp.concatenate([
        jnp.broadcast_to(a, (size,))
        for a, size in zip(gates, (n, n, n * n))])
    bias = jnp.concatenate([b.reshape(-1) for b in biases])
    gb = jnp.pad(jnp.stack([gate, bias]), ((0, 0), (0, padded - count)))
    return kt, gb[..., None]


def h_res_of(coef, streams):
    """(B, K, S) -> ``H_res`` (n, n, B, S), the module's own layout."""
    n = streams
    batch, _, seq = coef.shape
    return coef[:, 2 * n:2 * n + n * n].reshape(
        batch, n, n, seq).transpose(1, 2, 0, 3)
