"""The gated delta rule in chunked form (Gated DeltaNet, arXiv:2412.06464;
Qwen3-Next's linear-attention layers) and, from the same lines, the
delta rule whose decay is a vector a head (Kimi Delta Attention,
arXiv:2510.26692). The rank of ``g`` decides which runs, nothing else:
``(B, H, S)``, a log decay a head and token, or ``(B, H, S, Dk)``, one a
key channel (last section). ``gdn_prepare_fwd`` / ``_bwd`` compute the
SCALAR rule's operands, ``kda_prepare_fwd`` / ``_bwd`` a decay a
channel's (PR 59), each where ``prepare_impl`` finds a TPU and a block in
its VMEM; the chunk-to-chunk recurrence is ``gdn_scan_fwd`` / ``_bwd``
wherever ``scan_impl`` says so, a channel's transposed (``kda_scan_*``).
``ops/ssd.py`` computes ANOTHER recurrence, Mamba-2's selective scan ``S
= exp(a) S + dt x B^T``: no ``(I - beta k k^T)``, so no inverse and no ``W
S`` here serves it, and its masked matmuls do not serve this rule.

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

Everything but ``V'``, ``O`` and ``S`` is independent of the state, so
it is batched matmuls over all chunks at once; the last two lines are
the chunk-to-chunk recurrence, which carries ``S``: a Pallas kernel
with the state in VMEM, or a ``lax.scan`` that runs two small matmuls a
step and hands every chunk's ``V'`` and entering state back for two
batched products of ``O`` (below). Every decay factor is an ``exp`` of
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

What is a kernel and what is not. On a TPU a segment of the rule is
four Pallas kernels under one VJP (``_chunks_pallas``) and nothing of
XLA's between them: ``gdn_prepare_fwd`` makes a chunk's operands,
``gdn_scan_fwd`` carries the state over them, and ``gdn_scan_bwd`` and
``gdn_prepare_bwd`` are their VJPs (``impl=pallas scan=pallas
prep=pallas`` on the rule's linear-attention line; ``impl=`` says what
runs the inverses, the kernels where ``prep`` is and XLA elsewhere).
Everywhere else the same lines are jnp: ``_chunk_operands`` with
``unit_lower_inverse`` in it, and ``_scan_xla``.

The operands (PR 39). Left to XLA, everything above that does not meet
the state is a dozen fusions around the inverse, each writing a
chunk-sized array to HBM for the next to read: ``K K^T``, the decay
matrix, ``A``, ``T``, ``beta V``, ``beta K e^G`` and the float32 ``P``,
the 64 x 64 float32 ones padded to 128 lanes (134 MB each a segment of
the cell), and autodiff of them again. ``gdn_prepare_fwd``'s grid runs
over (batch, key heads, blocks of the segment's chunks), all parallel;
a grid step reads its chunks' q and k once, the ``R`` value heads' v, g
and beta, and between the loads and the stores everything stays in
VMEM: G cumulated in float32 (a masked sum over the lanes where XLA
calls ``cumsum``: equal to float32 rounding, not bit for bit), ``K
K^T`` and ``Q K^T`` once a key head (operands in the compute dtype,
float32 out), ``A`` a value head, ``T`` by the product form above at
precision highest, ``[U | W] = T [beta V | beta K e^G]`` (one product,
``T`` and the right operands rounded to the compute dtype as
``_matmul`` rounds them), the decayed keys and queries, ``P`` rounded to
the compute dtype and ``exp(G_last)``. It writes exactly what
``gdn_scan_fwd`` reads, in the ``(B, Hk, R, N, C, .)`` layout where
that kernel reads it (a reshape between a producer and a
``pallas_call`` cost more than the kernel won, PR 34) and, called under
differentiation, ``T`` with two 64 x 64 matrices (one of 128) a 128-lane
row, which DMAs at twice the rate of a 64-lane row. ``gdn_prepare_bwd``
reads q, k, v, g, beta, ``T`` and the six cotangents (``dU`` in the
compute dtype as ``gdn_scan_bwd`` leaves it: no cast between the two),
makes the decays and the forward's products again and writes dq and dk
(summed over the key head's ``R`` value heads in VMEM), dv, dg and
dbeta::

    dT = [dU | dW] [beta V | beta K e^G]^T     [dX | dY] = T^T [dU | dW]
    dA = -T^T dT T^T  (float32, highest)       Z = dA . A + dP . P
    dG = rowsum(Z) - colsum(Z) + (terms of e^G, e^(G_last - G))
    dg = the reverse cumulated sum of dG

``K K^T``, the decay matrix, ``A``, ``beta V``, ``beta K e^G`` and the
float32 ``P`` never reach HBM, forward or backward. Cotangents are
matmul operands in the compute dtype, as the TPU's default precision
rounds them for autodiff of ``_chunk_operands``; the kernel keeps
``dX``, ``dY``, ``dA`` and every row sum in float32 where autodiff
rounds each transposed product's result to its operand's dtype: equal
to the operands' rounding and not bit for bit (on the chip, against
the XLA lines: dq 0.2%, dk 0.4%, dv 0.001%, dg 0.15%, dbeta 0.01% rms,
PERF.md Section 6, PR 39). A segment's operands take 1.84 ms where the
XLA lines took 3.79, their VJP 1.61 where autodiff took 4.71.
``prepare_impl`` decides beside ``scan_impl`` below, from the same
things and with no switch for a user: wherever ``scan_impl`` says
``pallas`` (the kernel's results are laid out for ``gdn_scan_fwd``) and
a block of the segment's chunks in whole 8-row tiles of ``g`` fits
``_PREPARE_BLOCK_BYTES`` -> the kernels; anything else ->
``_chunk_operands``, which stays as the path of the CPU, float64, other
chunks, widths and meshes and as the tests' oracle.

The inverse lives inside ``gdn_prepare_fwd`` (``_inverse_rows``). Two
64 x 64 matrices lie side by side on the 128 lanes and meet a
block-diagonal right operand, so a pass fills the MXU's depth (each
output element stays the same sum of
the same products; the other matrix's lanes meet zeros, so a nan or inf
in one matrix reaches its lane neighbour's result too, where XLA kept
it to its own: the step's health check sees either); ``inverse`` and
``power`` of one span, which share their right operand, are stacked on
the rows (``inverse + inverse P`` and ``P P`` are ``[inverse; P] @ P``);
and ``_CHAINS`` independent pairs are interleaved span by span, because
one pair's five dependent spans alone leave the MXU waiting (3.9 ms for
4,096 matrices where eight chains take 2.1 and XLA 6.6; PERF.md Section
6, PR 32). Its VJP (``_inverse_grad_rows``) runs ``T^T (dT T^T)``
where the XLA path runs ``(T^T dT) T^T``: the same two products at the
same precision in the other association, equal to float32 rounding and
not bit for bit. Where the operands are XLA's, the inverse is
``unit_lower_inverse``, the product form in jnp under its own VJP. A
``pallas_call`` has no GSPMD partitioning rule
(``ops/attention.py:_shard_over_mesh``), and the rule opens no
``shard_map`` of its own yet, so on a mesh it stays what GSPMD can
partition.

The chunk-to-chunk recurrence is the kernel pair ``gdn_scan_fwd`` /
``gdn_scan_bwd`` (PR 34). As a ``lax.scan`` it is 128 dependent steps a
segment of two batched 64 x 128 x 128 matmuls, each step a loop
iteration of its own with a decay, a cast and two dynamic-update-slices
around them; the operands are copied chunks-first for it, and it
writes one state a chunk to HBM for ``Q~ S`` to read back once. The
forward kernel's grid runs over (blocks of value heads: parallel; the
segment's chunks, a few a grid step: arbitrary) with one float32
``(Dk, Dv)`` state a head in a VMEM scratch from the segment's first
chunk to its last; a grid step reads its chunks' ``U`` (float32), ``W``,
decayed keys, ``Q~``, ``P`` (compute dtype) and ``exp(G_last)`` from the
``(B, Hk, R, N, C, .)`` arrays where they lie and writes ``O``: the
lines above, the same four products at the same precision (``[W; Q~]
S`` is one product, the two sharing their right operand; ``Q~`` and
``P`` are rounded to the compute dtype by what makes them, as
``_matmul`` rounded them; ``O`` leaves rounded to the compute dtype,
the cast the rule ends with). ``_SCAN_HEADS`` heads' chains are
interleaved in one body as ``_CHAINS`` are for the inverses; on the
chip the kernels move their bytes at the HBM's rate (0.49 GB a segment
in 0.82 ms, PERF.md Section 6, PR 34). Called under differentiation
(the segment's recompute, one forward in three) it also writes ``V'``
and the float32 state every chunk met. ``gdn_scan_bwd`` reads those and
runs the reverse recurrence with ``dS`` carried the same way::

    dV' = P^T dO + K~ dS            dS <- exp(G_last) dS + Q~^T dO - W^T dV'
    dU = dV'     dW = -dV' S^T      dQ~ = dO S^T      dK~ = V' dS^T
    dP = dO V'^T                    d exp(G_last) = <dS, S>

(``dS`` the leaving state's gradient on the left of the arrow, the
entering state's on its right). Cotangents are rounded to the compute
dtype where they are matmul operands, as the TPU's default precision
rounds them for autodiff of the scan; the kernel sums ``dV'`` and
``dS`` in float32 and rounds once where autodiff rounds each term: equal
to the operands' rounding and not bit for bit. ``scan_impl`` decides,
with no switch either: a TPU, operands bfloat16 or float32 with ``v``
in the same dtype, the float32 state and
decay (``state_dtype`` / ``decay_dtype`` at their defaults), key and
value widths in whole 128-lane rows, chunk 64 or 128, one device or a
region already manual over the mesh -> the kernels (``scan=pallas`` on
the line); anything else -> the ``lax.scan`` (``scan=xla``).

Memory: the backward keeps one state a chunk (autodiff through the
``lax.scan`` one in the compute dtype, as its matmul operand, and a
float32 one for the decay's gradient; the kernels the float32 one, 256
MB a segment at the shape below) and the chunk's ``W``, ``V'``,
decayed keys, ``Q~``, ``P`` and ``T`` (by XLA also ``A``, the decay
matrix and the float32 ``P``, C x C float32 matrices which the TPU pads
from 64 to 128 lanes, 134 MB each a segment; the operands' kernel keeps
``T`` alone, two matrices a lane row, 67 MB): about 4 GB a layer at
32,768 tokens, 32 heads of 128 x 128 and chunk 64, too much beside 10
GB of optimizer state. So a sequence runs in segments of ``segment``
chunks, each under ``jax.checkpoint``, with the state carried from one
to the next: the backward rebuilds one segment's forward at a time and
holds a ``1 / segments`` part of that (a recompute by groups of chunks;
its cost is one more forward of the rule, 0.5% of the cell's FLOPs).
The inverse has a VJP of its own from ``T`` alone. The output carries
``checkpoint_name`` ``GDN_OUT_NAME`` so that a remat policy can name it
as it names flash's (today's policies do not: the backward rebuilds the
segments' residuals whether or not ``o`` was kept).

A decay a channel (PR 58). With ``g_t`` a vector over the key's
channels the state's ROWS decay, each by its own: ``S = Diag(exp(g_t))
S``, the rest of the token's step as above. In a chunk, with ``G_i``
(a vector) the log decay cumulated through token ``i``, the lines
above hold with the decay moved INSIDE the contraction over the
channels::

    A[i, j] = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])    (i > j)
    P[i, j] =        sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])    (i >= j)
    W = T (beta K . exp(G))      Q~ = Q . exp(G)
    S <- Diag(exp(G_last)) S + (K . exp(G_last - G))^T V'

``A`` and ``P`` are no longer a product times a decay MATRIX. Written
``(K e^G)(K e^-G)^T`` they would be matmuls again, and ``e^-G``
overflows as soon as a channel cumulates past -88 inside a chunk. So a
chunk is cut into sub-blocks of ``_SUB`` = 8 rows
(``_decayed_products``): between sub-blocks ``I > J``, ``exp(G_i -
G_j) = exp(G_i - G_r) exp(G_r - G_j)`` with ``r`` the first row of
``I``, both exponents ``<= 0``, so those blocks are matmuls of decayed
operands (the keys decayed once a sub-block of rows); on the diagonal
the 8 x 8 pairs are summed over the channels directly
(``_decayed_diagonal``, under a VJP of its own that keeps none of the
(sub, sub, Dk) decays and runs ``_CUBE_BYTES`` of them at a time). No
exponent of the rule is ever positive, for either rank. ``T``, the
segments, the carried state and the ``(B, Hk, R, N, C, .)`` layouts are
the scalar rule's; the state's update scales rows by a vector where the
scalar rule multiplies by a number (``_scan_xla``: ``exp(G_last)`` is
``(.., 1)`` or ``(.., Dk)``). Checked against the per-token loop in
float64 to 1e-12, at decays of -50 a token on some channels and 0 on
others too (``tests/test_kda_rule.py``).

The scan's kernels carry that state TRANSPOSED, ``S^T`` (Dv, Dk) in
the VMEM scratch and in the residuals (``_scan_pallas_by_channel``
turns it on its way in and out of a segment): a channel's
``exp(G_last)`` then lies on its own lane of the chunk's (1, Dk) row,
where the scalar rule's one number lies on every lane of a (1, Dv)
row, and ``e * S^T`` is the same broadcast over the sublanes. The four
products are the scalar kernel's with the state's side of each turned
(``[W; Q~] S`` contracts the lanes of both operands, ``S^T <- e S^T +
V'^T K~``), at the same precision; ``d exp(G_last)`` is the sum over
the rows of ``dS^T . S^T``, whole a channel. A column of decays
against the untransposed state would be a (Dk, 1) block, one number a
128-lane row in VMEM and in HBM. As a ``lax.scan`` the recurrence was
6% of Kimi Linear's step and 82,000 of the 116,000 operations it
executed, each an event of the profiler: ``stop_trace`` held the loop
10 s for two traced steps (PERF.md Section 6, PR 58). The operands
(``_decayed_products``, the inverses, ``U`` and ``W``) were half that
step as XLA's lines; since PR 59 they are the ``kda_prepare_*`` pair,
described where it stands below (PERF.md Section 6, PR 59).
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
# the chunks the kernels take: a 128-lane row holds two matrices of 64
# or one of 128
_KERNEL_CHUNKS = (64, 128)
_LANES = 128
# ``g``'s rank: (B, Hv, S) one decay a head and token, (B, Hv, S, Dk)
# one a channel of the key (Kimi Delta Attention)
SCALAR_DECAY, VECTOR_DECAY = 3, 4
# rows of a sub-block of a chunk under a decay a channel
# (``_decayed_products``: one float32 tile of 8 sublanes; the direct sums
# on the diagonals move (C, sub, Dk) decays a chunk through HBM and the
# matmuls between sub-blocks (C / sub, C, Dk) decayed keys: at 8 the
# cell's step takes 2.59 s where it took 2.96 at the published kernels'
# 16, PERF.md Section 6, PR 58), and the bytes the (sub, sub, Dk) decays of the
# sub-blocks on the diagonals may take at a time (``_by_chunk_groups``)
_SUB = 8
_CUBE_BYTES = 2**28
# independent lane rows (pairs of 64 x 64 matrices) a loop iteration
# interleaves
_CHAINS = 8
# the scan's kernels: the value heads whose chains a grid step
# interleaves, the most chunks it takes of each, the VMEM its blocks may
# take (every operand and result double-buffered, the carried states
# beside them) and the limit the pallas_calls state
_SCAN_HEADS = 8
_SCAN_CHUNKS = 4
_SCAN_BLOCK_BYTES = 16 * 2**20
_SCAN_VMEM_LIMIT = 32 * 2**20
# the operands' kernels: the VMEM a grid step's blocks may take (double-
# buffered) and the limit the pallas_calls state, which the unrolled
# body's spilled values stay under too
_PREPARE_BLOCK_BYTES = 12 * 2**20
_PREPARE_VMEM_LIMIT = 32 * 2**20
# groups of ``_CHAINS`` lane rows a grid step's straight-line body holds:
# with two the scheduler has one group's elementwise work to put beside
# the other's inverses (the cell's segment: 1.92 -> 1.83 ms forward, 1.71
# -> 1.58 backward; four do not fit the VMEM; PERF.md Section 6, PR 39)
_PREPARE_GROUPS = 2


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


def scan_impl(dtype, chunk, dk, dv, state_dtype=jnp.float32,
              decay_dtype=jnp.float32, out_dtype=None, mesh=None):
    """``"pallas"`` or ``"xla"``: what carries the state from chunk to
    chunk, from the backend, the mesh (``jax_compat.kernels_can_run``)
    and the rule's own shapes: the ``gdn_scan_*`` kernels take operands
    of ``dtype`` bfloat16 or float32 with the float32 state and decay,
    key and value widths in whole 128-lane rows and a chunk of 64 or
    128, and write ``o`` in ``dtype`` (``out_dtype``, ``v``'s, has to be
    it: the rounding is then the cast the rule ends with). Everything
    else, the tests' ``state_dtype`` / ``decay_dtype`` experiments among
    it, is the ``lax.scan``. The decay's rank does not bear on it: the
    kernels carry a decay a channel too (``by_channel``)."""
    fits = (
        jax_compat.kernels_can_run(mesh)
        and dtype in (jnp.bfloat16, jnp.float32)
        and out_dtype in (None, dtype)
        and state_dtype == jnp.float32
        and decay_dtype == jnp.float32
        and chunk in _KERNEL_CHUNKS
        and dk % _LANES == 0
        and dv % _LANES == 0
    )
    return "pallas" if fits else "xla"


def _dot(x, y, contract=((1,), (0,))):
    return jax.lax.dot_general(
        x, y, (contract, ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


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


def _inverse_rows(rows, size):
    """``(I + a)^-1`` of every lane row of ``rows`` (each ``pack``
    strictly lower matrices side by side, (size, 128) float32) by the
    product form, the rows' independent chains interleaved span by
    span."""
    row = jax.lax.broadcasted_iota(jnp.int32, (size, _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (size, _LANES), 1)
    eye = (col % size == row).astype(jnp.float32)
    inverses = [eye - a for a in rows]
    powers = [_dot(a, _block_diagonal(a, size)) for a in rows]  # a^2
    span = 2
    while 2 * span < size:
        # [inverse; a^span] @ a^span: inverse (I + a^span) less
        # inverse, and a^(2 span)
        both = [
            _dot(jnp.concatenate([i, p], axis=0), _block_diagonal(p, size))
            for i, p in zip(inverses, powers)]
        inverses = [i + b[:size] for i, b in zip(inverses, both)]
        powers = [b[size:] for b in both]
        span *= 2
    return [i + _dot(i, _block_diagonal(p, size))
            for i, p in zip(inverses, powers)]


def _inverse_grad_rows(ts, ds, size):
    """``-T^T dT T^T`` for every lane row of ``ts`` and its cotangents'
    ``ds``: a list a lane row of its ``pack`` (size, size) matrices.
    ``T dT^T``, then ``(T dT^T) T = -(da)^T``, matrix by matrix."""
    pack = _LANES // size
    ys = [_dot(t, _block_diagonal(d, size), ((1,), (1,)))
          for t, d in zip(ts, ds)]
    ys = [_dot(y, _block_diagonal(t, size)) for y, t in zip(ys, ts)]
    grads = []
    for y in ys:
        if pack > 1:
            # rows padded to the lanes: one square transpose lays
            # matrix m's own transpose on rows m x size.., lanes
            # 0..size
            y = jnp.concatenate(
                [y, jnp.zeros((_LANES - size, _LANES), y.dtype)], axis=0)
        da = -y.T
        grads.append(
            [da[m * size:(m + 1) * size, :size] for m in range(pack)])
    return grads


def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., C, C),
    C a power of two, by the product form (module docstring). Its
    gradient is the inverse's own, ``da = -T^T dT T^T``, from ``T``
    alone: autodiff of the product would keep every square and every
    partial product, ten C x C float32 matrices a chunk and head where
    this keeps one."""
    size = a.shape[-1]
    if size & (size - 1):
        raise ValueError("the chunk must be a power of two, got %d" % size)
    return _inverse(a)


# a traced step names the call by this function (the recorded jaxprs of
# ``tests/test_qkv_conv_kernels.py`` read ``name=_inverse``)
@jax.custom_vjp
def _inverse(a):
    return _inverse_product(a)


def _inverse_vjp_fwd(a):
    inverse = _inverse(a)
    return inverse, inverse


def _inverse_vjp_bwd(inverse, d_inverse):
    t = jnp.swapaxes(inverse, -1, -2)
    return (-_exact(_exact(t, d_inverse), t),)


_inverse.defvjp(_inverse_vjp_fwd, _inverse_vjp_bwd)


# ------------------------------------------------ the scan's kernels
# The chunk-to-chunk recurrence with the state in VMEM (PR 34), over the
# (B, Hk, R, N, C, .) arrays ``_chunk_operands`` builds, read where they
# lie: a reshape between the fusion that makes an operand and the
# kernel would keep XLA from fusing the key heads' broadcast into it.


def _divisor(n, most):
    """The largest divisor of ``n`` that is at most ``most``."""
    return max(d for d in range(1, max(1, min(n, most)) + 1) if n % d == 0)


def _tile_bytes(rows, cols, itemsize):
    """VMEM of a (rows, cols) block: whole tiles of 128 lanes by 8
    float32 (16 bfloat16) rows."""
    sublanes = 8 * 4 // itemsize
    return (rows + -rows % sublanes) * (cols + -cols % _LANES) * itemsize


def scan_vmem_bytes(heads, chunks, chunk, dk, dv, itemsize, kind):
    """VMEM of a grid step of the scan's kernel ``kind`` (``fwd``,
    ``fwd_residuals`` or ``bwd``) over ``heads`` x ``chunks``: every
    operand's and result's block double-buffered, the entering and the
    leaving state's too, and the carried states' scratch."""
    value = _tile_bytes(chunk, dv, itemsize)
    key = _tile_bytes(chunk, dk, itemsize)
    square = _tile_bytes(chunk, chunk, itemsize)
    decay = _tile_bytes(1, dv, 4)
    state = _tile_bytes(dk, dv, 4)
    a_chunk = {
        # decay, (w, k, q), p, u -> o
        "fwd": decay + _tile_bytes(chunk, dv, 4) + 3 * key + square + value,
        # ... -> v', states
        "fwd_residuals": (
            decay + _tile_bytes(chunk, dv, 4) + 3 * key + square
            + 2 * value + state),
        # decay, (w, k, q), p, states, v', do -> du, (dw, dk, dq), dp,
        # d decay
        "bwd": 2 * decay + 6 * key + 2 * square + state + 3 * value,
    }[kind]
    return heads * (2 * chunks * a_chunk + 5 * state)


def scan_block(heads, chunks, chunk, dk, dv, itemsize, kind, rep=1):
    """(value heads, chunks) a grid step takes of ``heads`` x
    ``chunks``: up to ``_SCAN_HEADS`` heads, whose independent chains
    one body interleaves, and as many chunks of each, up to
    ``_SCAN_CHUNKS``, as ``_SCAN_BLOCK_BYTES`` holds (fewer heads where
    that many do not fit with a chunk each). The heads are whole groups
    of the ``rep`` value heads of a key head, or a part of one group;
    everything divides what it is taken of."""
    size = lambda block, step: scan_vmem_bytes(
        block, step, chunk, dk, dv, itemsize, kind)

    def fewer(most):
        groups = _divisor(heads // rep, most // rep) * rep
        return groups if groups <= most else _divisor(rep, most)

    block = fewer(_SCAN_HEADS)
    while block > 1 and size(block, 1) > _SCAN_BLOCK_BYTES:
        block = fewer(block - 1)
    fit = (_SCAN_BLOCK_BYTES - size(block, 0)) // (
        size(block, 1) - size(block, 0))
    return block, _divisor(chunks, min(_SCAN_CHUNKS, fit))


def _mxu(x, y, contract=((1,), (0,))):
    """``x @ y`` (or, by ``contract``, a transposed operand's), float32
    out: the operands arrive in the compute dtype."""
    return jax.lax.dot_general(
        x, y, (contract, ((), ())), preferred_element_type=jnp.float32)


_TN = ((0,), (0,))  # x^T y
_NT = ((1,), (1,))  # x y^T


def _block_heads(ref):
    """The index in its block of every head of a (1, key heads, their
    value heads, ...) block; less its first entry, the head's in the
    carried states' scratch."""
    _, keys, reps = ref.shape[:3]
    return [(0, a, r) for a in range(keys) for r in range(reps)]


def _segment_ends():
    """Whether this grid step is the first, the last of its heads'
    chunks (the grid's last axis)."""
    step = pl.program_id(3)
    return step == 0, step == pl.num_programs(3) - 1


def _scan_fwd_kernel(e_ref, w_ref, k_ref, q_ref, p_ref, u_ref, s0_ref,
                     o_ref, *rest, residuals, by_channel):
    """A block of heads over a block of chunks: V' = U - W S, O = Q~ S +
    P V', S <- exp(G_last) S + K~^T V'. ``[W; Q~] S`` is one product
    (the two share their right operand, as the inverse's stacked rows
    do); the heads' chains are interleaved stage by stage.
    ``by_channel``: the state is carried TRANSPOSED, ``S^T`` (Dv, Dk),
    so that the decays of its rows, one a channel of the key, lie on
    the lanes of ``e``'s row as the one number of the scalar rule does:
    the same four products with the state's side of each turned."""
    if residuals:
        v_ref, states_ref, s1_ref, s_scr = rest
    else:
        s1_ref, s_scr = rest
    chunks, chunk = u_ref.shape[3:5]
    dtype = w_ref.dtype
    heads = _block_heads(u_ref)
    first, last = _segment_ends()
    if by_channel:
        onto = lambda x, state: _mxu(x, state, _NT)  # x S
        write = lambda k, v: _mxu(v, k, _TN)  # (K~^T V')^T
    else:
        onto = _mxu
        write = lambda k, v: _mxu(k, v, _TN)

    @pl.when(first)
    def _():
        s_scr[...] = s0_ref[0]

    for c in range(chunks):
        states = [s_scr[at[1:]] for at in heads]
        both = [
            onto(jnp.concatenate([w_ref[at + (c,)], q_ref[at + (c,)]],
                                 axis=0), state.astype(dtype))
            for at, state in zip(heads, states)]
        new_v = [(u_ref[at + (c,)] - b[:chunk]).astype(dtype)
                 for at, b in zip(heads, both)]
        for at, state, v in zip(heads, states, new_v):
            s_scr[at[1:]] = e_ref[at + (c,)] * state + write(
                k_ref[at + (c,)], v)
        for at, state, b, v in zip(heads, states, both, new_v):
            o_ref[at + (c,)] = (
                b[chunk:] + _mxu(p_ref[at + (c,)], v)).astype(o_ref.dtype)
            if residuals:
                v_ref[at + (c,)] = v
                states_ref[at + (c,)] = state

    @pl.when(last)
    def _():
        s1_ref[0] = s_scr[...]


def _scan_bwd_kernel(e_ref, w_ref, k_ref, q_ref, p_ref, s_ref, v_ref,
                     do_ref, ds1_ref, du_ref, dw_ref, dk_ref, dq_ref,
                     dp_ref, de_ref, ds0_ref, ds_scr, *, by_channel):
    """The reverse recurrence, the chunks last to first, ``dS`` carried
    as the forward carries ``S``: dV' = P^T dO + K~ dS; dW = -dV' S^T
    and dQ~ = dO S^T (one product, ``[dV'; dO] S^T``); dK~ = V' dS^T;
    dP = dO V'^T; d exp(G_last) = <dS, S> (summed over the rows here,
    over the lanes by the caller); dS <- exp(G_last) dS + Q~^T dO -
    W^T dV' (one product, ``[Q~; W]^T [dO; -dV']``). Cotangents are
    rounded to the compute dtype where they are operands, ``dV'`` once
    after its float32 sum. ``by_channel``: ``S^T`` and ``dS^T`` as the
    forward carries them, and the sum over the rows of ``dS^T . S^T``
    is the whole of a channel's ``d exp(G_last)``."""
    chunks, chunk = do_ref.shape[3:5]
    dtype = w_ref.dtype
    heads = _block_heads(do_ref)
    first, last = _segment_ends()
    if by_channel:
        onto = lambda x, state: _mxu(x, state, _NT)  # x S
        off = _mxu  # x S^T
        write = lambda rows, values: _mxu(values, rows, _TN)
    else:
        onto = _mxu
        off = lambda x, state: _mxu(x, state, _NT)
        write = lambda rows, values: _mxu(rows, values, _TN)

    @pl.when(first)
    def _():
        ds_scr[...] = ds1_ref[0]

    for c in reversed(range(chunks)):
        grads = [ds_scr[at[1:]] for at in heads]
        lows = [g.astype(dtype) for g in grads]
        d_v = [
            (_mxu(p_ref[at + (c,)], do_ref[at + (c,)], _TN)
             + onto(k_ref[at + (c,)], low)).astype(dtype)
            for at, low in zip(heads, lows)]
        for at, grad, d in zip(heads, grads, d_v):
            ds_scr[at[1:]] = e_ref[at + (c,)] * grad + write(
                jnp.concatenate([q_ref[at + (c,)], w_ref[at + (c,)]], axis=0),
                jnp.concatenate([do_ref[at + (c,)], -d], axis=0))
        for at, grad, low, d in zip(heads, grads, lows, d_v):
            at = at + (c,)
            state = s_ref[at]
            both = off(jnp.concatenate([d, do_ref[at]], axis=0),
                       state.astype(dtype))
            du_ref[at] = d
            dw_ref[at] = (-both[:chunk]).astype(dtype)
            dq_ref[at] = both[chunk:].astype(dtype)
            dk_ref[at] = off(v_ref[at], low).astype(dtype)
            dp_ref[at] = _mxu(do_ref[at], v_ref[at], _NT).astype(dtype)
            de_ref[at] = jnp.sum(grad * state, axis=0, keepdims=True)

    @pl.when(last)
    def _():
        ds0_ref[0] = ds_scr[...]


def _scan_call(kernel, name, kind, a_chunk, a_head, out_chunk, reverse,
               interpret):
    """``kernel`` over the grid (batch, blocks of key heads, blocks of
    their value heads: parallel; blocks of chunks: arbitrary, last to
    first if ``reverse``), every array read and written where it lies.
    ``a_chunk``: the operands with a block a head and chunk, (B, Hk, R,
    N, rows, cols), the second of them ``w``; ``a_head``: the
    state-like one, (B, Hk, R, Dk, Dv) (a decay a channel's: (.., Dv,
    Dk)), followed in the results by its like; ``out_chunk``: (rows,
    cols, dtype) of the results a head and chunk."""
    batch, hk, rep, chunks, chunk, dk = a_chunk[1].shape
    dv = a_chunk[-1].shape[-1]
    state = a_head.shape[3:]
    block, step = scan_block(
        hk * rep, chunks, chunk, dk, dv, a_chunk[1].dtype.itemsize, kind,
        rep)
    # whole groups of a key head's value heads, or a part of one group
    keys, reps = max(1, block // rep), min(block, rep)
    steps = chunks // step
    at = (lambda n: steps - 1 - n) if reverse else (lambda n: n)
    chunk_spec = lambda rows, cols: pl.BlockSpec(
        (1, keys, reps, step, rows, cols),
        lambda b, a, r, n: (b, a, r, at(n), 0, 0))
    head_spec = pl.BlockSpec(
        (1, keys, reps) + state, lambda b, a, r, n: (b, a, r, 0, 0))
    struct = lambda shape, dtype: jax_compat.out_struct(
        shape, dtype, *a_chunk, a_head)
    return pl.pallas_call(
        kernel,
        grid=(batch, hk // keys, rep // reps, steps),
        in_specs=[chunk_spec(*x.shape[4:]) for x in a_chunk] + [head_spec],
        out_specs=(
            [chunk_spec(rows, cols) for rows, cols, _ in out_chunk]
            + [head_spec]),
        out_shape=[
            struct((batch, hk, rep, chunks, rows, cols), dtype)
            for rows, cols, dtype in out_chunk
        ] + [struct(a_head.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((keys, reps) + state, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=_SCAN_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
    )(*a_chunk, a_head)


@functools.partial(  # edlint: disable=obs-bare-jit (as the inverse's)
    jax.jit, static_argnames=("residuals", "interpret", "by_channel"))
def gdn_scan_fwd(state, decay, w, k, q, p, u, residuals=False,
                 interpret=False, by_channel=False):
    """The segment's recurrence from ``state`` (B, Hk, R, Dk, Dv)
    float32: decay (B, Hk, R, N, 1, Dv) float32, exp(G_last) on every
    lane of its row; w, k, q (B, Hk, R, N, C, Dk) and p (B, Hk, R, N, C,
    C) in the compute dtype; u (B, Hk, R, N, C, Dv) float32 -> (the
    leaving state, o (B, Hk, R, N, C, Dv) in the compute dtype) and,
    with ``residuals`` (the call under differentiation), V' in the
    compute dtype and the float32 state every chunk met, which
    ``gdn_scan_bwd`` reads. ``by_channel`` (a decay a channel; the
    kernel is then named ``kda_scan_fwd``): decay (B, Hk, R, N, 1, Dk),
    a channel's exp(G_last) on its own lane, and every state TRANSPOSED,
    (B, Hk, R, Dv, Dk), the entering, the leaving and the residuals'."""
    chunk, dv = u.shape[4:]
    dk, dtype = w.shape[5], w.dtype
    out = [(chunk, dv, dtype)]
    if residuals:
        out += [(chunk, dv, dtype), state.shape[3:] + (jnp.float32,)]
    *outs, state = _scan_call(
        functools.partial(
            _scan_fwd_kernel, residuals=residuals, by_channel=by_channel),
        "kda_scan_fwd" if by_channel else "gdn_scan_fwd",
        "fwd_residuals" if residuals else "fwd",
        [decay, w, k, q, p, u], state, out, False, interpret)
    return (state, *outs)


@functools.partial(  # edlint: disable=obs-bare-jit (as the inverse's)
    jax.jit, static_argnames=("interpret", "by_channel"))
def gdn_scan_bwd(decay, w, k, q, p, states, new_v, d_o, d_state,
                 interpret=False, by_channel=False):
    """The VJP of ``gdn_scan_fwd`` from its residuals, ``d_o`` in the
    compute dtype and the leaving state's float32 ``d_state``: ->
    (the entering state's gradient, d decay, dw, dk, dq, dp, du), ``du``
    in the compute dtype (V' was rounded to it), d decay float32, the
    others in their operands'. ``by_channel`` (``kda_scan_bwd``): the
    states and their gradients transposed as the forward's, d decay
    (B, Hk, R, N, 1, Dk) whole a channel."""
    chunk, dv = d_o.shape[4:]
    dk, dtype = w.shape[5], w.dtype
    du, dw, d_k, dq, dp, de, d_state = _scan_call(
        functools.partial(_scan_bwd_kernel, by_channel=by_channel),
        "kda_scan_bwd" if by_channel else "gdn_scan_bwd", "bwd",
        [decay, w, k, q, p, states, new_v, d_o], d_state,
        [(chunk, dv, dtype)] + [(chunk, dk, dtype)] * 3
        + [(chunk, chunk, dtype), (1,) + decay.shape[5:] + (jnp.float32,)],
        True, interpret)
    return d_state, de, dw, d_k, dq, dp, du


def _scan_under_vjp(by_channel):
    """The chunk-to-chunk recurrence by the kernels under their own
    VJP, shapes as ``gdn_scan_fwd``: (state, decay, w, k, q, p, u) ->
    (the leaving state, o)."""
    @jax.custom_vjp
    def _scan(state, decay, w, k, q, p, u):
        return gdn_scan_fwd(
            state, decay, w, k, q, p, u, by_channel=by_channel)

    def _scan_vjp_fwd(state, decay, w, k, q, p, u):
        leaving, o, new_v, states = gdn_scan_fwd(
            state, decay, w, k, q, p, u, residuals=True,
            by_channel=by_channel)
        # u is no residual: the backward reads V' in its place
        return (leaving, o), (decay, w, k, q, p, states, new_v)

    def _scan_vjp_bwd(residuals, cotangents):
        d_state, d_o = cotangents
        *grads, du = gdn_scan_bwd(
            *residuals, d_o, d_state, by_channel=by_channel)
        return (*grads, du.astype(jnp.float32))

    _scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)
    return _scan


_scan, _scan_by_channel = _scan_under_vjp(False), _scan_under_vjp(True)


# --------------------------------------------- the chunks' operands
# Everything of the rule that does not meet the state, a block of a key
# head's chunks in VMEM from the loads to the stores (PR 39): what
# ``_chunk_operands`` and autodiff of it do below, with ``K K^T``, the
# decay matrix, ``A``, ``beta V``, ``beta K e^G`` and the float32 ``P``
# never in HBM. The results are ``gdn_scan_fwd``'s operands in the (B,
# Hk, R, N, C, .) layout where it reads them.


def prepare_vmem_bytes(rep, chunks, chunk, dk, dv, itemsize, kind):
    """VMEM of a grid step of the operands' kernel ``kind`` (``fwd``,
    ``fwd_residuals`` or ``bwd``) over ``chunks`` chunks of one key head
    and its ``rep`` value heads: every operand's and result's block
    double-buffered."""
    key = _tile_bytes(chunk, dk, itemsize)
    value = _tile_bytes(chunk, dv, itemsize)
    square = _tile_bytes(chunk, chunk, itemsize)
    wide = _tile_bytes(chunk, dv, 4)
    decay = _tile_bytes(1, dv, 4)
    inverse = chunk * chunk * 4  # two of 64 a 128-lane row
    # g, beta and their gradients: a row a chunk of a (chunks, chunk)
    # float32 block
    rows = 4 * _tile_bytes(chunks, chunk, 4)
    a_chunk = {
        # q, k; v -> decay, (w, k, q), p, u
        "fwd": 2 * key + rep * (value + decay + 3 * key + square + wide),
        # ... -> T
        "fwd_residuals": 2 * key + rep * (
            value + decay + 3 * key + square + wide + inverse),
        # q, k; v, T, d decay, (dw, dk, dq), dp, du -> dq, dk; dv
        "bwd": 4 * key + rep * (
            3 * value + inverse + decay + 3 * key + square),
    }[kind]
    return 2 * (chunks * a_chunk + rep * rows)


def prepare_block(rep, chunks, chunk, dk, dv, itemsize):
    """Chunks a grid step of the operands' kernels takes of a segment's
    ``chunks`` (one key head's, with its ``rep`` value heads), the same
    for the forward and the backward, which share ``T``'s layout: a
    divisor of ``chunks`` in whole 8-row tiles of the (chunks, chunk)
    blocks of ``g`` and ``beta`` (or all of them), the smallest that
    gives the inverses ``_PREPARE_GROUPS`` groups of ``_CHAINS`` lane
    rows, inside ``_PREPARE_BLOCK_BYTES``. None where no such block
    fits: the rule then stays XLA's."""
    size = lambda step: max(
        prepare_vmem_bytes(rep, step, chunk, dk, dv, itemsize, kind)
        for kind in ("fwd_residuals", "bwd"))
    steps = [d for d in range(1, chunks + 1)
             if chunks % d == 0 and (d % 8 == 0 or d == chunks)
             and size(d) <= _PREPARE_BLOCK_BYTES]
    if not steps:
        return None
    enough = [d for d in steps
              if rep * d >= _PREPARE_GROUPS * _CHAINS * (_LANES // chunk)]
    return enough[0] if enough else steps[-1]


def prepare_impl(dtype, chunk, dk, dv, rep, chunks, state_dtype=jnp.float32,
                 decay_dtype=jnp.float32, out_dtype=None, mesh=None,
                 decay_rank=SCALAR_DECAY):
    """``"pallas"`` or ``"xla"``: what makes a chunk's operands (``A``,
    its inverse, ``U``, ``W``, the decayed keys and queries, ``P``),
    from what ``scan_impl`` sees: the
    ``gdn_prepare_*`` kernels write what ``gdn_scan_fwd`` reads where it
    reads it, so they run where the scan's kernels do (a TPU, one device
    or a manual region, operands bfloat16 or float32, float32 decay and
    state, chunk 64 or 128, whole 128-lane rows) and a block of the
    segment's ``chunks`` chunks of ``rep`` value heads fits their VMEM;
    anything else is ``_chunk_operands`` by XLA. ``decay_rank``: ``g``'s
    rank as the rule got it: a decay a channel (rank 4) asks the same of
    the ``kda_prepare_*`` kernels and of their own account of a block
    (``kda_prepare_block``: ``g`` is a (chunk, Dk) tile a chunk there),
    and is ``_chunk_operands_by_channel`` otherwise."""
    block = prepare_block if decay_rank == SCALAR_DECAY else kda_prepare_block
    fits = (
        scan_impl(dtype, chunk, dk, dv, state_dtype, decay_dtype,
                  out_dtype, mesh) == "pallas"
        and block(
            rep, chunks, chunk, dk, dv, jnp.dtype(dtype).itemsize) is not None
    )
    return "pallas" if fits else "xla"


def _chunk_masks(chunk):
    """(row >= col, row > col, row == col) of a chunk's square."""
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return row >= col, row > col, row == col


def _turned(x, eye):
    """A (1, C) row as a (C, 1) column, a column as a row: the entry on
    the diagonal of its broadcast, summed over the other axis."""
    return jnp.sum(
        jnp.where(eye, jnp.broadcast_to(x, eye.shape), 0.0),
        axis=int(x.shape[0] == 1), keepdims=True)


def _chunk_decays(g, beta, masks):
    """A chunk's ``g`` and ``beta`` (1, C) float32 rows -> (G (C, 1),
    the decay matrix D (C, C), beta (C, 1)): G cumulated from the
    chunk's first token in float32 (a masked sum over the lanes where
    ``_chunk_operands`` calls ``cumsum``: equal to float32 rounding),
    D an exp of a masked difference (above the diagonal the difference
    is positive and may overflow)."""
    lower, _, eye = masks
    cum = jnp.sum(
        jnp.where(lower, jnp.broadcast_to(g, lower.shape), 0.0), axis=1,
        keepdims=True)
    decay = jnp.exp(jnp.where(lower, cum - _turned(cum, eye), -jnp.inf))
    return cum, decay, _turned(beta, eye)


def _paired(matrices, size):
    """(size, size) matrices, ``pack`` side by side a (size, 128) lane
    row, the last row filled with zero matrices."""
    pack = _LANES // size
    if pack == 1:
        return list(matrices)
    matrices = list(matrices) + [
        jnp.zeros((size, size), jnp.float32)] * (-len(matrices) % pack)
    return [jnp.concatenate(matrices[i:i + pack], axis=1)
            for i in range(0, len(matrices), pack)]


def _unpaired(rows, size, count):
    """The first ``count`` matrices of lane rows, as ``_paired`` laid
    them."""
    pack = _LANES // size
    if pack == 1:
        return list(rows)
    return [rows[m // pack][:, m % pack * size:(m % pack + 1) * size]
            for m in range(count)]


def _by_chains(fn, rows, *more):
    """``fn`` over ``_CHAINS`` lane rows at a time."""
    out = []
    for i in range(0, len(rows), _CHAINS):
        out += fn(rows[i:i + _CHAINS], *(m[i:i + _CHAINS] for m in more))
    return out


def _prepare_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, e_ref, w_ref,
                        ko_ref, qi_ref, p_ref, u_ref, *t_ref):
    """A block of chunks of one key head and its value heads: ``K K^T``
    and ``Q K^T`` once a chunk, ``A``, ``P``, the decayed keys and
    queries and ``exp(G_last)`` a value head, the inverses by the
    product form ``_CHAINS`` lane rows at a time, then ``[U | W] = T
    [beta V | beta K e^G]`` (one product, the two sharing their left
    operand). ``beta V`` (float32) and ``beta K e^G`` wait in ``u``'s
    and ``w``'s blocks for their ``T``."""
    _, _, rep, chunks, chunk, dv = u_ref.shape
    dtype = q_ref.dtype
    masks = _chunk_masks(chunk)
    lower, strict, _ = masks
    heads = [(0, 0, r, c) for c in range(chunks) for r in range(rep)]
    a = []
    for c in range(chunks):
        k, q = k_ref[0, 0, 0, c], q_ref[0, 0, 0, c]
        kk, qk = _mxu(k, k, _NT), _mxu(q, k, _NT)
        k, q = k.astype(jnp.float32), q.astype(jnp.float32)
        for r in range(rep):
            at = (0, 0, r, c)
            cum, decay, beta = _chunk_decays(
                g_ref[0, 0, r, pl.ds(c, 1), :],
                beta_ref[0, 0, r, pl.ds(c, 1), :], masks)
            a.append(jnp.where(strict, kk * beta * decay, 0.0))
            p_ref[at] = jnp.where(lower, qk * decay, 0.0).astype(dtype)
            into, last = jnp.exp(cum), cum[chunk - 1:]
            ko_ref[at] = (jnp.exp(last - cum) * k).astype(dtype)
            qi_ref[at] = (into * q).astype(dtype)
            e_ref[at] = jnp.broadcast_to(jnp.exp(last), (1, dv))
            u_ref[at] = beta * v_ref[at].astype(jnp.float32)
            w_ref[at] = ((beta * into) * k).astype(dtype)
    rows = _by_chains(
        functools.partial(_inverse_rows, size=chunk), _paired(a, chunk))
    if t_ref:
        for i, row in enumerate(rows):
            t_ref[0][0, 0, 0, i] = row
    for at, t in zip(heads, _unpaired(rows, chunk, len(heads))):
        both = _mxu(t.astype(dtype), jnp.concatenate(
            [u_ref[at].astype(dtype), w_ref[at]], axis=1))
        u_ref[at] = both[:, :dv]
        w_ref[at] = both[:, dv:].astype(dtype)


def _prepare_bwd_kernel(q_ref, k_ref, v_ref, de_ref, dw_ref, dko_ref, dqi_ref,
                        dp_ref, du_ref, g_ref, beta_ref, t_ref, dq_ref,
                        dk_ref, dv_ref, dg_ref, dbeta_ref):
    """The VJP of ``_prepare_fwd_kernel`` from q, k, v, g, beta, ``T``
    and the six cotangents, the decays and the products of the forward
    made again in VMEM: ``dT = [dU | dW] [beta V | beta K e^G]^T``,
    ``dA = -T^T dT T^T`` (float32, precision highest, as
    ``_inverse_vjp_bwd``), then products with the decay matrix, row sums
    and one reverse cumulated sum a chunk; ``dq`` and ``dk`` summed over
    the key head's value heads here. Cotangents are matmul operands in
    the compute dtype, every sum float32."""
    _, _, rep, chunks, chunk, dv = v_ref.shape
    dtype = q_ref.dtype
    f32 = jnp.float32
    masks = _chunk_masks(chunk)
    lower, strict, eye = masks
    heads = [(0, 0, r, c) for c in range(chunks) for r in range(rep)]
    ts = [t_ref[0, 0, 0, i] for i in range(t_ref.shape[3])]
    held, d_ts = [], []
    for at, t in zip(heads, _unpaired(ts, chunk, len(heads))):
        r, c = at[2:]
        k = k_ref[0, 0, 0, c].astype(f32)
        cum, decay, beta = _chunk_decays(
            g_ref[0, 0, r, pl.ds(c, 1), :],
            beta_ref[0, 0, r, pl.ds(c, 1), :], masks)
        into = jnp.exp(cum)
        right = jnp.concatenate(
            [(beta * v_ref[at].astype(f32)).astype(dtype),
             ((beta * into) * k).astype(dtype)], axis=1)
        left = jnp.concatenate([du_ref[at], dw_ref[at]], axis=1)
        d_ts.append(_mxu(left, right, _NT))
        # [dX | dY] = T^T [dU | dW]
        held.append((cum, decay, beta, into, _mxu(t.astype(dtype), left, _TN)))
    d_as = _by_chains(
        functools.partial(_inverse_grad_rows, size=chunk), ts,
        _paired(d_ts, chunk))
    d_as = [d for row in d_as for d in row]
    tail = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    for c in range(chunks):
        k_low, q_low = k_ref[0, 0, 0, c], q_ref[0, 0, 0, c]
        kk, qk = _mxu(k_low, k_low, _NT), _mxu(q_low, k_low, _NT)
        k, q = k_low.astype(f32), q_low.astype(f32)
        d_kk = d_qk = d_k = d_q = 0.0
        for r in range(rep):
            at = (0, 0, r, c)
            cum, decay, beta, into, both = held[c * rep + r]
            d_x, d_y = both[:, :dv], both[:, dv:]
            onto = jnp.exp(cum[chunk - 1:] - cum)
            # A = beta . K K^T . D below the diagonal, P = Q K^T . D on
            # and below it; Z = dD . D
            d_a = jnp.where(strict, d_as[c * rep + r], 0.0)
            d_p = jnp.where(lower, dp_ref[at].astype(f32), 0.0)
            by_kk, by_qk = d_a * decay, d_p * decay
            by_beta = by_kk * kk
            d_kk = d_kk + beta * by_kk
            d_qk = d_qk + by_qk
            z = beta * by_beta + by_qk * qk
            d_ko, d_qi = dko_ref[at].astype(f32), dqi_ref[at].astype(f32)
            rows = lambda x: jnp.sum(x, axis=1, keepdims=True)
            by_y = rows(d_y * k)
            d_beta = (
                rows(by_beta) + rows(d_x * v_ref[at].astype(f32))
                + into * by_y)
            left_over = rows(d_ko * k) * onto
            d_last = (
                jnp.sum(left_over, axis=0, keepdims=True)
                + rows(de_ref[at]) * jnp.exp(cum[chunk - 1:]))
            d_cum = (
                rows(z) - _turned(jnp.sum(z, axis=0, keepdims=True), eye)
                + into * (beta * by_y + rows(d_qi * q))
                - left_over + jnp.where(tail, d_last, 0.0))
            # g reaches G_i for every i at or after its token
            dg_ref[0, 0, r, pl.ds(c, 1), :] = jnp.sum(
                jnp.where(lower, jnp.broadcast_to(d_cum, lower.shape), 0.0),
                axis=0, keepdims=True)
            dbeta_ref[0, 0, r, pl.ds(c, 1), :] = _turned(d_beta, eye)
            dv_ref[at] = (beta * d_x).astype(dv_ref.dtype)
            d_k = d_k + (beta * into) * d_y + onto * d_ko
            d_q = d_q + into * d_qi
        d_kk, d_qk = d_kk.astype(dtype), d_qk.astype(dtype)
        # K K^T and Q K^T: [dKK; dQK] K, dKK^T K, dQK^T Q
        both = _mxu(jnp.concatenate([d_kk, d_qk], axis=0), k_low)
        dk_ref[0, 0, 0, c] = (
            d_k + both[:chunk] + _mxu(d_kk, k_low, _TN)
            + _mxu(d_qk, q_low, _TN)).astype(dk_ref.dtype)
        dq_ref[0, 0, 0, c] = (d_q + both[chunk:]).astype(dq_ref.dtype)


def _prepare_call(kernel, name, key_like, value_like, rows, inverse,
                  out_key, out_value, out_rows, out_inverse, interpret,
                  step=None, scratch=()):
    """``kernel`` over the grid (batch, key heads, blocks of chunks),
    all parallel. Operands and results by their blocks: ``key_like``
    (B, Hk, 1, N, C, Dk); ``value_like`` (B, Hk, R, N, rows, cols);
    ``rows`` (B, Hk, R, N, C) float32; ``inverse``: ``T`` (B, Hk, grid
    steps, lane rows, C, 128) or nothing. ``out_key`` / ``out_rows``:
    how many results like the first of their operands; ``out_value``:
    (rows, cols, dtype) each; ``out_inverse``: whether ``T`` is the
    last result; ``step``: the chunks a grid step takes
    (``prepare_block``'s unless given); ``scratch``: the kernel's VMEM
    scratch, after its results."""
    batch, hk, rep, chunks, chunk, dv = value_like[0].shape
    dk, dtype = key_like[0].shape[-1], key_like[0].dtype
    step = step or prepare_block(rep, chunks, chunk, dk, dv, dtype.itemsize)
    steps = chunks // step
    lane_rows = -(-rep * step // (_LANES // chunk))
    key_spec = pl.BlockSpec(
        (1, 1, 1, step, chunk, dk), lambda b, a, n: (b, a, 0, n, 0, 0))
    value_spec = lambda rows, cols: pl.BlockSpec(
        (1, 1, rep, step, rows, cols), lambda b, a, n: (b, a, 0, n, 0, 0))
    rows_spec = pl.BlockSpec(
        (1, 1, rep, step, chunk), lambda b, a, n: (b, a, 0, n, 0))
    inverse_spec = pl.BlockSpec(
        (1, 1, 1, lane_rows, chunk, _LANES),
        lambda b, a, n: (b, a, n, 0, 0, 0))
    operands = list(key_like) + list(value_like) + list(rows) + list(inverse)
    struct = lambda shape, dtype: jax_compat.out_struct(
        shape, dtype, *operands)
    return pl.pallas_call(
        kernel,
        grid=(batch, hk, steps),
        in_specs=(
            [key_spec] * len(key_like)
            + [value_spec(*x.shape[4:]) for x in value_like]
            + [rows_spec] * len(rows) + [inverse_spec] * len(inverse)),
        out_specs=(
            [key_spec] * out_key
            + [value_spec(rows, cols) for rows, cols, _ in out_value]
            + [rows_spec] * out_rows + [inverse_spec] * out_inverse),
        out_shape=(
            [struct(key_like[0].shape, dtype)] * out_key
            + [struct((batch, hk, rep, chunks, rows, cols), dtype)
               for rows, cols, dtype in out_value]
            + [struct(rows[0].shape, jnp.float32)] * out_rows
            + [struct((batch, hk, steps, lane_rows, chunk, _LANES),
                      jnp.float32)] * out_inverse),
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_PREPARE_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
    )(*operands)


@functools.partial(  # edlint: disable=obs-bare-jit (as the inverse's)
    jax.jit, static_argnames=("residuals", "interpret"))
def gdn_prepare_fwd(q, k, v, g, beta, residuals=False, interpret=False):
    """A segment's operands of ``gdn_scan_fwd`` from q, k (B, Hk, 1, N,
    C, Dk), v (B, Hk, R, N, C, Dv) in the compute dtype and g, beta (B,
    Hk, R, N, C) float32: -> (decay (B, Hk, R, N, 1, Dv) float32,
    exp(G_last) on every lane; W, the decayed keys, Q~ (B, Hk, R, N, C,
    Dk) and P (B, Hk, R, N, C, C) in the compute dtype; U (B, Hk, R, N,
    C, Dv) float32) and, with ``residuals`` (the call under
    differentiation), ``T`` float32 with two 64 x 64 matrices (or one of
    128) a 128-lane row, (B, Hk, grid steps, lane rows, C, 128), which
    ``gdn_prepare_bwd`` reads as it lies."""
    chunk, dk = q.shape[4:]
    dv, dtype = v.shape[5], q.dtype
    return _prepare_call(
        _prepare_fwd_kernel, "gdn_prepare_fwd", [q, k], [v], [g, beta], [],
        0, [(1, dv, jnp.float32)] + [(chunk, dk, dtype)] * 3
        + [(chunk, chunk, dtype), (chunk, dv, jnp.float32)], 0,
        int(residuals), interpret)


@functools.partial(  # edlint: disable=obs-bare-jit (as the inverse's)
    jax.jit, static_argnames=("interpret",))
def gdn_prepare_bwd(q, k, v, g, beta, inverse, d_decay, dw, d_k, dq, dp, du,
                    interpret=False):
    """The VJP of ``gdn_prepare_fwd`` from its operands, ``T`` and the
    six cotangents (``du`` in the compute dtype, as ``gdn_scan_bwd``
    hands it on; ``d_decay`` float32, summed over its lanes here): ->
    (dq, dk (B, Hk, 1, N, C, Dk), dv in their operands' dtype, dg,
    dbeta float32)."""
    chunk, dv = v.shape[4:]
    return _prepare_call(
        _prepare_bwd_kernel, "gdn_prepare_bwd", [q, k],
        [v, d_decay, dw, d_k, dq, dp, du], [g, beta], [inverse],
        2, [(chunk, dv, v.dtype)], 2, 0, interpret)


# ------------------------------- the chunks' operands, a decay a channel
# ``kda_prepare_fwd`` / ``kda_prepare_bwd`` (PR 59): what
# ``_chunk_operands_by_channel`` and autodiff of it do further down, a
# block of a key head's chunks in VMEM from the loads to the stores, the
# siblings of ``gdn_prepare_*`` as ``kda_scan_*`` are of ``gdn_scan_*``.
# They stand below the scalar rule's kernels, whose bodies keep their
# lines (a Mosaic body carries its frames' file and line into the
# program: ``scripts/step_fingerprint.py``).
#
# Left to XLA the vector rule's operands were half of Kimi Linear's
# step: the float32 inverses, each product a fusion reading and writing
# a (B, H, N, 64, 64) array, the (sub, sub, Dk) decays of the diagonals
# written to HBM a group of chunks at a time and read back, and the
# copies between the layouts all of that wants. Here a grid step takes a
# block of one key head's chunks (``kda_prepare_block``); q, k, v and the
# (chunk, Dk) float32 tile of ``g`` a chunk and head are read once and
# nothing but the scan's six operands (and ``T`` under differentiation)
# is written. The cut of a chunk is ``_decayed_products``': sub-blocks
# of ``_KDA_SUB`` rows; between sub-blocks matmuls of operands decayed
# to the sub-block's first row (``_earlier_decay``: one (16, Dk) x (Dk,
# C) product a sub-block, its rows the sub-block's decayed k and q);
# on the diagonal the pairs ``d`` rows apart, d = 0..7, are ONE
# elementwise pass over the whole chunk each (``_pairs_apart``: the
# other row comes by a load at a sublane offset from a scratch that
# holds the chunk's k and G, its decay is masked with ``-inf`` where the
# offset left the sub-block) and one sum over the lanes a product,
# float32. No exponent is ever positive and none is clipped,
# so a channel that does not decay keeps its whole gradient. ``G`` is a
# product with a triangle of ones at precision highest (float32 sums on
# the MXU, which the elementwise work leaves idle), ``dg`` the same with
# the triangle turned. The inverses, their gradient, the pairing of two
# 64 x 64 matrices a lane row and the chains are the scalar kernels'
# own helpers (``_inverse_rows``, ``_inverse_grad_rows``, ``_paired``,
# ``_unpaired``, ``_by_chains``), called, not copied. The loop over a
# block's chunks is a ``fori_loop`` (the body is ~1,000 vector
# operations a chunk forward, ~2,500 backward: unrolled 16 times it is
# Mosaic's compile time and nothing else); ``A`` (the backward: ``G``,
# ``dT`` and ``[dX | dY]``) waits in a VMEM scratch for the inverses,
# which run by chains over the whole block after it. On the chip a
# segment of the cell (2,048 chunks and heads) takes 1.74 ms forward
# and 2.64 backward where XLA's lines took 10.3 and 12.0 more for their
# VJP (PERF.md Section 6, PR 59).

# rows of a sub-block of a chunk INSIDE the kernels: one float32 tile,
# so a sub-block's rows are whole tiles of the (chunk, Dk) arrays and a
# pair on the diagonal is at most 7 rows apart
_KDA_SUB = 8


def kda_prepare_vmem_bytes(rep, chunks, chunk, dk, dv, itemsize, kind):
    """``prepare_vmem_bytes`` for the vector rule's kernels: ``g`` and
    ``dg`` are (chunk, Dk) float32 tiles a chunk and head, ``exp(G_last)``
    a (1, Dk) row, and the scratch that hands ``A`` (the backward: ``G``,
    ``dT`` and ``[dX | dY]``) from one phase of a grid step to the next
    is counted once, beside the double-buffered blocks."""
    key = _tile_bytes(chunk, dk, itemsize)
    value = _tile_bytes(chunk, dv, itemsize)
    square = _tile_bytes(chunk, chunk, itemsize)
    wide = _tile_bytes(chunk, dv, 4)
    gate = _tile_bytes(chunk, dk, 4)
    decay = _tile_bytes(1, dk, 4)
    inverse = chunk * chunk * 4  # two of 64 a 128-lane row
    rows = 2 * _tile_bytes(chunks, chunk, 4)  # beta and its gradient
    matrix = _tile_bytes(chunk, chunk, 4)
    staged = 2 * _tile_bytes(_KDA_SUB + chunk, dk, 4)  # ``_stage_rows``'
    a_chunk, scratch = {
        # q, k; v, g -> decay, (w, k, q), p, u
        "fwd": (2 * key + rep * (
            value + gate + decay + 3 * key + square + wide), matrix),
        # ... -> T
        "fwd_residuals": (2 * key + rep * (
            value + gate + decay + 3 * key + square + wide + inverse),
            matrix),
        # q, k; v, g, T, d decay, (dw, dk, dq), dp, du -> dq, dk; dv, dg
        "bwd": (4 * key + rep * (
            3 * value + 2 * gate + inverse + decay + 3 * key + square),
            matrix + gate + _tile_bytes(chunk, dv + dk, 4)),
    }[kind]
    return (2 * (chunks * a_chunk + rep * rows) + rep * chunks * scratch
            + staged)


def kda_prepare_block(rep, chunks, chunk, dk, dv, itemsize):
    """``prepare_block`` for the vector rule's kernels, by their own
    account: the smallest divisor of ``chunks`` in whole 8-row tiles of
    ``beta``'s (chunks, chunk) blocks (or all of them) that gives the
    inverses a group of ``_CHAINS`` lane rows, inside
    ``_PREPARE_BLOCK_BYTES``. None where no block fits."""
    size = lambda step: max(
        kda_prepare_vmem_bytes(rep, step, chunk, dk, dv, itemsize, kind)
        for kind in ("fwd_residuals", "bwd"))
    steps = [d for d in range(1, chunks + 1)
             if chunks % d == 0 and (d % 8 == 0 or d == chunks)
             and size(d) <= _PREPARE_BLOCK_BYTES]
    if not steps:
        return None
    enough = [d for d in steps if rep * d >= _CHAINS * (_LANES // chunk)]
    return enough[0] if enough else steps[-1]


def _cumulated(g, reverse=False):
    """The sum of a (C, Dk) float32 tile over its rows up to each row
    (``reverse``: from each row on): a product with a triangle of ones
    at precision highest, so float32 sums on the MXU where XLA calls
    ``cumsum`` (equal to float32 rounding, not bit for bit)."""
    chunk = g.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    ones = (row <= col) if reverse else (row >= col)
    return _dot(ones.astype(jnp.float32), g)


def _sub_firsts(x, pick=lambda block: block[:1]):
    """(C, D) -> (C, D): on every row ``pick`` of the row's sub-block of
    ``_KDA_SUB`` rows (its first row; a (1, D) row of it in general)."""
    return jnp.concatenate(
        [jnp.broadcast_to(pick(x[i:i + _KDA_SUB]), (_KDA_SUB, x.shape[1]))
         for i in range(0, x.shape[0], _KDA_SUB)], axis=0)


def _chunk_indices(chunk, dk):
    """What the loop over a block's chunks reads and never changes,
    made once a grid step: (``apart`` (C, C): row - col for a pair of
    ONE sub-block, -1 for any other; over a (C, Dk) array a row's index
    in its sub-block, and its index in the chunk)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    apart = jnp.where(
        row // _KDA_SUB == col // _KDA_SUB, row - col, -1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk), 0)
    return apart, rows & (_KDA_SUB - 1), rows


def _stage_rows(scr, k, cum):
    """k and cum (C, Dk) float32 into the (2, sub + C, Dk) scratch under
    ``_KDA_SUB`` rows of zeros, where ``_pairs_apart`` reads them ``d``
    rows up: a load at a sublane offset where a roll of the value would
    queue on the unit that also sums over the lanes."""
    zeros = jnp.zeros((_KDA_SUB, k.shape[1]), jnp.float32)
    for i, x in enumerate((k, cum)):
        scr[i, :_KDA_SUB] = zeros
        scr[i, _KDA_SUB:] = x


def _pairs_apart(scr, k, cum, d, sub_row):
    """The pairs (i, i - d) of a chunk inside one sub-block: (k[i - d],
    exp(G[i] - G[i - d])) on row i, the decay 0 where i - d lies in
    another sub-block (a mask with ``-inf``: no exponent is positive,
    and none is clipped). The rows ``d`` up come from ``_stage_rows``'
    scratch."""
    if d == 0:
        return k, None
    chunk = k.shape[0]
    apart = cum - scr[1, pl.ds(_KDA_SUB - d, chunk)]
    return scr[0, pl.ds(_KDA_SUB - d, chunk)], jnp.exp(
        jnp.where(sub_row >= d, apart, -jnp.inf))


def _earlier_decay(cum, block, rows):
    """What is left of a token's key at the first row of sub-block
    ``block``: ``exp(G_first - G)`` for the tokens of the sub-blocks
    before it, 0 from there on (those pairs are the diagonal's or
    nobody's); (C, Dk) float32."""
    first = cum[block * _KDA_SUB:block * _KDA_SUB + 1]
    return jnp.exp(jnp.where(rows < block * _KDA_SUB, first - cum, -jnp.inf))


def _sub_rows(block, *arrays):
    """Sub-block ``block``'s rows of each (C, .) float32 array, stacked."""
    return jnp.concatenate(
        [x[block * _KDA_SUB:(block + 1) * _KDA_SUB] for x in arrays], axis=0)


def _stacked_from(pieces, part, width):
    """Part ``part`` of every sub-block's (2 sub, width) result, one
    under the other from sub-block 1 on, zeros for sub-block 0."""
    return jnp.concatenate(
        [jnp.zeros((_KDA_SUB, width), jnp.float32)] + [
            x[part * _KDA_SUB:(part + 1) * _KDA_SUB] for x in pieces], axis=0)


def _decayed_products_rows(k, q, cum, dtype, indices, scr):
    """``_decayed_products`` of one chunk and head in VMEM: k, q, cum (C,
    Dk) float32 -> ``K K^T`` strictly below the diagonal and ``Q K^T``
    on and below it, the decay of a pair inside the contraction, 0
    elsewhere; (C, C) float32. Between sub-blocks: matmuls of operands
    decayed to the sub-block's first row, rounded to ``dtype`` as
    ``_matmul`` rounds them; on the diagonal the pairs ``d`` rows apart
    are one elementwise pass over the chunk and one sum over the lanes
    a product, float32. ``indices``: ``_chunk_indices``'."""
    chunk = k.shape[0]
    apart, sub_row, rows = indices
    lanes = lambda x: jnp.sum(x, axis=1, keepdims=True)
    lead = jnp.exp(cum - _sub_firsts(cum))
    k_lead, q_lead = k * lead, q * lead
    below = [
        _mxu(_sub_rows(block, k_lead, q_lead).astype(dtype),
             (k * _earlier_decay(cum, block, rows)).astype(dtype), _NT)
        for block in range(1, chunk // _KDA_SUB)]
    kk = _stacked_from(below, 0, chunk)
    qk = _stacked_from(below, 1, chunk)
    _stage_rows(scr, k, cum)
    for d in range(_KDA_SUB):
        other, decay = _pairs_apart(scr, k, cum, d, sub_row)
        if d:
            other = other * decay
            kk = jnp.where(apart == d, lanes(k * other), kk)
        qk = jnp.where(apart == d, lanes(q * other), qk)
    return kk, qk


def _kda_prepare_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, e_ref,
                            w_ref, ko_ref, qi_ref, p_ref, u_ref, *rest):
    """A block of chunks of one key head and its value heads. A loop
    over the chunks: ``G`` cumulated a channel, ``K K^T`` and ``Q K^T``
    with the decay inside (``_decayed_products_rows``), ``A`` into the
    scratch, ``P``, the decayed keys and queries and ``exp(G_last)`` to
    their blocks, ``beta V`` (float32) and ``beta K e^G`` waiting in
    ``u``'s and ``w``'s blocks; then the inverses ``_CHAINS`` lane rows
    at a time and ``[U | W] = T [beta V | beta K e^G]``, as the scalar
    rule's kernel ends."""
    *t_ref, a_scr, rows_scr = rest
    _, _, rep, chunks, chunk, dv = u_ref.shape
    dtype = q_ref.dtype
    f32 = jnp.float32
    lower, strict, eye = _chunk_masks(chunk)
    indices = _chunk_indices(chunk, q_ref.shape[-1])

    def a_chunk(c, carry):
        k, q = k_ref[0, 0, 0, c].astype(f32), q_ref[0, 0, 0, c].astype(f32)
        for r in range(rep):
            at = (0, 0, r, c)
            cum = _cumulated(g_ref[at])
            beta = _turned(beta_ref[0, 0, r, pl.ds(c, 1), :], eye)
            kk, qk = _decayed_products_rows(
                k, q, cum, dtype, indices, rows_scr)
            a_scr[c * rep + r] = jnp.where(strict, kk * beta, 0.0)
            p_ref[at] = jnp.where(lower, qk, 0.0).astype(dtype)
            into, last = jnp.exp(cum), cum[chunk - 1:]
            ko_ref[at] = (jnp.exp(last - cum) * k).astype(dtype)
            qi_ref[at] = (into * q).astype(dtype)
            e_ref[at] = jnp.exp(last)
            u_ref[at] = beta * v_ref[at].astype(f32)
            w_ref[at] = ((beta * into) * k).astype(dtype)
        return carry

    jax.lax.fori_loop(0, chunks, a_chunk, 0)
    heads = [(0, 0, r, c) for c in range(chunks) for r in range(rep)]
    rows = _by_chains(
        functools.partial(_inverse_rows, size=chunk),
        _paired([a_scr[m] for m in range(len(heads))], chunk))
    if t_ref:
        for i, row in enumerate(rows):
            t_ref[0][0, 0, 0, i] = row
    for at, t in zip(heads, _unpaired(rows, chunk, len(heads))):
        both = _mxu(t.astype(dtype), jnp.concatenate(
            [u_ref[at].astype(dtype), w_ref[at]], axis=1))
        u_ref[at] = both[:, :dv]
        w_ref[at] = both[:, dv:].astype(dtype)


def _turned_pair(a, b):
    """``[a^T | b^T]`` of two (C, C) float32 matrices, (C, 2 C): one
    square transpose of whole 128-lane rows a pair of 64 (two of 128)."""
    chunk = a.shape[0]
    if chunk == _LANES:
        return jnp.concatenate([a.T, b.T], axis=1)
    both = jnp.concatenate([a, b], axis=1)  # (C, 2 C = 128)
    both = jnp.concatenate(
        [both, jnp.zeros((_LANES - chunk, _LANES), both.dtype)], axis=0).T
    return jnp.concatenate([both[:chunk, :chunk], both[chunk:, :chunk]],
                           axis=1)


def _kda_prepare_bwd_kernel(q_ref, k_ref, v_ref, g_ref, de_ref, dw_ref,
                            dko_ref, dqi_ref, dp_ref, du_ref, beta_ref,
                            t_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                            cum_scr, dt_scr, dxy_scr, rows_scr):
    """The VJP of ``_kda_prepare_fwd_kernel`` from q, k, v, g, beta,
    ``T`` and the six cotangents, every decay made again in VMEM and
    none kept. A head at a time ``dT = [dU | dW] [beta V | beta K
    e^G]^T`` and ``[dX | dY] = T^T [dU | dW]`` into the scratch; ``dA =
    -T^T dT T^T`` by chains (``_inverse_grad_rows``); then a loop over
    the chunks: the VJP of the products with the decay inside, pair by
    pair what ``_diagonal_bwd`` sums (``d cum = x dx - y dy``), the
    matmuls between sub-blocks transposed, ``dg`` the reverse cumulated
    sum of ``dG`` a channel, ``dbeta`` a row; ``dq`` and ``dk`` summed
    over the key head's value heads here. Cotangents are matmul
    operands in the compute dtype, every sum float32."""
    _, _, rep, chunks, chunk, dv = v_ref.shape
    dk = k_ref.shape[-1]
    dtype = q_ref.dtype
    f32 = jnp.float32
    lower, strict, eye = _chunk_masks(chunk)
    heads = [(0, 0, r, c) for c in range(chunks) for r in range(rep)]
    ts = [t_ref[0, 0, 0, i] for i in range(t_ref.shape[3])]
    for m, (at, t) in enumerate(zip(heads, _unpaired(ts, chunk, len(heads)))):
        r, c = at[2:]
        cum = _cumulated(g_ref[at])
        beta = _turned(beta_ref[0, 0, r, pl.ds(c, 1), :], eye)
        right = jnp.concatenate(
            [(beta * v_ref[at].astype(f32)).astype(dtype),
             ((beta * jnp.exp(cum)) * k_ref[0, 0, 0, c].astype(f32)
              ).astype(dtype)], axis=1)
        left = jnp.concatenate([du_ref[at], dw_ref[at]], axis=1)
        cum_scr[m] = cum
        dt_scr[m] = _mxu(left, right, _NT)
        dxy_scr[m] = _mxu(t.astype(dtype), left, _TN)
    d_as = _by_chains(
        functools.partial(_inverse_grad_rows, size=chunk), ts,
        _paired([dt_scr[m] for m in range(len(heads))], chunk))
    for m, d_a in enumerate([d for row in d_as for d in row][:len(heads)]):
        dt_scr[m] = d_a
    blocks = chunk // _KDA_SUB
    apart, sub_row, rows = _chunk_indices(chunk, dk)
    # the sub-block of a row of [beta K e; Q e], one under the other
    stacked = jnp.concatenate([rows, rows], axis=0) // _KDA_SUB
    lanes = lambda x: jnp.sum(x, axis=1, keepdims=True)
    over_rows = lambda x: jnp.sum(x, axis=0, keepdims=True)
    back = lambda x, d: pltpu.roll(x, chunk - d, 0)  # row i - d gets row i's

    def a_chunk(c, carry):
        k, q = k_ref[0, 0, 0, c].astype(f32), q_ref[0, 0, 0, c].astype(f32)
        d_k = d_q = jnp.zeros((chunk, dk), f32)
        for r in range(rep):
            at, m = (0, 0, r, c), c * rep + r
            cum = cum_scr[m]
            beta = _turned(beta_ref[0, 0, r, pl.ds(c, 1), :], eye)
            d_a = jnp.where(strict, dt_scr[m], 0.0)  # of K K^T, less beta
            d_p = jnp.where(lower, dp_ref[at].astype(f32), 0.0)
            into, last = jnp.exp(cum), cum[chunk - 1:]
            onto = jnp.exp(last - cum)
            lead = jnp.exp(cum - _sub_firsts(cum))
            k_lead, q_lead = k * lead, q * lead
            # between sub-blocks: out = [K e; Q e][block] cols^T
            turned = _turned_pair(d_a, d_p).astype(dtype)  # (C, 2 C)
            sides = jnp.concatenate([beta * k_lead, q_lead], axis=0)
            d_rows, d_cum, from_cols, firsts = [], 0.0, 0.0, []
            for block in range(1, blocks):
                decay = _earlier_decay(cum, block, rows)
                cols = k * decay
                d_rows.append(_mxu(
                    _sub_rows(block, d_a, d_p).astype(dtype),
                    cols.astype(dtype)))
                d_cols = _mxu(turned, jnp.where(
                    stacked == block, sides, 0.0).astype(dtype))
                z = cols * d_cols
                from_cols = from_cols + decay * d_cols
                d_cum = d_cum - z
                firsts.append(over_rows(z))
            d_rows_k = _stacked_from(d_rows, 0, dk)
            d_rows_q = _stacked_from(d_rows, 1, dk)
            grow_k, grow_q = lead * d_rows_k, lead * d_rows_q
            z = k_lead * (beta * d_rows_k) + q_lead * d_rows_q
            # the sub-block's first row: what its rows' leads and the
            # earlier keys' decays hand it
            at_first = jnp.concatenate(
                [jnp.zeros((_KDA_SUB, dk), f32)] + [
                    jnp.broadcast_to(x, (_KDA_SUB, dk)) for x in firsts],
                axis=0) - _sub_firsts(z, over_rows)
            d_cum = d_cum + z + jnp.where(sub_row == 0, at_first, 0.0)
            # on the diagonal: the pairs d rows apart
            _stage_rows(rows_scr, k, cum)
            for d in range(_KDA_SUB):
                other, decay = _pairs_apart(rows_scr, k, cum, d, sub_row)
                on = apart == d
                d_qk = lanes(jnp.where(on, d_p, 0.0))
                if d == 0:
                    grow_q = grow_q + d_qk * other
                    from_cols = from_cols + d_qk * q
                    continue
                d_kk = lanes(jnp.where(on, d_a, 0.0))
                decayed = other * decay
                grow_k = grow_k + d_kk * decayed
                grow_q = grow_q + d_qk * decayed
                pulled = decay * ((beta * d_kk) * k + d_qk * q)
                from_cols = from_cols + back(pulled, d)
                z = pulled * other
                d_cum = d_cum + z - back(z, d)
            both = dxy_scr[m]
            d_x, d_y = both[:, :dv], both[:, dv:]
            by_y = d_y * k
            v = v_ref[at].astype(f32)
            d_beta = lanes(k * grow_k + into * by_y) + lanes(d_x * v)
            d_ko, d_qi = dko_ref[at].astype(f32), dqi_ref[at].astype(f32)
            left_over = d_ko * k * onto
            d_last = over_rows(left_over) + de_ref[at] * jnp.exp(last)
            d_cum = (
                d_cum + into * (beta * by_y + d_qi * q) - left_over
                + jnp.where(rows == chunk - 1, d_last, 0.0))
            # g reaches G_i for every i at or after its token
            dg_ref[at] = _cumulated(d_cum, reverse=True)
            dbeta_ref[0, 0, r, pl.ds(c, 1), :] = _turned(d_beta, eye)
            dv_ref[at] = (beta * d_x).astype(dv_ref.dtype)
            d_k = (d_k + beta * grow_k + from_cols + (beta * into) * d_y
                   + onto * d_ko)
            d_q = d_q + grow_q + into * d_qi
        dk_ref[0, 0, 0, c] = d_k.astype(dk_ref.dtype)
        dq_ref[0, 0, 0, c] = d_q.astype(dq_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, a_chunk, 0)


@functools.partial(  # edlint: disable=obs-bare-jit (as the inverse's)
    jax.jit, static_argnames=("residuals", "interpret"))
def kda_prepare_fwd(q, k, v, g, beta, residuals=False, interpret=False):
    """``gdn_prepare_fwd`` for a decay a channel, g (B, Hk, R, N, C, Dk)
    float32: a segment's operands of ``kda_scan_fwd`` where it reads
    them -> (decay (B, Hk, R, N, 1, Dk) float32, a channel's exp(G_last)
    on its own lane; W, the decayed keys, Q~, P in the compute dtype; U
    float32) and, with ``residuals``, ``T`` as ``gdn_prepare_fwd`` leaves
    it."""
    rep, chunks, chunk, dv = v.shape[2:]
    dk, dtype = q.shape[5], q.dtype
    step = kda_prepare_block(rep, chunks, chunk, dk, dv, dtype.itemsize)
    return _prepare_call(
        _kda_prepare_fwd_kernel, "kda_prepare_fwd", [q, k], [v, g], [beta],
        [], 0, [(1, dk, jnp.float32)] + [(chunk, dk, dtype)] * 3
        + [(chunk, chunk, dtype), (chunk, dv, jnp.float32)], 0,
        int(residuals), interpret, step,
        [pltpu.VMEM((rep * step, chunk, chunk), jnp.float32),
         pltpu.VMEM((2, _KDA_SUB + chunk, dk), jnp.float32)])


@functools.partial(  # edlint: disable=obs-bare-jit (as the inverse's)
    jax.jit, static_argnames=("interpret",))
def kda_prepare_bwd(q, k, v, g, beta, inverse, d_decay, dw, d_k, dq, dp, du,
                    interpret=False):
    """The VJP of ``kda_prepare_fwd`` from its operands, ``T`` and the
    six cotangents (``du`` in the compute dtype, as ``kda_scan_bwd``
    hands it on; ``d_decay`` (B, Hk, R, N, 1, Dk) float32, whole a
    channel): -> (dq, dk (B, Hk, 1, N, C, Dk), dv in their operands'
    dtype, dg (B, Hk, R, N, C, Dk) and dbeta float32)."""
    rep, chunks, chunk, dv = v.shape[2:]
    dk = q.shape[5]
    step = kda_prepare_block(rep, chunks, chunk, dk, dv, q.dtype.itemsize)
    heads = rep * step
    return _prepare_call(
        _kda_prepare_bwd_kernel, "kda_prepare_bwd", [q, k],
        [v, g, d_decay, dw, d_k, dq, dp, du], [beta], [inverse], 2,
        [(chunk, dv, v.dtype), (chunk, dk, jnp.float32)], 1, 0, interpret,
        step, [pltpu.VMEM((heads, chunk, dk), jnp.float32),
               pltpu.VMEM((heads, chunk, chunk), jnp.float32),
               pltpu.VMEM((heads, chunk, dv + dk), jnp.float32),
               pltpu.VMEM((2, _KDA_SUB + chunk, dk), jnp.float32)])


def _chunks_under_vjp(prepare_fwd, prepare_bwd, by_channel):
    """The rule over a segment's chunks by four kernels under ONE VJP,
    nothing of XLA's between them, shapes as ``_chunks``: (state, q, k,
    v, g, beta) -> (the leaving state, o). ``by_channel``: the
    ``kda_*`` kernels, ``state`` and the leaving state TRANSPOSED, (B,
    Hk, R, Dv, Dk), as the scan's kernels carry them."""
    @jax.custom_vjp
    def _chunks_pallas(state, q, k, v, g, beta):
        return gdn_scan_fwd(
            state, *prepare_fwd(q, k, v, g, beta), by_channel=by_channel)

    def _chunks_vjp_fwd(state, q, k, v, g, beta):
        *operands, u, inverse = prepare_fwd(q, k, v, g, beta, residuals=True)
        leaving, o, new_v, states = gdn_scan_fwd(
            state, *operands, u, residuals=True, by_channel=by_channel)
        return (leaving, o), (
            q, k, v, g, beta, inverse, *operands, states, new_v)

    def _chunks_vjp_bwd(residuals, cotangents):
        *inputs, inverse = residuals[:6]
        d_state, d_o = cotangents
        d_state, *grads = gdn_scan_bwd(
            *residuals[6:], d_o, d_state, by_channel=by_channel)
        # du stays in the compute dtype between the two kernels
        return (d_state, *prepare_bwd(*inputs, inverse, *grads))

    _chunks_pallas.defvjp(_chunks_vjp_fwd, _chunks_vjp_bwd)
    return _chunks_pallas


# the kernels by their names in this module at the call, as a test that
# interprets them patches them
_chunks_pallas = _chunks_under_vjp(
    lambda *a, **kw: gdn_prepare_fwd(*a, **kw),
    lambda *a: gdn_prepare_bwd(*a), False)
_chunks_pallas_by_channel = _chunks_under_vjp(
    lambda *a, **kw: kda_prepare_fwd(*a, **kw),
    lambda *a: kda_prepare_bwd(*a), True)


@functools.lru_cache(maxsize=None)
def _log_once(hk, hv, dk, chunk, scan, prep, tokens, decay):
    """One line per distinct call of the rule (this runs at trace time),
    beside the attention line of ``ops/attention.py``, from where the
    paths are chosen. ``scan``: what carries the state from chunk to
    chunk, ``pallas`` (the ``gdn_scan_*`` kernels; ``kda_scan_*``, the
    same with the state transposed, under a decay a channel) or ``xla``
    (a ``lax.scan``); ``prep``: what makes the chunks' operands,
    ``pallas`` (the ``gdn_prepare_*`` kernels, under a decay a channel
    the ``kda_prepare_*`` ones, the inverses inside them) or ``xla``
    (``_chunk_operands`` / ``_chunk_operands_by_channel`` around
    ``unit_lower_inverse``); ``impl``: what runs the chunks' inverses,
    which is what ``prep`` says; ``decay``: ``scalar`` (a number a head
    and token) or ``vector`` (a number a channel of the key)."""
    logger.info(
        "linear attention heads k=%d v=%d dim=%d chunk=%d impl=%s "
        "scan=%s prep=%s (tokens=%d) decay=%s", hk, hv, dk, chunk, prep,
        scan, prep, tokens, decay)


@jax.custom_vjp
def _decayed_diagonal(xs, y, cum):
    """The sub-blocks ON a chunk's diagonal under a decay a channel:
    ``out[m, i, j] = sum_c xs[m, i, c] y[j, c] exp(cum[i, c] - cum[j,
    c])`` for ``i >= j``, 0 above; xs (B, Hk, R, N, nb, M, sub, D), y
    and cum (B, Hk, R, N, nb, sub, D) -> (B, Hk, R, N, nb, M, sub, sub).
    Summed over the channels directly: every exponent is ``<= 0``
    whatever the decay, where ``(x e^G)(y e^-G)^T`` overflows as soon
    as a channel cumulates past -88. The (sub, sub, D) decays of a
    sub-block are alive for ``_CUBE_BYTES`` of chunks at a time
    (``_by_chunk_groups``), and the VJP makes them again and keeps none
    (autodiff would keep them: 2 GB a segment at 32 heads of 128
    lanes)."""
    return _by_chunk_groups(_diagonal_fwd, xs, y, cum)


def _decay_cube(cum):
    """``exp(cum[i] - cum[j])`` a channel for ``i >= j``, 0 above:
    (..., sub, D) -> (..., sub, sub, D)."""
    sub = cum.shape[-2]
    lower = jnp.arange(sub)[:, None, None] >= jnp.arange(sub)[None, :, None]
    return jnp.exp(jnp.where(
        lower, cum[..., :, None, :] - cum[..., None, :, :], -jnp.inf))


def _diagonal_fwd(xs, y, cum):
    decayed = y[..., None, :, :] * _decay_cube(cum)  # (.., i, j, c)
    return jnp.sum(
        xs[..., :, :, None, :] * decayed[..., None, :, :, :], axis=-1)


def _diagonal_bwd(xs, y, cum, d_out):
    decay = _decay_cube(cum)
    across = y[..., None, :, :]  # y[j] beside the pair (i, j)
    # what the pair (i, j) hands to y[j]; times y[j], to cum[i] and,
    # negated, to cum[j]. The products ``m`` are summed term by term:
    # everything of a pair is then elementwise in (i, j, c), and the
    # sums over j are ONE reduction of three results, inside which XLA
    # makes the decays (the forward's sum over c is slower so: 62 ms a
    # call against 26 for the two fusions with the decays in HBM
    # between them, PERF.md Section 6, PR 58)
    pulled = decay * sum(
        d_out[..., m, :, :, None] * xs[..., m, :, None, :]
        for m in range(xs.shape[-3]))
    terms = tuple(
        d_out[..., m, :, :, None] * (across * decay)
        for m in range(xs.shape[-3])) + (pulled * across,)
    *d_xs, onto = jax.lax.reduce(
        terms, (jnp.zeros((), decay.dtype),) * len(terms),
        lambda a, b: tuple(x + y for x, y in zip(a, b)),
        (decay.ndim - 2,))
    d_y = jnp.sum(pulled, axis=-3)
    return jnp.stack(d_xs, axis=-3), d_y, onto - y * d_y


def _by_chunk_groups(fn, *operands):
    """``fn`` over its operands' chunks (axis 3 of each, and of each
    result) a group at a time: the fewest groups whose sub-blocks'
    (sub, sub, D) arrays are ``_CUBE_BYTES`` each, one after the
    other."""
    cum = operands[2]
    chunks, sub = cum.shape[3], cum.shape[-2]
    cube = cum.size * sub * cum.dtype.itemsize
    groups = next(
        d for d in range(1, chunks + 1)
        if chunks % d == 0 and (cube <= d * _CUBE_BYTES or d == chunks))
    if groups == 1:
        return fn(*operands)
    split = lambda x: jnp.moveaxis(x.reshape(
        x.shape[:3] + (groups, chunks // groups) + x.shape[4:]), 3, 0)
    join = lambda x: jnp.moveaxis(x, 0, 3).reshape(
        x.shape[1:4] + (chunks,) + x.shape[5:])
    return jax.tree_util.tree_map(join, jax.lax.map(
        lambda xs: fn(*xs), tuple(map(split, operands))))


def _decayed_diagonal_fwd(xs, y, cum):
    return _decayed_diagonal(xs, y, cum), (xs, y, cum)


def _decayed_diagonal_bwd(residuals, d_out):
    return _by_chunk_groups(_diagonal_bwd, *residuals, d_out)


_decayed_diagonal.defvjp(_decayed_diagonal_fwd, _decayed_diagonal_bwd)


def _decayed_products(q, k, cum):
    """``K K^T`` and ``Q K^T`` of every chunk with the decay of a pair
    INSIDE the contraction over the channels, which a decay a channel
    asks for (``sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])``: no decay
    matrix factors out): q, k (B, Hk, 1, N, C, Dk), cum (B, Hk, R, N, C,
    Dk) -> two float32 (B, Hk, R, N, C, C), what is above a chunk's
    diagonal left to the caller's masks.

    A chunk is cut into sub-blocks of ``_SUB`` rows. Between two
    sub-blocks ``I > J``, ``exp(G_i - G_j) = exp(G_i - G_r) exp(G_r -
    G_j)`` with ``r`` the first row of ``I``: both exponents ``<= 0``,
    so those blocks are matmuls of decayed operands (the keys decayed
    once a sub-block of rows, (nb, C, Dk) a chunk). On the diagonal the
    pairs are summed over the channels directly
    (``_decayed_diagonal``). No exponent is ever positive."""
    dtype, (chunk, dk) = q.dtype, q.shape[-2:]
    sub = min(_SUB, chunk)
    blocks = lambda x: x.reshape(x.shape[:-2] + (chunk // sub, sub, dk))
    wide = jnp.promote_types(dtype, jnp.float32)
    whole = lambda x: jnp.broadcast_to(
        blocks(x).astype(wide), blocks(cum).shape)
    qk_rows = jnp.stack([whole(k), whole(q)], axis=-3)  # (.., nb, 2, sub, Dk)
    diagonal = _decayed_diagonal(qk_rows, whole(k), blocks(cum))
    first = blocks(cum)[..., :1, :]  # G at each sub-block's first row
    rows = qk_rows * jnp.exp(blocks(cum) - first)[..., None, :, :]
    # the keys as sub-block I's rows meet them: those of the sub-blocks
    # before I (at and past I's first row the difference is positive,
    # and those pairs are the diagonal's or nobody's)
    block = jnp.arange(chunk) // sub
    before = (block[None, :] < jnp.arange(chunk // sub)[:, None])[..., None]
    cols = k[..., None, :, :] * jnp.exp(jnp.where(
        before, first - cum[..., None, :, :], -jnp.inf))
    below = _matmul(
        rows.reshape(rows.shape[:-3] + (2 * sub, dk)),
        jnp.swapaxes(cols, -1, -2), dtype)  # (.., nb, 2 sub, C)
    same = (block[:, None] == block[None, :]).reshape(-1, sub, chunk)

    def laid(m):
        """Product ``m`` (K K^T, Q K^T) as a chunk's (C, C)."""
        off = below[..., m * sub:(m + 1) * sub, :]
        on = jnp.tile(diagonal[..., m, :, :], chunk // sub)
        return jnp.where(same, on, off).reshape(
            below.shape[:-3] + (chunk, chunk))

    return laid(0), laid(1)


def _chunk_operands(q, k, v, g, beta, decay_dtype):
    """Everything of the rule that does not meet the state, batched
    over all chunks: q, k (B, Hk, 1, N, C, Dk), v (B, Hk, R, N, C, Dv),
    g, beta (B, Hk, R, N, C) float32 -> (G_last (B, Hk, R, N, 1), W, the
    decayed keys, Q~, P, U), float32 but ``W`` and the keys, which are
    matmul operands only."""
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

    kt = jnp.swapaxes(k, -1, -2)
    kk = _matmul(k, kt, dtype)  # (B, Hk, 1, N, C, C)
    a = jnp.where(row > col, kk * beta[..., :, None] * decay, 0.0)
    t = unit_lower_inverse(a)
    u = _matmul(t, beta[..., None] * v, dtype)
    # matmul operands only from here on: kept in the compute dtype
    w = _matmul(t, (beta * into)[..., None] * k, dtype).astype(dtype)
    k_onto = (onto[..., None] * k).astype(dtype)  # (B, Hk, R, N, C, Dk)
    q_into = into[..., None] * q
    attn = jnp.where(row >= col, _matmul(q, kt, dtype) * decay, 0.0)
    return last, w, k_onto, q_into, attn, u


def _chunk_operands_by_channel(q, k, v, g, beta, decay_dtype):
    """``_chunk_operands`` for a decay a channel, g (B, Hk, R, N, C, Dk):
    the same operands, ``G_last`` (B, Hk, R, N, Dk) over the state's
    rows. The decay sits inside ``A``'s and ``P``'s contractions
    (``_decayed_products``) and scales the keys and queries a channel."""
    dtype, chunk = q.dtype, q.shape[-2]
    cum = jnp.cumsum(g.astype(decay_dtype), axis=4).astype(g.dtype)
    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    into = jnp.exp(cum)  # what reaches token i of the entering state
    last = cum[..., -1:, :]
    onto = jnp.exp(last - cum)  # what is left of token i at the end
    kk, attn = _decayed_products(q, k, cum)
    a = jnp.where(row > col, kk * beta[..., :, None], 0.0)
    t = unit_lower_inverse(a)
    u = _matmul(t, beta[..., None] * v, dtype)
    w = _matmul(t, (beta[..., None] * into) * k, dtype).astype(dtype)
    k_onto = (onto * k).astype(dtype)
    attn = jnp.where(row >= col, attn, 0.0)
    return last[..., 0, :], w, k_onto, into * q, attn, u


def _scan_xla(state, last, w, k_onto, q_into, attn, u, dtype):
    """The chunk-to-chunk recurrence as a ``lax.scan`` over the chunks,
    which hands back every chunk's V' and entering state for the two
    batched products of ``O``: operands as ``_chunk_operands`` gives
    them, ``state`` (B, Hk, R, Dk, Dv) in the dtype it is carried in ->
    (the leaving state, o float32)."""
    swap = lambda x: jnp.swapaxes(x, -1, -2)

    def step(state, xs):
        u_n, w_n, k_n, end = xs
        new_v = u_n - _matmul(w_n, state, dtype)
        held = state
        # the state's rows by ``end`` (.., 1), one number, or (.., Dk),
        # one a channel
        state = (
            jnp.exp(end)[..., None] * state
            + _matmul(swap(k_n), new_v, dtype)
        ).astype(state.dtype)
        return state, (new_v.astype(dtype), held.astype(dtype))

    chunks_first = lambda x: jnp.moveaxis(x, 3, 0)
    state, (new_v, states) = jax.lax.scan(
        step, state, tuple(map(chunks_first, (u, w, k_onto, last))))
    new_v = jnp.moveaxis(new_v, 0, 3)
    states = jnp.moveaxis(states, 0, 3)  # (B, Hk, R, N, Dk, Dv)
    return state, (
        _matmul(q_into, states, dtype) + _matmul(attn, new_v, dtype))


def _scan_operands(last, w, k_onto, q_into, attn, u, dtype):
    """``_chunk_operands``' results as ``gdn_scan_fwd`` reads them (and
    as ``gdn_prepare_fwd`` writes them): exp(G_last) on the lanes of a
    row a chunk, Q~ and P rounded to the compute dtype as ``_matmul``
    would round them."""
    leaves = jnp.broadcast_to(
        jnp.exp(last)[..., None], last.shape + u.shape[-1:])
    return leaves, w, k_onto, q_into.astype(dtype), attn.astype(dtype), u


def _scan_pallas(state, last, w, k_onto, q_into, attn, u, dtype):
    """The same recurrence by the ``gdn_scan_*`` kernels, the operands
    read where they lie (no chunks-first copy, no stacked states); o
    comes back rounded to the compute dtype, as the rule's caller would
    round it next."""
    return _scan(state, *_scan_operands(
        last, w, k_onto, q_into, attn, u, dtype))


def _scan_pallas_by_channel(state, last, w, k_onto, q_into, attn, u, dtype):
    """``_scan_pallas`` for ``_chunk_operands_by_channel``'s operands,
    ``last`` (B, Hk, R, N, Dk): the kernels take a channel's
    exp(G_last) on its own lane of the chunk's row and carry the state
    transposed, so it is turned on its way in and out (2 MB a segment
    at 32 heads of 128 x 128)."""
    swap = lambda x: jnp.swapaxes(x, -1, -2)
    leaving, o = _scan_by_channel(
        swap(state), jnp.exp(last)[..., None, :], w, k_onto,
        q_into.astype(dtype), attn.astype(dtype), u)
    return swap(leaving), o


def _chunks(state, q, k, v, g, beta, decay_dtype, scan, prep):
    """The rule over whole chunks from the state ``state`` (in the
    dtype it is carried in): -> (the state after them, o (B, Hk, R, N,
    C, Dv)). ``scan``: what carries the state; ``prep``: what makes
    the chunks' operands (``pallas``: the four kernels under one VJP,
    nothing of XLA's between them)."""
    if prep == "pallas" and g.ndim == beta.ndim:
        return _chunks_pallas(state, q, k, v, g, beta)
    if prep == "pallas":
        # the scan's kernels carry a decay a channel's state transposed
        swap = lambda x: jnp.swapaxes(x, -1, -2)
        leaving, o = _chunks_pallas_by_channel(swap(state), q, k, v, g, beta)
        return swap(leaving), o
    if g.ndim == beta.ndim:
        operands, kernels = _chunk_operands, _scan_pallas
    else:
        operands, kernels = _chunk_operands_by_channel, _scan_pallas_by_channel
    carry = kernels if scan == "pallas" else _scan_xla
    return carry(state, *operands(q, k, v, g, beta, decay_dtype), q.dtype)


def segments_of(seq, chunk=DEFAULT_CHUNK, segment=DEFAULT_SEGMENT):
    """(the tokens ``gated_delta_rule`` pads a sequence of ``seq`` by,
    the segments it then runs one at a time): what a producer needs to
    write q, k, v where the rule's scan over segments reads them."""
    span = chunk if seq <= chunk * segment else chunk * segment
    pad = -seq % span
    return pad, max(1, (seq + pad) // (chunk * segment))


def gated_delta_rule(q, k, v, g, beta, chunk=DEFAULT_CHUNK,
                     segment=DEFAULT_SEGMENT, state_dtype=None,
                     decay_dtype=None, mesh=None):
    """q, k: (B, Hk, S, Dk), already normalised and scaled; v: (B, Hv,
    S, Dv) with ``Hv`` a multiple of ``Hk`` (value head ``h`` reads key
    head ``h // (Hv / Hk)``; q and k are never repeated in memory);
    beta: (B, Hv, S) float32; g: (B, Hv, S) float32, one log decay a
    head and token (Gated DeltaNet), or (B, Hv, S, Dk), one a channel
    of the key (Kimi Delta Attention: ``S <- Diag(exp(g_t)) S``). The
    operand's rank decides, no flag: each rank has its operands'
    kernels where ``prepare_impl`` says (a decay a channel
    ``_decayed_products`` on XLA's lines elsewhere) and carries its
    state where ``scan_impl`` says. Returns o (B, Hv, S, Dv) in ``v``'s
    dtype.

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
    caller's step is sharded over, if any (``scan_impl``: the kernels
    run where nothing is left to partition); the rule itself places
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
    if g.shape != beta.shape and g.shape != beta.shape + (dk,):
        raise ValueError(
            "g is beta's shape %s (a decay a token) or that and the "
            "key's %d channels, got %s" % (beta.shape, dk, g.shape))
    scan = scan_impl(
        q.dtype, chunk, dk, dv, state_dtype, decay_dtype, v.dtype, mesh)
    pad, segments = segments_of(seq, chunk, segment)
    if pad:
        widen = lambda x: jnp.pad(
            x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    num = (seq + pad) // (segments * chunk)  # chunks a segment
    prep = prepare_impl(
        q.dtype, chunk, dk, dv, rep, num, state_dtype, decay_dtype, v.dtype,
        mesh, g.ndim)
    _log_once(hk, hv, dk, chunk, scan, prep, batch * seq,
              "scalar" if g.ndim == SCALAR_DECAY else "vector")
    # segments first; key-like (B, Hk, 1, N, C, Dk), value-like (B, Hk,
    # R, N, C, ...)
    split = lambda x, heads, *rest: jnp.moveaxis(
        x.reshape((batch,) + heads + (segments, num, chunk) + rest),
        1 + len(heads), 0)
    xs = (
        split(q, (hk, 1), dk), split(k, (hk, 1), dk),
        split(v, (hk, rep), dv),
        split(g.astype(wide), (hk, rep), *g.shape[3:]),
        split(beta.astype(wide), (hk, rep)),
    )
    run = lambda state, xs: _chunks(state, *xs, decay_dtype, scan, prep)
    state0 = jnp.zeros((batch, hk, rep, dk, dv), state_dtype)
    if segments == 1:
        _, o = run(state0, tuple(x[0] for x in xs))
    else:
        _, o = jax.lax.scan(jax.checkpoint(run), state0, xs)
        o = jnp.moveaxis(o, 0, 3)  # (B, Hk, R, segments, N, C, Dv)
    o = o.reshape(batch, hv, seq + pad, dv)[:, :, :seq]
    return checkpoint_name(o.astype(v.dtype), GDN_OUT_NAME)


def gated_delta_recurrence(q, k, v, g, beta, state=None):
    """The rule one token a step, in the inputs' dtype: the definition
    the chunked form is tested against, for a decay a token and for a
    decay a channel. Shapes as ``gated_delta_rule``; ``state`` (B, Hv,
    Dk, Dv): the state the sequence starts from (None: zero)."""
    rep = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(x, rep, axis=1) for x in (q, k))
    # the decay over the state's rows: (B, H, S, 1 or Dk, 1)
    g = g.reshape(g.shape[:3] + (-1, 1))

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (B, H, D), (B, H, 1 | Dk, 1), (B, H)
        state = jnp.exp(g_t) * state
        u = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    tokens_first = lambda x: jnp.moveaxis(x, 2, 0)
    if state is None:
        state = jnp.zeros(v.shape[:2] + (q.shape[-1], v.shape[-1]), v.dtype)
    _, o = jax.lax.scan(
        step, state, tuple(map(tokens_first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2)
