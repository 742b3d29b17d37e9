"""The double-gated short convolution (LFM2's ``conv`` mixer, between
its two projections): for one channel and position t, with the input
projection's columns lying ``B | C | X``,

    z[t] = B[t] X[t]
    c[t] = sum_{j < K} w[j] z[t - (K - 1) + j]        z[< 0] = 0
    y[t] = C[t] c[t]

causal, depthwise (every channel its own K taps), no bias and no
activation; K = ``conv_L_cache`` = 3 in LFM2-8B-A1B, so a position reads
itself and the two before it.

Plain ``jax.numpy``: K shifted multiply-adds over the sequence axis
(``ops/qkv_conv.py:conv_silu_xla``'s form; a ``conv_general_dilated``
with one group a channel lowers to a convolution the TPU runs on the
MXU at a channel a pass). The arithmetic is float32 under narrower
operands and the result is rounded once: the passes are bound by
the bytes they move (a forward reads ``3 C`` and writes ``C`` elements a
token, a backward reads ``4 C`` and writes ``3 C``), so the wider
arithmetic is free, and the taps' gradient is a sum over every token.
The call is a ``jax.checkpoint``: what its backward keeps is its two
operands, and it forms ``z`` and ``c`` again (two multiplies and K
multiply-adds an element) where a plain trace would keep both in
float32, 0.5 GB a layer at 32,768 x 2048. A Pallas kernel for it is a
later change's: ``short_conv_gate_roofline`` (the benchmark's) reads
how far these lines are from the HBM's peak.
"""

import functools

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory

logger = _logger_factory("elasticdl_tpu.ops.short_conv")


@functools.lru_cache(maxsize=None)
def log_choice(channels, taps, tokens):
    """One line per distinct layer shape (this runs at trace time)."""
    logger.info(
        "short conv channels=%d taps=%d impl=xla (tokens=%d; B | C | X "
        "one projection, float32 arithmetic, one rounding)",
        channels, taps, tokens)


def causal_depthwise_conv(z, taps):
    """``c[t] = sum_j taps[j] z[t - (K - 1) + j]`` over axis -2 of ``z``
    (..., S, C) with ``taps`` (K, C); positions before the first are 0."""
    seq, k = z.shape[-2], taps.shape[0]
    padded = jnp.pad(z, [(0, 0)] * (z.ndim - 2) + [(k - 1, 0), (0, 0)])
    return sum(
        taps[j] * jax.lax.slice_in_dim(padded, j, j + seq, axis=z.ndim - 2)
        for j in range(k))


@jax.checkpoint
def gated_short_conv(bcx, taps):
    """``C * conv(B * X)``: ``bcx`` (..., S, 3 C) the input projection's
    result, ``taps`` (K, C); returns (..., S, C) in ``bcx``'s dtype."""
    channels = taps.shape[1]
    if bcx.shape[-1] != 3 * channels:
        raise ValueError(
            "the projection is B | C | X, three times the taps' %d "
            "channels wide; got %d" % (channels, bcx.shape[-1]))
    wide = jnp.promote_types(bcx.dtype, jnp.float32)
    b, c, x = (
        bcx[..., i * channels:(i + 1) * channels].astype(wide)
        for i in range(3))
    conv = causal_depthwise_conv(b * x, taps.astype(wide))
    return (c * conv).astype(bcx.dtype)
