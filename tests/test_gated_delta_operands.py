"""``gdn_prepare_fwd`` (``ops/gated_delta.py``, ISSUE 39) in interpret
mode on the CPU: everything of the rule that does not meet the state, a
block of a key head's chunks in VMEM, against ``_chunk_operands``. Its
VJP, the rule through it, the chooser and the budget are
``test_gated_delta_operands_vjp.py``'s. An interpreted kernel costs by
what its body unrolls (here chunks x value heads a grid step) and by
the trace: a case has the chunks its comment names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gated_delta
from tests.gdn_common import _split_inputs, _xla_lines


_OPERANDS = ("decay", "w", "k_onto", "q_into", "p", "u")


@jax.jit
def _with_and_without_residuals(*args):
    """The kernel's two forms under one program: called an operation at
    a time, an interpreted kernel pays its dispatches besides its
    lowering."""
    return (gated_delta.gdn_prepare_fwd(*args, interpret=True),
            gated_delta.gdn_prepare_fwd(
                *args, residuals=True, interpret=True))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk,rep,num", [
    (64, 1, 3),     # three matrices: the second lane row half empty
    (64, 2, 16),    # sixteen lane rows a key head: two groups of eight in
                    # one grid step (bfloat16) or in two (float32)
    (128, 1, 2),    # a matrix a lane row
    (128, 2, 2),
], ids=["64-rep1", "64-rep2-16-chunks", "128-rep1", "128-rep2"])
def test_the_operands_kernel_is_chunk_operands(chunk, rep, num, dtype):
    """The six operands of ``gdn_scan_fwd`` in its layout and dtypes,
    and ``T`` with two 64 x 64 matrices (one of 128) a lane row: equal
    to float32 rounding of the decays (the kernel cumulates ``g`` by a
    masked sum where XLA calls ``cumsum``), so an operand in bfloat16
    may differ by one rounding, ``U`` by ``T``'s."""
    args = _split_inputs(num, chunk, rep, dtype)
    want = _xla_lines(*args)
    plain, (*got, inverse) = _with_and_without_residuals(*args)
    assert len(plain) == len(got) == 6
    exact = dtype == jnp.float32
    for name, a, b, c in zip(_OPERANDS, got, want, plain):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_array_equal(
            np.float32(a), np.float32(c), err_msg=name)
        scale = float(jnp.abs(b.astype(jnp.float32)).max())
        np.testing.assert_allclose(
            np.float32(a), np.float32(b), rtol=0,
            atol=(2e-5 if exact or name == "decay" else 1e-2) * scale,
            err_msg=name)
    # T as the backward reads it: the grid step's matrices, chunks
    # first and a key head's value heads within, ``pack`` a lane row
    step = gated_delta.prepare_block(rep, num, chunk, 128, 128,
                                     jnp.dtype(dtype).itemsize)
    pack = 128 // chunk
    rows = -(-rep * step // pack)
    assert inverse.shape == (1, 2, num // step, rows, chunk, 128)
    assert inverse.dtype == jnp.float32
    lower = np.tril(np.ones((chunk, chunk), bool))

    @jax.jit
    def inverses(q, k, v, g, beta):
        cum = jnp.cumsum(g, axis=-1)
        decay = jnp.exp(jnp.where(
            lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        kk = gated_delta._matmul(k, jnp.swapaxes(k, -1, -2), dtype)
        return gated_delta._inverse_product(jnp.where(
            np.tril(lower, -1), kk * beta[..., :, None] * decay, 0.0))

    t = np.asarray(inverses(*args))  # (1, Hk, R, N, C, C)
    inverse = np.asarray(inverse)
    for head in range(2):
        for n in range(num):
            for r in range(rep):
                m = (n % step) * rep + r
                found = inverse[0, head, n // step, m // pack, :,
                                m % pack * chunk:(m % pack + 1) * chunk]
                np.testing.assert_allclose(
                    found, t[0, head, r, n], rtol=0,
                    atol=2e-5 * np.abs(t[0, head, r, n]).max())
    if rep * step % pack:
        # the lane row's spare half holds the inverse of a zero matrix
        np.testing.assert_array_equal(
            inverse[0, :, :, -1, :, chunk:],
            np.broadcast_to(np.eye(chunk, dtype=np.float32),
                            (2, num // step, chunk, chunk)))
