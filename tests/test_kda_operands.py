"""``kda_prepare_fwd`` / ``kda_prepare_bwd`` (``ops/gated_delta.py``,
ISSUE 59) in interpret mode on the CPU: the operands of the
chunk-to-chunk scan under a decay a channel, a block of a key head's
chunks in VMEM, against ``_chunk_operands_by_channel`` as
``_scan_pallas_by_channel`` hands its results to the scan, and against
autodiff of those lines; decays of -50 a token on some channels and 0
on others; the rule through the four ``kda_*`` kernels against the
per-token loop; the chooser for a decay of rank 4 and the kernels' own
account of a block. An interpreted kernel costs by the trace: a case
runs each kernel once (``_case`` keeps what two tests share)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from elasticdl_tpu.ops import gated_delta
from tests.gdn_common import _force_pallas, _value_and_grads

_OPERANDS = ("decay", "w", "k_onto", "q_into", "p", "u")
_GRADS = ("dq", "dk", "dv", "dg", "dbeta")
# the cell's chunk in the cell's dtype, and the other chunk and a key
# head's two value heads in the dtype that compares to rounding
_CASES = {
    "64-rep1-bfloat16": (64, 1, jnp.bfloat16, "drawn"),
    "128-rep2-float32": (128, 2, jnp.float32, "drawn"),
    "64-hard": (64, 1, jnp.float32, "hard"),
    "64-mixed": (64, 1, jnp.float32, "mixed"),
}


def _inputs(chunk, rep, dtype, regime, hk=1, num=2):
    """A segment's q, k (1, Hk, 1, N, C, 128), v, g (1, Hk, R, N, C,
    128) and beta (1, Hk, R, N, C). ``regime`` as ``test_kda_rule.py``
    names them: ``drawn``, ``hard`` (-50 a token on the even channels, 0
    on the odd ones) or ``mixed`` (drawn, the second chunk's first half
    at -50 on the even channels)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    key_like, value_like = (1, hk, 1, num, chunk), (1, hk, rep, num, chunk)
    q = unit(jax.random.normal(keys[0], key_like + (128,))) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], key_like + (128,)))
    v = jax.random.normal(keys[2], value_like + (128,))
    g = -0.3 * jnp.exp(jax.random.normal(keys[3], value_like + (128,)))
    even = jnp.arange(128) % 2 == 0
    if regime == "hard":
        g = jnp.broadcast_to(jnp.where(even, -50.0, 0.0), g.shape)
    elif regime == "mixed":
        run = (jnp.arange(num)[:, None] == 1) & (
            jnp.arange(chunk)[None, :] < chunk // 2)
        g = jnp.where(run[..., None] & even, -50.0, g)
        g = jnp.where(~even & (jnp.arange(128) % 4 == 1), 0.0, g)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], value_like))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


@jax.jit
def _lines(q, k, v, g, beta):
    """What ``_scan_pallas_by_channel`` hands ``kda_scan_fwd`` with
    ``prep=xla``: one program a shape."""
    last, w, k_onto, q_into, attn, u = (
        gated_delta._chunk_operands_by_channel(q, k, v, g, beta, jnp.float32))
    return (jnp.exp(last)[..., None, :], w, k_onto, q_into.astype(q.dtype),
            attn.astype(q.dtype), u)


# the kernels as programs of this file: cases of one shape and dtype
# (``64-hard`` and ``64-mixed`` differ in values) lower them once
_prepare = jax.jit(functools.partial(
    gated_delta.kda_prepare_fwd, residuals=True, interpret=True))
_prepare_bwd = jax.jit(functools.partial(
    gated_delta.kda_prepare_bwd, interpret=True))
_lines_vjp = jax.jit(lambda args, cotangents: jax.vjp(
    _lines, *args)[1](cotangents))


@functools.lru_cache(maxsize=None)
def _case(name):
    """(inputs, the lines' operands, the kernel's operands, its T)."""
    args = _inputs(*_CASES[name])
    *got, inverse = _prepare(*args)
    return args, _lines(*args), got, inverse


def _tolerance(name, operand=None):
    """Of the largest entry. A run of -50 a token cumulates to -1,600
    in a chunk, where a float32's last bit is 1e-4: the two ways of
    cumulating differ by that in an ``exp`` of a difference."""
    exact = _CASES[name][2] == jnp.float32
    if _CASES[name][3] != "drawn":
        return 5e-4
    return 2e-5 if exact or operand == "decay" else 1e-2


@pytest.mark.parametrize("name", list(_CASES))
def test_the_operands_kernel_is_the_lines(name):
    """The six operands of ``kda_scan_fwd`` in its layout and dtypes
    (a channel's ``exp(G_last)`` on its own lane of a (1, Dk) row):
    equal to float32 rounding of the decays (the kernel cumulates ``g``
    by a product with a triangle of ones where XLA calls ``cumsum``),
    so an operand in bfloat16 may differ by one rounding; finite, every
    entry, at -50 a token and at 0."""
    args, want, got, _ = _case(name)
    chunk, rep = _CASES[name][:2]
    assert got[0].shape == (1, 1, rep, 2, 1, 128)
    for operand, a, b in zip(_OPERANDS, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, operand
        a, b = np.float32(a), np.float32(b)
        assert np.isfinite(a).all(), operand
        np.testing.assert_allclose(
            a, b, rtol=0, atol=_tolerance(name, operand) * np.abs(b).max(),
            err_msg=operand)


@pytest.mark.parametrize("name", ["64-rep1-bfloat16", "128-rep2-float32"])
def test_the_inverse_lies_as_the_scalar_kernel_leaves_it(name):
    """``T`` with two 64 x 64 matrices (one of 128) a lane row, the grid
    step's chunks first and a key head's value heads within, (B, Hk,
    grid steps, lane rows, C, 128) float32: ``gdn_prepare_fwd``'s
    layout, and without ``residuals`` the same six operands."""
    args, _, got, inverse = _case(name)
    chunk, rep, dtype, _ = _CASES[name]
    plain = jax.jit(functools.partial(
        gated_delta.kda_prepare_fwd, interpret=True))(*args)
    assert len(plain) == 6
    for operand, a, c in zip(_OPERANDS, got, plain):
        np.testing.assert_array_equal(
            np.float32(a), np.float32(c), err_msg=operand)
    step = gated_delta.kda_prepare_block(
        rep, 2, chunk, 128, 128, jnp.dtype(dtype).itemsize)
    assert step == 2
    pack = 128 // chunk
    assert inverse.shape == (1, 1, 1, -(-rep * step // pack), chunk, 128)
    assert inverse.dtype == jnp.float32

    @jax.jit
    def inverses(q, k, v, g, beta):
        kk, _ = gated_delta._decayed_products(q, k, jnp.cumsum(g, axis=4))
        strict = np.tril(np.ones((chunk, chunk), bool), -1)
        return gated_delta._inverse_product(
            jnp.where(strict, kk * beta[..., :, None], 0.0))

    t = np.asarray(inverses(*args))  # (1, Hk, R, N, C, C)
    inverse = np.asarray(inverse)
    for n in range(2):
        for r in range(rep):
            m = n * rep + r
            found = inverse[0, 0, 0, m // pack, :,
                            m % pack * chunk:(m % pack + 1) * chunk]
            np.testing.assert_allclose(
                found, t[0, 0, r, n], rtol=0,
                atol=_tolerance(name) * np.abs(t).max())


@pytest.mark.parametrize("name", list(_CASES))
def test_the_operands_kernel_s_vjp_is_autodiff_of_the_lines(name):
    """dq, dk (summed over the key head's value heads in the kernel),
    dv, dg A CHANNEL and dbeta from random cotangents of all six
    operands against ``jax.vjp`` of the lines: in float32 equal to
    rounding, in bfloat16 to the operands' rounding. Where a channel
    does not decay (``g`` = 0 beside channels at -50 a token) its ``dg``
    is the lines', whole: a mask with ``-inf``, never a clipped
    exponent, which would halve it."""
    args, primal, _, inverse = _case(name)
    dtype = _CASES[name][2]
    keys = jax.random.split(jax.random.PRNGKey(7), len(primal))
    cotangents = [
        jax.random.normal(key, x.shape).astype(x.dtype)
        for key, x in zip(keys, primal)]
    # du arrives in the compute dtype, as ``kda_scan_bwd`` hands it on
    low = cotangents[:-1] + [cotangents[-1].astype(dtype)]
    cotangents[-1] = low[-1].astype(jnp.float32)
    want = _lines_vjp(args, tuple(cotangents))
    got = _prepare_bwd(*args, inverse, *low)
    for grad, a, b in zip(_GRADS, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, grad
        a, b = np.float32(a), np.float32(b)
        assert np.isfinite(a).all(), grad
        np.testing.assert_allclose(
            a, b, rtol=0, atol=_tolerance(name) * np.abs(b).max(),
            err_msg=grad)
    if _CASES[name][3] != "drawn":
        still = np.asarray(args[3] == 0.0)
        assert still.any() and np.abs(np.float32(want[3])[still]).max() > 0
        np.testing.assert_allclose(
            np.float32(got[3])[still], np.float32(want[3])[still],
            rtol=1e-3, atol=1e-5 * np.abs(np.float32(want[3])).max())


def test_the_rule_by_the_four_kernels_is_the_recurrence(monkeypatch):
    """``gated_delta_rule`` with a decay a channel as a TPU runs it
    (``kda_prepare_*`` and ``kda_scan_*`` under one VJP, interpreted)
    over two segments of float32 operands at 128-wide heads, value and
    gradients against the per-token loop; the state crosses a segment's
    boundary turned and turned back."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (1, 1, 256, 128)
    q = unit(jax.random.normal(keys[0], shape)) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    g = -0.1 * jnp.exp(jax.random.normal(keys[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    args = (q, k, v, g, beta)
    want = _value_and_grads(gated_delta.gated_delta_recurrence, args)
    _force_pallas(monkeypatch)
    gated_delta._log_once.cache_clear()
    text, got = _value_and_grads(
        lambda *a: gated_delta.gated_delta_rule(*a, chunk=64, segment=2),
        args, jaxpr=True)
    for kernel in ("kda_prepare_fwd", "kda_prepare_bwd", "kda_scan_fwd",
                   "kda_scan_bwd"):
        assert "name=%s" % kernel in text, kernel
    assert "gdn_prepare" not in text
    for name, a, b in zip(("o",) + _GRADS, got, want):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=5e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize(
    "backend,dtype,chunk,dim,rep,chunks,place,prep", [
        # the cell: 32 heads of 128, chunk 64, segments of 64 chunks
        ("tpu", "bfloat16", 64, 128, 1, 64, None, "pallas"),
        ("tpu", "bfloat16", 128, 128, 1, 64, None, "pallas"),
        ("tpu", "float32", 64, 128, 1, 8, None, "pallas"),
        ("cpu", "bfloat16", 64, 128, 1, 64, None, "xla"),
        ("tpu", "bfloat16", 64, 128, 1, 64, "a four-device mesh", "xla"),
        ("tpu", "float16", 64, 128, 1, 64, None, "xla"),
        ("tpu", "bfloat16", 16, 128, 1, 64, None, "xla"),
        ("tpu", "bfloat16", 64, 64, 1, 64, None, "xla"),
        # no block of whole 8-row tiles of beta fits the VMEM budget:
        # 100 chunks a segment; 8 value heads a key head
        ("tpu", "bfloat16", 64, 128, 1, 100, None, "xla"),
        ("tpu", "bfloat16", 64, 128, 8, 64, None, "xla"),
    ], ids=lambda v: str(v))
def test_the_choice_of_the_vector_rule_s_operands(
        monkeypatch, backend, dtype, chunk, dim, rep, chunks, place, prep):
    """``prepare_impl`` for a decay of rank 4, from what it can observe
    and nothing else: the ``kda_prepare_*`` kernels wherever the scan's
    run and a block of the segment's chunks fits THEIR VMEM (``g`` and
    ``dg`` are (chunk, Dk) float32 tiles a chunk and head);
    ``_chunk_operands_by_channel`` everywhere else, the tests'
    ``decay_dtype`` / ``state_dtype`` experiments among it."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = None if place is None else Mesh(
        np.array(jax.devices()[:4]), ("data",))
    choose = lambda **kw: gated_delta.prepare_impl(
        jnp.dtype(dtype), chunk, dim, dim, rep, chunks, mesh=mesh,
        decay_rank=gated_delta.VECTOR_DECAY, **kw)
    assert choose() == prep
    assert choose(decay_dtype=jnp.bfloat16) == "xla"
    assert choose(state_dtype=jnp.bfloat16) == "xla"
    block = gated_delta.kda_prepare_block(
        rep, chunks, chunk, dim, dim, jnp.dtype(dtype).itemsize)
    if prep == "pallas":
        for kind in ("fwd", "fwd_residuals", "bwd"):
            assert gated_delta.kda_prepare_vmem_bytes(
                rep, block, chunk, dim, dim, jnp.dtype(dtype).itemsize,
                kind) <= gated_delta._PREPARE_BLOCK_BYTES
        assert chunks % block == 0 and (block % 8 == 0 or block == chunks)
    elif backend == "tpu" and place is None and dtype == "bfloat16" and (
            chunk, dim) == (64, 128):
        assert block is None
