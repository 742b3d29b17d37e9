"""``Band``'s kernels (``ops/flash_attention.py``) in interpret mode on
the CPU against dense masked softmax at groups 6 and 8, under both
backward schedules; a gradient from one q-block; the band against the
diagonal; the outputs' names. The layout alone is
``tests/test_flash_band.py``'s, the grid of runs against the
rectangle's walk ``tests/test_flash_band_grid.py``'s. A case costs by
the kernels it compiles (a layout and a schedule each their own), not
by its shapes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.ops.attention import dot_product_attention, xla_attention
from tests.kernel_common import BAND_KERNEL_CASES as KERNEL_CASES, dense_band
from tests.test_mask_layouts import _qkv, _value_and_grads

@functools.lru_cache(maxsize=None)
def _references(case):
    """(the inputs, dense masked softmax's forward and gradients, the
    XLA path's): neither knows of a backward schedule, so once a
    case."""
    seq, window, heads, kv_heads, dim, _, _, dtype = case
    q, k, v, do = _qkv(seq, heads, kv_heads, dim, dtype)
    kept = jnp.asarray(dense_band(seq, window))

    def dense(q, k, v):
        group = heads // kv_heads
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * dim ** -0.5
        p = jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)

    return (q, k, v, do), _value_and_grads(dense, q, k, v, do), (
        _value_and_grads(
            lambda q, k, v: dot_product_attention(
                q, k, v, mask=F.Band(window), impl="xla"), q, k, v, do))


@pytest.mark.parametrize("schedule", ["fused", "split"])
@pytest.mark.parametrize(
    "case", list(KERNEL_CASES.values()), ids=list(KERNEL_CASES))
def test_flash_under_the_band_is_dense_masked_softmax(
        case, schedule, monkeypatch):
    """Forward and the three gradients of the kernels in interpret mode
    against softmax over the dense mask built from the equation (not
    from the layout), under both backward schedules."""
    _, window, _, _, _, block_q, block_k, dtype = case
    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    layout = F.Band(window)
    (q, k, v, do), want, xla = _references(case)
    got = _value_and_grads(
        lambda q, k, v: F.flash_attention(
            q, k, v, mask=layout, block_q=block_q, block_k=block_k,
            interpret=True), q, k, v, do)
    tol = 5e-2 if dtype == jnp.bfloat16 else 3e-4
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol)
    # and the XLA path builds its dense mask from the same layout
    for a, b in zip(xla, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol)


@pytest.mark.parametrize("lit", [0, 2, 3], ids=["first", "inner", "last"])
@pytest.mark.parametrize("schedule", ["fused", "split"])
def test_a_gradient_from_one_q_block_reaches_its_rows_alone(
        lit, schedule, monkeypatch):
    """A backward whose ``do`` is zero but for one q-block, in the
    second of two heads that share dq's accumulator (the first leaves
    every row of it dirty): dq is exactly zero outside that block's
    rows, so every other row was zeroed and rounded out, and dense
    masked softmax's inside them; dk and dv are the dense ones."""
    seq, window, dim, block = 512, 200, 32, 128
    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    q, k, v, do = _qkv(seq, 2, 2, dim, jnp.float32)
    rows = slice(lit * block, (lit + 1) * block)
    do = do.at[:, 1].set(0.0).at[:, 1, rows].set(do[:, 1, rows])
    layout = F.Band(window)
    got = _value_and_grads(
        lambda q, k, v: F.flash_attention(
            q, k, v, mask=layout, block_q=block, block_k=block,
            interpret=True), q, k, v, do)
    want = _value_and_grads(
        lambda q, k, v: xla_attention(q, k, v, mask=layout), q, k, v, do)
    dq = np.asarray(got[1])
    dark = np.ones(seq, bool)
    dark[rows] = False
    assert np.abs(dq[:, 0]).min(axis=-1).max() > 0
    np.testing.assert_array_equal(dq[:, 1, dark], 0.0)
    assert np.abs(dq[:, 1, rows]).max() > 1e-3
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=3e-4)


def test_a_causal_mask_in_the_band_s_place_is_another_function():
    q, k, v, _ = _qkv(512, 2, 2, 64, jnp.float32)
    band = xla_attention(q, k, v, mask=F.Band(64))
    causal = xla_attention(q, k, v, causal=True)
    assert float(jnp.abs(band - causal).max()) > 0.1
    # and a window that holds the whole prefix is the causal mask
    np.testing.assert_allclose(
        xla_attention(q, k, v, mask=F.Band(512)), causal, atol=1e-6)


def test_the_flash_policy_names_the_band_call_s_outputs():
    """``remat_block``'s ``flash`` policy saves ``flash_out`` /
    ``flash_lse``: the band's call names its outputs so too."""
    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    text = str(jax.make_jaxpr(lambda q: F.flash_attention(
        q, q, q, mask=F.Band(64), block_q=128, block_k=128,
        interpret=True))(q))
    assert "name=" + F.FLASH_OUT_NAME in text
    assert "name=" + F.FLASH_LSE_NAME in text
    assert "flash_band_fwd" in text and "name=flash_fwd" not in text
