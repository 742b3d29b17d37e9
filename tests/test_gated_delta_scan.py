"""``gdn_scan_fwd`` / ``gdn_scan_bwd`` (``ops/gated_delta.py``, ISSUE
34) in interpret mode on the CPU: the chunk-to-chunk recurrence with the
state in VMEM against the ``lax.scan`` over the same operands; where
``scan_impl`` chooses them; what a grid step takes. The rule by them
against the per-token loop is ``test_gated_delta_scan_rule.py``'s. Key
and value widths of 128: the kernels take whole lane rows. An
interpreted kernel costs by what its body unrolls (heads x chunks a
grid step) and by the trace, so a case is as many chunks and heads as
its assertion reads."""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.ops import gated_delta
from elasticdl_tpu.ops.gated_delta import gated_delta_rule
from tests.gdn_common import (  # noqa: F401
    _MANUAL,
    _MESH4,
    _force_pallas,
    _split_inputs,
    x64,
)


def _segment_operands(chunk, rep, dtype, hk=2, num=2, seed=0):
    """A segment's operands as ``_chunk_operands`` builds them (batch 1,
    ``hk`` key heads, ``num`` chunks: two, one grid step, show the
    state handed from a chunk to the next; the carry between grid steps
    is the rule's file's) and a non-zero entering state."""
    operands = jax.jit(
        lambda *a: gated_delta._chunk_operands(*a, jnp.float32))(
            *_split_inputs(num, chunk, rep, dtype, hk=hk, seed=seed))
    state = 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (1, hk, rep, 128, 128))
    return (state,) + operands


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
# the last column, the key heads: a block of two heads either way (two
# key heads with a value head each, one with its two) but at the cell's
# chunk and ratio, where two key heads with two each make a block of
# four, key heads and their value heads both counted past one
@pytest.mark.parametrize("chunk,rep,hk", [
    (64, 1, 2), (64, 2, 2), (128, 1, 2), (128, 2, 1),
], ids=["64-rep1", "64-rep2", "128-rep1", "128-rep2"])
def test_the_scan_s_kernels_are_the_lax_scan(monkeypatch, chunk, rep, hk,
                                             dtype):
    """``O``, the leaving state and ``V'`` from a non-zero entering
    state, and the gradients of all six operands and the entering
    state's: in float32 equal to rounding; in bfloat16 the forward bit
    for bit (the same four products at the same precision) and the
    backward to the operands' rounding (the kernel sums ``dV'`` and
    ``dS`` in float32 and rounds once where autodiff rounds each
    term)."""
    args = _segment_operands(chunk, rep, dtype, hk)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[-1].shape)

    def outputs(carry):
        def loss(*a):
            leaving, o = carry(*a, dtype)
            return ((o.astype(jnp.float32) * weight).sum()
                    + (leaving * leaving).sum())
        # the call alone (no residuals kept) and the differentiated one
        return jax.jit(lambda *a: carry(*a, dtype) + jax.grad(
            loss, argnums=tuple(range(7)))(*a))(*args)

    want = outputs(gated_delta._scan_xla)
    _force_pallas(monkeypatch)
    got = outputs(gated_delta._scan_pallas)
    exact = dtype == jnp.float32
    names = ("leaving", "o", "d_state", "d_last", "d_w", "d_k_onto",
             "d_q_into", "d_attn", "d_u")
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape, name
        a, b = np.float32(a), np.float32(b)
        scale = float(np.abs(b).max())
        if name == "o" and not exact:
            np.testing.assert_array_equal(
                a, np.float32(jnp.asarray(b).astype(dtype)), err_msg=name)
            continue
        if name == "leaving":
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-6 * scale)
            continue
        if name == "d_attn":
            # above the diagonal P is masked: its gradient there is
            # dropped by the mask's own transpose, outside the scan
            a, b = np.tril(a), np.tril(b)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=(2e-5 if exact else 2e-2) * scale,
            err_msg=name)
    # V' and the states the backward reads, as the scan hands them on
    state, last, w, k_onto, q_into, attn, u = args
    decay = jnp.broadcast_to(jnp.exp(last)[..., None], last.shape + (128,))
    leaving, o, new_v, states = jax.jit(functools.partial(
        gated_delta.gdn_scan_fwd, residuals=True))(
            state, decay, w, k_onto, q_into.astype(dtype),
            attn.astype(dtype), u)
    np.testing.assert_array_equal(np.float32(leaving), np.float32(got[0]))
    assert new_v.dtype == dtype and states.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.float32(states[:, :, :, 0]), np.float32(state))
    first = u[..., 0, :, :] - gated_delta._matmul(
        w[..., 0, :, :], state, dtype)
    np.testing.assert_allclose(
        np.float32(new_v[:, :, :, 0]), np.float32(first), rtol=0,
        atol=(1e-5 if exact else 1e-2) * float(jnp.abs(first).max()))


@pytest.mark.parametrize(
    "backend,dtype,chunk,dim,state,decay,out,place,scan", [
        ("tpu", "bfloat16", 64, 128, None, None, None, None, "pallas"),
        ("tpu", "float32", 128, 256, None, None, None, None, "pallas"),
        ("tpu", "bfloat16", 64, 128, None, None, None, _MANUAL, "pallas"),
        ("cpu", "bfloat16", 64, 128, None, None, None, None, "xla"),
        ("tpu", "float64", 64, 128, None, None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 128, None, None, None, _MESH4, "xla"),
        ("tpu", "bfloat16", 64, 128, "bfloat16", None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 128, None, "bfloat16", None, None, "xla"),
        ("tpu", "bfloat16", 64, 128, None, None, "float32", None, "xla"),
        ("tpu", "bfloat16", 32, 128, None, None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 64, None, None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 192, None, None, None, None, "xla"),
    ], ids=lambda v: str(v))
def test_the_choice_of_the_scan(monkeypatch, caplog, x64, backend, dtype,
                                chunk, dim, state, decay, out, place, scan):
    """From the backend, the dtypes, the widths, the chunk and the
    placement alone, and the rule's line says which: the kernels on a
    TPU for bfloat16 or float32 operands with the float32 state and
    decay, whole lane rows and a chunk of 64 or 128, on one device or
    inside a region already manual over the mesh; the ``lax.scan`` on
    the CPU, in float64, on a mesh of several devices (no partitioning
    rule), under the tests' ``state_dtype`` / ``decay_dtype``
    experiments, for an output of another dtype, at other widths."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = None if place is None else Mesh(
        np.array(jax.devices()[:4]), ("data",))
    dtype = jnp.dtype(dtype)
    given = {name: jnp.dtype(value) for name, value in (
        ("state_dtype", state), ("decay_dtype", decay)) if value}
    seen = []

    def trace(x):
        struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)
        wide = jnp.promote_types(dtype, jnp.float32)
        seen.append(gated_delta.scan_impl(
            dtype, chunk, dim, dim, out_dtype=out and jnp.dtype(out),
            mesh=mesh, **given))
        gated_delta._log_once.cache_clear()
        jax.eval_shape(
            functools.partial(
                gated_delta_rule, chunk=chunk, mesh=mesh, **given),
            struct((1, 1, 2 * chunk, dim), dtype),
            struct((1, 1, 2 * chunk, dim), dtype),
            struct((1, 2, 2 * chunk, dim), out or dtype),
            struct((1, 2, 2 * chunk), wide), struct((1, 2, 2 * chunk), wide))
        return x

    with caplog.at_level(logging.INFO):
        if place == _MANUAL:
            jax.eval_shape(jax_compat.shard_map(
                trace, mesh=mesh, in_specs=P("data"), out_specs=P("data")),
                jnp.zeros(4))
        else:
            trace(None)
    gated_delta._log_once.cache_clear()
    assert seen == [scan]
    # the operands' kernels go where the scan's do
    assert " scan=%s prep=%s (tokens=%d)" % (
        scan, scan, 2 * chunk) in caplog.text


def test_the_scan_s_grid_step_fits_its_budget():
    """The heads and chunks a grid step takes, from shapes: both divide
    what they are taken of, every double-buffered block and the carried
    states inside the budget, the budget inside the limit the kernels
    state; the cell's blocks by name."""
    kinds = ("fwd", "fwd_residuals", "bwd")
    assert [gated_delta.scan_block(32, 128, 64, 128, 128, 2, kind, 2)
            for kind in kinds] == [(8, 4), (8, 4), (8, 2)]
    for heads, rep, chunks, chunk, dk, dv, itemsize in (
            (32, 2, 128, 64, 128, 128, 2), (32, 1, 64, 128, 128, 128, 2),
            (6, 3, 9, 64, 256, 128, 4), (7, 1, 5, 128, 256, 256, 4),
            (1, 1, 1, 64, 128, 128, 2), (64, 16, 128, 128, 256, 512, 4),
            (48, 16, 8, 64, 128, 128, 2)):
        for kind in kinds:
            block, step = gated_delta.scan_block(
                heads, chunks, chunk, dk, dv, itemsize, kind, rep)
            assert heads % block == 0 and chunks % step == 0
            # whole groups of a key head's value heads, or part of one
            assert block % rep == 0 or rep % block == 0
            assert 1 <= block <= gated_delta._SCAN_HEADS
            assert 1 <= step <= gated_delta._SCAN_CHUNKS
            assert gated_delta.scan_vmem_bytes(
                block, step, chunk, dk, dv, itemsize, kind
            ) <= gated_delta._SCAN_BLOCK_BYTES, (heads, chunks, kind)
    # a bfloat16 (64, 64) block holds whole 128-lane rows in VMEM, a
    # decay row whole 8-row tiles
    assert gated_delta._tile_bytes(64, 64, 2) == 64 * 128 * 2
    assert gated_delta._tile_bytes(1, 128, 4) == 8 * 128 * 4
    assert gated_delta._SCAN_BLOCK_BYTES < gated_delta._SCAN_VMEM_LIMIT
