"""``gdn_scan_fwd`` / ``gdn_scan_bwd`` (``ops/gated_delta.py``, ISSUE
34) in interpret mode on the CPU: the chunk-to-chunk recurrence with the
state in VMEM against the ``lax.scan`` over the same operands, and the
rule by them against the per-token loop. Key and value widths of 128:
the kernels take whole lane rows."""

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
from elasticdl_tpu.ops.gated_delta import (
    gated_delta_recurrence,
    gated_delta_rule,
)
from tests.gdn_common import (  # noqa: F401
    _MANUAL,
    _MESH4,
    _force_pallas,
    _inputs,
    _split_inputs,
    _value_and_grads,
    x64,
)


def _segment_operands(chunk, rep, dtype, num=4, seed=0):
    """A segment's operands as ``_chunk_operands`` builds them (batch 1,
    2 key heads, ``num`` chunks) and a non-zero entering state."""
    operands = gated_delta._chunk_operands(
        *_split_inputs(num, chunk, rep, dtype, seed=seed), jnp.float32, "xla")
    state = 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (1, 2, rep, 128, 128))
    return (state,) + operands


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk,rep", [(64, 1), (64, 2), (128, 1), (128, 2)],
                         ids=["64-rep1", "64-rep2", "128-rep1", "128-rep2"])
def test_the_scan_s_kernels_are_the_lax_scan(monkeypatch, chunk, rep, dtype):
    """``O``, the leaving state and ``V'`` from a non-zero entering
    state, and the gradients of all six operands and the entering
    state's: in float32 equal to rounding; in bfloat16 the forward bit
    for bit (the same four products at the same precision) and the
    backward to the operands' rounding (the kernel sums ``dV'`` and
    ``dS`` in float32 and rounds once where autodiff rounds each
    term)."""
    args = _segment_operands(chunk, rep, dtype)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[-1].shape)

    def outputs(carry):
        def loss(*a):
            leaving, o = carry(*a, dtype)
            return ((o.astype(jnp.float32) * weight).sum()
                    + (leaving * leaving).sum())
        return carry(*args, dtype) + jax.grad(
            loss, argnums=tuple(range(7)))(*args)

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
    leaving, o, new_v, states = gated_delta.gdn_scan_fwd(
        state, decay, w, k_onto, q_into.astype(dtype), attn.astype(dtype),
        u, residuals=True)
    np.testing.assert_array_equal(np.float32(leaving), np.float32(got[0]))
    assert new_v.dtype == dtype and states.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.float32(states[:, :, :, 0]), np.float32(state))
    first = u[..., 0, :, :] - gated_delta._matmul(
        w[..., 0, :, :], state, dtype)
    np.testing.assert_allclose(
        np.float32(new_v[:, :, :, 0]), np.float32(first), rtol=0,
        atol=(1e-5 if exact else 1e-2) * float(jnp.abs(first).max()))


@pytest.mark.parametrize("seq,chunk,segment,hk,hv", [
    (512, 64, 128, 2, 4),   # one segment of eight chunks: two grid steps
    (256, 64, 1, 2, 2),     # four segments, the state carried between
    (300, 64, 2, 1, 2),     # a length the segment does not divide
    (256, 128, 1, 1, 1),
    (200, 128, 128, 2, 2),  # one segment, the chunk does not divide
], ids=["512-64", "256-64-seg1", "300-64-seg2", "256-128-seg1", "200-128"])
@pytest.mark.parametrize("prep", ["pallas", "xla"])
def test_the_rule_by_the_scan_s_kernels(monkeypatch, seq, chunk, segment,
                                        hk, hv, prep):
    """``gated_delta_rule`` by the kernels against the ``lax.scan`` path
    and against the per-token recurrence, float32: values and all five
    gradients, over one and several segments and lengths that the chunk
    or the segment does not divide. ``prep=pallas``: what a TPU chooses,
    the operands' and the scan's kernels under one VJP; ``prep=xla``:
    the scan's kernels after ``_chunk_operands`` with the inverses'
    kernels in it (PR 34's program). Padded tokens write nothing: the
    cut output and the gradients are the unpadded recurrence's."""
    args = _inputs(seq, jnp.float32, decay=2.0, batch=1, hk=hk, hv=hv,
                   dim=128)
    rule = lambda *a: gated_delta_rule(*a, chunk=chunk, segment=segment)
    by_xla = _value_and_grads(rule, args)
    by_token = _value_and_grads(gated_delta_recurrence, args)
    _force_pallas(monkeypatch)
    if prep == "xla":
        monkeypatch.setattr(
            gated_delta, "prepare_impl", lambda *a, **kw: "xla")
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: rule(*a).sum(), argnums=(0, 1, 2, 3, 4)))(*args))
    assert "gdn_scan_fwd" in text and "gdn_scan_bwd" in text
    for name in ("gdn_prepare_fwd", "gdn_prepare_bwd"):
        assert (name in text) == (prep == "pallas")
    for name in ("gdn_inverse_fwd", "gdn_inverse_bwd"):
        assert (name in text) == (prep == "xla")
    got = _value_and_grads(rule, args)
    for a, b, c in zip(got, by_xla, by_token):
        assert a.shape == c.shape and a.dtype == c.dtype
        scale = 1e-3 + float(jnp.abs(c).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * scale)
        np.testing.assert_allclose(a, c, rtol=0, atol=1e-4 * scale)


def test_the_scan_s_kernels_hold_bfloat16_s_rounding(monkeypatch):
    """The cell's dtypes: bfloat16 operands, float32 state and decay.
    The kernels' output is the ``lax.scan``'s bit for bit, and their
    gradients stay as close to the float32 recurrence's as its own."""
    args = _inputs(256, jnp.float32, decay=2.0, batch=1, dim=128)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    rule = lambda *a: gated_delta_rule(*a, chunk=64, segment=2)
    want = _value_and_grads(gated_delta_recurrence, args)
    by_xla = _value_and_grads(rule, low)
    _force_pallas(monkeypatch)
    # the scan's kernels after the XLA lines (the operands' kernels
    # cumulate g in another order: their own test below)
    monkeypatch.setattr(gated_delta, "prepare_impl", lambda *a, **kw: "xla")
    got = _value_and_grads(rule, low)
    assert got[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.float32(got[0]), np.float32(by_xla[0]))
    err = lambda a, b: float(jnp.sqrt(
        jnp.mean((a.astype(jnp.float32) - b) ** 2) / jnp.mean(b ** 2)))
    for a, b, c in zip(got[1:], by_xla[1:], want[1:]):
        assert err(a, c) < 1.25 * err(b, c) + 1e-4


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
