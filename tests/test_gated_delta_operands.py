"""``gdn_prepare_fwd`` / ``gdn_prepare_bwd`` (``ops/gated_delta.py``,
ISSUE 39) in interpret mode on the CPU: everything of the rule that
does not meet the state, a block of a key head's chunks in VMEM,
against ``_chunk_operands`` and autodiff of it."""

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


def _xla_lines(q, k, v, g, beta):
    """What the scan's kernels are handed with ``prep=xla``:
    ``_chunk_operands`` and the casts and the broadcast of
    ``_scan_operands``."""
    return gated_delta._scan_operands(*gated_delta._chunk_operands(
        q, k, v, g, beta, jnp.float32, "xla"), q.dtype)


_OPERANDS = ("decay", "w", "k_onto", "q_into", "p", "u")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk,rep,num", [
    (64, 1, 3),     # three matrices: the second lane row half empty
    (64, 2, 16),    # sixteen lane rows a key head: two groups of eight in
                    # one grid step (bfloat16) or in two (float32)
    (128, 1, 2),
    (128, 2, 3),
], ids=["64-rep1", "64-rep2-16-chunks", "128-rep1", "128-rep2"])
def test_the_operands_kernel_is_chunk_operands(chunk, rep, num, dtype):
    """The six operands of ``gdn_scan_fwd`` in its layout and dtypes,
    and ``T`` with two 64 x 64 matrices (one of 128) a lane row: equal
    to float32 rounding of the decays (the kernel cumulates ``g`` by a
    masked sum where XLA calls ``cumsum``), so an operand in bfloat16
    may differ by one rounding, ``U`` by ``T``'s."""
    args = _split_inputs(num, chunk, rep, dtype)
    want = _xla_lines(*args)
    plain = gated_delta.gdn_prepare_fwd(*args, interpret=True)
    *got, inverse = gated_delta.gdn_prepare_fwd(
        *args, residuals=True, interpret=True)
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
    q, k, v, g, beta = args
    cum = jnp.cumsum(g, axis=-1)
    lower = np.tril(np.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    kk = gated_delta._matmul(k, jnp.swapaxes(k, -1, -2), dtype)
    a = jnp.where(np.tril(lower, -1), kk * beta[..., :, None] * decay, 0.0)
    t = np.asarray(gated_delta._inverse_product(a))  # (1, Hk, R, N, C, C)
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


@pytest.mark.parametrize("chunk,rep,num,dtype,decay", [
    (64, 1, 3, "float32", 2.0),
    (64, 2, 16, "float32", 2.0),
    (64, 2, 4, "bfloat16", 2.0),
    (64, 2, 4, "float32", 30.0),     # exp(G) underflows inside a chunk
    (128, 1, 2, "bfloat16", 2.0),
    (128, 2, 3, "float32", 1e-3),
    (128, 2, 3, "bfloat16", 30.0),
], ids=lambda v: str(v))
def test_the_operands_kernel_s_vjp(chunk, rep, num, dtype, decay):
    """dq, dk (summed over the key head's value heads in the kernel),
    dv, dg and dbeta from random cotangents of all six operands against
    autodiff of the XLA lines: in float32 equal to rounding; in bfloat16
    to the operands' rounding (the kernel keeps ``dX``, ``dY`` and every
    sum in float32 where autodiff rounds the transposed products'
    results to the compute dtype). A strongly negative ``g`` leaves
    every gradient finite: the decays are exps of differences ``<= 0``
    in the backward too."""
    dtype = jnp.dtype(dtype)
    args = _split_inputs(num, chunk, rep, dtype, decay=decay)
    primal, vjp = jax.vjp(_xla_lines, *args)
    keys = jax.random.split(jax.random.PRNGKey(7), len(primal))
    cotangents = [
        jax.random.normal(key, x.shape).astype(x.dtype)
        for key, x in zip(keys, primal)]
    # du arrives in the compute dtype, as ``gdn_scan_bwd`` hands it on
    low = cotangents[:-1] + [cotangents[-1].astype(dtype)]
    cotangents[-1] = low[-1].astype(jnp.float32)
    want = vjp(tuple(cotangents))
    *_, inverse = gated_delta.gdn_prepare_fwd(
        *args, residuals=True, interpret=True)
    got = gated_delta.gdn_prepare_bwd(*args, inverse, *low, interpret=True)
    exact = dtype == jnp.float32
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.float32(a), np.float32(b)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(
            a, b, rtol=0, atol=(3e-5 if exact else 2e-2) * np.abs(b).max(),
            err_msg=name)


def test_the_operands_kernels_hold_bfloat16_s_rounding(monkeypatch):
    """The cell's dtypes over two segments and a padded length: the
    rule's output and gradients with ``prep=pallas`` stay as close to
    the float32 recurrence's as ``prep=xla``'s."""
    args = _inputs(200, jnp.float32, decay=2.0, batch=1, dim=128)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    rule = lambda *a: gated_delta_rule(*a, chunk=64, segment=2)
    want = _value_and_grads(gated_delta_recurrence, args)
    _force_pallas(monkeypatch)
    got = _value_and_grads(rule, low)
    monkeypatch.setattr(gated_delta, "prepare_impl", lambda *a, **kw: "xla")
    by_xla = _value_and_grads(rule, low)
    assert got[0].dtype == jnp.bfloat16
    err = lambda a, b: float(jnp.sqrt(
        jnp.mean((a.astype(jnp.float32) - b) ** 2) / jnp.mean(b ** 2)))
    for a, b, c in zip(got, by_xla, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert err(a, c) < 1.25 * err(b, c) + 1e-4


@pytest.mark.parametrize(
    "backend,dtype,chunk,dim,rep,chunks,state,decay,out,place,prep", [
        ("tpu", "bfloat16", 64, 128, 2, 128, None, None, None, None,
         "pallas"),
        ("tpu", "float32", 128, 128, 1, 3, None, None, None, None, "pallas"),
        ("tpu", "bfloat16", 64, 128, 2, 128, None, None, None, _MANUAL,
         "pallas"),
        ("cpu", "bfloat16", 64, 128, 2, 128, None, None, None, None, "xla"),
        ("tpu", "float64", 64, 128, 2, 128, None, None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 128, 2, 128, None, None, None, _MESH4,
         "xla"),
        # whatever keeps the scan's kernels away keeps these away: they
        # write what ``gdn_scan_fwd`` reads
        ("tpu", "bfloat16", 64, 128, 2, 128, "bfloat16", None, None, None,
         "xla"),
        ("tpu", "bfloat16", 64, 128, 2, 128, None, "bfloat16", None, None,
         "xla"),
        ("tpu", "bfloat16", 64, 128, 2, 128, None, None, "float32", None,
         "xla"),
        ("tpu", "bfloat16", 32, 128, 2, 128, None, None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 64, 2, 128, None, None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 192, 2, 128, None, None, None, None, "xla"),
        # no block of whole 8-row tiles of g fits the VMEM budget: 100
        # chunks a segment; 16 value heads a key head
        ("tpu", "bfloat16", 64, 128, 4, 100, None, None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 128, 16, 128, None, None, None, None, "xla"),
        ("tpu", "bfloat16", 64, 128, 4, 128, None, None, None, None,
         "pallas"),
    ], ids=lambda v: str(v))
def test_the_choice_of_the_operands_kernels(
        monkeypatch, caplog, x64, backend, dtype, chunk, dim, rep, chunks,
        state, decay, out, place, prep):
    """A third chooser beside ``inverse_impl`` and ``scan_impl``, from
    the same things and the segment's shape: the kernels wherever the
    scan's run and a block of the segment's chunks fits their VMEM;
    ``_chunk_operands`` everywhere else. The rule's line says which,
    after ``scan=``."""
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
        seen.append(gated_delta.prepare_impl(
            dtype, chunk, dim, dim, rep, chunks,
            out_dtype=out and jnp.dtype(out), mesh=mesh, **given))
        gated_delta._log_once.cache_clear()
        seq = chunks * chunk
        jax.eval_shape(
            functools.partial(
                gated_delta_rule, chunk=chunk, segment=chunks, mesh=mesh,
                **given),
            struct((1, 1, seq, dim), dtype), struct((1, 1, seq, dim), dtype),
            struct((1, rep, seq, dim), out or dtype),
            struct((1, rep, seq), wide), struct((1, rep, seq), wide))
        return x

    with caplog.at_level(logging.INFO):
        if place == _MANUAL:
            jax.eval_shape(jax_compat.shard_map(
                trace, mesh=mesh, in_specs=P("data"), out_specs=P("data")),
                jnp.zeros(4))
        else:
            trace(None)
    gated_delta._log_once.cache_clear()
    assert seen == [prep]
    assert " prep=%s (tokens=%d)" % (prep, chunks * chunk) in caplog.text


def test_the_operands_grid_step_fits_its_budget():
    """The chunks a grid step takes, from shapes: a divisor of the
    segment's in whole 8-row tiles of ``g`` (or all of them), the
    smallest that gives the inverses two groups of ``_CHAINS`` lane rows
    (or the largest that fits), every double-buffered block inside the
    budget, the budget inside the limit the kernels state; the cell's
    block by name."""
    assert gated_delta.prepare_block(2, 128, 64, 128, 128, 2) == 16
    assert gated_delta.prepare_block(1, 128, 64, 128, 128, 2) == 16
    assert gated_delta.prepare_block(2, 128, 64, 128, 128, 4) == 8
    assert gated_delta.prepare_block(2, 64, 128, 128, 128, 2) == 8
    assert gated_delta.prepare_block(2, 3, 64, 128, 128, 4) == 3
    assert gated_delta.prepare_block(2, 12, 64, 128, 128, 2) == 12
    assert gated_delta.prepare_block(2, 12, 64, 128, 128, 4) is None
    assert gated_delta.prepare_block(2, 24, 64, 128, 128, 4) == 8
    assert gated_delta.prepare_block(16, 100, 64, 128, 128, 2) is None
    kinds = ("fwd", "fwd_residuals", "bwd")
    for rep, chunks, chunk, dk, dv, itemsize in (
            (2, 128, 64, 128, 128, 2), (1, 128, 64, 128, 128, 2),
            (2, 64, 128, 128, 128, 2), (3, 9, 64, 256, 128, 4),
            (1, 5, 128, 256, 256, 4), (1, 1, 64, 128, 128, 2),
            (16, 128, 128, 256, 512, 4), (16, 8, 64, 128, 128, 2)):
        step = gated_delta.prepare_block(rep, chunks, chunk, dk, dv, itemsize)
        if step is None:
            continue
        assert chunks % step == 0 and (step % 8 == 0 or step == chunks)
        for kind in kinds:
            assert gated_delta.prepare_vmem_bytes(
                rep, step, chunk, dk, dv, itemsize, kind
            ) <= gated_delta._PREPARE_BLOCK_BYTES, (rep, chunks, kind)
    # the cell's: under 11 MiB of double-buffered blocks a grid step
    assert gated_delta.prepare_vmem_bytes(
        2, 16, 64, 128, 128, 2, "bwd") < 11 * 2**20
    assert (gated_delta._PREPARE_BLOCK_BYTES
            < gated_delta._PREPARE_VMEM_LIMIT)
