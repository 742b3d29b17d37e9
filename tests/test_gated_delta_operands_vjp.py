"""``gdn_prepare_bwd`` (``ops/gated_delta.py``, ISSUE 39) in interpret
mode on the CPU against autodiff of ``_chunk_operands``; where
``prepare_impl`` chooses the kernels; what a grid step takes. The
forward kernel alone is ``test_gated_delta_operands.py``'s, the rule
with ``prep=pallas`` in the cell's dtypes
``test_gated_delta_bfloat16.py``'s."""

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
    _split_inputs,
    _xla_lines,
    x64,
)


@jax.jit
def _both_vjps(args, cotangents, low):
    """(the kernels' gradients, autodiff's of the XLA lines): one
    program a shape and dtype, so cases that differ in values alone
    lower the two interpreted kernels once."""
    want = jax.vjp(_xla_lines, *args)[1](cotangents)
    *_, inverse = gated_delta.gdn_prepare_fwd(
        *args, residuals=True, interpret=True)
    return gated_delta.gdn_prepare_bwd(
        *args, inverse, *low, interpret=True), want


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
    primal = _xla_lines(*args)
    keys = jax.random.split(jax.random.PRNGKey(7), len(primal))
    cotangents = [
        jax.random.normal(key, x.shape).astype(x.dtype)
        for key, x in zip(keys, primal)]
    # du arrives in the compute dtype, as ``gdn_scan_bwd`` hands it on
    low = cotangents[:-1] + [cotangents[-1].astype(dtype)]
    cotangents[-1] = low[-1].astype(jnp.float32)
    got, want = _both_vjps(args, tuple(cotangents), tuple(low))
    exact = dtype == jnp.float32
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.float32(a), np.float32(b)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(
            a, b, rtol=0, atol=(3e-5 if exact else 2e-2) * np.abs(b).max(),
            err_msg=name)


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
    """The chooser beside ``scan_impl``, from
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
