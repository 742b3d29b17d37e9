"""The gated delta rule in chunked form (``ops/gated_delta.py``, ISSUE
31) against the per-token recurrence, and ``GatedDeltaNet`` against its
equations, at small sizes on the CPU with seeded inputs. Float64 where
the comparison is exact (the chunked form reorders sums, nothing else),
bfloat16 where the precision rules are the subject."""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.models.transformer import GatedDeltaDims, GatedDeltaNet
from elasticdl_tpu.ops import gated_delta
from elasticdl_tpu.ops.gated_delta import (
    gated_delta_recurrence,
    gated_delta_rule,
    unit_lower_inverse,
)


@pytest.fixture
def x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def _inputs(seq, dtype, decay=1.0, seed=0, batch=2, hk=2, hv=4, dim=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (batch, hk, seq, dim))) * dim ** -0.5
    k = unit(jax.random.normal(keys[1], (batch, hk, seq, dim)))
    v = jax.random.normal(keys[2], (batch, hv, seq, dim))
    g = -decay * jax.random.uniform(keys[3], (batch, hv, seq))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, hv, seq)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (
        g.astype(jnp.promote_types(dtype, jnp.float32)),
        beta.astype(jnp.promote_types(dtype, jnp.float32)))


def _value_and_grads(rule, args):
    loss = lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2)
    return (rule(*args),) + jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("decay", [1e-3, 1.0, 30.0],
                         ids=["g-near-0", "g-1", "g-strongly-negative"])
@pytest.mark.parametrize("seq,chunk,segment", [
    (256, 64, 128),   # whole chunks, one segment
    (200, 64, 128),   # the chunk does not divide the length
    (130, 32, 128),
    (256, 16, 4),     # four segments of four chunks, each rematerialised
    (200, 16, 4),     # and a length the segment does not divide
], ids=["256-64", "200-64", "130-32", "256-16-seg4", "200-16-seg4"])
def test_chunked_rule_is_the_recurrence(x64, seq, chunk, segment, decay):
    """Values and all five gradients, in float64: equal to rounding."""
    args = _inputs(seq, jnp.float64, decay)
    got = _value_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk, segment=segment), args)
    want = _value_and_grads(gated_delta_recurrence, args)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-11 * (
            1 + float(jnp.abs(b).max())))


def test_strongly_negative_decay_is_finite_in_float32():
    """exp of every decay difference is taken of a number <= 0: a state
    that decays by e^-50 a token underflows to 0 and never to inf or
    nan, in the values and in the gradients."""
    args = _inputs(128, jnp.float32, decay=50.0)
    for out in _value_and_grads(
            lambda *a: gated_delta_rule(*a, chunk=64), args):
        assert bool(jnp.isfinite(out).all())


def test_key_heads_are_shared_and_never_repeated():
    """Value head h reads key head h // 2; the rule's jaxpr holds no
    array with q's or k's lanes at the value heads' count but the
    decayed copies the algorithm itself needs."""
    args = _inputs(64, jnp.float32)
    q, k, v, g, beta = args
    rep = lambda x: jnp.repeat(x, 2, axis=1)
    np.testing.assert_allclose(
        gated_delta_rule(*args, chunk=16),
        gated_delta_rule(rep(q), rep(k), v, g, beta, chunk=16),
        atol=1e-5)
    with pytest.raises(ValueError, match="do not divide"):
        gated_delta_rule(q, k, v[:, :3], g[:, :3], beta[:, :3])


@pytest.mark.parametrize("size", [2, 16, 64])
def test_the_inverse_by_its_product_form(x64, size):
    rng = np.random.RandomState(size)
    a = jnp.asarray(np.tril(0.2 * rng.randn(3, size, size), -1))
    inverse = unit_lower_inverse(a)
    eye = np.eye(size)
    np.testing.assert_allclose(
        inverse @ (eye + a), np.broadcast_to(eye, a.shape), atol=1e-9)
    # its own VJP against autodiff of a solve
    weight = jnp.asarray(rng.randn(3, size, size))
    got = jax.grad(lambda a: jnp.sum(unit_lower_inverse(a) * weight))(a)
    want = jax.grad(
        lambda a: jnp.sum(jnp.linalg.inv(eye + a) * weight))(a)
    np.testing.assert_allclose(got, want, atol=1e-8)


def test_the_inverse_takes_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        unit_lower_inverse(jnp.zeros((48, 48)))


# ------------------------------------------- the inverse's kernels
# ``gdn_inverse_fwd`` / ``gdn_inverse_bwd`` in interpret mode on the CPU
# (float32; ISSUE 32). What the chip's compiler makes of them is
# tests/test_flash_tpu_compile.py's.


def _lower(kind, count, size, seed=0):
    """``count`` strictly lower matrices with entries of the size the
    cell has: ``0.2 x randn``, or ``beta K K^T`` from l2-normalised
    keys."""
    rng = np.random.RandomState(seed + size)
    if kind == "randn":
        a = 0.2 * rng.randn(count, size, size)
    else:
        k = rng.randn(count, size, 32)
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        beta = 1 / (1 + np.exp(-rng.randn(count, size, 1)))
        a = beta * np.einsum("mik,mjk->mij", k, k)
    return jnp.asarray(np.tril(a, -1), jnp.float32)


@pytest.mark.parametrize("kind", ["randn", "keys"])
@pytest.mark.parametrize("size,count", [
    (64, 32),    # two whole loop iterations of eight pairs
    (64, 21),    # a count neither the block nor a pair divides
    (128, 8),
    (128, 3),
], ids=["64-whole", "64-ragged", "128-whole", "128-ragged"])
def test_the_kernel_s_inverse_is_the_product_form_s(kind, size, count):
    a = _lower(kind, count, size)
    got = gated_delta.gdn_inverse_fwd(a, interpret=True)
    assert got.shape == a.shape and got.dtype == jnp.float32
    want = gated_delta._inverse_product(a)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * (
        1 + float(jnp.abs(want).max())))
    eye = np.eye(size)
    np.testing.assert_allclose(
        np.float64(got) @ (eye + np.float64(a)),
        np.broadcast_to(eye, a.shape), atol=1e-5)


@pytest.mark.parametrize("size,count", [(64, 21), (128, 3)],
                         ids=["64", "128"])
def test_the_kernel_s_vjp(monkeypatch, size, count):
    """Against today's ``-T^T dT T^T`` and against autodiff of
    ``jnp.linalg.inv``."""
    a = _lower("randn", count, size)
    rng = np.random.RandomState(size)
    weight = jnp.asarray(rng.randn(count, size, size), jnp.float32)
    inverse = gated_delta._inverse_product(a)
    got = gated_delta.gdn_inverse_bwd(inverse, weight, interpret=True)
    want, = gated_delta._inverse_vjp_bwd("xla", inverse, weight)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-6 * scale)
    # through the custom_vjp, the kernels chosen as a TPU would
    _force_pallas(monkeypatch)
    eye = jnp.eye(size)
    got = jax.grad(lambda a: jnp.sum(unit_lower_inverse(a) * weight))(a)
    solve = jax.grad(
        lambda a: jnp.sum(jnp.linalg.inv(eye + a) * weight))(a)
    np.testing.assert_allclose(got, solve, rtol=0, atol=1e-5 * scale)


def _force_pallas(monkeypatch):
    """What a TPU backend would choose, run by the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("gdn_inverse_fwd", "gdn_inverse_bwd", "gdn_scan_fwd",
                 "gdn_scan_bwd", "gdn_prepare_fwd", "gdn_prepare_bwd"):
        monkeypatch.setattr(gated_delta, name, functools.partial(
            getattr(gated_delta, name), interpret=True))


def test_the_rule_s_gradients_are_equal_between_the_two_paths(monkeypatch):
    """``jax.grad`` of the rule at 512 tokens, chunk 64, float32: the
    kernels' path against XLA's, to float32 rounding."""
    args = _inputs(512, jnp.float32, decay=2.0, batch=1)
    rule = lambda *a: gated_delta_rule(*a, chunk=64)
    assert gated_delta.inverse_impl(jnp.float32, 64) == "xla"
    want = _value_and_grads(rule, args)
    _force_pallas(monkeypatch)
    assert gated_delta.inverse_impl(jnp.float32, 64) == "pallas"
    assert "gdn_inverse_fwd" in str(jax.make_jaxpr(rule)(*args))
    got = _value_and_grads(rule, args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * (
            1e-3 + float(jnp.abs(b).max())))


@pytest.mark.parametrize("backend,dtype,size,devices,impl", [
    ("cpu", "float32", 64, 1, "xla"),
    ("tpu", "float64", 64, 1, "xla"),
    ("tpu", "bfloat16", 64, 1, "xla"),
    ("tpu", "float32", 16, 1, "xla"),
    ("tpu", "float32", 2, 1, "xla"),
    ("tpu", "float32", 64, 1, "pallas"),
    ("tpu", "float32", 128, 1, "pallas"),
    # a pallas_call has no partitioning rule: on a mesh of several
    # devices the product form stays what GSPMD can partition
    ("tpu", "float32", 64, 4, "xla"),
], ids=lambda v: str(v))
def test_the_choice_of_path(
        monkeypatch, caplog, backend, dtype, size, devices, impl):
    """From the backend, the matrices' dtype, the chunk and the mesh
    alone; the rule's line says which (its matrices are float32
    whatever it computes in, bfloat16 here as in the cell)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("data",))
    assert gated_delta.inverse_impl(jnp.dtype(dtype), size, mesh) == impl
    if devices == 1:
        assert gated_delta.inverse_impl(jnp.dtype(dtype), size) == impl
    dims = GatedDeltaDims(
        num_key_heads=1, num_value_heads=2, key_head_dim=8,
        value_head_dim=8, conv_kernel_dim=4, chunk=size)
    layer = GatedDeltaNet(dims, mesh=mesh)
    x = jax.ShapeDtypeStruct((1, 2 * size, 16), jnp.bfloat16)
    gated_delta._log_once.cache_clear()
    with caplog.at_level(logging.INFO):
        jax.eval_shape(
            lambda x: layer.init_with_output(jax.random.PRNGKey(0), x)[0], x)
    gated_delta._log_once.cache_clear()
    assert "chunk=%d impl=%s scan=xla prep=xla (tokens=%d)" % (
        size, gated_delta.inverse_impl(jnp.float32, size, mesh), 2 * size
    ) in caplog.text


def test_the_kernels_run_inside_a_region_manual_over_the_mesh(monkeypatch):
    """Where the caller has already opened a ``shard_map`` over the
    whole mesh (the pipeline's stage body) the matrices are one shard,
    and the kernels take them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    seen = []

    def shard(x):
        seen.append(gated_delta.inverse_impl(jnp.float32, 64, mesh))
        return x

    jax.eval_shape(jax_compat.shard_map(
        shard, mesh=mesh, in_specs=P("data"), out_specs=P("data")),
        jnp.zeros(4))
    assert seen == ["pallas"]
    assert gated_delta.inverse_impl(jnp.float32, 64, mesh) == "xla"


def test_the_block_fits_its_budget():
    """The matrices a grid step takes, from shapes: whole loop
    iterations, every double-buffered block inside the budget, the
    budget inside the limit the kernels state."""
    assert gated_delta.inverse_block(4096, 64, 2) == 64
    assert gated_delta.inverse_block(4096, 64, 3) == 32
    assert gated_delta.inverse_block(4096, 128, 2) == 32
    assert gated_delta.inverse_block(4096, 128, 3) == 16
    assert gated_delta.inverse_block(3, 64, 2) == 16
    assert gated_delta.inverse_block(3, 128, 3) == 8
    for size in (64, 128):
        for arrays in (2, 3):
            block = gated_delta.inverse_block(4096, size, arrays)
            assert gated_delta.inverse_vmem_bytes(
                block, size, arrays) <= gated_delta._INVERSE_BLOCK_BYTES
    assert (gated_delta._INVERSE_BLOCK_BYTES
            < gated_delta._INVERSE_VMEM_LIMIT)


# ---------------------------------------------- the scan's kernels
# ``gdn_scan_fwd`` / ``gdn_scan_bwd`` in interpret mode on the CPU
# (ISSUE 34): the chunk-to-chunk recurrence with the state in VMEM
# against the ``lax.scan`` over the same operands, and the rule by them
# against the per-token loop. Key and value widths of 128: the kernels
# take whole lane rows.


def _split_inputs(num, chunk, rep, dtype, decay=2.0, hk=2, seed=0):
    """q, k, v, g, beta of ``num`` chunks as ``gated_delta_rule`` splits
    them for a segment: key-like (1, Hk, 1, N, C, 128), value-like (1,
    Hk, R, N, C, ...)."""
    q, k, v, g, beta = _inputs(
        num * chunk, dtype, decay=decay, seed=seed, batch=1, hk=hk,
        hv=hk * rep, dim=128)
    split = lambda x, heads, *rest: x.reshape(
        (1,) + heads + (num, chunk) + rest)
    return (split(q, (hk, 1), 128), split(k, (hk, 1), 128),
            split(v, (hk, rep), 128), split(g, (hk, rep)),
            split(beta, (hk, rep)))


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


_MESH4 = "a four-device mesh"
_MANUAL = "a region manual over it"


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


# ------------------------------------------- the operands' kernels
# ``gdn_prepare_fwd`` / ``gdn_prepare_bwd`` in interpret mode on the CPU
# (ISSUE 39): everything of the rule that does not meet the state, a
# block of a key head's chunks in VMEM, against ``_chunk_operands`` and
# autodiff of it.


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


def test_precision_rules_in_bfloat16():
    """bfloat16 operands, float32 decay, inverse and state: the rule
    stays within bfloat16's rounding of the float32 recurrence. The
    decay CUMULATED in bfloat16 is several times worse (the benchmark's
    check has to tell the two apart, PERF.md Section 6). The state
    CARRIED in bfloat16 is not: it is rounded to bfloat16 wherever it is
    a matmul operand, so a second rounding of the carry changes
    little."""
    args = _inputs(512, jnp.float32, decay=2.0, batch=1)
    want = gated_delta_recurrence(*args)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    err = lambda out: float(
        jnp.sqrt(jnp.mean((out.astype(jnp.float32) - want) ** 2)
                 / jnp.mean(want ** 2)))
    stated = err(gated_delta_rule(*low, chunk=64))
    assert stated < 0.01
    assert err(gated_delta_rule(
        *low, chunk=64, decay_dtype=jnp.bfloat16)) > 4 * stated
    carried = err(gated_delta_rule(
        *low, chunk=64, state_dtype=jnp.bfloat16))
    assert 0.9 * stated < carried < 1.5 * stated
    assert gated_delta_rule(*low, chunk=64).dtype == jnp.bfloat16


def test_the_output_is_named_for_remat_policies():
    args = _inputs(64, jnp.float32)
    text = str(jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, chunk=16))(*args))
    assert "name=%s" % gated_delta.GDN_OUT_NAME in text


# ---------------------------------------------------------- the module

DIMS = GatedDeltaDims(
    num_key_heads=2, num_value_heads=4, key_head_dim=16, value_head_dim=8,
    conv_kernel_dim=4, chunk=16)


def _by_the_equations(x, p, dims):
    """ISSUE 31's equations for one sequence, one token a step, numpy
    float64."""
    hk, hv = dims.num_key_heads, dims.num_value_heads
    dk, dv = dims.key_head_dim, dims.value_head_dim
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    seq = x.shape[0]
    silu = lambda t: t / (1 + np.exp(-t))
    qkvz = x @ p["in_proj_qkvz"]["kernel"]
    ba = x @ p["in_proj_ba"]["kernel"]
    conv_dim = 2 * hk * dk + hv * dv
    taps = p["conv_kernel"]
    padded = np.concatenate(
        [np.zeros((len(taps) - 1, conv_dim)), qkvz[:, :conv_dim]])
    qkv = silu(sum(taps[j] * padded[j:j + seq] for j in range(len(taps))))
    beta = 1 / (1 + np.exp(-ba[:, :hv]))
    g = -np.exp(p["A_log"]) * np.log1p(np.exp(ba[:, hv:] + p["dt_bias"]))
    q = qkv[:, :hk * dk].reshape(seq, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(seq, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(seq, hv, dv)
    z = qkvz[:, conv_dim:].reshape(seq, hv, dv)
    l2 = lambda t: t / np.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q, k = l2(q) * dk ** -0.5, l2(k)
    out = np.zeros((seq, hv, dv))
    for h in range(hv):
        state = np.zeros((dk, dv))
        for t in range(seq):
            state = np.exp(g[t, h]) * state
            u = beta[t, h] * (v[t, h] - state.T @ k[t, h // (hv // hk)])
            state = state + np.outer(k[t, h // (hv // hk)], u)
            out[t, h] = state.T @ q[t, h // (hv // hk)]
    out = out / np.sqrt((out * out).mean(-1, keepdims=True) + 1e-6)
    out = out * p["out_norm"]["scale"] * silu(z)
    return np.einsum("shv,hvd->sd", out, p["out_proj"]["kernel"])


def test_gated_delta_net_against_its_equations():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    layer = GatedDeltaNet(DIMS)
    variables = layer.init(jax.random.PRNGKey(2), x)
    params = variables["params"]
    assert {k: v.shape for k, v in params.items() if hasattr(v, "shape")} == {
        "conv_kernel": (4, 96), "A_log": (4,), "dt_bias": (4,)}
    assert params["in_proj_qkvz"]["kernel"].shape == (32, 128)
    assert params["in_proj_ba"]["kernel"].shape == (32, 8)
    assert params["out_proj"]["kernel"].shape == (4, 8, 32)
    # as the published code initialises them
    assert bool((params["dt_bias"] == 1).all())
    assert bool((params["out_norm"]["scale"] == 1).all())
    assert bool((jnp.exp(params["A_log"]) < 16).all())
    got = layer.apply(variables, x)
    for row in range(2):
        np.testing.assert_allclose(
            got[row], _by_the_equations(
                np.asarray(x[row], np.float64), params, DIMS),
            atol=2e-5)


def test_the_convolution_is_causal():
    """Token t's output depends on tokens <= t alone (the conv pads on
    the left, the rule is a recurrence)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 32))
    layer = GatedDeltaNet(DIMS)
    variables = layer.init(jax.random.PRNGKey(4), x)
    base = layer.apply(variables, x)
    moved = layer.apply(variables, x.at[:, 12:].add(1.0))
    np.testing.assert_allclose(base[:, :12], moved[:, :12], atol=1e-6)
    assert float(jnp.abs(base[:, 12:] - moved[:, 12:]).max()) > 1e-3


def test_the_scopes_and_the_line(caplog):
    gated_delta._log_once.cache_clear()
    x = jnp.zeros((1, 32, 32))
    layer = GatedDeltaNet(DIMS)
    variables = layer.init(jax.random.PRNGKey(0), x)
    with caplog.at_level(logging.INFO):
        gated_delta._log_once.cache_clear()
        text = jax.jit(
            lambda v, x: jax.grad(
                lambda v: layer.apply(v, x).sum())(v)
        ).lower(variables, x).as_text(debug_info=True)
    for scope in ("in_proj", "conv", "gates", "scan", "out_norm",
                  "out_proj"):
        assert "gdn/%s" % scope in text, scope
    assert (
        "linear attention heads k=2 v=4 dim=16 chunk=16 impl=xla scan=xla "
        "prep=xla (tokens=32)" in caplog.text)
