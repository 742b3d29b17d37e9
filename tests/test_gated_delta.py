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
from tests.gdn_common import (  # noqa: F401
    _inputs,
    _program,
    _value_and_grads,
    x64,
)


# a shape's three decays are values: a program a shape for the rule, a
# program a length for the recurrence
_RECURRENCE = _program(gated_delta_recurrence)


@functools.lru_cache(maxsize=None)
def _rule(chunk, segment):
    return _program(
        lambda *a: gated_delta_rule(*a, chunk=chunk, segment=segment))


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
    got, want = _rule(chunk, segment)(*args), _RECURRENCE(*args)
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
    rule = jax.jit(lambda *a: gated_delta_rule(*a, chunk=16))
    np.testing.assert_allclose(
        rule(*args), rule(rep(q), rep(k), v, g, beta), atol=1e-5)
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


# ----------------------------------------------- the choice of path
# What runs the rule is chosen from the backend, the dtype, the shapes
# and the mesh; the rule's line says which. The kernels themselves are
# tests/test_gated_delta_scan*.py's and test_gated_delta_operands*.py's.


@pytest.mark.parametrize("backend,dtype,chunk,dim,rep,chunks,devices,paths", [
    ("cpu", "bfloat16", 64, 128, 2, 2, 1, "impl=xla scan=xla prep=xla"),
    ("tpu", "bfloat16", 64, 128, 2, 2, 1,
     "impl=pallas scan=pallas prep=pallas"),
    ("tpu", "float32", 128, 128, 2, 2, 1,
     "impl=pallas scan=pallas prep=pallas"),
    ("tpu", "float16", 64, 128, 2, 2, 1, "impl=xla scan=xla prep=xla"),
    ("tpu", "bfloat16", 16, 128, 2, 2, 1, "impl=xla scan=xla prep=xla"),
    # heads that are no whole 128-lane rows
    ("tpu", "bfloat16", 64, 8, 2, 2, 1, "impl=xla scan=xla prep=xla"),
    # a segment whose chunks fit no block of the operands' kernels: the
    # operands by XLA, the inverses among them, the state by the scan's
    ("tpu", "bfloat16", 64, 256, 4, 8, 1, "impl=xla scan=pallas prep=xla"),
    # a pallas_call has no partitioning rule: on a mesh of several
    # devices the rule stays what GSPMD can partition
    ("tpu", "bfloat16", 64, 128, 2, 2, 4, "impl=xla scan=xla prep=xla"),
], ids=lambda v: str(v).replace(" ", "-"))
def test_the_choice_of_path(
        monkeypatch, caplog, backend, dtype, chunk, dim, rep, chunks,
        devices, paths):
    """From the backend, the dtype, the chunk, the widths, the segment
    and the mesh alone; ``impl=`` says what runs the inverses, which is
    what ``prep=`` says. Shapes alone are read: nothing runs."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("data",))
    tokens = chunks * chunk
    struct = lambda heads, *rest: jax.ShapeDtypeStruct(
        (1, heads, tokens) + rest, jnp.dtype(dtype))
    wide = lambda: jax.ShapeDtypeStruct((1, rep, tokens), jnp.float32)
    gated_delta._log_once.cache_clear()
    with caplog.at_level(logging.INFO):
        out = jax.eval_shape(
            lambda *a: gated_delta_rule(*a, chunk=chunk, mesh=mesh),
            struct(1, dim), struct(1, dim), struct(rep, dim), wide(), wide())
    gated_delta._log_once.cache_clear()
    assert out.shape == (1, rep, tokens, dim)
    assert "dim=%d chunk=%d %s (tokens=%d)" % (
        dim, chunk, paths, tokens) in caplog.text


def test_the_kernels_run_inside_a_region_manual_over_the_mesh(monkeypatch):
    """Where the caller has already opened a ``shard_map`` over the
    whole mesh (the pipeline's stage body) the arrays are one shard,
    and the kernels take them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    choice = lambda: (
        jax_compat.kernels_can_run(mesh),
        gated_delta.scan_impl(jnp.bfloat16, 64, 128, 128, mesh=mesh))
    seen = []

    def shard(x):
        seen.append(choice())
        return x

    jax.eval_shape(jax_compat.shard_map(
        shard, mesh=mesh, in_specs=P("data"), out_specs=P("data")),
        jnp.zeros(4))
    assert seen == [(True, "pallas")]
    assert choice() == (False, "xla")


def test_precision_rules_in_bfloat16():
    """bfloat16 operands, float32 decay, inverse and state: the rule
    stays within bfloat16's rounding of the float32 recurrence. The
    decay CUMULATED in bfloat16 is several times worse (the benchmark's
    check has to tell the two apart, PERF.md Section 6). The state
    CARRIED in bfloat16 is not: it is rounded to bfloat16 wherever it is
    a matmul operand, so a second rounding of the carry changes
    little."""
    args = _inputs(512, jnp.float32, decay=2.0, batch=1)
    want = jax.jit(gated_delta_recurrence)(*args)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    err = lambda out: float(
        jnp.sqrt(jnp.mean((out.astype(jnp.float32) - want) ** 2)
                 / jnp.mean(want ** 2)))
    rule = lambda **kw: jax.jit(
        lambda *a: gated_delta_rule(*a, chunk=64, **kw))(*low)
    out = rule()
    stated = err(out)
    assert stated < 0.01
    assert err(rule(decay_dtype=jnp.bfloat16)) > 4 * stated
    carried = err(rule(state_dtype=jnp.bfloat16))
    assert 0.9 * stated < carried < 1.5 * stated
    assert out.dtype == jnp.bfloat16


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
    variables = jax.jit(layer.init)(jax.random.PRNGKey(2), x)
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
    got = jax.jit(layer.apply)(variables, x)
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
    variables = jax.jit(layer.init)(jax.random.PRNGKey(4), x)
    apply = jax.jit(layer.apply)
    base = apply(variables, x)
    moved = apply(variables, x.at[:, 12:].add(1.0))
    np.testing.assert_allclose(base[:, :12], moved[:, :12], atol=1e-6)
    assert float(jnp.abs(base[:, 12:] - moved[:, 12:]).max()) > 1e-3


def test_the_scopes_and_the_line(caplog):
    gated_delta._log_once.cache_clear()
    x = jnp.zeros((1, 32, 32))
    layer = GatedDeltaNet(DIMS)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
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
