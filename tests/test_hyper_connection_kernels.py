"""The hyper-connection's Pallas kernels (PR 38,
``ops/hyper_connection.py``) in interpret mode on the CPU against the
module's own lines: the COMPOSED sublayer around a sublayer F that is
not trivial (the pair ``pre`` / ``post`` is a derivative only together),
``mix_impl``'s table, the log line that says which path a program got,
and that X's cotangent is written once. What interpret mode cannot see
(the chip's tiling and VMEM) is ``scripts/mhc_coef.py``'s on the chip.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.ops import hyper_connection as H

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM = 128
KERNELS = ("mhc_pre_fwd", "mhc_post_fwd", "mhc_post_bwd", "mhc_pre_bwd")
GRADIENTS = ("p_pre", "p_post", "p_res", "a_pre", "a_post", "a_res",
             "b_pre", "b_post", "b_res")


def force_pallas(monkeypatch):
    """What a TPU backend would choose, run by the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in KERNELS:
        monkeypatch.setattr(H, name, functools.partial(
            getattr(H, name), interpret=True))


@pytest.fixture(scope="module")
def moved():
    """``check.py``'s own move of a hyper-connection's gates and biases
    to where a trained run's would be."""
    from benchmark.lib.refcheck import load_by_path

    check = load_by_path("xing_check_for_kernels", os.path.join(
        REPO, "benchmark", "configs", "xing4.0-29b-a4b-1chip", "check.py"))
    return lambda params: check.trained_hyper_connections(
        {"hc": dict(params)}, jax.random.PRNGKey(7))["hc"]


@functools.lru_cache(maxsize=None)
def sublayer(n, dtype, seq):
    """(f, its arguments): value, ``H_res`` and facts, and through
    ``jax.grad`` every gradient, of ``sum(target . X')`` for the
    hyper-connected sublayer ``X' = write(F(u))`` with ``F(u) = tanh(u
    W)``. Made once a shape: the cases differ in where the gates are."""
    x = jax.random.normal(
        jax.random.PRNGKey(1), (2, n, seq, DIM), jnp.float32).astype(dtype)
    module = T.HyperConnection(T.HyperDims(n), select=1)
    params = jax.jit(module.init)(jax.random.PRNGKey(0), x)["params"]
    w = jax.random.normal(
        jax.random.PRNGKey(5), (DIM, DIM), jnp.float32) / DIM ** 0.5
    target = jax.random.normal(jax.random.PRNGKey(6), x.shape, jnp.float32)

    def f(params, x, w):
        (u, write, facts), sown = module.apply(
            {"params": params}, x, mutable=["intermediates"])
        y = jnp.tanh(u.astype(jnp.float32) @ w).astype(dtype)
        out = write(y)
        return jnp.sum(out.astype(jnp.float32) * target), (
            out, u, sown["intermediates"]["h_res"][0], facts)

    return f, (params, x, w)


@functools.lru_cache(maxsize=None)
def both_ways(n, dtype, seq):
    """The sublayer's value and gradients as two programs of their own:
    a case calls (and so traces) the first before ``force_pallas`` and
    the second after it, and the case with the gates moved runs at the
    same shapes what the first one compiled."""
    f, _ = sublayer(n, dtype, seq)
    program = lambda: jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))
    return program(), program()


def worst(got, want):
    """The largest difference over the largest wanted magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


@pytest.mark.parametrize("gates", ["initial", "moved"])
@pytest.mark.parametrize("seq", [128, 384], ids=["one-tile", "three-tiles"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n", [2, 4])
def test_the_composed_sublayer_is_the_module_s_lines(
        monkeypatch, moved, n, dtype, seq, gates):
    _, (params, x, w) = sublayer(n, dtype, seq)
    if gates == "moved":
        params = moved(params)
    by_xla, by_pallas = both_ways(n, dtype, seq)
    assert H.mix_impl(dtype, n, DIM, seq) == "xla"
    (_, (want_out, want_u, want_res, want_facts)), want_grads = by_xla(
        params, x, w)
    force_pallas(monkeypatch)
    assert H.mix_impl(dtype, n, DIM, seq) == "pallas"
    # (the value is a sum of cancelling terms: ``out`` is its check)
    (_, (out, u, h_res, facts)), grads = by_pallas(params, x, w)
    # float32: summation order; bfloat16: a last bit of the streams'
    # dtype where a sum rounds the other way
    tight, loose = (2e-5, 2e-5) if dtype == jnp.float32 else (1e-5, 2e-2)
    assert h_res.shape == (n, n, 2, seq)
    assert worst(h_res, want_res) < tight
    for name in ("row_err", "diag_mean"):
        assert float(facts[name]) == pytest.approx(
            float(want_facts[name]), abs=1e-5)
    assert worst(u, want_u) < loose
    assert worst(out, want_out) < loose
    assert worst(grads[1], want_grads[1]) < loose, "dX"
    assert worst(grads[2], want_grads[2]) < loose, "dW (through du and y)"
    for name in GRADIENTS:
        assert worst(grads[0][name], want_grads[0][name]) < loose, name


def test_h_res_may_be_differentiated_itself(monkeypatch):
    """The coefficients ``pre`` returns are outputs like any other: a
    cotangent that reaches them from outside ``post`` joins the one the
    kernels compute."""
    f, args = sublayer(4, jnp.float32, 128)
    weight = jax.random.normal(jax.random.PRNGKey(8), (4, 4, 2, 128))

    def g(params, x, w):
        value, (_, _, h_res, _) = f(params, x, w)
        return value + jnp.sum(weight * h_res)

    want = jax.jit(jax.grad(g, argnums=(0, 1)))(*args)
    force_pallas(monkeypatch)
    got = jax.jit(jax.grad(g, argnums=(0, 1)))(*args)
    assert worst(got[1], want[1]) < 2e-5
    for name in GRADIENTS:
        assert worst(got[0][name], want[0][name]) < 2e-5, name


def test_u_alone_is_a_derivative_too(monkeypatch):
    """Nothing written: ``dX'`` is zero and ``pre``'s backward is the
    gradient through ``u``."""
    f, (params, x, _) = sublayer(4, jnp.float32, 128)
    module = T.HyperConnection(T.HyperDims(4), select=1)
    read = lambda params, x: jnp.sum(
        module.apply({"params": params}, x)[0] ** 2)
    want = jax.jit(jax.grad(read, argnums=(0, 1)))(params, x)
    force_pallas(monkeypatch)
    got = jax.jit(jax.grad(read, argnums=(0, 1)))(params, x)
    assert worst(got[1], want[1]) < 2e-5
    for name in ("p_pre", "a_pre", "b_pre"):
        assert worst(got[0][name], want[0][name]) < 2e-5, name


def test_x_s_cotangent_is_written_once(monkeypatch):
    """The trap of two consumers: no full-size ``add_any`` in the
    sublayer's VJP, one ``mhc_pre_bwd`` writes dX, and the program holds
    each of the four kernels once."""
    force_pallas(monkeypatch)
    f, args = sublayer(4, jnp.bfloat16, 256)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: f(*a)[0], argnums=(0, 1)))(*args)
    full = args[1].shape

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for param in eqn.params.values():
                for inner in (param if isinstance(param, (list, tuple))
                              else [param]):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from equations(inner)

    eqns = list(equations(jaxpr.jaxpr))
    adds = [e for e in eqns if e.primitive.name in ("add_any", "add")
            and tuple(e.outvars[0].aval.shape) == full]
    assert not adds
    calls = [e.params["name"] for e in eqns
             if e.primitive.name == "pallas_call"]
    assert sorted(calls) == sorted(KERNELS)


TPU, CPU = "tpu", "cpu"


class FourDevices:
    size, axis_names = 4, ("dp", "fsdp", "sp", "tp")


class OneDevice:
    size, axis_names = 1, ("dp", "fsdp", "sp", "tp")


@pytest.mark.parametrize("backend,dtype,streams,dim,tokens,mesh,want", [
    (TPU, jnp.bfloat16, 4, 3584, 4096, None, "pallas"),   # the cell
    (TPU, jnp.float32, 4, 3584, 4096, None, "pallas"),
    (TPU, jnp.bfloat16, 2, 128, 128, None, "pallas"),
    (TPU, jnp.bfloat16, 4, 3584, 4096, OneDevice, "pallas"),
    (TPU, jnp.bfloat16, 1, 2048, 2048, None, "pallas"),
    (CPU, jnp.bfloat16, 4, 3584, 4096, None, "xla"),
    (TPU, jnp.float64, 4, 3584, 4096, None, "xla"),
    (TPU, jnp.float16, 4, 3584, 4096, None, "xla"),
    (TPU, jnp.bfloat16, 4, 3584 + 64, 4096, None, "xla"),  # half a lane row
    (TPU, jnp.bfloat16, 4, 16, 32, None, "xla"),           # the tests' size
    (TPU, jnp.bfloat16, 4, 3584, 4096 + 64, None, "xla"),  # half a tile
    (TPU, jnp.bfloat16, 4, 3584, 4096, FourDevices, "xla"),
    (TPU, jnp.bfloat16, 9, 1024, 4096, None, "xla"),       # over _MAX_STREAMS
    (TPU, jnp.bfloat16, 4, 16384, 4096, None, "xla"),      # over the VMEM
])
def test_mix_impl_chooses_from_what_it_sees(
        monkeypatch, backend, dtype, streams, dim, tokens, mesh, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert H.mix_impl(dtype, streams, dim, tokens, mesh) == want


def test_mix_impl_takes_a_region_manual_over_the_mesh(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    monkeypatch.setattr(H.jax_compat, "manual_over", lambda mesh: True)
    assert H.mix_impl(jnp.bfloat16, 4, 3584, 4096, FourDevices) == "pallas"


def test_the_kernels_names_are_what_the_trace_reader_charges():
    """``benchmark/lib/mhc_trace.py`` charges a Mosaic kernel named
    ``mhc...`` to the layer; the compile ledger lists a kernel by its
    jitted caller's name."""
    for name in KERNELS:
        assert name.startswith("mhc_")
        assert getattr(H, name).__name__ == name
