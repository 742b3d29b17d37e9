"""Shared by the gated delta rule's test files
(``test_gated_delta.py``: the rule, the inverse and the module;
``test_gated_delta_scan.py``: the scan's kernels;
``test_gated_delta_scan_rule.py``: the rule through them;
``test_gated_delta_operands.py``, ``test_gated_delta_operands_vjp.py``:
the operands' kernels; ``test_kda_rule.py``, ``test_kda_operands.py``:
a decay a channel): seeded
inputs, the rule's value and gradients, and how a test runs what a TPU
backend would choose under the interpreter."""

import functools

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.ops import gated_delta


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


def _program(rule):
    """``rule``'s output and the gradients of its five arguments as one
    program, not yet traced, from one forward pass, the differentiated
    one. A file whose cases differ in values alone keeps one a shape
    and calls it."""
    def loss(*a):
        out = rule(*a)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    def values(*a):
        grads, out = jax.grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*a)
        return (out,) + grads

    return jax.jit(values)


def _value_and_grads(rule, args, jaxpr=False):
    """``_program(rule)`` run on ``args`` (a new program a call:
    ``rule`` is traced under whatever the test has patched by then); an
    interpreted kernel costs by the trace, and what a kernel returns
    without residuals is its own file's comparison. With ``jaxpr``,
    (the traced program's text, the values): the kernels' names are
    read from the trace that runs."""
    traced = _program(rule).trace(*args)
    values = traced.lower().compile()(*args)
    return (str(traced.jaxpr), values) if jaxpr else values


def _force_pallas(monkeypatch):
    """What a TPU backend would choose, run by the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("gdn_scan_fwd", "gdn_scan_bwd", "gdn_prepare_fwd",
                 "gdn_prepare_bwd", "kda_prepare_fwd", "kda_prepare_bwd"):
        monkeypatch.setattr(gated_delta, name, functools.partial(
            getattr(gated_delta, name), interpret=True))


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


@jax.jit
def _xla_lines(q, k, v, g, beta):
    """What the scan's kernels are handed with ``prep=xla``:
    ``_chunk_operands`` and the casts and the broadcast of
    ``_scan_operands``. One program a shape, as the rule's own step
    runs them; dispatched an operation at a time they cost more than
    the kernel they are held against."""
    return gated_delta._scan_operands(*gated_delta._chunk_operands(
        q, k, v, g, beta, jnp.float32), q.dtype)


_MESH4 = "a four-device mesh"
_MANUAL = "a region manual over it"
