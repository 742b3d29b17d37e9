"""The gated delta rule's kernels in the cell's dtypes (bfloat16
operands, float32 state and decay), interpreted on the CPU: the scan's
kernels give the ``lax.scan``'s output bit for bit, and with the
operands' kernels too the gradients stay as close to the float32
recurrence's as XLA's lines'. The two tests came from
``test_gated_delta_scan_rule.py`` and
``test_gated_delta_operands_vjp.py``, which hold the float32 cases:
each file summed past the rule's 100 s (``ROADMAP.md`` Queue 3 item
12)."""

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.ops import gated_delta
from elasticdl_tpu.ops.gated_delta import (
    gated_delta_recurrence,
    gated_delta_rule,
)
from tests.gdn_common import _force_pallas, _inputs, _value_and_grads


def test_the_scan_s_kernels_hold_bfloat16_s_rounding(monkeypatch):
    """The cell's dtypes: bfloat16 operands, float32 state and decay.
    The kernels' output is the ``lax.scan``'s bit for bit, and their
    gradients stay as close to the float32 recurrence's as its own."""
    # two segments of two chunks, two value heads to the key head
    args = _inputs(256, jnp.float32, decay=2.0, batch=1, hk=1, hv=2,
                   dim=128)
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
    # the call alone keeps no residuals: other kernels, the same bits
    np.testing.assert_array_equal(
        np.float32(jax.jit(rule)(*low)), np.float32(got[0]))
    err = lambda a, b: float(jnp.sqrt(
        jnp.mean((a.astype(jnp.float32) - b) ** 2) / jnp.mean(b ** 2)))
    for a, b, c in zip(got[1:], by_xla[1:], want[1:]):
        assert err(a, c) < 1.25 * err(b, c) + 1e-4


def test_the_operands_kernels_hold_bfloat16_s_rounding(monkeypatch):
    """The cell's dtypes over two segments and a padded length: the
    rule's output and gradients with ``prep=pallas`` stay as close to
    the float32 recurrence's as ``prep=xla``'s."""
    # 200 tokens: a whole segment of two chunks and a padded one; two
    # value heads to the one key head
    args = _inputs(200, jnp.float32, decay=2.0, batch=1, hk=1, hv=2,
                   dim=128)
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
