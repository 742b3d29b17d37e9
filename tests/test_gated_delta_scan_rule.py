"""``gated_delta_rule`` through ``gdn_scan_fwd`` / ``gdn_scan_bwd``
(``ops/gated_delta.py``, ISSUE 34) in interpret mode on the CPU, against
the ``lax.scan`` path and the per-token recurrence: values and all five
gradients over one and several segments, grid steps and lengths that
the chunk or the segment does not divide. The kernels alone are
``test_gated_delta_scan.py``'s. Key and value widths of 128: the
kernels take whole lane rows; an interpreted kernel costs by what its
body unrolls (heads x chunks a grid step) and by the trace, so a case
has the chunks, segments and heads its comment names and no more."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gated_delta
from elasticdl_tpu.ops.gated_delta import (
    gated_delta_recurrence,
    gated_delta_rule,
)
from tests.gdn_common import _force_pallas, _inputs, _value_and_grads


@functools.lru_cache(maxsize=None)
def _references(seq, chunk, segment, hk, hv):
    """(the inputs, the rule by its ``lax.scan`` path, the per-token
    recurrence), once a shape: ``prep=pallas`` and ``prep=xla`` read the
    same two. Called before a test patches anything."""
    args = _inputs(seq, jnp.float32, decay=2.0, batch=1, hk=hk, hv=hv,
                   dim=128)
    assert jax.default_backend() == "cpu"
    rule = lambda *a: gated_delta_rule(*a, chunk=chunk, segment=segment)
    return (args, _value_and_grads(rule, args),
            _value_and_grads(gated_delta_recurrence, args))


@pytest.mark.parametrize("seq,chunk,segment,hk,hv", [
    # one segment of six chunks: two grid steps of three, the state
    # carried in VMEM from the first to the second (``_SCAN_CHUNKS`` is
    # 4: six is the least that makes two steps of more than a chunk)
    (384, 64, 128, 1, 1),
    (256, 64, 1, 2, 2),     # four segments, the state carried between
    # a segment of two chunks, and a length neither it nor the chunk
    # divides: one whole segment and a padded one, two value heads a
    # key head
    (200, 64, 2, 1, 2),
    (256, 128, 1, 1, 1),
    (200, 128, 128, 2, 2),  # one segment, the chunk does not divide
], ids=["one-segment-two-grid-steps", "256-64-seg1",
        "the-segment-does-not-divide", "256-128-seg1", "200-128"])
@pytest.mark.parametrize("prep", ["pallas", "xla"])
def test_the_rule_by_the_scan_s_kernels(monkeypatch, seq, chunk, segment,
                                        hk, hv, prep):
    """``gated_delta_rule`` by the kernels against the ``lax.scan`` path
    and against the per-token recurrence, float32: values and all five
    gradients, over one and several segments and lengths that the chunk
    or the segment does not divide. ``prep=pallas``: what a TPU chooses,
    the operands' and the scan's kernels under one VJP; ``prep=xla``:
    the scan's kernels after ``_chunk_operands``, the inverses by the
    product form in it. Padded tokens write nothing: the
    cut output and the gradients are the unpadded recurrence's."""
    args, by_xla, by_token = _references(seq, chunk, segment, hk, hv)
    rule = lambda *a: gated_delta_rule(*a, chunk=chunk, segment=segment)
    _force_pallas(monkeypatch)
    if prep == "xla":
        monkeypatch.setattr(
            gated_delta, "prepare_impl", lambda *a, **kw: "xla")
    text, got = _value_and_grads(rule, args, jaxpr=True)
    assert "gdn_scan_fwd" in text and "gdn_scan_bwd" in text
    for name in ("gdn_prepare_fwd", "gdn_prepare_bwd"):
        assert (name in text) == (prep == "pallas")
    assert "gdn_inverse" not in text
    for a, b, c in zip(got, by_xla, by_token):
        assert a.shape == c.shape and a.dtype == c.dtype
        scale = 1e-3 + float(jnp.abs(c).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * scale)
        np.testing.assert_allclose(a, c, rtol=0, atol=1e-4 * scale)
