"""``Band``'s grid of runs (``ops/flash_attention.py``) in interpret
mode on the CPU, bit for bit against the walk over the whole rectangle
the band had before its grid was its own length (``RectangleBand``,
kept here alone), at ``tests/test_flash_band_kernels.py``'s cases. A
case costs by the kernels it compiles: two layouts, a schedule's
backward each, and the forward once for both schedules."""

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import flash_attention as F
from tests.kernel_common import BAND_KERNEL_CASES as KERNEL_CASES
from tests.test_mask_layouts import _qkv


@dataclasses.dataclass(frozen=True)
class RectangleBand:
    """The band as it walked before its grid was its own length (PR 42):
    the whole (q-block, k-block) rectangle, a step outside a row's
    (column's) run clamped to the run's nearer end. The kernels take it
    down the rectangle's path, ``Causal``'s and ``BlockDiffusion``'s;
    nothing but these tests builds one."""

    window: int

    def __str__(self):
        return "rectangle_window(%d)" % self.window

    def keep(self, q_pos, k_pos):
        return F.Band(self.window).keep(q_pos, k_pos)

    def pair(self, q_block, k_block, block_q, block_k):
        return F.Band(self.window).pair(q_block, k_block, block_q, block_k)

    def k_named(self, q_block, k_block, block_q, block_k):
        first, last = F.Band(self.window).run(q_block, block_q, block_k)
        return jnp.clip(k_block, first, last)

    def q_named(self, q_block, k_block, block_q, block_k, num_q):
        first, last = F.Band(self.window).run(
            k_block, block_q, block_k, k_outer=True)
        return jnp.minimum(jnp.clip(q_block, first, last), num_q - 1)

    def refusal(self, seq_q, seq_k, block_q, block_k):
        return ""


@functools.lru_cache(maxsize=None)
def _forward(layout, case):
    """(the inputs, o, lse) of the forward kernel in interpret mode:
    the same call under both backward schedules, so made once a case
    and a layout."""
    seq, _, heads, kv_heads, dim, block_q, block_k, dtype = case
    qkv_do = tuple(
        t.reshape((-1,) + t.shape[2:])
        for t in _qkv(seq, heads, kv_heads, dim, dtype))
    q, k, v, _ = qkv_do
    return qkv_do, F._fwd(
        q, k, v, dim ** -0.5, layout, block_q, block_k, True)


def _outputs(layout, case):
    """(o, lse, dq, dk, dv) of the kernels in interpret mode."""
    _, _, _, _, dim, block_q, block_k, _ = case
    (q, k, v, do), (o, lse) = _forward(layout, case)
    dq, dk, dv = F._bwd(
        q, k, v, o, lse, do, dim ** -0.5, layout, block_q, block_k, True)
    return o, lse, dq, dk, dv


@pytest.mark.parametrize("schedule", ["fused", "split"])
@pytest.mark.parametrize(
    "case", list(KERNEL_CASES.values()), ids=list(KERNEL_CASES))
def test_the_run_grid_has_the_rectangle_s_bits(case, schedule, monkeypatch):
    """At equal tiles the grid of runs computes the tiles the rectangle
    computed, in its order: o, lse and the three gradients are the
    parent's walk's to the last bit, under both backward schedules."""
    window = case[1]
    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    got = _outputs(F.Band(window), case)
    want = _outputs(RectangleBand(window), case)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32), name)
