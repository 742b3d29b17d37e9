"""The flash kernels' backward in Pallas interpret mode on the CPU: the
fused ``flash_bwd`` against XLA's autodiff and the split pair, dq's
block in one buffer, the schedule and the blocks chosen from shapes,
the three classes of a causal call's (q-block, k-block) pairs and the
steps a skipped pair names, and the attention line that says which
backward ran. The forward, the ring and Ulysses are
``test_attention_ops.py``'s, whose file this was part of."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.attention import xla_attention
from elasticdl_tpu.ops.flash_attention import flash_attention
from tests.kernel_common import (
    dq_block_buffers,
    flash_names,
    traced_flash,
)
from tests.test_attention_ops import _inputs


# The fused backward (flash_bwd: dq accumulated beside dk and dv) against
# XLA's autodiff of the plain attention and against the split
# flash_dq / flash_dkv pair it falls back to above its VMEM budget.
# (causal, heads' width, seq_q, seq_k, block_q, block_k, dtype)
FUSED_BACKWARD_CASES = {
    "causal-several-blocks-d128":
        (True, 128, 512, 512, 128, 256, jnp.float32),
    "full-several-blocks-d128":
        (False, 128, 512, 512, 128, 256, jnp.float32),
    "causal-one-block-each-d256":
        (True, 256, 256, 256, 256, 256, jnp.float32),
    "full-several-blocks-d256":
        (False, 256, 256, 256, 128, 128, jnp.float32),
    # the ring's call: a block of another rank's keys, never causal
    "full-seq-q-shorter-than-seq-k":
        (False, 128, 256, 512, 128, 128, jnp.float32),
    "full-seq-q-longer-than-seq-k":
        (False, 128, 512, 256, 128, 256, jnp.float32),
    # the pythia cells' class of shape: causal, head 256, several blocks
    "causal-several-blocks-d256":
        (True, 256, 512, 512, 128, 256, jnp.float32),
    "causal-several-blocks-bfloat16-d256":
        (True, 256, 512, 512, 128, 256, jnp.bfloat16),
    "causal-bfloat16-d128":
        (True, 128, 512, 512, 128, 256, jnp.bfloat16),
    "causal-block-k-below-block-q":
        (True, 128, 512, 512, 256, 128, jnp.float32),
}


def _flash_grads(case):
    from elasticdl_tpu.ops.attention import dot_product_attention

    causal, dim, seq_q, seq_k, block_q, block_k, dtype = case
    rng = np.random.RandomState(7)

    def mk(seq):
        return jnp.asarray(
            rng.normal(size=(2, 2, seq, dim), scale=0.5), dtype)

    q, k, v = mk(seq_q), mk(seq_k), mk(seq_k)

    def loss(impl, **kw):
        def fn(q, k, v):
            out = dot_product_attention(
                q, k, v, causal=causal, impl=impl, **kw
            ).astype(jnp.float32)
            return jnp.sum(out * jnp.cos(out))
        return jax.grad(fn, argnums=(0, 1, 2))

    flash = loss(
        "pallas", block_q=block_q, block_k=block_k, interpret=True
    )
    return flash, loss("xla"), (q, k, v)


@pytest.mark.parametrize(
    "case", list(FUSED_BACKWARD_CASES.values()),
    ids=list(FUSED_BACKWARD_CASES),
)
def test_fused_backward_matches_xla_and_the_split_pair(case, monkeypatch):
    from elasticdl_tpu.ops import flash_attention as F

    flash, xla, args = _flash_grads(case)
    names, fused = traced_flash(flash, args)
    assert names == ["flash_bwd", "flash_fwd"]
    bfloat16 = case[-1] == jnp.bfloat16
    for got, ref in zip(fused, jax.jit(xla)(*args)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        tol = 5e-2 if bfloat16 else 3e-4
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol,
        )
    # a budget nothing fits: the same call falls back to the pair (a
    # new function: jax keeps the traces of the old one)
    monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    flash, _, _ = _flash_grads(case)
    names, pair = traced_flash(flash, args)
    assert names == ["flash_dkv", "flash_dq", "flash_fwd"]
    dq, dk, dv = (np.asarray(g, np.float32) for g in pair)
    # dk and dv are the pair's statements unchanged; dq's terms arrive
    # in ascending k in both schedules
    np.testing.assert_array_equal(np.asarray(fused[1], np.float32), dk)
    np.testing.assert_array_equal(np.asarray(fused[2], np.float32), dv)
    np.testing.assert_allclose(
        np.asarray(fused[0], np.float32), dq, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("case", [
    "causal-several-blocks-bfloat16-d256", "full-seq-q-shorter-than-seq-k"])
def test_one_buffer_of_dq_s_block_changes_no_gradient(case, monkeypatch):
    """A budget between the two counts (``fused_bwd_vmem_bytes`` with
    one and with two buffers of dq's whole-head output block) gives
    the fused kernel with that block under ``pl.Buffered(1)``, which is
    what 32,768 x 256 and 32,768 x 192 / 128 get under the real budget
    (PR 61): the same body, so dq, dk, dv are the two-buffer form's to
    the last bit, and the split pair's as the fused kernel's always
    were (dk, dv equal, dq's terms in ascending k in both)."""
    from elasticdl_tpu.ops import flash_attention as F

    case = FUSED_BACKWARD_CASES[case]
    _, dim, seq_q, seq_k, block_q, block_k, dtype = case
    shapes = (seq_q, seq_k, dim, dtype, block_q, block_k)
    one, two = (
        F.fused_bwd_vmem_bytes(
            seq_q, dim, block_q, block_k, jnp.dtype(dtype).itemsize,
            dq_buffers=buffers) for buffers in (1, 2))
    assert one < two and F.fused_dq_buffers(*shapes) == 2

    def grads(budget, buffers, kernels):
        # a new function a budget: jax keeps the traces of the last
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", budget)
        assert F.fused_dq_buffers(*shapes) == buffers
        flash, _, args = _flash_grads(case)
        traced = jax.jit(flash).trace(*args)
        assert flash_names(traced.jaxpr) == kernels
        # the block's mode is in the program that is lowered
        assert dq_block_buffers(traced.jaxpr) == [buffers] * bool(buffers)
        return [np.asarray(g, np.float32)
                for g in traced.lower().compile()(*args)]

    single = grads(one, 1, ["flash_bwd", "flash_fwd"])
    double = grads(two, 2, ["flash_bwd", "flash_fwd"])
    pair = grads(one - 1, 0, ["flash_dkv", "flash_dq", "flash_fwd"])
    for got, want in zip(single, double):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(single[1], pair[1])
    np.testing.assert_array_equal(single[2], pair[2])
    np.testing.assert_allclose(single[0], pair[0], atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("shape,dtype,schedule,dq_buffers", [
    # the benchmark's cells: pythia-1b at 2k and 16k, OLMoE at 4k
    ((2048, 2048, 256), jnp.bfloat16, "fused", 2),
    ((16384, 16384, 256), jnp.bfloat16, "fused", 2),
    ((4096, 4096, 128), jnp.bfloat16, "fused", 2),
    # at 512 q-rows (`_blocks`) the two buffers still fit: 63 MiB
    ((24576, 24576, 256), jnp.bfloat16, "fused", 2),
    # dq's accumulator and two buffers of its output block are over the
    # budget, the accumulator and one are not (PR 61: 79 -> 63 MiB at
    # qwen3next80b-s32k's shape, 75.5 -> 59.5 at 65,536 x 128)
    ((32768, 32768, 256), jnp.bfloat16, "fused", 1),
    ((65536, 65536, 128), jnp.bfloat16, "fused", 1),
    # dq's accumulator alone is the whole budget
    ((65536, 65536, 256), jnp.bfloat16, "split", 0),
    ((131072, 131072, 128), jnp.bfloat16, "split", 0),
    # a ring block: dq's size follows seq_q, not seq_k
    ((4096, 65536, 128), jnp.bfloat16, "fused", 2),
    # float32 doubles dq's output block (a model's init trace at 16k:
    # 68 MiB with two buffers, 52 with one)
    ((16384, 16384, 256), jnp.float32, "fused", 1),
    ((32768, 32768, 256), jnp.float32, "split", 0),
    ((2048, 2048, 256), jnp.float32, "fused", 2),
])
def test_backward_schedule_is_chosen_from_the_shapes(
        shape, dtype, schedule, dq_buffers):
    from elasticdl_tpu.ops import flash_attention as F

    assert F.backward_schedule(*shape, dtype) == schedule
    assert F.fused_dq_buffers(*shape, dtype) == dq_buffers


# The three classes of a causal call's (q-block, k-block) pairs
# (ops/flash_attention.py:_causal_pair): skipped, interior, diagonal.
# (seq_q, seq_k, block_q, block_k)
PAIR_CASES = {
    "512-1024": (4096, 4096, 512, 1024),
    "1024-1024": (4096, 4096, 1024, 1024),
    "512-512": (4096, 4096, 512, 512),
    "128-256": (2048, 2048, 128, 256),
    "256-128": (2048, 2048, 256, 128),
    "seq-q-shorter": (2048, 4096, 512, 1024),
    "seq-q-longer": (4096, 2048, 512, 512),
    "seq-q-shorter-256-128": (1024, 4096, 256, 128),
}
pair_cases = pytest.mark.parametrize(
    "case", list(PAIR_CASES.values()), ids=list(PAIR_CASES))


def _runs(q_block, k_block, block_q, block_k):
    from elasticdl_tpu.ops import flash_attention as F

    last_k, first_q, masked = F._causal_pair(
        q_block, k_block, block_q, block_k)
    # the two orientations' predicates are one statement
    assert (k_block <= last_k) == (q_block >= first_q)
    return k_block <= last_k, masked


@pair_cases
def test_causal_pair_classes_match_the_position_matrix(case):
    """Every pair's class against the brute-force ``q_pos >= k_pos``
    matrix: a skipped pair keeps no element, an interior pair keeps
    every element, a diagonal pair some; ``causal_pairs`` counts them."""
    from elasticdl_tpu.ops import flash_attention as F

    seq_q, seq_k, block_q, block_k = case
    num_q, num_k = seq_q // block_q, seq_k // block_k
    kept = (np.arange(seq_q)[:, None] >= np.arange(seq_k)[None, :])
    tiles = kept.reshape(num_q, block_q, num_k, block_k)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    counts = {"run": 0, "masked": 0, "skipped": 0}
    for i in range(num_q):
        for j in range(num_k):
            run, masked = _runs(i, j, block_q, block_k)
            assert run == some[i, j], (i, j)
            if run:
                assert masked == (not every[i, j]), (i, j)
            counts["run"] += run
            counts["masked"] += run and masked
            counts["skipped"] += not run
    assert F.causal_pairs(seq_q, seq_k, block_q, block_k) == (
        counts["run"], counts["masked"], counts["skipped"])


@pytest.mark.parametrize("case,pairs", [
    ((16384, 16384, 512, 1024), (272, 32, 240)),   # until PR 28
    ((16384, 16384, 1024, 1024), (136, 16, 120)),  # pythia1b-s16k
    ((2048, 2048, 512, 1024), (6, 4, 2)),          # the 2k cells
    ((2048, 2048, 512, 512), (10, 4, 6)),          # and their backward
    ((4096, 4096, 512, 1024), (20, 8, 12)),        # olmoe1b7b-s4k
])
def test_causal_pairs_of_the_cells(case, pairs):
    from elasticdl_tpu.ops import flash_attention as F

    assert F.causal_pairs(*case) == pairs
    # every pair is in exactly one class
    assert pairs[0] + pairs[2] == (case[0] // case[2]) * (case[1] // case[3])
    # not causal: every pair runs, none masked, none skipped
    assert F.causal_pairs(*case, causal=False) == (
        pairs[0] + pairs[2], 0, 0)


def _walk(case, causal, k_outer):
    """One head's grid in the order the pipeline walks it: for each
    step, whether the pair runs and the (q-block, k-block, lse-block)
    its index maps name."""
    from elasticdl_tpu.ops import flash_attention as F

    seq_q, seq_k, block_q, block_k = case
    num_q, num_k = seq_q // block_q, seq_k // block_k
    q_idx, k_idx, stat_idx = F._index_maps(
        causal, block_q, block_k, num_q, k_outer=k_outer)
    steps = []
    for outer in range(num_k if k_outer else num_q):
        for inner in range(num_q if k_outer else num_k):
            i, j = (inner, outer) if k_outer else (outer, inner)
            named = (
                int(q_idx(0, outer, inner)[1]),
                int(k_idx(0, outer, inner)[1]),
                int(stat_idx(0, outer, inner)[2]),
            )
            assert q_idx(0, outer, inner)[0] == 0 == k_idx(0, outer, inner)[0]
            run = _runs(i, j, block_q, block_k)[0] if causal else True
            steps.append((run, (i, j, i), named))
    return steps


def _changes(blocks):
    return sum(a != b for a, b in zip(blocks, blocks[1:]))


@pytest.mark.parametrize("k_outer", [False, True], ids=["q-outer", "k-outer"])
@pair_cases
def test_skipped_steps_name_a_block_already_there(case, k_outer):
    """A step that runs names its own blocks; a skipped step names what
    a neighbouring step that runs names, so over a head's whole walk
    each operand's block index changes as often as it does over the
    steps that run and no more: nothing is fetched for a skipped step."""
    steps = _walk(case, True, k_outer)
    assert any(not run for run, _, _ in steps)
    for run, own, named in steps:
        if run:
            assert named == own
    # q, do, lse and delta move with the inner axis of a k-outer grid,
    # k and v with that of a q-outer one; the outer axis' operands (and
    # every output) are the step's own (above: a k-block no q-block
    # needs still writes its zero dk and dv)
    for operand in ((0, 2) if k_outer else (1,)):
        walked = [named[operand] for _, _, named in steps]
        ran = [named[operand] for run, _, named in steps if run]
        assert set(walked) <= {own[operand] for _, own, _ in steps}
        assert _changes(walked) == _changes(ran)
    for operand in ((1,) if k_outer else (0, 2)):
        assert all(named[operand] == own[operand] for _, own, named in steps)


@pytest.mark.parametrize("k_outer", [False, True], ids=["q-outer", "k-outer"])
def test_a_call_that_is_not_causal_keeps_the_identity_maps(k_outer):
    for case in PAIR_CASES.values():
        for run, own, named in _walk(case, False, k_outer):
            assert run and named == own


# (seq_q, seq_k, head width, block_q, block_k, dtype): several interior
# pairs, several diagonal ones, several skipped. Head widths whose
# sm_scale is a power of two: the CPU compiler that stands in for the
# chip here contracts ``dot * sm_scale - m`` into one fma where no
# select sits between the two, and only an exact product rounds the
# same both ways (at 128 a float32 call differs in the last place here;
# on the chip, compiled, it does not: PERF.md Section 6, PR 28).
CLASS_CASES = {
    "float32-128-256-d64": (1024, 1024, 64, 128, 256, jnp.float32),
    "bfloat16-256-128-d256": (1024, 1024, 256, 256, 128, jnp.bfloat16),
    "float32-seq-q-shorter-d256": (512, 1024, 256, 128, 256, jnp.float32),
    "bfloat16-128-128-d64": (512, 512, 64, 128, 128, jnp.bfloat16),
}


def _forward_and_backward(case, seed=11, backward=True):
    """o, lse, dq, dk, dv of one causal call through the kernels'
    own entry points (what ``ops/ring_attention.py`` calls too); o and
    lse alone where the backward is not read."""
    from elasticdl_tpu.ops import flash_attention as F

    seq_q, seq_k, dim, block_q, block_k, dtype = case
    rng = np.random.RandomState(seed)
    mk = lambda seq: jnp.asarray(
        rng.normal(size=(2, seq, dim), scale=0.5), dtype)
    q, k, v, do = mk(seq_q), mk(seq_k), mk(seq_k), mk(seq_q)
    sm_scale = dim ** -0.5
    o, lse = F._fwd(q, k, v, sm_scale, True, block_q, block_k, True)
    if not backward:
        return (q, k, v, do), (o, lse)
    grads = F._bwd(
        q, k, v, o, lse, do, sm_scale, True, block_q, block_k, True)
    return (q, k, v, do), (o, lse) + tuple(grads)


@pytest.mark.parametrize("schedule", ["fused", "split"])
@pytest.mark.parametrize(
    "case", list(CLASS_CASES.values()), ids=list(CLASS_CASES))
def test_interior_pairs_without_the_mask_change_no_bit(
        case, schedule, monkeypatch):
    """o, lse, dq, dk, dv with the three classes equal, to the last
    bit, the same call with every pair that runs forced to "diagonal"
    (the parent's kernel: a mask on every tile), under both backward
    schedules; and both are the XLA attention's within tolerance."""
    from elasticdl_tpu.ops import flash_attention as F

    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    (q, k, v, do), got = _forward_and_backward(case)
    real = F._causal_pair

    def force(masked_as):
        def pair(*args):
            last_k, first_q, masked = real(*args)
            return last_k, first_q, masked_as(masked)
        return pair

    monkeypatch.setattr(F, "_causal_pair", force(lambda m: m | True))
    _, all_diagonal = _forward_and_backward(case)
    for a, b in zip(got, all_diagonal):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    # the patch reaches the kernels: with no pair masked the diagonal
    # tiles attend to the future and the output moves
    monkeypatch.setattr(F, "_causal_pair", force(lambda m: m & False))
    _, never_masked = _forward_and_backward(case, backward=False)
    assert not np.array_equal(
        np.asarray(got[0], np.float32),
        np.asarray(never_masked[0], np.float32))

    def ref(q, k, v):
        return xla_attention(q[:, None], k[:, None], v[:, None],
                             causal=True)[:, 0]

    def ref_and_vjp(q, k, v, do):
        o_ref, vjp = jax.vjp(ref, q, k, v)
        return (o_ref,) + vjp(do)

    tol = 5e-2 if case[-1] == jnp.bfloat16 else 3e-4
    for a, b in zip((got[0],) + got[2:], jax.jit(ref_and_vjp)(q, k, v, do)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol)


@pytest.mark.parametrize("shape,dtype,forward,backward", [
    # the cells: pythia-1b at 2k and 16k, OLMoE at 4k
    ((2048, 2048, 256), jnp.bfloat16, (512, 1024), (512, 512)),
    ((16384, 16384, 256), jnp.bfloat16, (1024, 1024), (1024, 1024)),
    ((4096, 4096, 128), jnp.bfloat16, (512, 1024), (512, 1024)),
    # a q-row over 512 bytes: the forward's VMEM
    ((16384, 16384, 256), jnp.float32, (512, 1024), (512, 1024)),
    ((16384, 16384, 128), jnp.float32, (1024, 1024), (1024, 1024)),
    # the taller block would cost the fused backward
    ((24576, 24576, 256), jnp.bfloat16, (512, 1024), (512, 1024)),
    ((32768, 32768, 256), jnp.bfloat16, (512, 1024), (512, 1024)),
    # short sequences: one block, or the largest that divides
    ((256, 256, 64), jnp.float32, (256, 256), (256, 256)),
    ((1536, 1536, 128), jnp.bfloat16, (512, 512), (512, 512)),
])
def test_blocks_are_chosen_from_the_shapes(shape, dtype, forward, backward):
    from elasticdl_tpu.ops import flash_attention as F

    assert F._blocks(*shape, dtype, None, None) == forward
    assert F._blocks(*shape, dtype, None, None, backward=True) == backward
    # what a caller states is what both kernels run with
    for flag in (False, True):
        assert F._blocks(*shape, dtype, 128, 256, backward=flag) == (128, 256)
    # the backward's blocks divide whatever the forward's divide
    assert forward[0] % backward[0] == 0 and forward[1] % backward[1] == 0


def test_forward_and_backward_with_blocks_of_their_own_match_xla():
    """At 2048 tokens the default blocks are 512 / 1024 forward and
    512 / 512 backward: the residuals (o, lse) pass between kernels
    whose grids differ."""
    import re

    q, k, v = _inputs(batch=1, heads=1, seq=2048, dim=64, seed=3)

    def loss(attention):
        def fn(q, k, v):
            out = attention(q, k, v, causal=True)
            return jnp.sum(out * jnp.cos(out))
        return jax.grad(fn, argnums=(0, 1, 2))

    flash = loss(lambda *a, **kw: flash_attention(*a, interpret=True, **kw))
    grids = re.findall(r"grid=\((\d+), (\d+), (\d+)\)",
                       str(jax.make_jaxpr(flash)(q, k, v)))
    assert sorted(grids) == [("1", "4", "2"), ("1", "4", "4")]
    for a, b in zip(flash(q, k, v), loss(xla_attention)(q, k, v)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=3e-4)


def test_attention_log_line_says_which_backward(monkeypatch, caplog):
    """``benchmark/lib/logs.py:ATTENTION_RE`` reads the first word after
    "resolved to"; the backward's schedule rides inside the
    parentheses."""
    import logging

    from elasticdl_tpu.ops import attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        attention._flash, "flash_attention", lambda q, k, v, **kw: q)
    attention._log_auto_once.cache_clear()
    q = jnp.zeros((1, 2, 2048, 128), jnp.bfloat16)
    with caplog.at_level(logging.INFO, logger=attention.logger.name):
        attention.dot_product_attention(q, q, q, causal=True)
        attention.dot_product_attention(q, q[:, :, :1000], q[:, :, :1000])
    attention._log_auto_once.cache_clear()
    lines = [r.getMessage() for r in caplog.records]
    assert lines[0] == (
        "attention impl=auto resolved to pallas (backend=tpu, "
        "q=(1, 2, 2048, 128) bfloat16, flash backward=fused, "
        "pairs run=6 masked=4 skipped=2 "
        "(backward run=10 masked=4 skipped=6))")
    assert "resolved to xla" in lines[1] and "backward" not in lines[1]
    assert "pairs" not in lines[1]
