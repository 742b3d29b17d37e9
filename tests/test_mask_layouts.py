"""The mask as a layout (``ops/flash_attention.py``: ``Causal``,
``Full``, ``BlockDiffusion``): the pair classifier and the skipped
steps' clamps against the brute-force position matrix, the kernels in
interpret mode against dense masked softmax, and the diagonal's
programs against what they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.ops.attention import (
    _flash_facts,
    _pallas_refusal,
    dot_product_attention,
    xla_attention,
)


def dense_block_diffusion(half_len, block, rows=None):
    """The Tentpole's equations, position by position, in numpy: every
    query's row, or the rows of the queries ``rows``."""
    pos = np.arange(2 * half_len)
    rows = pos if rows is None else rows
    half, blk = pos // half_len, (pos % half_len) // block
    hq, hk = half[rows][:, None], half[None, :]
    bq, bk = blk[rows][:, None], blk[None, :]
    return (
        ((hq == 0) & (hk == 0) & (bk == bq))
        | ((hq == 0) & (hk == 1) & (bk < bq))
        | ((hq == 1) & (hk == 1) & (bk <= bq))
    )


# (half_len, block, block_q, block_k)
LAYOUT_CASES = {
    "the-cell-1024": (8192, 4, 1024, 1024),
    "the-cell-512": (8192, 4, 512, 512),
    "512-1024": (4096, 4, 512, 1024),
    "1024-512": (4096, 4, 1024, 512),
    "128-256-b32": (1024, 32, 128, 256),
    "block-is-the-tile": (1024, 128, 128, 128),
    "block-over-the-tile": (1024, 256, 128, 128),
    "block-3-straddles": (768, 3, 128, 256),
    "one-tile-a-half": (256, 4, 256, 256),
}
layout_cases = pytest.mark.parametrize(
    "case", list(LAYOUT_CASES.values()), ids=list(LAYOUT_CASES))


@layout_cases
def test_keep_is_the_equations(case):
    """``keep`` reads positions, no tile: from 8,192 positions up (the
    cell's 16,384 are 268M entries a copy of the matrix, and the
    equations are the same at every query) the rows are every 61st
    query, which meets every place in a block in both halves, and the
    queries either side of the halves' seam and at the end, each
    against all the keys; the smaller cases keep every row."""
    half_len, block, _, _ = case
    layout = F.BlockDiffusion(half_len, block)
    pos = np.arange(2 * half_len)
    sampled = half_len >= 4096
    rows = pos if not sampled else np.unique(np.concatenate([
        pos[::61], half_len + np.arange(-block, block), pos[-block:]]))
    want = dense_block_diffusion(half_len, block, rows)
    np.testing.assert_array_equal(
        layout.keep(rows[:, None], pos[None, :]), want)
    np.testing.assert_array_equal(np.asarray(layout.keep(
        jnp.asarray(rows)[:, None], jnp.asarray(pos)[None, :])), want)
    # a query keeps its block's worth of keys a block up to its own:
    # every row holds a key; L^2 + L B entries are kept
    np.testing.assert_array_equal(
        want.sum(axis=1), (rows % half_len // block + 1) * block)
    if not sampled:
        assert want.sum() == half_len ** 2 + half_len * block


@layout_cases
def test_pair_classes_match_the_position_matrix(case):
    """Every tile's class against the dense mask: skipped iff it keeps
    nothing, interior iff it keeps everything; ``causal_pairs`` counts
    what the enumeration counts; traced scalars say what numpy says."""
    half_len, block, block_q, block_k = case
    layout = F.BlockDiffusion(half_len, block)
    assert layout.refusal(
        2 * half_len, 2 * half_len, block_q, block_k) == ""
    num_q, num_k = 2 * half_len // block_q, 2 * half_len // block_k
    tiles = dense_block_diffusion(half_len, block).reshape(
        num_q, block_q, num_k, block_k)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    run, masked = layout.pair(
        np.arange(num_q)[:, None], np.arange(num_k)[None, :],
        block_q, block_k)
    np.testing.assert_array_equal(run, some)
    np.testing.assert_array_equal(masked[some], ~every[some])
    assert F.causal_pairs(
        2 * half_len, 2 * half_len, block_q, block_k, causal=layout
    ) == (int(some.sum()), int((some & ~every).sum()), int((~some).sum()))
    traced = jax.jit(lambda i, j: layout.pair(i, j, block_q, block_k))
    for i, j in [(0, 0), (0, num_k - 1), (num_q - 1, 0),
                 (num_q - 1, num_k - 1), (num_q // 2, num_k // 2),
                 (num_q // 2 - 1, num_k // 2)]:
        got_run, got_masked = traced(jnp.int32(i), jnp.int32(j))
        assert bool(got_run) == some[i, j]
        if some[i, j]:
            assert bool(got_masked) == (not every[i, j])


def test_the_cell_s_counts():
    """16,384 positions of ``sdar30b-bd-s8k``: the clean -> noisy
    quadrant skipped whole, noisy -> noisy its diagonal tiles, the two
    others a triangle."""
    layout = F.BlockDiffusion(8192, 4)
    assert F.causal_pairs(16384, 16384, 1024, 1024, causal=layout) == (
        8 + 36 + 36, 8 + 8 + 8, 256 - 80)
    assert F.causal_pairs(16384, 16384, 512, 512, causal=layout) == (
        16 + 136 + 136, 48, 1024 - 288)


def _changes(blocks):
    return sum(a != b for a, b in zip(blocks, blocks[1:]))


@pytest.mark.parametrize("k_outer", [False, True], ids=["q-outer", "k-outer"])
@layout_cases
def test_skipped_steps_name_a_block_already_there(case, k_outer):
    """As the diagonal's test in tests/test_attention_ops.py: a step
    that runs names its own blocks, and over a head's walk the moving
    operand's block index changes as often as over the steps that run
    alone, so nothing is fetched for a skipped step."""
    half_len, block, block_q, block_k = case
    layout = F.BlockDiffusion(half_len, block)
    num_q, num_k = 2 * half_len // block_q, 2 * half_len // block_k
    q_idx, k_idx, stat_idx = F._index_maps(
        layout, block_q, block_k, num_q, k_outer=k_outer)
    run, _ = layout.pair(
        np.arange(num_q)[:, None], np.arange(num_k)[None, :],
        block_q, block_k)
    moving = 0 if k_outer else 1
    for outer in range(num_k if k_outer else num_q):
        walked, ran = [], []
        for inner in range(num_q if k_outer else num_k):
            i, j = (inner, outer) if k_outer else (outer, inner)
            named = (int(q_idx(0, outer, inner)[1]),
                     int(k_idx(0, outer, inner)[1]),
                     int(stat_idx(0, outer, inner)[2]))
            assert named[0] == named[2]
            assert named[1 - moving] == (i, j)[1 - moving]
            assert 0 <= named[moving] < (num_q, num_k)[moving]
            walked.append(named[moving])
            if run[i, j]:
                assert named[:2] == (i, j)
                ran.append(named[moving])
        assert ran, "a row or column of tiles that never runs"
        assert set(walked) == set(ran)
        assert _changes(walked) == _changes(ran)


def test_tiles_that_straddle_the_halves_are_refused():
    layout = F.BlockDiffusion(384, 4)
    assert "do not divide" in layout.refusal(768, 768, 256, 128)
    assert "covers 768 positions" in layout.refusal(512, 768, 128, 128)
    q = jnp.zeros((1, 2, 768, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="do not divide"):
        F.flash_attention(q, q, q, mask=layout, block_q=256, block_k=128)
    assert "do not divide" in _pallas_refusal(q, q, q, 256, 128, layout)
    assert _pallas_refusal(q, q, q, 128, 128, layout) == ""


def test_as_layout_takes_the_boolean_every_caller_had():
    assert F.as_layout(True) is F.CAUSAL
    assert F.as_layout(False) is F.FULL
    layout = F.BlockDiffusion(8192, 4)
    assert F.as_layout(layout) is layout
    assert str(layout) == "block_diffusion(8192, 4)"
    assert hash(layout) == hash(F.BlockDiffusion(8192, 4))


def test_the_attention_line_says_the_layout_and_its_tiles():
    q = jnp.zeros((1, 32, 16384, 128), jnp.bfloat16)
    k = jnp.zeros((1, 4, 16384, 128), jnp.bfloat16)
    line = _flash_facts(q, k, k, F.BlockDiffusion(8192, 4), None, None)
    assert line == (
        "kv_heads=4 group=8, flash backward=fused, "
        "mask=block_diffusion(8192, 4) pairs run=80 masked=24 "
        "skipped=176 blocks=1024x1024")
    # the diagonal's line is what it was
    assert _flash_facts(q, k, k, True, None, None) == (
        "kv_heads=4 group=8, flash backward=fused, "
        "pairs run=136 masked=16 skipped=120")


def _qkv(seq, heads, kv_heads, dim, dtype, seed=5):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(
        rng.normal(size=(2, h, seq, dim), scale=0.7), dtype)
    return mk(heads), mk(kv_heads), mk(kv_heads), mk(heads)


def _value_and_grads(fn, q, k, v, do):
    """One program a call (a new one: ``fn`` is traced under what the
    test has patched by then), not an operation at a time."""
    def both(q, k, v, do):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(do)

    return jax.jit(both)(q, k, v, do)


# (half_len, block, heads, kv heads, width, block_q, block_k, dtype)
KERNEL_CASES = {
    "group-1-float32": (256, 4, 2, 2, 64, 128, 128, jnp.float32),
    "group-8-bfloat16": (256, 4, 8, 1, 64, 128, 256, jnp.bfloat16),
    "group-1-256-128": (512, 8, 2, 2, 64, 256, 128, jnp.float32),
    "group-8-block-over-tile": (256, 128, 8, 1, 32, 128, 128, jnp.float32),
}


@pytest.mark.parametrize("schedule", ["fused", "split"])
@pytest.mark.parametrize(
    "case", list(KERNEL_CASES.values()), ids=list(KERNEL_CASES))
def test_flash_under_the_layout_is_dense_masked_softmax(
        case, schedule, monkeypatch):
    """Forward and the three gradients of the kernels in interpret mode
    against softmax over the dense mask built from the equations (not
    from the layout), under both backward schedules."""
    half_len, block, heads, kv_heads, dim, block_q, block_k, dtype = case
    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    layout = F.BlockDiffusion(half_len, block)
    q, k, v, do = _qkv(2 * half_len, heads, kv_heads, dim, dtype)
    kept = jnp.asarray(dense_block_diffusion(half_len, block))

    def dense(q, k, v):
        group = heads // kv_heads
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * dim ** -0.5
        p = jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)

    got = _value_and_grads(
        lambda q, k, v: F.flash_attention(
            q, k, v, mask=layout, block_q=block_q, block_k=block_k,
            interpret=True), q, k, v, do)
    want = _value_and_grads(dense, q, k, v, do)
    tol = 5e-2 if dtype == jnp.bfloat16 else 3e-4
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol)
    # and the XLA path builds its dense mask from the same layout
    xla = _value_and_grads(
        lambda q, k, v: dot_product_attention(
            q, k, v, mask=layout, impl="xla"), q, k, v, do)
    for a, b in zip(xla, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol)


def test_a_causal_mask_in_the_layout_s_place_is_another_function():
    q, k, v, _ = _qkv(512, 2, 2, 64, jnp.float32)
    block = xla_attention(q, k, v, mask=F.BlockDiffusion(256, 4))
    causal = xla_attention(q, k, v, causal=True)
    assert float(jnp.abs(block - causal).max()) > 0.1


def _jaxpr_of_the_causal_call(module, **kwargs):
    q = jnp.zeros((1, 2, 512, 64), jnp.bfloat16)
    k = jnp.zeros((1, 1, 512, 64), jnp.bfloat16)

    def loss(q, k, v):
        return module.flash_attention(
            q, k, v, block_q=128, block_k=256, interpret=True, **kwargs
        ).astype(jnp.float32).sum()

    return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k))


def test_a_causal_call_traces_what_it_traced():
    """``causal=True`` and ``mask=CAUSAL`` are one program, and the
    kernels' bodies hold the diagonal's operations and none of a
    layout's (no shift, no second select)."""
    text = _jaxpr_of_the_causal_call(F, causal=True)
    assert text == _jaxpr_of_the_causal_call(F, mask=F.CAUSAL)
    assert "shift_right" not in text
    full = _jaxpr_of_the_causal_call(F, causal=False)
    assert "iota" not in full.split("pallas_call", 1)[1]
