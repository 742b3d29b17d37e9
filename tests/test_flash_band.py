"""The band as a mask layout (``ops/flash_attention.py:Band``):
``keep`` against the dense mask, the pair classifier and the skipped
steps' clamps against brute force with the window under, at and over a
block and no multiple of one, the kernels in interpret mode against
dense masked softmax at groups 6 and 8, the counts of the cell, the
refusals by name, the kernels' names, and the older layouts' programs
against what they were (``tests/test_mask_layouts.py`` is the
protocol's own file; ``tests/test_block_diffusion.py`` pins the
diagonal's kernels at the cells' shapes)."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.ops.attention import (
    _flash_facts,
    _pallas_refusal,
    dot_product_attention,
    xla_attention,
)
from tests.test_mask_layouts import _changes, _qkv, _value_and_grads


def dense_band(seq, window):
    """ISSUE 42's equation, position by position, in numpy: a query
    sees itself and the ``window - 1`` keys before it."""
    pos = np.arange(seq)
    return (pos[None, :] <= pos[:, None]) & (
        pos[:, None] - pos[None, :] < window)


# (seq, window, block_q, block_k)
BAND_CASES = {
    "the-cell-1024": (32768, 512, 1024, 1024),
    "the-cell-512": (8192, 512, 512, 512),
    "window-under-a-block": (2048, 100, 256, 256),
    "window-is-a-block": (2048, 256, 256, 256),
    "window-over-a-block": (2048, 700, 256, 256),
    "window-no-multiple": (1024, 129, 128, 128),
    "512-1024": (4096, 512, 512, 1024),
    "1024-512": (4096, 512, 1024, 512),
    "window-of-one": (1024, 1, 128, 256),
    "window-is-the-sequence": (1024, 1024, 256, 128),
    "window-over-the-sequence": (1024, 5000, 128, 128),
    "one-tile": (256, 64, 256, 256),
}
band_cases = pytest.mark.parametrize(
    "case", list(BAND_CASES.values()), ids=list(BAND_CASES))


def _tiles(case):
    """(some, every): which (q-block, k-block) tiles keep an entry,
    which keep all, from the equation (for the cell's 32,768, by
    intervals: a tile's rows q0..q1 and columns k0..k1)."""
    seq, window, block_q, block_k = case
    num_q, num_k = seq // block_q, seq // block_k
    if seq <= 8192:
        tiles = dense_band(seq, window).reshape(
            num_q, block_q, num_k, block_k)
        return tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    q0 = (np.arange(num_q) * block_q)[:, None]
    k0 = (np.arange(num_k) * block_k)[None, :]
    q1, k1 = q0 + block_q - 1, k0 + block_k - 1
    # the nearest pair of a tile, then the farthest two
    some = (k0 <= q1) & (np.maximum(q0, k0) - k1 < window)
    every = (k1 <= q0) & (q1 - k0 < window)
    return some, every


@band_cases
def test_keep_is_the_equation(case):
    seq, window, _, _ = case
    seq = min(seq, 4096)
    layout = F.Band(window)
    pos = np.arange(seq)
    want = dense_band(seq, window)
    np.testing.assert_array_equal(
        layout.keep(pos[:, None], pos[None, :]), want)
    np.testing.assert_array_equal(np.asarray(layout.keep(
        jnp.asarray(pos)[:, None], jnp.asarray(pos)[None, :])), want)
    # every row holds a key (itself); S W - W (W - 1) / 2 are kept
    assert want.diagonal().all()
    w = min(window, seq)
    assert want.sum() == seq * w - w * (w - 1) // 2


@band_cases
def test_pair_classes_match_the_position_matrix(case):
    """Every tile's class against the dense mask: skipped iff it keeps
    nothing, interior iff it keeps everything; ``causal_pairs`` counts
    what the enumeration counts; traced scalars say what numpy says."""
    seq, window, block_q, block_k = case
    layout = F.Band(window)
    assert layout.refusal(seq, seq, block_q, block_k) == ""
    num_q, num_k = seq // block_q, seq // block_k
    some, every = _tiles(case)
    run, masked = layout.pair(
        np.arange(num_q)[:, None], np.arange(num_k)[None, :],
        block_q, block_k)
    np.testing.assert_array_equal(run, some)
    np.testing.assert_array_equal(masked[some], ~every[some])
    assert F.causal_pairs(seq, seq, block_q, block_k, causal=layout) == (
        int(some.sum()), int((some & ~every).sum()), int((~some).sum()))
    traced = jax.jit(lambda i, j: layout.pair(i, j, block_q, block_k))
    for i, j in [(0, 0), (0, num_k - 1), (num_q - 1, 0),
                 (num_q - 1, num_k - 1), (num_q // 2, num_k // 2),
                 (num_q // 2, max(num_k // 2 - 1, 0))]:
        got_run, got_masked = traced(jnp.int32(i), jnp.int32(j))
        assert bool(got_run) == some[i, j]
        if some[i, j]:
            assert bool(got_masked) == (not every[i, j])


def test_the_cell_s_counts():
    """32,768 positions under a window of 512 (``laguna-xs2-s32k``): at
    1024 x 1024 a row of tiles runs its diagonal tile and the one
    before it, both masked; half the tiles' size runs as many a row."""
    band = F.Band(512)
    assert F.causal_pairs(32768, 32768, 1024, 1024, causal=band) == (
        63, 63, 1024 - 63)
    assert F.causal_pairs(32768, 32768, 512, 512, causal=band) == (
        127, 127, 4096 - 127)
    # a window of a block and one key more reaches a third tile
    assert F.causal_pairs(4096, 4096, 512, 512, causal=F.Band(514))[0] == (
        8 + 7 + 6)
    # the blocks the cell's shapes get, forward and backward alike
    for backward in (False, True):
        assert F._blocks(32768, 32768, 128, jnp.bfloat16, None, None,
                         backward=backward) == (1024, 1024)
    assert F.backward_schedule(32768, 32768, 128, jnp.bfloat16) == "fused"


@pytest.mark.parametrize("k_outer", [False, True], ids=["q-outer", "k-outer"])
@band_cases
def test_skipped_steps_name_a_block_already_there(case, k_outer):
    """A step that runs names its own blocks, and over a head's walk
    the moving operand's block index changes as often as over the steps
    that run alone, so nothing is fetched for a skipped step: the
    clamps work at both ends of the run."""
    seq, window, block_q, block_k = case
    if seq > 8192:
        seq = 8192  # the same tiles and window, a shorter walk
    layout = F.Band(window)
    num_q, num_k = seq // block_q, seq // block_k
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


def test_the_clamp_holds_a_column_to_the_grid():
    """``seq_q`` may end before a column's run does: the q-block a
    skipped step names is one of the grid's."""
    layout = F.Band(512)
    for k_block in range(8):
        named = layout.q_named(
            np.arange(4), np.int64(k_block), 256, 256, 4)
        assert named.min() >= 0 and named.max() <= 3


def test_the_refusals_by_name():
    band = F.Band(512)
    assert band.refusal(1024, 1024, 128, 128) == ""
    assert "at least the query" in F.Band(0).refusal(1024, 1024, 128, 128)
    assert "across the shards" in band.refusal(512, 1024, 128, 128)
    q = jnp.zeros((1, 2, 1024, 64), jnp.bfloat16)
    k = jnp.zeros((1, 2, 2048, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="across the shards"):
        F.flash_attention(q, k, k, mask=band)
    assert "across the shards" in _pallas_refusal(
        q, k, k, 128, 128, band)
    assert _pallas_refusal(q, q, q, 128, 128, band) == ""
    # the sequence-parallel schedules take the causal mask alone
    mixer = T.Attention(4, attention_impl="ring", mask=band)
    with pytest.raises(ValueError, match="attention_impl='ring' takes"):
        mixer.init(jax.random.PRNGKey(0), jnp.zeros((1, 128, 64)))


def test_as_layout_and_the_names():
    band = F.Band(512)
    assert F.as_layout(band) is band and str(band) == "window(512)"
    assert hash(band) == hash(F.Band(512)) and band != F.Band(256)
    assert F._kernel_name(band, "fwd") == "flash_band_fwd"
    assert F._kernel_name(band, "dkv") == "flash_band_dkv"
    for older in (True, False, F.CAUSAL, F.FULL, F.BlockDiffusion(256, 4)):
        assert F._kernel_name(older, "bwd") == "flash_bwd"


def test_the_attention_line_says_the_band_and_the_group():
    k = jnp.zeros((1, 8, 32768, 128), jnp.bfloat16)
    for heads, group in ((48, 6), (64, 8)):
        q = jnp.zeros((1, heads, 32768, 128), jnp.bfloat16)
        assert _flash_facts(q, k, k, F.Band(512), None, None) == (
            "kv_heads=8 group=%d, flash backward=fused, mask=window(512) "
            "pairs run=63 masked=63 skipped=961 blocks=1024x1024" % group)
    # the full layers' line is the diagonal's, at their own group
    q = jnp.zeros((1, 48, 32768, 128), jnp.bfloat16)
    assert _flash_facts(q, k, k, True, None, None) == (
        "kv_heads=8 group=6, flash backward=fused, "
        "pairs run=528 masked=32 skipped=496")


# (seq, window, heads, kv heads, width, block_q, block_k, dtype)
KERNEL_CASES = {
    "group-6-float32": (512, 100, 6, 1, 64, 128, 128, jnp.float32),
    "group-8-bfloat16": (512, 128, 8, 1, 64, 128, 256, jnp.bfloat16),
    "group-8-256-128": (512, 200, 16, 2, 32, 256, 128, jnp.float32),
    "group-1-window-over-a-block": (512, 300, 2, 2, 64, 128, 128,
                                    jnp.float32),
    "group-6-window-of-one": (256, 1, 6, 1, 32, 128, 128, jnp.float32),
}


@pytest.mark.parametrize("schedule", ["fused", "split"])
@pytest.mark.parametrize(
    "case", list(KERNEL_CASES.values()), ids=list(KERNEL_CASES))
def test_flash_under_the_band_is_dense_masked_softmax(
        case, schedule, monkeypatch):
    """Forward and the three gradients of the kernels in interpret mode
    against softmax over the dense mask built from the equation (not
    from the layout), under both backward schedules."""
    seq, window, heads, kv_heads, dim, block_q, block_k, dtype = case
    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    layout = F.Band(window)
    q, k, v, do = _qkv(seq, heads, kv_heads, dim, dtype)
    kept = jnp.asarray(dense_band(seq, window))

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


def test_a_causal_mask_in_the_band_s_place_is_another_function():
    q, k, v, _ = _qkv(512, 2, 2, 64, jnp.float32)
    band = xla_attention(q, k, v, mask=F.Band(64))
    causal = xla_attention(q, k, v, causal=True)
    assert float(jnp.abs(band - causal).max()) > 0.1
    # and a window that holds the whole prefix is the causal mask
    np.testing.assert_allclose(
        xla_attention(q, k, v, mask=F.Band(512)), causal, atol=1e-6)


def test_the_flash_policy_names_the_band_call_s_outputs():
    """``remat_block``'s ``flash`` policy saves ``flash_out`` /
    ``flash_lse``: the band's call names its outputs so too."""
    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    text = str(jax.make_jaxpr(lambda q: F.flash_attention(
        q, q, q, mask=F.Band(64), block_q=128, block_k=128,
        interpret=True))(q))
    assert "name=" + F.FLASH_OUT_NAME in text
    assert "name=" + F.FLASH_LSE_NAME in text
    assert "flash_band_fwd" in text and "name=flash_fwd" not in text


def _sha(text):
    return hashlib.sha256(
        re.sub(r" at 0x[0-9a-f]+", "", text).encode()).hexdigest()[:16]


# sha256 of the jaxpr (kernel bodies and index maps included) of the
# flash call's gradient under the two older layouts that
# ``tests/test_block_diffusion.py`` does not pin, recorded on the parent
# of PR 42 (4c389d9) with the pinned jax: a fourth layout and the
# kernels' names by layout left them what they were.
OLDER_CALLS = {
    "sdar30b-bd-s8k": ((1, 32, 16384, 128), 4,
                       dict(mask=F.BlockDiffusion(8192, 4)),
                       "307e65137f17be52"),
    "no-mask-2k": ((1, 8, 2048, 256), 8, dict(causal=False),
                   "d7ef17daac8808d0"),
}


@pytest.mark.parametrize(
    "case", list(OLDER_CALLS.values()), ids=list(OLDER_CALLS))
def test_the_older_layouts_trace_what_they_traced(case):
    q_shape, kv_heads, kwargs, want = case
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(
        (q_shape[0], kv_heads) + q_shape[2:], jnp.bfloat16)

    def loss(q, k, v):
        return F.flash_attention(q, k, v, **kwargs).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k))
    assert _sha(text) == want
