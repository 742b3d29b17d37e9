"""The band as a mask layout (``ops/flash_attention.py:Band``):
``keep`` against the dense mask, the pair classifier and the grid of
runs against brute force with the window under, at and over a block
and no multiple of one, the counts of the cell, the attention line
under the benchmark's own regular expression, the refusals by name, the
kernels' names, and the older layouts' programs against what they were
(``tests/test_mask_layouts.py`` is the protocol's own file;
``tests/test_block_diffusion.py`` pins the diagonal's kernels at the
cells' shapes). The kernels in interpret mode are
``tests/test_flash_band_kernels.py``'s (against dense masked softmax)
and ``tests/test_flash_band_grid.py``'s (against the rectangle's
walk)."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import window_trace
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.ops import attention as A
from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.ops.attention import _flash_facts, _pallas_refusal
from tests.kernel_common import dense_band


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
# the tiles ``_blocks`` gives the cell's band: (forward's, backward's)
CELL_BLOCKS = ((512, 1024), (512, 512))


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
    the pairs the enumeration counts (its third number is the run
    grid's, ``test_the_run_grid_names_every_pair...``); traced scalars
    say what numpy says."""
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
    assert F.causal_pairs(
        seq, seq, block_q, block_k, causal=layout)[:2] == (
            int(some.sum()), int((some & ~every).sum()))
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
    before it, both masked; half the tiles' size runs as many a row.
    The grid is the band's length: one step a head computes nothing
    (row 0's second slot; the last column's), where the rectangle had
    961."""
    band = F.Band(512)
    for k_outer in (False, True):
        assert F.causal_pairs(
            32768, 32768, 1024, 1024, causal=band, k_outer=k_outer) == (
                63, 63, 1)
        assert F.causal_pairs(
            32768, 32768, 512, 512, causal=band, k_outer=k_outer) == (
                127, 127, 1)
        assert F._inner_steps(band, 1024, 1024, 32, 32, k_outer) == 2
        assert F._inner_steps(band, 512, 512, 64, 64, k_outer) == 2
    # where the blocks differ a shorter run has slots to spare, and
    # the two grid orders have their own counts
    assert F.causal_pairs(32768, 32768, 512, 1024, causal=band) == (
        95, 95, 64 * 2 - 95)
    assert F.causal_pairs(
        32768, 32768, 512, 1024, causal=band, k_outer=True) == (
            95, 95, 32 * 3 - 95)
    # a window of a block and one key more reaches a third tile
    assert F.causal_pairs(4096, 4096, 512, 512, causal=F.Band(514))[0] == (
        8 + 7 + 6)
    # the blocks the cell's shapes get, by the window and not by the
    # sequence, the forward's and the backward's (``_blocks``' table)
    for backward in (False, True):
        assert F._blocks(32768, 32768, 128, jnp.bfloat16, None, None,
                         backward=backward, layout=band) == CELL_BLOCKS[
                             backward]
        # another layout's are what they were; a caller's are kept
        assert F._blocks(32768, 32768, 128, jnp.bfloat16, None, None,
                         backward=backward) == (1024, 1024)
        assert F._blocks(32768, 32768, 128, jnp.bfloat16, 1024, 256,
                         backward=backward, layout=band) == (1024, 256)
    assert F.backward_schedule(
        32768, 32768, 128, jnp.bfloat16, layout=band) == "fused"


def _walk(layout, case, k_outer):
    """One head's grid, step by step: ``{outer: [(q-block, k-block,
    live, named)]}`` with ``named`` the (q, k, stat) blocks the index
    maps give the step and ``live`` whether the kernels compute it."""
    seq, _, block_q, block_k = case
    num_q, num_k = seq // block_q, seq // block_k
    num_outer, num_inner = (num_k, num_q) if k_outer else (num_q, num_k)
    steps = F._inner_steps(layout, block_q, block_k, num_q, num_k, k_outer)
    q_idx, k_idx, stat_idx = F._index_maps(
        layout, block_q, block_k, num_q, k_outer=k_outer, num_k=num_k)
    walk = {}
    for outer in range(num_outer):
        walk[outer] = []
        for inner in range(steps):
            block, live = F._grid_step(
                layout, outer, inner, block_q, block_k, num_inner, k_outer)
            i, j = (block, outer) if k_outer else (outer, block)
            named = (int(q_idx(0, outer, inner)[1]),
                     int(k_idx(0, outer, inner)[1]),
                     int(stat_idx(0, outer, inner)[2]))
            walk[outer].append((int(i), int(j), bool(live), named))
    return walk


@pytest.mark.parametrize("k_outer", [False, True], ids=["q-outer", "k-outer"])
@band_cases
def test_the_run_grid_names_every_pair_that_runs_exactly_once(case, k_outer):
    """The grid of runs against the pair classifier: every pair that
    runs has exactly one live step and no other pair has one; a row's
    (column's) live steps come first and name ascending blocks of the
    moving side; every index the maps give lies inside the arrays; a
    step off the run's end names the block the step before it fetched;
    the inner axis is as long as the longest run and no longer; and the
    traced arithmetic of the kernels says what numpy says."""
    seq, window, block_q, block_k = case
    if seq > 8192:
        case = (8192, window, block_q, block_k)  # the same tiles, shorter
        seq = 8192
    layout = F.Band(window)
    num_q, num_k = seq // block_q, seq // block_k
    run, _ = layout.pair(
        np.arange(num_q)[:, None], np.arange(num_k)[None, :],
        block_q, block_k)
    walk = _walk(layout, case, k_outer)
    moving = 0 if k_outer else 1
    lives = np.zeros((num_q, num_k), int)
    longest = 0
    for outer, steps in walk.items():
        live_blocks = [step[moving] for step in steps if step[2]]
        # live steps first, on ascending blocks, one step each
        assert [step[2] for step in steps] == sorted(
            (step[2] for step in steps), reverse=True)
        assert live_blocks == sorted(set(live_blocks)) and live_blocks
        longest = max(longest, len(live_blocks))
        for at, (i, j, live, named) in enumerate(steps):
            assert named[0] == named[2]
            assert 0 <= named[0] < num_q and 0 <= named[1] < num_k
            assert named[1 - moving] == outer
            if live:
                assert named[:2] == (i, j)
                lives[i, j] += 1
            else:
                assert named == steps[at - 1][3]
    np.testing.assert_array_equal(lives, run.astype(int))
    assert longest == len(walk[0])
    assert F.causal_pairs(
        seq, seq, block_q, block_k, causal=layout, k_outer=k_outer
    )[2] == len(walk) * longest - int(run.sum())
    # a window as long as the sequence: the rectangle's side
    if window >= seq:
        assert longest == (num_q if k_outer else num_k)
    traced = jax.jit(lambda outer, inner: F._grid_step(
        layout, outer, inner, block_q, block_k,
        num_q if k_outer else num_k, k_outer))
    for outer in {0, len(walk) // 2, len(walk) - 1}:
        for inner in range(longest):
            block, live = traced(jnp.int32(outer), jnp.int32(inner))
            i, j, want, named = walk[outer][inner]
            assert bool(live) == want and int(block) == named[moving]


@band_cases
def test_dq_s_rows_are_zeroed_and_rounded_once(case):
    """The fused backward's predicates on its (k-block, q-block) grid
    of runs (``_dkv_kernel``): over a head's walk every q-block's rows
    of the dq accumulator are zeroed at exactly one live step, before
    any term is added, and rounded out at exactly one, after the last;
    the terms between arrive in ascending k."""
    seq, window, block_q, block_k = case
    if seq > 8192:
        case = (8192, window, block_q, block_k)
        seq = 8192
    layout = F.Band(window)
    num_q, num_k = seq // block_q, seq // block_k
    first_k, last_k = layout.run(np.arange(num_q), block_q, block_k)
    last_k = np.minimum(last_k, num_k - 1)
    events = {i: [] for i in range(num_q)}
    for k_block, steps in _walk(layout, case, True).items():
        for i, j, live, _ in steps:
            if not live:
                continue
            assert j == k_block
            if j == first_k[i]:
                events[i].append("zero")
            events[i].append(j)
            if j == last_k[i]:
                events[i].append("round")
    for i, seen in events.items():
        terms = [e for e in seen if not isinstance(e, str)]
        assert seen == ["zero"] + terms + ["round"]
        assert terms == list(range(first_k[i], last_k[i] + 1))


def test_a_run_the_sequence_cuts_stays_on_the_grid():
    """``seq_q`` may end before a column's run does: the last columns'
    spare slots are dead and name the grid's last q-block; row 0 has no
    block before the diagonal's."""
    layout = F.Band(512)
    case = (1024, 512, 256, 256)
    columns = _walk(layout, case, True)
    assert [sum(step[2] for step in steps) for steps in columns.values()] == [
        3, 3, 2, 1]
    assert all(named[0] == 3 for *_, live, named in columns[3] if not live)
    rows = _walk(layout, case, False)
    assert [sum(step[2] for step in steps) for steps in rows.values()] == [
        1, 2, 3, 3]
    assert [named[1] for *_, named in rows[0]] == [0, 0, 0]


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
            "%s" % (group, LINES[None][0]))
    # the full layers' line is the diagonal's, at their own group
    q = jnp.zeros((1, 48, 32768, 128), jnp.bfloat16)
    assert _flash_facts(q, k, k, True, None, None) == (
        "kv_heads=8 group=6, flash backward=fused, "
        "pairs run=528 masked=32 skipped=496")


# (block_q, block_k): the line's tail, what ``fill`` reads from it
LINES = {
    None: ("pairs run=95 masked=95 skipped=33 blocks=512x1024 (backward "
           "run=127 masked=127 skipped=1 blocks=512x512) run_len=2",
           (95, 95, 33, 512, 1024), (127, 127, 1, 512, 512)),
    (512, 512): (
        "pairs run=127 masked=127 skipped=1 blocks=512x512 run_len=2",
        (127, 127, 1, 512, 512), (127, 127, 1, 512, 512)),
    (1024, 1024): (
        "pairs run=63 masked=63 skipped=1 blocks=1024x1024 run_len=2",
        (63, 63, 1, 1024, 1024), (63, 63, 1, 1024, 1024)),
    (512, 1024): (
        "pairs run=95 masked=95 skipped=33 blocks=512x1024 (backward "
        "run=95 masked=95 skipped=1 blocks=512x1024) run_len=2 "
        "(backward 3)",
        (95, 95, 33, 512, 1024), (95, 95, 1, 512, 1024)),
}


@pytest.mark.parametrize("blocks", list(LINES), ids=str)
def test_the_benchmark_s_reader_takes_the_line(blocks, monkeypatch):
    """The worker's line as ``ops/attention.py`` logs it, under the
    regular expression and the fill ``benchmark/lib/window_trace.py``
    has (imported, not copied): the counts are the run grid's, forward
    and backward each on its own, nothing stands between ``blocks=``
    and the backward's parenthesis, and ``window_flash_fill`` is the
    kept entries over the tiles that run."""
    tail, forward, backward = LINES[blocks]
    logged = []
    monkeypatch.setattr(A.logger, "info", lambda *a: logged.append(a[0] % a[1:]))
    q = jnp.zeros((1, 64, 32768, 128), jnp.bfloat16)
    k = jnp.zeros((1, 8, 32768, 128), jnp.bfloat16)
    block_q, block_k = blocks or (None, None)
    A._log_auto_once.__wrapped__(
        "tpu", "pallas", "", q.shape, q.dtype.name,
        "heads=64 gate=sigmoid rotary=128/128, " + _flash_facts(
            q, k, k, F.Band(512), block_q, block_k))
    assert len(logged) == 1 and logged[0].endswith(tail + ")")
    line = window_trace.attention_line("\n".join(
        ["attention impl=auto resolved to xla (backend=cpu)"] + logged))
    assert line == {"seq": 32768, "window": 512,
                    "forward": forward, "backward": backward}
    kept = 32768 * 512 - 512 * 511 / 2.0
    computed = sum(
        products * run * block_q * block_k
        for products, (run, _, _, block_q, block_k) in (
            (2, forward), (5, backward)))
    assert window_trace.fill(line) == pytest.approx(
        100.0 * 7 * kept / computed, rel=1e-12)
    # what the cell's ``window_flash_fill`` reads; a quarter at the
    # rectangle's 1024 x 1024, half at 512 x 512 in both directions
    want = {None: 43.79, (1024, 1024): 25.2, (512, 512): 50.0}
    if blocks in want:
        assert round(window_trace.fill(line), 2) == want[blocks]


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
