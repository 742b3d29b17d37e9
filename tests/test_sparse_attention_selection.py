"""The learned indexer's scorer and its selection
(``ops/sparse_attention.py``): the scores against their equation, the
kept set against a loop over queries, ties, the packing by planes and
the order of floats. The attention over the kept keys and its kernels
are ``test_sparse_attention.py``'s, whose file this was part of and
whose seeded operands and loop these tests take."""

import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import sparse_attention as S
from tests.test_sparse_attention import kept_by_loop, operands


def test_the_scores_are_the_equation():
    _, _, _, qi, ki, w = operands(0, 48)
    got = np.asarray(S.scores_reference(qi, ki, w))
    qi, ki, w = (np.asarray(t, np.float64) for t in (qi, ki, w))
    for t in (0, 5, 47):
        for s in (0, 3, t):
            if s > t:
                continue
            want = sum(
                w[0, t, j] * max(qi[0, j, t] @ ki[0, s], 0.0)
                for j in range(qi.shape[1]))
            assert got[0, t, s] == pytest.approx(want, rel=1e-5, abs=1e-6)
    assert (got[0][np.triu_indices(48, 1)] == S.NEG_INF).all()


@pytest.mark.parametrize("select", [S.select_reference, S.select],
                         ids=["top_k", "bisection"])
@pytest.mark.parametrize("seq,topk", [(64, 8), (96, 32), (40, 64), (33, 1)])
def test_the_selection_against_a_loop_over_queries(select, seq, topk):
    _, _, _, qi, ki, w = operands(seq + topk, seq, batch=2)
    scores = S.scores_reference(qi, ki, w)
    keep = np.asarray(select(scores, topk))
    assert (keep == kept_by_loop(scores, topk)).all()
    # exactly min(topk, t + 1) a query, none after itself
    assert (keep.sum(-1) == np.minimum(topk, np.arange(seq) + 1)).all()
    assert not keep[:, np.triu_indices(seq, 1)[0],
                    np.triu_indices(seq, 1)[1]].any()


@pytest.mark.parametrize("select", [S.select_reference, S.select],
                         ids=["top_k", "bisection"])
@pytest.mark.parametrize("levels", [1, 2, 5])
def test_ties_go_to_the_lower_position(select, levels):
    """Scores of a few levels only: nearly every threshold has more
    equals than it may keep, zeros of both signs among them."""
    seq, topk = 64, 16
    rng = np.random.RandomState(levels)
    values = rng.randint(0, levels, size=(1, seq, seq)).astype(np.float32)
    values[0, :, ::7] *= -1.0  # -0.0 where the level is 0
    causal = np.tril(np.ones((seq, seq), bool))
    scores = jnp.where(causal, values - (levels - 1) / 2.0, S.NEG_INF)
    keep = np.asarray(select(scores, topk))
    assert (keep == kept_by_loop(scores, topk)).all()
    assert (keep.sum(-1) == np.minimum(topk, np.arange(seq) + 1)).all()


def test_one_level_keeps_the_first_positions():
    seq, topk = 32, 4
    causal = np.tril(np.ones((seq, seq), bool))
    scores = jnp.where(causal, 0.0, S.NEG_INF)[None]
    for select in (S.select_reference, S.select):
        keep = np.asarray(select(scores, topk))[0]
        assert (keep[:, :topk] == causal[:, :topk]).all()
        assert not keep[:, topk:].any()


@pytest.mark.parametrize("seq,topk,planes,levels", [
    (64, 8, 1, 0), (64, 16, 2, 0), (96, 32, 4, 0), (128, 24, 8, 0),
    (64, 16, 8, 3), (64, 16, 2, 1)])
def test_the_kept_set_packs_by_planes_and_comes_back(
        seq, topk, planes, levels):
    """``dsa_mask``'s layout as ``jax.numpy`` lines: bit ``b`` of byte
    ``[t, j]`` is the pair ``(t, b * S / planes + j)``; ``levels``:
    scores of so few values that thresholds are cut among equals."""
    if levels:
        rng = np.random.RandomState(levels)
        values = rng.randint(0, levels, size=(2, seq, seq)).astype(np.float32)
        scores = jnp.where(
            np.tril(np.ones((seq, seq), bool)), values, S.NEG_INF)
    else:
        _, _, _, qi, ki, w = operands(seq + planes, seq, batch=2)
        scores = S.scores_reference(qi, ki, w)
    want = S.select_reference(scores, topk)
    packed = S.pack_planes(want, planes)
    width = seq // planes
    assert packed.shape == (2, seq, width) and packed.dtype == jnp.int8
    assert (np.asarray(S.unpack_planes(packed, planes))
            == np.asarray(want)).all()
    bytes_ = np.asarray(packed).view(np.uint8)
    for b in range(planes):
        assert ((bytes_ >> b & 1).astype(bool) == np.asarray(
            want)[..., b * width:(b + 1) * width]).all()
    assert not (bytes_ >> planes).any()


@pytest.mark.parametrize("seq,planes", [
    (512, 1), (1024, 1), (2048, 2), (4096, 4), (8192, 8), (32768, 8),
    (1000, 0)])
def test_the_planes_follow_from_the_shape(seq, planes):
    """Eight keys a byte where an eighth of the sequence is whole tiles
    of the widest reader (the forward's 1,024 keys from 1,024 positions
    on), fewer below; no packing, and a refusal, where a tile does not
    divide the sequence."""
    assert S._planes(seq, 128, jnp.bfloat16) == planes
    q = jnp.zeros((1, 4, seq, 128), jnp.bfloat16)
    assert bool(S._refusal(q)) == (planes == 0)


@pytest.mark.parametrize("value", [0.0, -0.0, 1.5, -1.5, 3e38, -3e38, 1e-45])
def test_sortable_keeps_the_order_of_floats(value):
    others = np.array([-2.0, -1e-30, -0.0, 0.0, 1e-30, 2.0], np.float32)
    key = lambda x: int(S._sortable(jnp.float32(x)))
    # the total order: as ``<`` but for the zeros, -0.0 below +0.0
    rank = lambda x: (float(x), not np.signbit(x))
    for other in others:
        a, b = np.float32(value), other
        assert (key(a) < key(b)) == (rank(a) < rank(b))
        assert (key(a) == key(b)) == (rank(a) == rank(b))
