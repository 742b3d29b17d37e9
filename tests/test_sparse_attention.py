"""Attention over the keys a learned indexer picks
(``ops/sparse_attention.py``, ``models/transformer.py:Attention.indexer``):
the masked attention and its gradients against a dense masked softmax,
the kernels in interpret mode against the ``jax.numpy`` lines, and the causal call
where a sequence is no longer than ``topk``. The scorer and the
selection against a loop over queries are
``test_sparse_attention_selection.py``'s, and the model that holds the
indexer (where its term's gradient goes and where the other losses'
does not, remat, the refusals) is ``test_sparse_attention_model.py``'s:
one file summed past the rule's 100 s (``ROADMAP.md`` Queue 3 item
12)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import sparse_attention as S
from elasticdl_tpu.ops.attention import dot_product_attention


def operands(seed, seq, batch=1, heads=4, kv_heads=2, dim=32, idx_heads=3,
             idx_dim=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda key, shape, d=dtype: jax.random.normal(key, shape, d)
    return (
        normal(keys[0], (batch, heads, seq, dim)),
        normal(keys[1], (batch, kv_heads, seq, dim)),
        normal(keys[2], (batch, kv_heads, seq, dim)),
        normal(keys[3], (batch, idx_heads, seq, idx_dim)),
        normal(keys[4], (batch, seq, idx_dim)),
        normal(keys[5], (batch, seq, idx_heads), jnp.float32) * 0.2,
    )


def kept_by_loop(scores, topk):
    """The selection, a query at a time: the ``min(topk, t + 1)``
    positions ``s <= t`` of the largest score, ties to the lower
    position (a stable sort), in the total order of floats that
    ``jax.lax.top_k`` compares by: -0.0 below +0.0."""
    bits = np.asarray(scores, np.float32).view(np.int32).astype(np.int64)
    scores = np.where(bits < 0, -(bits & 0x7FFFFFFF) - 1, bits)
    keep = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            order = np.argsort(-scores[b, t, :t + 1], kind="stable")
            keep[b, t, order[:min(topk, t + 1)]] = True
    return keep


# ---------------------------------------------------------------------------
# The attention over the kept keys, and its gradients
# ---------------------------------------------------------------------------


def dense_oracle(q, k, v, qi, ki, w, topk):
    """Softmax over the kept keys alone, a head at a time, in float64
    numpy: ``(out, kl (B,))``."""
    scores = S.scores_reference(qi, ki, w)
    keep = kept_by_loop(scores, topk)
    q, k, v, scores = (np.asarray(t, np.float64) for t in (q, k, v, scores))
    group = q.shape[1] // k.shape[1]
    out = np.zeros(q.shape)
    probs_mean = np.zeros(keep.shape)
    for b in range(q.shape[0]):
        for h in range(q.shape[1]):
            s = q[b, h] @ k[b, h // group].T / np.sqrt(q.shape[-1])
            s = np.where(keep[b], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, h] = p @ v[b, h // group]
            probs_mean[b] += p / q.shape[1]
    masked = np.where(keep, scores, -np.inf)
    log_q = masked - masked.max(-1, keepdims=True)
    log_q = log_q - np.log(np.exp(log_q).sum(-1, keepdims=True))
    with np.errstate(divide="ignore", invalid="ignore"):
        kl = np.where(
            keep & (probs_mean > 0),
            probs_mean * (np.log(probs_mean) - log_q), 0.0).sum(-1)
    return out, kl.mean(-1)


@pytest.mark.parametrize("seq,topk", [(64, 16), (96, 24)])
def test_the_lines_against_a_dense_masked_softmax(seq, topk):
    args = operands(3, seq, batch=2)
    out, kl, facts = jax.jit(
        lambda *a: S.dsa_attention(*a, topk, impl="xla"))(*args)
    want_out, want_kl = dense_oracle(*args, topk)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(kl, want_kl, rtol=2e-5)
    assert float(facts["kept_mean"]) == pytest.approx(
        np.minimum(topk, np.arange(seq) + 1).mean())
    assert 0.0 < float(facts["near_share"]) <= 1.0


def test_nothing_outside_the_kept_set_enters():
    """A key no query keeps moves neither the output nor a gradient: its
    value and its key get exactly zero."""
    seq, topk = 64, 8
    q, k, v, qi, ki, w = operands(4, seq)
    keep = np.asarray(S.select_reference(S.scores_reference(qi, ki, w), topk))
    unread = np.flatnonzero(~keep[0].any(0))
    assert unread.size

    def total(k, v):
        out, _, _ = S.dsa_attention(q, k, v, qi, ki, w, topk, impl="xla")
        return (out ** 2).sum()

    dk, dv = jax.jit(jax.grad(total, argnums=(0, 1)))(k, v)
    assert not np.asarray(dk)[0, :, unread].any()
    assert not np.asarray(dv)[0, :, unread].any()
    assert np.asarray(dv)[0, :, np.flatnonzero(keep[0].any(0))].any()


def grads(impl, args, topk, weight=3.0, interpret=True):
    def total(*args):
        out, kl, facts = S.dsa_attention(
            *args, topk, impl=impl, interpret=interpret)
        return (out.astype(jnp.float32) ** 2).sum() + weight * kl.sum(), (
            out, kl, facts)
    (_, aux), g = jax.jit(jax.value_and_grad(
        total, argnums=tuple(range(6)), has_aux=True))(*args)
    return aux, g


@pytest.mark.parametrize("seq,topk,batch", [
    (512, 128, 2), (1024, 96, 1), (2048, 96, 1)])
def test_the_kernels_against_the_lines_float32(seq, topk, batch):
    args = operands(seq, seq, batch=batch)
    (out_x, kl_x, facts_x), g_x = grads("xla", args, topk)
    (out_p, kl_p, facts_p), g_p = grads("pallas", args, topk)
    np.testing.assert_allclose(out_p, out_x, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(kl_p, kl_x, rtol=1e-5)
    for name in ("kept_mean", "entropy", "near_share"):
        assert float(facts_p[name]) == pytest.approx(
            float(facts_x[name]), rel=1e-5)
    for got, want in zip(g_p, g_x):
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=1e-3)


def test_the_kernels_against_the_lines_bfloat16():
    seq, topk = 512, 128
    args = operands(7, seq, dtype=jnp.bfloat16)
    (out_x, kl_x, _), g_x = grads("xla", args, topk)
    (out_p, kl_p, _), g_p = grads("pallas", args, topk)
    rel = lambda a, b: float(
        jnp.sqrt(jnp.mean((a.astype(jnp.float32) - b.astype(jnp.float32))
                          ** 2))
        / jnp.sqrt(jnp.mean(b.astype(jnp.float32) ** 2)))
    assert rel(out_p, out_x) < 0.01
    np.testing.assert_allclose(kl_p, kl_x, rtol=1e-3)
    for got, want in zip(g_p, g_x):
        assert rel(got, want) < 0.02


def kept_by_kernels(qi, ki, w, topk):
    """The kept set of ``dsa_select`` + ``dsa_mask`` in interpret mode,
    unpacked (the planes as ``operands``' 32-wide heads give them)."""
    planes = S._planes(qi.shape[2], 32, qi.dtype)
    threshold, tie = S._select_call(qi, ki, w, topk, True)
    packed = S._mask_call(qi, ki, w, threshold, tie, topk, planes, True)[0]
    assert packed.shape == ki.shape[:2] + (qi.shape[2] // planes,)
    return np.asarray(S.unpack_planes(packed, planes))


@pytest.mark.parametrize("seq,topk,batch", [(512, 64, 2), (2048, 96, 1)],
                         ids=["one-plane", "two-planes"])
def test_the_kernels_kept_set_is_top_k_s(seq, topk, batch):
    _, _, _, qi, ki, w = operands(9, seq, batch=batch)
    assert (kept_by_kernels(qi, ki, w, topk)
            == np.asarray(S.select_reference(
                S.scores_reference(qi, ki, w), topk))).all()


def test_the_kernels_cut_ties_where_top_k_does():
    """One indexer head whose keys are a few vectors repeated: whole
    runs of pairs score the same bits, so thresholds have more equals
    than they may keep and ``dsa_select``'s second bisection runs."""
    seq, topk = 512, 48
    _, _, _, qi, ki, w = operands(10, seq, idx_heads=1)
    ki = jnp.tile(ki[:, :4], (1, seq // 4, 1))
    scores = S.scores_reference(qi, ki, w)
    want = np.asarray(S.select_reference(scores, topk))
    assert (want == kept_by_loop(scores, topk)).all()
    assert int((S._select_call(qi, ki, w, topk, True)[1] < seq).sum()) > 0
    assert (kept_by_kernels(qi, ki, w, topk) == want).all()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("seq,topk", [(128, 128), (256, 2048)])
def test_a_short_sequence_is_the_causal_call_bit_for_bit(impl, seq, topk):
    args = operands(11, seq)
    out, kl, facts = jax.jit(lambda *a: S.dsa_attention(
        *a, topk, impl=impl, interpret=True))(*args)
    want = jax.jit(lambda *a: dot_product_attention(
        *a, causal=True, impl=impl, interpret=True))(*args[:3])
    assert (np.asarray(out) == np.asarray(want)).all()
    assert float(facts["kept_mean"]) == pytest.approx((seq + 1) / 2.0)
    assert float(facts["near_share"]) == 1.0
    _, want_kl = dense_oracle(*args, topk)
    np.testing.assert_allclose(kl, want_kl, rtol=2e-5)


def test_an_unknown_impl_is_refused():
    with pytest.raises(ValueError, match="unknown sparse attention impl"):
        S.dsa_attention(*operands(0, 64), 8, impl="ring")


@pytest.mark.parametrize("seq,topk,want", [
    (32768, 2048, 65012736), (2048, 2048, 2098176), (64, 2048, 2080)])
def test_tiles_facts_from_shapes(seq, topk, want):
    facts = S.tiles_facts(seq, topk)
    assert facts["kept"] == want == sum(
        min(topk, t + 1) for t in range(seq))
    run, masked, skipped, block_q, block_k = facts["forward"]
    assert run == masked and (run + skipped) * block_q * block_k == seq * seq
    assert 0.0 < facts["fill"] <= 1.0
    if seq == 32768:
        assert facts["forward"] == (528, 528, 496, 1024, 1024)
        assert facts["backward"] == (528, 528, 496, 1024, 1024)
        assert facts["fill"] == pytest.approx(0.1174, abs=1e-4)


@pytest.mark.parametrize("impl,held", [
    ("pallas", "kept_set=bits planes=8 saved_bytes=134217728)"),
    ("xla", "kept_set=dense)")])
def test_the_attention_line_says_how_the_kept_set_is_held(
        caplog, impl, held):
    """After ``fill=``, so that ``benchmark/lib/dsa_trace.py:LINE_RE``
    reads the line as it did."""
    import logging

    from benchmark.lib import dsa_trace

    S._log_once.cache_clear()
    with caplog.at_level(logging.INFO, logger=S.logger.name):
        S._log_once(impl, "tpu", "", (1, 32, 32768, 128), "bfloat16", 4,
                    (16, 64), 2048)
    S._log_once.cache_clear()
    line = caplog.records[-1].getMessage()
    assert line.endswith("kept=65012736 fill=0.1174 " + held)
    assert dsa_trace.attention_line(line)["kept"] == 65012736
