"""Attention over the keys a learned indexer picks
(``ops/sparse_attention.py``, ``models/transformer.py:Attention.indexer``):
the scorer and the selection against a loop over queries, the masked
attention and its gradients against a dense masked softmax, the kernels
in interpret mode against the ``jax.numpy`` lines, where the indexer's
term's gradient goes and where the other losses' does not, the causal
call where a sequence is no longer than ``topk``, and the refusals."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.moe_transformer import MoeTransformerLM, loss
from elasticdl_tpu.models.transformer import IndexerDims
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
# The scorer and the selection
# ---------------------------------------------------------------------------


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
    out, kl, facts = S.dsa_attention(*args, topk, impl="xla")
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

    dk, dv = jax.grad(total, argnums=(0, 1))(k, v)
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
    out, kl, facts = S.dsa_attention(
        *args, topk, impl=impl, interpret=True)
    want = dot_product_attention(
        *args[:3], causal=True, impl=impl, interpret=True)
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


# ---------------------------------------------------------------------------
# The model: who learns from what
# ---------------------------------------------------------------------------


def model(**changes):
    fields = dict(
        vocab_size=128, num_layers=2, num_heads=4, embed_dim=64, head_dim=16,
        num_kv_heads=2, head_norm="rmsnorm", num_experts=8, top_k=2,
        expert_dim=32, expert_act="swiglu", moe_every=1, norm="rmsnorm",
        dispatch_impl="sorted", indexer=IndexerDims(2, 8, 16))
    fields.update(changes)
    return MoeTransformerLM(**fields)


@pytest.fixture(scope="module")
def trained():
    net = model()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 128)
    params = jax.jit(net.init)(jax.random.PRNGKey(1), tokens)["params"]
    return net, tokens, params


INDEXER_LEAVES = ("indexer_q", "indexer_k", "indexer_k_norm", "indexer_w")


def by_term(trained, term):
    net, tokens, params = trained

    def value(params):
        out = net.apply({"params": params}, tokens, training=True)
        if term == "indexer":
            return out["indexer_loss"].sum()
        total, _ = loss(tokens, dict(out, indexer_loss_coef=0.0))
        return total.sum()

    return jax.jit(jax.grad(value))(params)


def test_the_indexer_s_parameters_and_their_names(trained):
    _, _, params = trained
    attn = params["block_0"]["attn"]
    assert set(attn) == {"query", "key", "value", "out_proj", "q_norm",
                         "k_norm", *INDEXER_LEAVES}
    assert attn["indexer_q"]["kernel"].shape == (64, 2, 8)
    assert attn["indexer_k"]["kernel"].shape == (64, 8)
    assert attn["indexer_w"]["kernel"].shape == (64, 2)
    assert set(attn["indexer_k_norm"]) == {"scale", "bias"}


def test_the_indexer_s_term_reaches_the_indexer_alone(trained):
    grads = by_term(trained, "indexer")
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        own = any(word in name for word in INDEXER_LEAVES)
        assert bool(jnp.abs(leaf).max() > 0) == own, name


def test_the_other_losses_reach_the_indexer_not_at_all(trained):
    grads = by_term(trained, "others")
    for block in ("block_0", "block_1"):
        attn = grads[block]["attn"]
        for name in INDEXER_LEAVES:
            for leaf in jax.tree_util.tree_leaves(attn[name]):
                assert not np.asarray(leaf).any(), (block, name)
        assert np.asarray(attn["query"]["kernel"]).any()
    assert np.asarray(grads["wte"]["embedding"]).any()


def test_the_loss_names_the_term_and_weighs_it(trained):
    net, tokens, params = trained
    out = jax.jit(lambda params: net.apply(
        {"params": params}, tokens, training=True))(params)
    assert out["indexer_loss"].shape == (2,)
    total, terms = loss(tokens, out)
    assert (np.asarray(terms["indexer_loss"])
            == np.asarray(out["indexer_loss"])).all()
    half, _ = loss(tokens, dict(out, indexer_loss_coef=0.5))
    np.testing.assert_allclose(
        total - half, 0.5 * out["indexer_loss"], rtol=1e-5)
    facts = out["dsa"]
    assert set(facts) == {"indexer_loss", "kept_mean", "entropy",
                          "near_share", "tiles_run", "tiles_causal"}
    assert facts["kept_mean"].shape == (2,)
    np.testing.assert_allclose(
        facts["indexer_loss"].sum(), out["indexer_loss"].mean(), rtol=1e-5)
    assert float(facts["kept_mean"][0]) == pytest.approx(
        np.minimum(16, np.arange(64) + 1).mean())
    # an evaluation call returns the logits alone
    assert jax.eval_shape(
        lambda: net.apply({"params": params}, tokens)).shape == (2, 64, 128)


def _whole_loss(net, tokens, params):
    out = net.apply({"params": params}, tokens, training=True)
    return loss(tokens, out)[0].sum()


@pytest.fixture(scope="module")
def without_remat(trained):
    """The whole loss's gradient with nothing made again: what every
    policy is held against, so once."""
    net, tokens, params = trained
    return jax.jit(jax.grad(functools.partial(_whole_loss, net, tokens)))(
        params)


@pytest.mark.parametrize("policy", ["full", "flash"])
def test_remat_changes_no_gradient(trained, without_remat, policy):
    _, tokens, params = trained
    other = model(remat=True, remat_policy=policy)
    got = jax.jit(jax.grad(functools.partial(_whole_loss, other, tokens)))(
        params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(without_remat)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def test_a_probe_is_sown_only_when_asked(trained):
    net, tokens, params = trained
    _, sown = jax.jit(lambda params: net.apply(
        {"params": params}, tokens, training=True,
        mutable=["intermediates"]))(params)
    attn = sown["intermediates"]["block_1"]["attn"]
    assert attn["kept_bits"][0].shape == (2, 64, 8)
    assert attn["scores_tail"][0].shape == (2, 64, 64)
    assert float(attn["kept_after"][0]) == 0.0
    counts = np.unpackbits(np.asarray(attn["kept_bits"][0]), axis=-1).sum(-1)
    assert (counts == np.minimum(16, np.arange(64) + 1)).all()


REFUSED = [
    ("block_diffusion", dict(objective="block_diffusion", bd_mask_id=1)),
    ("'window', 'linear', 'conv', 'kda' or 'mamba'",
     dict(layer_kinds=("full", "window"))),
    ("latent attention", "latent"),
    ("hyper-connections", "hc"),
    ("prediction module", dict(mtp_layers=1)),
    ("a dense block", dict(first_k_dense=1)),
    ("a dense block", dict(moe_every=2)),
    ("'ring' / 'ulysses'", dict(attention_impl="ring")),
    ("'ring' / 'ulysses'", dict(attention_impl="ulysses")),
]


@pytest.mark.parametrize("words,changes", REFUSED,
                         ids=[str(i) for i in range(len(REFUSED))])
def test_what_the_indexer_was_not_built_beside_is_refused(words, changes):
    from elasticdl_tpu.models.transformer import HyperDims, LatentDims

    if changes == "latent":
        changes = dict(latent=LatentDims(16, 8, 8, 16), head_dim=None,
                       num_kv_heads=None, head_norm=None)
    elif changes == "hc":
        changes = dict(hc=HyperDims(2))
    tokens = jnp.zeros((1, 64), jnp.int32)
    with pytest.raises(ValueError) as refused:
        model(**changes).init(jax.random.PRNGKey(0), tokens)
    assert words in str(refused.value) and "indexer heads=2" in str(
        refused.value)


def test_a_mesh_of_several_devices_is_refused():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1, 1, 1, 1),
                ("dp", "fsdp", "ep", "tp", "sp", "pp"))
    with pytest.raises(ValueError, match="a mesh of 2 devices"):
        model(mesh=mesh).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 64), jnp.int32))


def test_a_mixer_without_keys_takes_no_indexer():
    from elasticdl_tpu.models.transformer import (
        ShortConvDims,
        make_attention,
    )

    with pytest.raises(ValueError, match="has no indexer"):
        make_attention(
            4, conv=ShortConvDims(3), norm_eps=1e-6,
            indexer=IndexerDims(2, 8, 16))
