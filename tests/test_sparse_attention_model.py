"""The model that holds a learned indexer
(``models/transformer.py:Attention.indexer``, ``MoeTransformerLM``) at
a small size on the CPU: where the indexer's term's gradient goes and
where the other losses' does not, what the loss names, remat against no
remat, the probe, and the refusals. The scorer, the selection and the
attention over the kept keys are ``test_sparse_attention.py``'s, whose
file this was part of."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.moe_transformer import MoeTransformerLM, loss
from elasticdl_tpu.models.transformer import IndexerDims

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


@pytest.fixture(scope="module")
def by_term(trained):
    """({term: the gradient of that term alone}, the training call's
    outputs): one program for the three tests that read them."""
    net, tokens, params = trained

    def indexer(params):
        out = net.apply({"params": params}, tokens, training=True)
        return out["indexer_loss"].sum(), out

    def others(params):
        out = net.apply({"params": params}, tokens, training=True)
        total, _ = loss(tokens, dict(out, indexer_loss_coef=0.0))
        return total.sum()

    def both(params):
        (_, out), own = jax.value_and_grad(indexer, has_aux=True)(params)
        return {"indexer": own, "others": jax.grad(others)(params)}, out

    return jax.jit(both)(params)


def test_the_indexer_s_parameters_and_their_names(trained):
    _, _, params = trained
    attn = params["block_0"]["attn"]
    assert set(attn) == {"query", "key", "value", "out_proj", "q_norm",
                         "k_norm", *INDEXER_LEAVES}
    assert attn["indexer_q"]["kernel"].shape == (64, 2, 8)
    assert attn["indexer_k"]["kernel"].shape == (64, 8)
    assert attn["indexer_w"]["kernel"].shape == (64, 2)
    assert set(attn["indexer_k_norm"]) == {"scale", "bias"}


def test_the_indexer_s_term_reaches_the_indexer_alone(by_term):
    grads = by_term[0]["indexer"]
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        own = any(word in name for word in INDEXER_LEAVES)
        assert bool(jnp.abs(leaf).max() > 0) == own, name


def test_the_other_losses_reach_the_indexer_not_at_all(by_term):
    grads = by_term[0]["others"]
    for block in ("block_0", "block_1"):
        attn = grads[block]["attn"]
        for name in INDEXER_LEAVES:
            for leaf in jax.tree_util.tree_leaves(attn[name]):
                assert not np.asarray(leaf).any(), (block, name)
        assert np.asarray(attn["query"]["kernel"]).any()
    assert np.asarray(grads["wte"]["embedding"]).any()


def test_the_loss_names_the_term_and_weighs_it(trained, by_term):
    net, tokens, params = trained
    out = by_term[1]
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
