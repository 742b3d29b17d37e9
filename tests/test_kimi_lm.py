"""Kimi Linear's block (``KimiDeltaAttention``, ``LatentAttention``
that rotates nothing, both in one ``MoeTransformerLM``) against the
configuration's plain reference on seeded weights, at a small size on
the CPU: the mixer alone, the five-layer model through the benchmark's
own check (logits, loss, gradients, choices), latent attention with and
without its rotation, the refusals by name, and the trees of the older
models, which this PR leaves leaf for leaf."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.refcheck import load_by_path
from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.models.moe_transformer import MoeTransformerLM
from elasticdl_tpu.models.transformer import (
    GatedDeltaDims,
    KdaDims,
    KimiDeltaAttention,
    LatentAttention,
    LatentDims,
    ShortConvDims,
    make_attention,
)
from tests.lm_common import PRESET, REPO, read_json, reference_check, tree_digest

KIMI = os.path.join(REPO, "benchmark", "configs", "kimi-linear-48b-a3b-1chip")


@pytest.fixture(scope="module")
def config():
    return read_json(PRESET, "configs", "tiny-kimi", "config.json")


@pytest.fixture(scope="module")
def reference():
    return load_by_path(
        "kimi_reference_for_lm", os.path.join(KIMI, "reference.py"))


@pytest.fixture(scope="module")
def zoo():
    return load_by_path("kimi_zoo_for_lm", os.path.join(KIMI, "zoo.py"))


def test_the_mixer_is_the_reference_s(config, reference, monkeypatch):
    """``KimiDeltaAttention`` on seeded weights: its output, the
    gradients of every leaf and of its input, and its facts. The
    reference runs its four heads in two groups, as the cell's runs its
    32 in four."""
    monkeypatch.setattr(reference, "HEAD_GROUP", 2)
    linear = config["linear_attn_config"]
    layer = KimiDeltaAttention(
        KdaDims(linear["num_heads"], linear["head_dim"],
                linear["short_conv_kernel_size"],
                config["assumed"]["kda_gate_rank"], chunk=32, segment=2),
        norm_eps=config["rms_norm_eps"])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 96, 64))
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]
    weight = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def system(params, x):
        y, facts = layer.apply({"params": params}, x)
        return (y * weight).sum(), (y, facts)

    def plain(params, x):
        with jax.default_matmul_precision("highest"):
            y = reference.kimi_delta_attention(x[0], params, config)[None]
        return (y * weight).sum(), (y, None)

    run = lambda f: jax.jit(jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True))(params, x)
    (_, (y, facts)), grads = run(system)
    (_, (want, _)), want_grads = run(plain)
    np.testing.assert_allclose(y, want, atol=2e-5)
    flat = lambda tree: sorted(
        ("/".join(p.key for p in path), leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree))
    for (name, got), (_, ref) in zip(flat(grads[0]), flat(want_grads[0])):
        scale = float(jnp.abs(ref).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            got, ref, atol=2e-4 * scale, err_msg=name)
    np.testing.assert_allclose(grads[1], want_grads[1], atol=2e-4)
    assert set(facts) == {
        "decay_mean", "decay_min", "underflow_share", "beta_mean"}
    assert 0 < float(facts["decay_min"]) <= float(facts["decay_mean"]) < 1
    assert float(facts["underflow_share"]) == 0
    assert 0.3 < float(facts["beta_mean"]) < 0.7


def test_the_facts_see_a_chunk_that_underflows():
    from elasticdl_tpu.models.transformer import kda_gate_facts

    g = jnp.zeros((1, 2, 96, 4)).at[:, 0, 32:64, 1].set(-3.0)
    facts = kda_gate_facts(g, jnp.full((1, 2, 96), 0.25), 32)
    # one (chunk, head, channel) triple of 3 x 2 x 4 cumulates to -96
    np.testing.assert_allclose(facts["underflow_share"], 1 / 24)
    np.testing.assert_allclose(facts["decay_min"], np.exp(-3.0), rtol=1e-6)
    np.testing.assert_allclose(facts["beta_mean"], 0.25)


@pytest.fixture(scope="module")
def two_layers(config):
    """The preset cut to one layer of every kind the check names: a KDA
    mixer in the leading dense block, latent attention in an expert
    block (the five-layer preset repeats the KDA layer three times more
    in expert blocks; ``tests/benchmark_harness/test_kimi_reference.py``
    runs that one)."""
    linear = dict(config["linear_attn_config"], kda_layers=[1],
                  full_attn_layers=[2])
    return dict(
        config, num_hidden_layers=2, linear_attn_config=linear,
        check_leaves=[
            "wte/embedding", "block_0/attn/A_log",
            "block_0/attn/f_down/kernel", "block_0/mlp_gate/kernel",
            "block_0/attn/dt_bias", "block_0/attn/conv_kernel",
            "block_0/attn/in_proj_qkv/kernel", "block_0/attn/g_up/kernel",
            "block_1/attn/kv_down/kernel", "block_1/attn/q_proj/kernel",
            "block_1/moe_mlp/router/kernel", "block_1/moe_mlp/w_gate"])


@pytest.fixture(scope="module")
def checked(two_layers):
    """The benchmark's own check of the tiny two-layer model, run once:
    (errors by name, the system's outputs, the variables)."""
    return reference_check(KIMI, two_layers, "tiny-kimi-s128.json", "kimi")


def test_the_model_is_the_reference_s(checked, two_layers):
    (errors, ok), system, _ = checked
    assert ok, errors
    assert set(errors) == {"logits", "loss", "choices",
                           "dropped_pairs_plus_one"} | {
        "grad:" + path for path in two_layers["check_leaves"]}
    # float32 on both sides: rounding, not bfloat16's
    assert max(errors.values()) < 1e-4, errors
    assert errors["choices"] == 0 and errors["dropped_pairs_plus_one"] == 0
    assert system["logits"].shape == (32, 512)


def test_the_model_s_tree_and_facts(config, zoo):
    model = zoo.model_from_config(config)
    assert model.layer_kinds == ("kda", "kda", "kda", "full", "kda")
    assert model.latent.rotary is False
    tokens = jnp.zeros((1, 64), jnp.int32)
    variables = jax.jit(
        lambda: model.init(jax.random.PRNGKey(0), tokens))()
    params = variables["params"]
    # a KDA mixer in the leading DENSE block, and in expert blocks
    assert {"mlp_gate", "mlp_up", "mlp_down", "attn"} <= set(
        params["block_0"])
    assert set(params["block_1"]["attn"]) == {
        "in_proj_qkv", "conv_kernel", "f_down", "f_up", "g_down", "g_up",
        "b_proj", "A_log", "dt_bias", "out_norm", "out_proj"}
    assert set(params["block_3"]["attn"]) == {
        "q_proj", "kv_down", "kv_norm", "kv_up", "out_proj"}
    assert params["block_1"]["attn"]["A_log"].shape == (4,)
    assert params["block_1"]["attn"]["dt_bias"].shape == (64,)
    a_log = np.exp(params["block_1"]["attn"]["A_log"])
    assert (a_log > 1).all() and (a_log < 16).all()
    dt = np.log1p(np.exp(params["block_1"]["attn"]["dt_bias"]))
    assert (dt > 9e-4).all() and (dt < 0.11).all()
    outputs, _ = jax.jit(lambda v: model.apply(
        v, tokens, training=True, mutable=["moe_state"]))(variables)
    assert {name: value.shape for name, value in outputs["kda"].items()} == {
        name: (4,) for name in (
            "decay_mean", "decay_min", "underflow_share", "beta_mean")}
    assert model.mixer_kinds() == {
        "kda_layers": 4, "full_layers": 1, "dense_layers": 1,
        "kda_heads": 4, "kda_head_dim": 16, "kda_taps": 4,
        "kda_gate_rank": 16, "kda_chunk": 32, "latent": True,
        "latent_rotary": False}
    # no leaf of the model falls to the sharding rules' catch-all
    rules = moe_transformer.moe_sharding_rules()
    for path, _ in jax.tree_util.tree_leaves_with_path(params):
        name = "/".join(p.key for p in path)
        assert next(pat.pattern for pat, _ in rules._rules
                    if pat.search(name)) != ".*", name


def test_latent_attention_without_its_rotation(config, reference):
    """Unrotated it is the reference's; rotated it is the reference's
    rotated one, and the two part past position 0 (position 0 rotates
    by nothing)."""
    dims = lambda rotary: LatentDims(
        config["kv_lora_rank"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"], rotary=rotary)
    layer = lambda rotary: LatentAttention(
        config["num_attention_heads"], dims(rotary), attention_impl="xla",
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 48, 64))
    params = jax.jit(layer(True).init)(jax.random.PRNGKey(1), x)["params"]
    out = {rotary: jax.jit(layer(rotary).apply)({"params": params}, x)[0]
           for rotary in (False, True)}
    with jax.default_matmul_precision("highest"):
        for rotary in (False, True):
            np.testing.assert_allclose(
                out[rotary], reference.latent_attention(
                    x[0], params, config, rotate=rotary), atol=2e-5)
    np.testing.assert_allclose(out[False][0], out[True][0], atol=1e-6)
    assert float(jnp.abs(out[False][1:] - out[True][1:]).max()) > 1e-2
    with pytest.raises(ValueError, match="rotates nothing"):
        from elasticdl_tpu.models.transformer import YarnScaling

        LatentAttention(
            4, dims(False), attention_impl="xla",
            rope_scaling=YarnScaling(4.0, 32)).init(
                jax.random.PRNGKey(0), x)


KDA = KdaDims(2, 16, 4, 8, chunk=16)
REFUSALS = {
    "block_diffusion": (
        dict(objective="block_diffusion", bd_mask_id=1), "block_diffusion"),
    "kind_fields": (
        dict(kind_fields={"full": moe_transformer.MixerKind(4, 1e4)}),
        "kind_fields"),
    "a_linear_layer_beside": (
        dict(layer_kinds=("kda", "linear"),
             linear=GatedDeltaDims(2, 2, 16, 16, 4)), "'linear', 'conv'"),
    "a_conv_layer_beside": (
        dict(layer_kinds=("kda", "conv"), conv=ShortConvDims(3)),
        "not built"),
    "hyper_connections": (
        dict(hc=moe_transformer.HyperDims(2)),
        "hyper-connections|not built"),
    "the_prediction_module": (dict(mtp_layers=1), "mtp_layers"),
    "an_indexer": (
        dict(indexer=moe_transformer.IndexerDims(2, 16, 8)), "not built"),
    "a_looped_stack": (
        dict(looped=moe_transformer.LoopedDims(2, 0.05), first_k_dense=2),
        "'kda' mixer"),
    "ring_attention": (dict(attention_impl="ring"), "ring"),
    "no_sizes": (dict(kda=None), "need their mixer's sizes"),
}


@pytest.mark.parametrize(
    "fields,match", list(REFUSALS.values()), ids=list(REFUSALS))
def test_what_a_kda_layer_was_not_built_beside_is_refused(fields, match):
    base = dict(
        vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
        layer_kinds=("kda", "full"), kda=KDA, num_experts=4, moe_every=1,
        dispatch_impl="sorted", norm="rmsnorm")
    model = MoeTransformerLM(**dict(base, **fields))
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0), "noise": jax.random.PRNGKey(1)},
            jnp.zeros((1, 32), jnp.int32), training=True))


def test_make_attention_takes_one_recurrent_kind_and_no_mask():
    with pytest.raises(ValueError, match="one of conv, linear, kda and mamba"):
        make_attention(4, kda=KDA, conv=ShortConvDims(3), norm_eps=1e-6)
    with pytest.raises(
            ValueError, match="a Kimi Delta Attention mixer has no mask"):
        make_attention(4, kda=KDA, mask=object(), norm_eps=1e-6)
    mixer = make_attention(
        4, kda=KDA, latent=LatentDims(8, 8, 4, 8), norm_eps=1e-5)
    assert isinstance(mixer, KimiDeltaAttention) and mixer.norm_eps == 1e-5


# sha256 of the sorted (path, shape, dtype) of every leaf, read at the
# parent commit (f79fc46): a model without the new kind keeps its tree
OLDER_TREES = {
    "tiny-moonlight": (
        28, "43eb6b7d2043179d2d0006e790324c7e57335f3075abad4494f133baa01a9662"),
    "tiny-qwen3next": (
        70, "438ae9cd8ed0fb3fa45d80064c8421525a67525fdcbcf9b0eec33bd187bedf02"),
}


@pytest.mark.parametrize("name", list(OLDER_TREES))
def test_the_older_models_trees_are_leaf_for_leaf_the_parent_s(name):
    digest, model = tree_digest(name)
    assert digest == OLDER_TREES[name]
    if name == "tiny-moonlight":
        assert model.latent.rotary is True
