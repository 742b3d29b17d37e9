"""Nemotron-H's stack (layers of ONE sublayer each: ``Mamba2Mixer`` at
several groups, ``MoeMlp`` with ``relu2`` experts and a ``relu2`` shared
expert, ``Attention`` that rotates nothing at a kv group of its own, all
in one ``MoeTransformerLM``) against the configuration's plain reference
on seeded weights, at a small size on the CPU: one layer of every kind
through the benchmark's own check (logits, loss, gradients, choices),
the nine-layer ``MEMEM*EME`` model's tree (one norm a layer) and facts,
the ``relu2`` bodies against the lines, the refusals by name, and the
trees of the older models, which this PR leaves leaf for leaf."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.refcheck import load_by_path
from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.models.moe_transformer import MoeMlp, MoeTransformerLM
from elasticdl_tpu.models.transformer import (
    Block,
    GatedDeltaDims,
    KdaDims,
    LatentDims,
    Mamba2Dims,
    ShortConvDims,
)
from tests.lm_common import PRESET, REPO, read_json, reference_check, tree_digest

NEMOTRON = os.path.join(
    REPO, "benchmark", "configs", "nemotron-3-nano-30b-a3b-1chip")


@pytest.fixture(scope="module")
def config():
    return read_json(PRESET, "configs", "tiny-nemotron", "config.json")


@pytest.fixture(scope="module")
def reference():
    return load_by_path(
        "nemotron_reference_for_lm", os.path.join(NEMOTRON, "reference.py"))


@pytest.fixture(scope="module")
def zoo():
    return load_by_path(
        "nemotron_zoo_for_lm", os.path.join(NEMOTRON, "zoo.py"))


@pytest.fixture(scope="module")
def three_layers(config):
    """The preset cut to one layer of every kind the check names
    (``tests/benchmark_harness/test_nemotron_reference.py`` and the
    nine-layer tree below hold the published pattern)."""
    return dict(
        config, num_hidden_layers=3, hybrid_override_pattern="ME*",
        check_leaves=[
            "wte/embedding", "block_0/attn/in_proj/kernel",
            "block_0/attn/A_log", "block_0/attn/dt_bias",
            "block_0/attn/conv_bias", "block_0/attn/out_norm_scale",
            "block_1/moe_mlp/router/kernel", "block_1/moe_mlp/w_up",
            "block_1/moe_mlp/w_down", "block_1/moe_mlp/shared_up/kernel",
            "block_1/moe_mlp/shared_down/kernel",
            "block_2/attn/key/kernel"])


@pytest.fixture(scope="module")
def checked(three_layers):
    """The benchmark's own check of the tiny three-layer model, run
    once."""
    return reference_check(
        NEMOTRON, three_layers, "tiny-nemotron-s128.json", "nemotron")


def test_the_model_is_the_reference_s(checked, three_layers):
    (errors, ok), system, variables = checked
    assert ok, errors
    assert set(errors) == {
        "logits", "loss", "choices", "dropped_pairs_plus_one"} | {
        "grad:" + path for path in three_layers["check_leaves"]}
    # float32 on both sides: rounding, not bfloat16's; the same choices
    assert max(errors.values()) < 1e-4, errors
    assert errors["choices"] == 0 and errors["dropped_pairs_plus_one"] == 0
    assert system["logits"].shape == (32, 512)
    # 128 tokens x 3 choices x 4 / 16 experts held on average
    assert 0 < float(variables["system_run"]["held_pairs"]) < 384


@pytest.fixture(scope="module")
def nine_layers(config, zoo):
    """(the model, its variables, a training call's outputs) of the
    tiny nine-layer ``MEMEM*EME`` model, built and applied once."""
    model = zoo.model_from_config(config)
    tokens = jnp.arange(64, dtype=jnp.int32)[None] % 512
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(0), tokens))()
    outputs = jax.jit(lambda v: model.apply(
        v, tokens, training=True, mutable=["moe_state"]))(variables)[0]
    return model, variables, outputs


def test_one_sublayer_a_layer(nine_layers):
    model, variables, _ = nine_layers
    params = variables["params"]
    assert model.layer_kinds == (
        "mamba", "experts", "mamba", "experts", "mamba", "full", "experts",
        "mamba", "experts")
    assert set(variables) == {"params", "moe_state"}
    for i, kind in enumerate(model.layer_kinds):
        block = params["block_%d" % i]
        # ONE norm a layer, and the mixer or the experts, never both
        assert set(block) == {
            "ln", "moe_mlp" if kind == "experts" else "attn"}, (i, kind)
        assert set(block["ln"]) == {"scale"}
    assert set(params["block_1"]["moe_mlp"]) == {
        "router", "w_up", "w_down", "shared_up", "shared_down"}
    assert params["block_1"]["moe_mlp"]["w_up"].shape == (4, 48, 40)
    assert params["block_1"]["moe_mlp"]["w_down"].shape == (4, 40, 48)
    assert params["block_1"]["moe_mlp"]["shared_up"]["kernel"].shape == (
        48, 80)
    assert params["block_1"]["moe_mlp"]["router"]["kernel"].shape == (48, 16)
    assert set(params["block_5"]["attn"]) == {
        "query", "key", "value", "out_proj"}
    # q is wider than the residual: 4 heads of 16 over d 48
    assert params["block_5"]["attn"]["query"]["kernel"].shape == (48, 4, 16)
    assert params["block_5"]["attn"]["key"]["kernel"].shape == (48, 2, 16)
    assert params["block_0"]["attn"]["in_proj"]["kernel"].shape == (
        48, 2 * 64 + 2 * 4 * 16 + 8)
    assert "lm_head" in params
    # no leaf of the model falls to the sharding rules' catch-all
    rules = moe_transformer.moe_sharding_rules()
    for path, _ in jax.tree_util.tree_leaves_with_path(params):
        name = "/".join(p.key for p in path)
        assert next(pat.pattern for pat, _ in rules._rules
                    if pat.search(name)) != ".*", name


def test_the_model_s_facts(nine_layers):
    model, _, outputs = nine_layers
    assert {name: value.shape for name, value in outputs["mamba"].items()} == {
        name: (4,) for name in (
            "dt_mean", "dt_max", "decay_mean", "decay_min",
            "underflow_share")}
    routing = outputs["routing"]
    assert {"relu2_active", "relu2_shared_active", "held", "dropped",
            "bias_abs_max", "rows_run"} <= set(routing)
    # seeded, zero-mean pre-activations: about half are above zero
    assert 0.3 < float(routing["relu2_active"]) < 0.7
    assert 0.4 < float(routing["relu2_shared_active"]) < 0.6
    assert float(routing["dropped"]) == 0
    assert model.mixer_kinds() == {
        "mamba_layers": 4, "full_layers": 1, "dense_layers": 0,
        "expert_layers": 4, "mamba_heads": 8, "mamba_head_dim": 8,
        "mamba_state": 16, "mamba_groups": 4, "mamba_taps": 4,
        "mamba_chunk": 32, "head_dim": 16, "kv_heads": 2, "rotary": False}
    from elasticdl_tpu.train.step_fns import FACTS

    journal = FACTS[0].journal(jax.device_get(routing))
    assert {"relu2_active_share", "relu2_shared_active_share",
            "held_pairs", "dropped_pairs"} <= set(journal)


@pytest.mark.parametrize("held", [None, (4, 4)])
def test_relu2_experts_and_shared_expert_are_the_lines(reference, held):
    """``MoeMlp(expert_act="relu2")`` with a ``relu2`` shared expert
    against ``relu(x W_up)^2 W_down`` written out, all the experts and a
    held share; the active shares against a count."""
    layer = MoeMlp(
        16, top_k=3, dispatch_impl="sorted", expert_dim=40,
        expert_act="relu2", scoring="sigmoid", gate_scale=2.5,
        shared_experts=2, held_experts=held, held_rows=512,
        router_float32=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 48))
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {
        "router", "w_up", "w_down", "shared_up", "shared_down"}
    y, aux = jax.jit(layer.apply)({"params": params}, x)
    config = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.5}
    first, count = held or (0, 16)
    with jax.default_matmul_precision("highest"):
        routed, _, chosen = reference.expert_layer(
            x[0], params, 0.0, config, (first, count))
        want = routed + reference.shared_expert(x[0], params)
        hidden = jnp.einsum("sd,edw->sew", x[0], params["w_up"])
        shared = x[0] @ params["shared_up"]["kernel"]
    np.testing.assert_allclose(y[0], want, atol=3e-5)
    picked = (chosen[:, :, None] == (
        first + jnp.arange(count))[None, None]).any(axis=1)  # (S, count)
    active = ((hidden > 0) & picked[:, :, None]).sum() / (picked.sum() * 40)
    np.testing.assert_allclose(
        aux["routing"]["relu2_active"], active, rtol=1e-5)
    np.testing.assert_allclose(
        aux["routing"]["relu2_shared_active"], (shared > 0).mean(),
        rtol=1e-5)
    # the square: a plain ReLU is another function
    with jax.default_matmul_precision("highest"):
        plain = reference.expert_layer(
            x[0], params, 0.0, config, (first, count), act="relu")[0]
    assert float(jnp.abs(plain - routed).max()) > 1e-2


def test_a_shared_expert_takes_the_experts_body_or_is_refused():
    x = jnp.zeros((1, 8, 16))
    for act, names in (("swiglu", {"shared_gate", "shared_up", "shared_down"}),
                       ("relu2", {"shared_up", "shared_down"})):
        layer = MoeMlp(4, dispatch_impl="sorted", expert_dim=8,
                       expert_act=act, shared_experts=1)
        shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
        assert {n for n in shapes["params"] if n.startswith("shared")} == names
    with pytest.raises(ValueError, match="expert_act='gelu'"):
        jax.eval_shape(MoeMlp(
            4, dispatch_impl="sorted", expert_dim=8,
            shared_experts=1).init, jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="'gelu', 'swiglu' or 'relu2'"):
        jax.eval_shape(MoeMlp(4, expert_act="relu").init,
                       jax.random.PRNGKey(0), x)


MAMBA = Mamba2Dims(4, 8, 8, 2, 4, chunk=16)
STACK = dict(
    vocab_size=64, num_layers=3, num_heads=4, embed_dim=32,
    layer_kinds=("mamba", "experts", "full"), mamba=MAMBA, num_experts=4,
    moe_every=1, dispatch_impl="sorted", norm="rmsnorm", rotary=False,
    expert_act="relu2")
REFUSALS = {
    "a_dense_mlp_alone": (
        dict(layer_kinds=("mamba", "mlp", "full")), "dense MLP alone"),
    "first_k_dense": (dict(first_k_dense=1), "first_k_dense=1"),
    "moe_every": (dict(moe_every=2), "moe_every=2"),
    "block_diffusion": (
        dict(objective="block_diffusion", bd_mask_id=1), "block_diffusion"),
    "a_linear_layer_beside": (
        dict(layer_kinds=("linear", "experts"),
             linear=GatedDeltaDims(2, 2, 16, 16, 4)), "'linear', 'conv'"),
    "a_conv_layer_beside": (
        dict(layer_kinds=("conv", "experts"), conv=ShortConvDims(3)),
        "'linear', 'conv'"),
    "a_kda_layer_beside": (
        dict(layer_kinds=("kda", "experts"),
             kda=KdaDims(2, 16, 4, 8, chunk=16)), "'linear', 'conv'"),
    "latent_attention": (dict(latent=LatentDims(8, 8, 4, 8)), "latent"),
    "kind_fields": (
        dict(kind_fields={"full": moe_transformer.MixerKind(4)}),
        "kind_fields"),
    "hyper_connections": (
        dict(hc=moe_transformer.HyperDims(2)), "hyper-connections"),
    "sandwich_norms": (dict(sandwich=True), "sandwich"),
    "a_looped_stack": (
        dict(looped=moe_transformer.LoopedDims(2, 0.05)),
        "expert block|looped"),
    "the_prediction_module": (dict(mtp_layers=1), "mtp_layers"),
    "an_indexer": (
        dict(indexer=moe_transformer.IndexerDims(2, 16, 8)), "indexer"),
    "a_scaled_residual": (dict(residual_scale=0.5), "residual_scale"),
    "ring_attention": (dict(attention_impl="ring"), "ring"),
    "no_sizes": (dict(mamba=None), "need their mixer's sizes"),
}


@pytest.mark.parametrize(
    "fields,match", list(REFUSALS.values()), ids=list(REFUSALS))
def test_what_the_stack_was_not_built_beside_is_refused(fields, match):
    model = MoeTransformerLM(**dict(STACK, **fields))
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0), "noise": jax.random.PRNGKey(1)},
            jnp.zeros((1, 32), jnp.int32), training=True))


def test_the_stack_builds_and_a_block_is_one_of_its_halves():
    shapes = jax.eval_shape(lambda: MoeTransformerLM(**STACK).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)))["params"]
    assert [sorted(shapes["block_%d" % i]) for i in range(3)] == [
        ["attn", "ln"], ["ln", "moe_mlp"], ["attn", "ln"]]
    x = jnp.zeros((1, 8, 16))
    mixer = dict(num_heads=2, attention_impl="xla")
    for fields, match in (
            (dict(mixer=mixer, only="second"), "has no mixer"),
            (dict(mixer=None, only="mixer"), "has no mixer"),
            (dict(mixer=mixer, only="both"), "only='both'"),
            (dict(mixer=mixer, only="mixer", sandwich=True), "sandwich")):
        with pytest.raises(ValueError, match=match):
            jax.eval_shape(Block(**fields).init, jax.random.PRNGKey(0), x)
    # a dense MLP alone is a block the class can build (the model
    # refuses the kind: no configuration asks for it)
    dense = jax.eval_shape(Block(None, only="second", mlp_dim=8).init,
                           jax.random.PRNGKey(0), x)["params"]
    assert set(dense) == {"ln", "mlp_up", "mlp_down"}


# sha256 of the sorted (path, shape, dtype) of every leaf, read at the
# parent commit (e29045b): a model without the new kind keeps its tree
OLDER_TREES = {
    "tiny-lm": (
        24, "060c9dc1db9be0140e50fcb957ba450dc3c976ea73e353af213ec51d2fde1433"),
    "tiny-lfm2": (
        61, "7e548631c3dc6b06da32b3197637435b8b683cfb47a3d574577d8dc353e3e987"),
    "tiny-kimi": (
        97, "f5626c7a077f28322e167fe119b2ff99aa8882d169d67b36008494e1eeebd27a"),
    "tiny-granite": (
        128, "1441b026a3d67563dbcbc8564cff37dc1e83fc8048250b636fb7ea3558033221"),
}


@pytest.mark.parametrize("name", list(OLDER_TREES))
def test_the_older_models_trees_are_leaf_for_leaf_the_parent_s(name):
    assert tree_digest(name)[0] == OLDER_TREES[name]

