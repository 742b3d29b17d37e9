"""granite-4.0-h's block (``Mamba2Mixer``, ``Attention`` that rotates
nothing under a scale of its own, the four multipliers, all in one
``MoeTransformerLM``) against the configuration's plain reference on
seeded weights, at a small size on the CPU: the mixer alone, the
ten-layer model (mamba x 5, attention, mamba x 4) through the
benchmark's own check (logits, loss, gradients), attention with and
without its rotation, the scale on both attention paths, each
multiplier, the refusals by name, the convolution without a bias, and
the trees of the older models, which this PR leaves leaf for leaf."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.refcheck import load_by_path
from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.models.moe_transformer import MoeTransformerLM
from elasticdl_tpu.models.transformer import (
    Attention,
    GatedDeltaDims,
    KdaDims,
    LatentDims,
    Mamba2Dims,
    Mamba2Mixer,
    ShortConvDims,
    make_attention,
    mamba_gate_facts,
)
from elasticdl_tpu.ops import qkv_conv
from tests.lm_common import PRESET, REPO, read_json, reference_check, tree_digest

GRANITE = os.path.join(
    REPO, "benchmark", "configs", "granite-4.0-h-micro-1chip")


@pytest.fixture(scope="module")
def config():
    return read_json(PRESET, "configs", "tiny-granite", "config.json")


@pytest.fixture(scope="module")
def reference():
    return load_by_path(
        "granite_reference_for_lm", os.path.join(GRANITE, "reference.py"))


@pytest.fixture(scope="module")
def zoo():
    return load_by_path("granite_zoo_for_lm", os.path.join(GRANITE, "zoo.py"))


def _flat(tree):
    return sorted(
        ("/".join(p.key for p in path), leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree))


@pytest.mark.parametrize("groups", [1, 2])
def test_the_mixer_is_the_reference_s(config, reference, groups):
    """``Mamba2Mixer`` on seeded weights: its output, the gradients of
    every leaf and of its input, and its facts; with one group (the
    model's: the norm over all the lanes) and with two."""
    config = dict(config, mamba_n_groups=groups)
    layer = Mamba2Mixer(
        Mamba2Dims(
            config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], groups, config["mamba_d_conv"],
            chunk=32, segment=2),
        norm_eps=config["rms_norm_eps"])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 96, 64))
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]
    # away from the ones they start at
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    params = dict(
        params,
        D=jax.random.uniform(keys[0], params["D"].shape, minval=0.5,
                             maxval=1.5),
        out_norm_scale=jax.random.uniform(
            keys[1], params["out_norm_scale"].shape, minval=0.5, maxval=1.5))
    weight = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def system(params, x):
        y, facts = layer.apply({"params": params}, x)
        return (y * weight).sum(), (y, facts)

    def plain(params, x):
        with jax.default_matmul_precision("highest"):
            y = reference.mamba2_mixer(x[0], params, config)[None]
        return (y * weight).sum(), (y, None)

    run = lambda f: jax.jit(jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True))(params, x)
    (_, (y, facts)), grads = run(system)
    (_, (want, _)), want_grads = run(plain)
    np.testing.assert_allclose(y, want, atol=2e-5)
    for (name, got), (_, ref) in zip(_flat(grads[0]), _flat(want_grads[0])):
        scale = float(jnp.abs(ref).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            got, ref, atol=2e-4 * scale, err_msg=name)
    np.testing.assert_allclose(grads[1], want_grads[1], atol=2e-4)
    assert set(params) == {
        "in_proj", "conv_kernel", "conv_bias", "A_log", "dt_bias", "D",
        "out_norm_scale", "out_proj"}
    assert params["in_proj"]["kernel"].shape == (
        64, 2 * 128 + 2 * groups * 16 + 8)
    assert set(facts) == {
        "dt_mean", "dt_max", "decay_mean", "decay_min", "underflow_share"}
    assert 0 < float(facts["decay_min"]) <= float(facts["decay_mean"]) < 1
    assert 0 < float(facts["dt_mean"]) <= float(facts["dt_max"])
    assert float(facts["underflow_share"]) == 0


def test_the_facts_see_a_chunk_that_underflows():
    a = jnp.zeros((1, 96, 2)).at[:, 32:64, 1].set(-3.0)
    facts = mamba_gate_facts(jnp.full((1, 96, 2), 0.25), a, 32)
    # one (chunk, head) pair of 3 x 2 cumulates to -96
    np.testing.assert_allclose(facts["underflow_share"], 1 / 6)
    np.testing.assert_allclose(facts["decay_min"], np.exp(-3.0), rtol=1e-6)
    np.testing.assert_allclose(facts["dt_max"], 0.25)


@pytest.fixture(scope="module")
def two_layers(config):
    """The preset cut to one layer of every kind the check names: a
    Mamba-2 layer and an attention layer (the ten-layer preset repeats
    the Mamba-2 layer eight times more;
    ``tests/benchmark_harness/test_granite_reference.py`` runs that
    one)."""
    return dict(
        config, num_hidden_layers=2, layer_types=["mamba", "attention"],
        check_leaves=[
            "wte/embedding", "block_0/attn/in_proj/kernel",
            "block_0/attn/A_log", "block_0/attn/conv_kernel",
            "block_0/attn/conv_bias", "block_0/attn/dt_bias",
            "block_0/attn/D", "block_0/attn/out_norm_scale",
            "block_0/mlp_down/kernel", "block_1/attn/key/kernel",
            "block_1/attn/out_proj/kernel"])


@pytest.fixture(scope="module")
def checked(two_layers):
    """The benchmark's own check of the tiny two-layer model, run once:
    (errors by name and whether they pass, the system's outputs, the
    variables)."""
    return reference_check(
        GRANITE, two_layers, "tiny-granite-s128.json", "granite")


def test_the_model_is_the_reference_s(checked, two_layers):
    (errors, ok), system, _ = checked
    assert ok, errors
    assert set(errors) == {"logits"} | {
        "grad:" + path for path in two_layers["check_leaves"]}
    # float32 on both sides: rounding, not bfloat16's
    assert max(errors.values()) < 1e-4, errors
    assert system["logits"].shape == (32, 512)


def test_the_model_s_tree_and_facts(config, zoo):
    model = zoo.model_from_config(config)
    assert model.layer_kinds == ("mamba",) * 5 + ("full",) + ("mamba",) * 4
    tokens = jnp.zeros((1, 64), jnp.int32)
    variables = jax.jit(
        lambda: model.init(jax.random.PRNGKey(0), tokens))()
    params = variables["params"]
    assert set(variables) == {"params"}
    # every block dense, the head tied
    assert "lm_head" not in params
    assert {"mlp_gate", "mlp_up", "mlp_down", "attn"} <= set(
        params["block_0"])
    assert set(params["block_5"]["attn"]) == {
        "query", "key", "value", "out_proj"}
    assert params["block_5"]["attn"]["key"]["kernel"].shape == (64, 2, 16)
    a_log = np.exp(params["block_1"]["attn"]["A_log"])
    assert (a_log > 1).all() and (a_log < 16).all()
    dt = np.log1p(np.exp(params["block_1"]["attn"]["dt_bias"]))
    assert (dt > 9e-4).all() and (dt < 0.11).all()
    bias = params["block_1"]["attn"]["conv_bias"]
    assert float(jnp.abs(bias).max()) <= 0.5 < 2 * float(jnp.abs(bias).max())
    outputs = jax.jit(lambda v: model.apply(v, tokens, training=True))(
        variables)
    assert {name: value.shape for name, value in outputs["mamba"].items()} == {
        name: (9,) for name in (
            "dt_mean", "dt_max", "decay_mean", "decay_min",
            "underflow_share")}
    assert model.mixer_kinds() == {
        "mamba_layers": 9, "full_layers": 1, "dense_layers": 10,
        "mamba_heads": 8, "mamba_head_dim": 16, "mamba_state": 16,
        "mamba_groups": 1, "mamba_taps": 4, "mamba_chunk": 32,
        "head_dim": 16, "kv_heads": 2, "rotary": False}
    # no leaf of the model falls to the sharding rules' catch-all
    rules = moe_transformer.moe_sharding_rules()
    for path, _ in jax.tree_util.tree_leaves_with_path(params):
        name = "/".join(p.key for p in path)
        assert next(pat.pattern for pat, _ in rules._rules
                    if pat.search(name)) != ".*", name


def _attention(config, **fields):
    return Attention(
        config["num_attention_heads"], attention_impl="xla",
        num_kv_heads=config["num_key_value_heads"],
        rope_theta=float(config["rope_theta"]), **fields)


def test_attention_without_its_rotation(config, reference):
    """Unrotated it is the reference's; rotated it is the reference's
    rotated one, and the two part past position 0 (position 0 rotates
    by nothing)."""
    scale = config["attention_multiplier"]
    layer = lambda rotary: _attention(config, rotary=rotary, sm_scale=scale)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 48, 64))
    params = jax.jit(layer(True).init)(jax.random.PRNGKey(1), x)["params"]
    out = {rotary: jax.jit(layer(rotary).apply)({"params": params}, x)[0]
           for rotary in (False, True)}
    with jax.default_matmul_precision("highest"):
        for rotary in (False, True):
            np.testing.assert_allclose(
                out[rotary], reference.attention(
                    x[0], params, config, rotate=rotary), atol=2e-5)
    np.testing.assert_allclose(out[False][0], out[True][0], atol=1e-6)
    assert float(jnp.abs(out[False][1:] - out[True][1:]).max()) > 1e-2
    with pytest.raises(ValueError, match="rotates nothing"):
        _attention(config, rotary=False, rotary_dim=8).init(
            jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_scale_of_its_own_reaches_both_paths(config, reference, impl,
                                               monkeypatch):
    """``sm_scale`` through the XLA path and through the flash kernels
    (interpret mode): each is the reference's at that scale, and not
    the default ``head width ** -0.5``."""
    from elasticdl_tpu.ops import attention as attention_ops

    if impl == "pallas":
        monkeypatch.setattr(
            attention_ops, "dot_product_attention",
            _interpreted(attention_ops.dot_product_attention))
        from elasticdl_tpu.models import transformer

        monkeypatch.setattr(
            transformer, "dot_product_attention",
            _interpreted(attention_ops.dot_product_attention))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 64))
    layer = lambda scale: Attention(
        config["num_attention_heads"], attention_impl=impl,
        num_kv_heads=config["num_key_value_heads"], rotary=False,
        sm_scale=scale)
    params = layer(None).init(jax.random.PRNGKey(1), x)["params"]
    with jax.default_matmul_precision("highest"):
        for scale in (1 / 64, 0.5):
            got = layer(scale).apply({"params": params}, x)[0]
            np.testing.assert_allclose(got, reference.attention(
                x[0], params, config, scale=scale), atol=3e-5)
        default = layer(None).apply({"params": params}, x)[0]
        np.testing.assert_allclose(default, reference.attention(
            x[0], params, config, scale=16 ** -0.5), atol=3e-5)
    assert float(jnp.abs(got - default).max()) > 1e-3


def _interpreted(call):
    return lambda *a, **kw: call(*a, **dict(kw, interpret=True))


MULTIPLIERS = {
    "embedding_scale": 12.0, "residual_scale": 0.22,
    "attention_scale": 1 / 64, "logits_divisor": 8.0, "rotary": False,
}


_TWO_LAYERS = dict(
    vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
    layer_kinds=("mamba", "full"),
    mamba=Mamba2Dims(4, 8, 8, 1, 4, chunk=16), first_k_dense=2,
    dense_act="swiglu", norm="rmsnorm", tie_embeddings=True,
    attention_impl="xla")


@pytest.fixture(scope="module")
def with_every_multiplier():
    """(tokens, variables, logits) of the two-layer model with all five
    fields set, made once for the five cases."""
    tokens = jnp.arange(24, dtype=jnp.int32)[None] % 64
    full = MoeTransformerLM(**_TWO_LAYERS, **MULTIPLIERS)
    variables = jax.jit(full.init)(jax.random.PRNGKey(0), tokens)
    return tokens, variables, jax.jit(full.apply)(variables, tokens)


@pytest.mark.parametrize("left_out", list(MULTIPLIERS))
def test_each_multiplier_changes_the_result(left_out, with_every_multiplier):
    tokens, variables, got = with_every_multiplier
    fields = dict(MULTIPLIERS)
    fields[left_out] = True if left_out == "rotary" else None
    other = MoeTransformerLM(**_TWO_LAYERS, **fields)
    # the same tree: a multiplier is no parameter
    assert jax.tree_util.tree_structure(jax.eval_shape(
        other.init, jax.random.PRNGKey(0), tokens)
    ) == jax.tree_util.tree_structure(variables)
    without = jax.jit(other.apply)(variables, tokens)
    assert float(jnp.abs(got - without).max()) > 1e-4


MAMBA = Mamba2Dims(4, 8, 8, 1, 4, chunk=16)
REFUSALS = {
    "block_diffusion": (
        dict(objective="block_diffusion", bd_mask_id=1), "block_diffusion"),
    "a_linear_layer_beside": (
        dict(layer_kinds=("mamba", "linear"),
             linear=GatedDeltaDims(2, 2, 16, 16, 4)), "'linear', 'conv'"),
    "a_conv_layer_beside": (
        dict(layer_kinds=("mamba", "conv"), conv=ShortConvDims(3)),
        "not built"),
    "a_kda_layer_beside": (
        dict(layer_kinds=("mamba", "kda"), kda=KdaDims(2, 16, 4, 8, chunk=16)),
        "not built"),
    "latent_attention": (dict(latent=LatentDims(8, 8, 4, 8)), "latent"),
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
    "no_sizes": (dict(mamba=None), "need their mixer's sizes"),
    "multipliers_beside_an_indexer": (
        dict(layer_kinds=None, mamba=None, residual_scale=0.22,
             indexer=moe_transformer.IndexerDims(2, 16, 8)),
        "residual_scale beside a learned indexer"),
    "multipliers_beside_a_loop": (
        dict(layer_kinds=None, mamba=None, logits_divisor=8.0,
             first_k_dense=2, looped=moe_transformer.LoopedDims(2, 0.05)),
        "logits_divisor beside a looped stack"),
}


@pytest.mark.parametrize(
    "fields,match", list(REFUSALS.values()), ids=list(REFUSALS))
def test_what_a_mamba_layer_was_not_built_beside_is_refused(fields, match):
    base = dict(
        vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
        layer_kinds=("mamba", "full"), mamba=MAMBA, num_experts=4,
        moe_every=1, dispatch_impl="sorted", norm="rmsnorm")
    model = MoeTransformerLM(**dict(base, **fields))
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0), "noise": jax.random.PRNGKey(1)},
            jnp.zeros((1, 32), jnp.int32), training=True))


def test_make_attention_takes_one_recurrent_kind_and_no_mask():
    with pytest.raises(
            ValueError, match="one of conv, linear, kda and mamba"):
        make_attention(4, mamba=MAMBA, conv=ShortConvDims(3), norm_eps=1e-6)
    with pytest.raises(ValueError, match="a Mamba-2 mixer has no mask"):
        make_attention(4, mamba=MAMBA, mask=object(), norm_eps=1e-6)
    with pytest.raises(ValueError, match="a Mamba-2 mixer has no sm_scale"):
        make_attention(4, mamba=MAMBA, sm_scale=0.5, norm_eps=1e-6)
    with pytest.raises(ValueError, match="LatentDims.rotary"):
        make_attention(
            4, latent=LatentDims(8, 8, 4, 8), rotary=False, norm_eps=1e-6)
    mixer = make_attention(4, mamba=MAMBA, norm_eps=1e-5)
    assert isinstance(mixer, Mamba2Mixer) and mixer.norm_eps == 1e-5


def test_the_convolution_without_a_bias_is_the_program_it_was():
    """``conv_silu_xla(bias=None)`` traces to the lines every older
    layer had (copied here from the parent commit); with a bias it is
    those plus the bias."""
    def parent(qkvz, taps, conv_dim):
        seq, k = qkvz.shape[1], taps.shape[0]
        qkv = qkvz[..., :conv_dim]
        padded = jnp.pad(qkv, ((0, 0), (k - 1, 0), (0, 0)))
        return jax.nn.silu(sum(
            taps[j] * padded[:, j:j + seq] for j in range(k)))

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 40), jnp.bfloat16)
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 32), jnp.bfloat16)
    bias = jax.random.normal(jax.random.PRNGKey(2), (32,), jnp.bfloat16)
    text = lambda f: jax.jit(f).lower(x, taps).as_text()
    assert text(lambda x, t: qkv_conv.conv_silu_xla(x, t, 32)) == text(
        lambda x, t: parent(x, t, 32))
    got = qkv_conv.conv_silu_xla(
        x.astype(jnp.float32), taps.astype(jnp.float32), 32,
        bias.astype(jnp.float32))
    padded = jnp.pad(x[..., :32].astype(jnp.float32), ((0, 0), (3, 0), (0, 0)))
    want = jax.nn.silu(bias.astype(jnp.float32) + sum(
        taps[j].astype(jnp.float32) * padded[:, j:j + 24] for j in range(4)))
    np.testing.assert_allclose(got, want, atol=1e-5)


# sha256 of the sorted (path, shape, dtype) of every leaf, read at the
# parent commit (f08aa23): a model without the new kind keeps its tree
OLDER_TREES = {
    "tiny-lm": (
        24, "060c9dc1db9be0140e50fcb957ba450dc3c976ea73e353af213ec51d2fde1433"),
    "tiny-lfm2": (
        61, "7e548631c3dc6b06da32b3197637435b8b683cfb47a3d574577d8dc353e3e987"),
    "tiny-kimi": (
        97, "f5626c7a077f28322e167fe119b2ff99aa8882d169d67b36008494e1eeebd27a"),
}


@pytest.mark.parametrize("name", list(OLDER_TREES))
def test_the_older_models_trees_are_leaf_for_leaf_the_parent_s(name):
    assert tree_digest(name)[0] == OLDER_TREES[name]
