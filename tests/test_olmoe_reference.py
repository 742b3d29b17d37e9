"""The repo's ``MoeTransformerLM`` as the OLMoE zoo builds it against
the configuration's plain reference (``benchmark/configs/
olmoe-1b-7b-1chip/reference.py``), at a small size on the CPU with
seeded weights: hidden 64, 8 experts top-2, width 32, 2 layers, in
float32 and bfloat16; and the check's tolerances against the two
faults they have to catch (experts computed in an 8-bit float, a
system that drops each token's lowest-gate expert)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.ops import moe as moe_ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OLMOE = os.path.join(REPO, "benchmark", "configs", "olmoe-1b-7b-1chip")
LEAVES = ["wte/embedding", "block_0/moe_mlp/router/kernel",
          "block_1/moe_mlp/w_gate", "block_0/attn/query/kernel"]


def small_config(**changes):
    with open(os.path.join(OLMOE, "config.json")) as f:
        config = json.load(f)
    config.update(
        hidden_size=64, intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
        num_hidden_layers=2, vocab_size=512, check_leaves=LEAVES,
        compute_dtype="")
    config.update(changes)
    return config


def spec(config, remat_policy="none"):
    return {
        "config": config, "seed": 5, "zoo": os.path.join(OLMOE, "zoo.py"),
        "reference": os.path.join(OLMOE, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy}},
    }


def build(config, tokens, remat_policy="none"):
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(OLMOE, "check.py"))
    return check.build(spec(config, remat_policy), tokens)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(1)
    return (rng.zipf(1.2, size=128) % 512).astype(np.int32)


@pytest.fixture(scope="module")
def reference(tokens):
    """(parts, seeded parameters, the float32 reference's output)."""
    parts = build(small_config(), tokens)
    params = jax.jit(parts["init"])(jax.random.PRNGKey(5), tokens)
    return parts, params, jax.jit(parts["reference"])(params, tokens)


@pytest.mark.parametrize("remat_policy", ["none", "dots", "full"])
def test_reference_equals_the_model_in_float32(
        tokens, reference, remat_policy):
    parts, params, want = reference
    got = jax.jit(build(small_config(), tokens, remat_policy)["system"])(
        params, tokens)
    assert set(got) == {"logits", "loss", "choices"} | {
        "grad:" + leaf for leaf in LEAVES}
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    # same function, same precision: rounding order only, and not one
    # (token, slot) choice differs
    assert max(errors.values()) < 1e-4, errors
    assert errors["choices"] == 0.0
    assert got["logits"].shape == (128, 512)
    assert got["choices"].shape == (2, 128, 8)
    np.testing.assert_array_equal(np.asarray(got["choices"]).sum(-1), 2)


def test_the_loss_has_three_parts(tokens, reference):
    """Cross-entropy + 0.01 x load balancing + 0.001 x z-loss: each
    auxiliary weight moves the reference's loss by its term."""
    _, params, want = reference
    ref = refcheck.sys.modules["edlbench_reference"]

    @jax.jit
    def weighted(balance, z):
        # the weights are values: one program for the three readings
        config = small_config()
        config["assumed"] = {"loss_weights": {
            "router_aux_loss_coef": balance, "router_z_loss_coef": z}}
        return ref.logits_loss_and_choices(params, tokens, config)[1]

    def loss(router_aux_loss_coef=0.0, router_z_loss_coef=0.0):
        return float(weighted(router_aux_loss_coef, router_z_loss_coef))

    ce = loss()
    balance = loss(router_aux_loss_coef=1.0) - ce
    z = loss(router_z_loss_coef=1.0) - ce
    # two layers, top-2: a uniform router would score 2 a layer
    assert 4.0 <= balance < 16.0 and z > 0.0
    assert float(want["loss"]) == pytest.approx(
        ce + 0.01 * balance + 0.001 * z, rel=1e-5)


def test_bfloat16_system_path_is_inside_the_tolerance(tokens, reference):
    parts, params, want = reference
    got = jax.jit(build(
        small_config(compute_dtype="bfloat16"), tokens, "dots")["system"])(
            params, tokens)
    # the reference is handed THIS system's choices for the arithmetic
    want = jax.jit(build(
        small_config(compute_dtype="bfloat16"), tokens, "dots")["reference"])(
            params, tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    # and it is a different computation: the tolerance is not vacuous
    assert errors["logits"] > 1e-4


def test_experts_in_an_eight_bit_float_fail(tokens, reference, monkeypatch):
    parts, params, _ = reference
    plain = moe_ops.grouped_matmul

    def coarse(x):
        # the value an 8-bit float holds, the gradient of the identity
        rounded = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        return x + jax.lax.stop_gradient(rounded - x)

    monkeypatch.setattr(
        moe_ops, "grouped_matmul",
        lambda rows, weights, *rest: plain(
            coarse(rows), coarse(weights), *rest))
    built = build(small_config(compute_dtype="bfloat16"), tokens)
    got = jax.jit(built["system"])(params, tokens)
    want = jax.jit(built["reference"])(params, tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok, errors
    leaf = "grad:block_1/moe_mlp/w_gate"
    assert errors[leaf] > refcheck.tolerance_of(leaf, parts["tolerance"])


def test_a_system_that_drops_the_lowest_gate_expert_fails(tokens, reference):
    """Top-(k-1) where the configuration says top-k (the small size's
    top-7 of 8): one choice in k differs, sqrt(1/k) by construction.
    ``choices`` alone holds it: handed the experts such a system
    applied, the reference applies the same and the arithmetic
    agrees."""
    parts, params, want = reference
    fewer = small_config(num_experts_per_tok=1)
    got = jax.jit(build(fewer, tokens)["system"])(params, tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok
    # exactly so in the first layer, whose router sees the same input
    assert float(refcheck.rel_rms(
        got["choices"][0], want["choices"][0])) == pytest.approx(
            np.sqrt(1 / 2), rel=1e-6)
    assert errors["choices"] > parts["tolerance"]["choices"]
    # as the check runs it: the reference of the configuration (top-2)
    # applying what the system chose (one expert a token)
    ref = refcheck.sys.modules["edlbench_reference"]
    applied = jnp.argmax(got["choices"], axis=-1)[..., None]
    logits, _, chosen = jax.jit(
        lambda forced: ref.logits_loss_and_choices(
            params, tokens, small_config(), forced=forced))(applied)
    assert float(refcheck.rel_rms(got["logits"], logits)) < 1e-4
    assert chosen.shape == (2, 128, 2)


def test_forced_choices_change_only_what_is_applied(tokens, reference):
    """``forced`` makes the reference apply other experts with its own
    gates for them; the choices it reports stay its own."""
    _, params, want = reference
    ref = refcheck.sys.modules["edlbench_reference"]
    config = small_config()
    free = jax.jit(
        lambda: ref.logits_loss_and_choices(params, tokens, config))()
    run = jax.jit(lambda forced: ref.logits_loss_and_choices(
        params, tokens, config, forced=forced))
    same = run(free[2])
    np.testing.assert_allclose(
        np.asarray(same[0]), np.asarray(free[0]), atol=1e-5)
    other = (free[2] + 1) % config["num_experts"]
    forced = run(other)
    assert float(refcheck.rel_rms(forced[0], free[0])) > 0.05
    # layer 0's router sees the same input either way
    np.testing.assert_array_equal(
        np.asarray(forced[2][0]), np.asarray(free[2][0]))


def test_the_zoo_reads_every_size_and_refuses_what_it_cannot_build():
    zoo = refcheck.load_by_path("edlbench_zoo", os.path.join(OLMOE, "zoo.py"))
    with open(os.path.join(OLMOE, "config.json")) as f:
        config = json.load(f)
    model = zoo.model_from_config(config, remat_policy="dots")
    assert (model.embed_dim, model.num_heads, model.num_experts,
            model.top_k, model.expert_dim) == (2048, 16, 64, 8, 1024)
    assert (model.vocab_size, model.num_layers) == (12576, 1)
    assert model.dispatch_impl == "sorted" and model.moe_every == 1
    assert model.normalize_gates is False and model.qk_norm is True
    assert (model.norm, model.norm_eps) == ("rmsnorm", 1e-5)
    assert (model.aux_loss_weight, model.z_loss_weight) == (0.01, 0.001)
    assert model.remat and model.remat_policy == "dots"
    for key in ("hidden_size", "intermediate_size", "num_experts",
                "num_experts_per_tok", "rms_norm_eps", "vocab_size"):
        with pytest.raises(KeyError):
            zoo.model_from_config(
                {k: v for k, v in config.items() if k != key})
    for key, value in (("hidden_act", "gelu"), ("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("num_key_value_heads", 4),
                       # the block's rotary base is a constant
                       ("rope_theta", 500000.0)):
        with pytest.raises(ValueError):
            zoo.model_from_config(dict(config, **{key: value}))
