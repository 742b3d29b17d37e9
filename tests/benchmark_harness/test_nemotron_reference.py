"""The repo's ``MoeTransformerLM`` as the Nemotron-3-Nano zoo builds it
against the configuration's plain reference (``benchmark/configs/
nemotron-3-nano-30b-a3b-1chip/reference.py``), at a small size on the
CPU with seeded weights: hidden 48, one layer of every kind (``ME*``;
the nine-layer pattern is ``tests/test_nemotron_lm.py``'s), a Mamba-2
mixer of 8 heads of 8 over a state of 16 in 4 groups, 4 of 16 experts
held, top 3, ``relu2`` bodies, attention of 4 / 2 heads of 16 that
rotates nothing; in float32. And the check's names against faults of
the kinds ISSUE 64's equations rule out."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark.lib import refcheck

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEMOTRON = os.path.join(
    REPO, "benchmark", "configs", "nemotron-3-nano-30b-a3b-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-nemotron",
    "config.json")
SEQ, VOCAB = 128, 512
# float32 against float32: a fault of the equations reads far outside
BOUNDS = {"logits": 0.01, "loss": 0.01, "grad": 0.02, "choices": 0.05,
          "dropped_pairs_plus_one": 0.0}
KEY = "grad:block_2/attn/key/kernel"
SHORT = dict(
    num_hidden_layers=3, hybrid_override_pattern="ME*",
    check_leaves=[
        "wte/embedding", "block_0/attn/in_proj/kernel", "block_0/attn/A_log",
        "block_0/attn/dt_bias", "block_0/attn/conv_bias",
        "block_0/attn/out_norm_scale", "block_1/moe_mlp/router/kernel",
        "block_1/moe_mlp/w_up", "block_1/moe_mlp/w_down",
        "block_1/moe_mlp/shared_up/kernel",
        "block_1/moe_mlp/shared_down/kernel", "block_2/attn/key/kernel"])


def small_config(**changes):
    with open(TINY) as f:
        config = json.load(f)
    config.update(SHORT)
    config.update(changes)
    return config


def build(config, tokens, remat_policy="none", last=None, variants=None):
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(NEMOTRON, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(NEMOTRON, "zoo.py"),
        "reference": os.path.join(NEMOTRON, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy},
                 "last_positions": last},
    }
    return check.build(spec, tokens, variants=variants)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(1)
    return (rng.zipf(1.2, size=SEQ) % VOCAB).astype(np.int32)


@pytest.fixture(scope="module")
def reference(tokens):
    """``lib/refcheck.py``'s order, once: (parts, variables, got,
    want)."""
    parts = build(small_config(), tokens)
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(5), tokens)
    got = jax.jit(parts["system"])(variables, tokens)
    want = jax.jit(parts["reference"])(variables, tokens)
    return parts, variables, got, want


def zoo():
    return refcheck.sys.modules["edlbench_zoo"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(NEMOTRON, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(token" in source  # one token a step
    assert "jnp.square(jax.nn.relu(hidden))" in source
    assert "no flax" in source and "import flax" not in source


def test_the_zoo_builds_the_published_pattern(reference):
    parts, variables, got, want = reference
    params = variables["params"]
    assert [sorted(params["block_%d" % i]) for i in range(3)] == [
        ["attn", "ln"], ["ln", "moe_mlp"], ["attn", "ln"]]
    with open(TINY) as f:
        whole = zoo().model_from_config(json.load(f))
    assert whole.layer_kinds == tuple(
        {"M": "mamba", "E": "experts", "*": "full"}[c] for c in "MEMEM*EME")
    assert (whole.num_experts, whole.held_experts, whole.top_k,
            whole.shared_experts, whole.expert_act, whole.gate_scale,
            whole.router_float32, whole.rotary) == (
        16, (4, 4), 3, 2, "relu2", 2.5, True, False)
    # ``init`` drew the biases, the skips and the gated norms' scales
    bias = np.asarray(
        variables["moe_state"]["block_1"]["moe_mlp"][
            "e_score_correction_bias"])
    assert bias.shape == (16,) and 0 < np.abs(bias).max() <= 0.1
    for name in ("D", "out_norm_scale"):
        leaf = np.asarray(params["block_0"]["attn"][name])
        assert leaf.min() >= 0.5 and leaf.max() <= 1.5 and leaf.std() > 0.1
    # float32 against float32: the sums' order and nothing else
    errors, ok = refcheck.compare(got, want, BOUNDS)
    assert ok and max(errors.values()) < 1e-4, errors
    # the routed leaves have a bound of their own, the shared expert's
    # two kernels the dense one
    assert parts["tolerance"]["grad:block_1/moe_mlp/w_up"] == (
        parts["tolerance"]["grad:block_1/moe_mlp/router/kernel"]) > (
        parts["tolerance"]["grad"])
    assert "grad:block_1/moe_mlp/shared_up/kernel" not in parts["tolerance"]
    for key, value in (("mlp_hidden_act", "silu"), ("n_group", 2),
                       ("tie_word_embeddings", True), ("mlp_bias", True),
                       ("norm_topk_prob", False), ("n_shared_experts", 2),
                       ("moe_shared_expert_intermediate_size", 90),
                       ("n_routed_experts", 8)):
        with pytest.raises(ValueError, match=key):
            zoo().model_from_config(small_config(**{key: value}))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        zoo().model_from_config(small_config(hybrid_override_pattern="M-*"))


def test_reference_equals_the_model_under_the_cell_s_remat(tokens):
    """Under the cell's remat policy and over the last positions (the
    fixture runs neither)."""
    parts = build(small_config(), tokens, "flash", 32)
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(5), tokens)
    got = jax.jit(parts["system"])(variables, tokens)
    want = jax.jit(parts["reference"])(variables, tokens)
    assert got["logits"].shape == (32, VOCAB)
    assert got["choices"].shape == (1, SEQ, 16)
    errors, ok = refcheck.compare(got, want, BOUNDS)
    assert ok and max(errors.values()) < 1e-4, errors


# the reference built wrong, against the system as it is: every variant
# ISSUE 64 lists, and the name that has to tell it
WRONG_REFERENCES = {
    "a_swiglu_expert": ({"experts": {"act": "swiglu"}}, "logits"),
    "a_plain_relu_expert": ({"experts": {"act": "relu"}}, "logits"),
    "the_shared_expert_left_out": ({"experts": {"shared": 0}}, "logits"),
    "the_shared_expert_twice": ({"experts": {"shared": 2}}, "logits"),
    "gates_not_renormalised": (
        {"experts": {"renormalise": False}}, "grad:block_1/moe_mlp/w_down"),
    "gates_not_scaled": (
        {"experts": {"scale": 1.0}}, "grad:block_1/moe_mlp/w_down"),
    "selection_without_the_bias": (
        {"experts": {"use_bias": False}}, "choices"),
    "a_norm_over_all_the_lanes": ({"mamba": {"norm_lanes": 64}}, "logits"),
    "the_gate_after_the_norm": (
        {"mamba": {"gate_after_norm": True}}, "logits"),
    "one_group_s_b_and_c": ({"mamba": {"groups": 1}}, "logits"),
    "q_and_k_rotated": ({"full": {"rotate": True}}, KEY),
    "kv_head_by_another_group": ({"full": {"group": 1}}, KEY),
}


@pytest.mark.parametrize(
    "variants,name", list(WRONG_REFERENCES.values()),
    ids=list(WRONG_REFERENCES))
def test_a_reference_built_wrong_is_outside_the_tolerances(
        tokens, reference, variants, name):
    _, variables, got, _ = reference
    wrong = build(small_config(), tokens, variants=variants)
    want = jax.jit(wrong["reference"])(variables, tokens)
    errors, ok = refcheck.compare(got, want, BOUNDS)
    assert not ok
    assert errors[name] > refcheck.tolerance_of(name, BOUNDS), errors
