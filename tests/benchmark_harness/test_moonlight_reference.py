"""The repo's ``MoeTransformerLM`` as the Moonlight zoo builds it
against the configuration's plain reference (``benchmark/configs/
moonlight-16b-a3b-1chip/reference.py``), at a small size on the CPU
with seeded weights and a seeded balancing bias: hidden 64, 4 heads of
16 nope + 8 rope / 16 v over a latent of 32, a dense SwiGLU layer of 96
then 8 experts of 32, top-3, 2 shared, sigmoid scores x 2.446; in
float32 and bfloat16, whole and over the last positions. And the
check's tolerances against the three faults ISSUE 29 names: attention
without the rope part of the head, a bias that also enters the gates,
a missing ``routed_scaling_factor``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.models import transformer
from elasticdl_tpu.ops import moe as moe_ops

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MOONLIGHT = os.path.join(
    REPO, "benchmark", "configs", "moonlight-16b-a3b-1chip")
SEQ, VOCAB = 128, 512
# At this size a sequence has 384 (token, slot) pairs among 8 experts,
# and bfloat16 flips 4 of them (1.04%: 0.1443, under the check's 0.20,
# which is sized for 49,152 pairs among 64 experts)


def real_config():
    with open(os.path.join(MOONLIGHT, "config.json")) as f:
        return json.load(f)


def small_config(**changes):
    config = real_config()
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=8, num_experts_per_tok=3, vocab_size=VOCAB,
        compute_dtype="")
    config.update(changes)
    return config


def build(config, tokens, remat_policy="none", last=None, model=None):
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(MOONLIGHT, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(MOONLIGHT, "zoo.py"),
        "reference": os.path.join(MOONLIGHT, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy},
                 "last_positions": last},
    }
    return check.build(spec, tokens, model=model)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(1)
    return (rng.zipf(1.2, size=SEQ) % VOCAB).astype(np.int32)


def run(parts, tokens, reference=None):
    """``lib/refcheck.py``'s order: the seeded variables (with the
    system side's one run in them), what ``system`` returns of it, and
    the reference (another build's, where a test holds a wrong system
    to the true configuration) applying that run's experts."""
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(5), tokens)
    got = jax.jit(parts["system"])(variables, tokens)
    want = jax.jit((reference or parts)["reference"])(variables, tokens)
    return variables, got, want


@pytest.fixture(scope="module")
def reference(tokens):
    """(parts, seeded variables, the float32 reference's output)."""
    parts = build(small_config(), tokens)
    variables, _, want = run(parts, tokens)
    return parts, variables, want


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(MOONLIGHT, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source


def test_init_draws_a_bias_the_selection_depends_on(reference):
    _, variables, want = reference
    bias = variables["moe_state"]["block_1"]["moe_mlp"][
        "e_score_correction_bias"]
    assert bias.shape == (8,) and float(jnp.abs(bias).max()) > 0.02
    assert set(variables["moe_state"]) == {"block_1"}  # block_0 is dense
    ref = refcheck.sys.modules["edlbench_reference"]
    zero = {"block_1": jnp.zeros_like(bias)}
    unbiased = ref.logits_loss_and_choices(
        variables["params"], zero, jnp.asarray(
            np.arange(SEQ) % VOCAB, jnp.int32), small_config())[2]
    biased = ref.logits_loss_and_choices(
        variables["params"], {"block_1": bias}, jnp.asarray(
            np.arange(SEQ) % VOCAB, jnp.int32), small_config())[2]
    assert (np.sort(unbiased, -1) != np.sort(biased, -1)).any()


@pytest.mark.parametrize("remat_policy", ["none", "dots", "flash", "full"])
def test_reference_equals_the_model_in_float32(
        tokens, reference, remat_policy):
    parts, _, want = reference
    _, got, _ = run(build(small_config(), tokens, remat_policy), tokens)
    assert set(got) == {"logits", "loss", "choices"} | {
        "grad:" + leaf for leaf in small_config()["check_leaves"]}
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    # same function, same precision: rounding order only, and not one
    # (token, slot) choice differs
    assert max(errors.values()) < 1e-4, errors
    assert errors["choices"] == 0.0
    assert got["logits"].shape == (SEQ, VOCAB)
    assert got["choices"].shape == (1, SEQ, 8)  # one expert layer of two
    np.testing.assert_array_equal(np.asarray(got["choices"]).sum(-1), 3)


def test_the_last_positions_are_compared_over_the_whole_context(
        tokens, reference):
    parts, _, whole = reference
    _, got, want = run(build(small_config(), tokens, last=32), tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and max(errors.values()) < 1e-4, errors
    assert want["logits"].shape == (32, VOCAB)
    np.testing.assert_allclose(
        np.asarray(want["logits"]), np.asarray(whole["logits"][-32:]),
        atol=1e-5)
    assert want["choices"].shape == (1, SEQ, 8)
    assert float(want["loss"]) != pytest.approx(float(whole["loss"]))


def test_the_loss_adds_the_weighted_sequence_balance(tokens, reference):
    _, variables, want = reference
    ref = refcheck.sys.modules["edlbench_reference"]
    bias = {"block_1": variables["moe_state"]["block_1"]["moe_mlp"][
        "e_score_correction_bias"]}

    def loss(alpha):
        config = small_config()
        config["assumed"] = dict(config["assumed"], aux_loss_alpha=alpha)
        return float(ref.logits_loss_and_choices(
            variables["params"], bias, tokens, config)[1])

    balance = loss(1.0) - loss(0.0)
    # one expert layer, one sequence: 1 for a uniform router, at most E
    assert 1.0 <= balance < 8.0
    assert float(want["loss"]) == pytest.approx(
        loss(0.0) + 0.001 * balance, rel=1e-5)


def test_bfloat16_system_path_is_inside_the_tolerance(tokens):
    built = build(small_config(compute_dtype="bfloat16"), tokens, "dots")
    variables, got, want = run(built, tokens)
    errors, ok = refcheck.compare(got, want, built["tolerance"])
    assert ok, errors
    # and it is a different computation: the tolerance is not vacuous
    assert errors["logits"] > 1e-4
    # the reference applied the experts of the run it is compared with
    applied = np.asarray(variables["system_run"]["applied_experts"])
    assert applied.shape == (1, SEQ, 3)
    hot = np.zeros((1, SEQ, 8))
    np.put_along_axis(hot, applied, 1.0, axis=-1)
    np.testing.assert_array_equal(hot, np.asarray(got["choices"]))


def test_nothing_of_the_check_goes_through_the_host(tokens):
    """A program with a host callback is never written to the compile
    cache, and one with a run's experts as a constant is never found
    there: each cost every run of the cell a compilation."""
    built = build(small_config(compute_dtype="bfloat16"), tokens, "dots")
    key = jax.random.PRNGKey(5)
    variables = jax.eval_shape(built["init"], key, tokens)
    for part, first in (("init", key), ("system", variables),
                        ("reference", variables)):
        closed = jax.make_jaxpr(built[part])(first, tokens)
        assert "callback" not in str(closed), part
        assert all(np.size(const) < SEQ for const in closed.consts), part


def wrong_system_against_the_reference(tokens, wrong):
    """The bfloat16 check with ``wrong`` as the system's side: the
    reference (true configuration) applies the experts the wrong system
    chose, as it does on the chip."""
    truth = build(small_config(compute_dtype="bfloat16"), tokens, "dots")
    _, got, want = run(wrong, tokens, reference=truth)
    return refcheck.compare(got, want, truth["tolerance"])


def test_attention_without_the_rope_part_fails(tokens, monkeypatch):
    """128 of 192 lanes at the published widths, 16 of 24 here: q and k
    cut to their nope part before the scores."""
    plain = transformer.dot_product_attention
    monkeypatch.setattr(
        transformer, "dot_product_attention",
        lambda q, k, v, **kw: plain(
            q[..., :16], k[..., :16], v,
            **dict(kw, sm_scale=24 ** -0.5)))
    wrong = build(small_config(compute_dtype="bfloat16"), tokens, "dots")
    errors, ok = wrong_system_against_the_reference(tokens, wrong)
    assert not ok, errors
    assert errors["logits"] > 0.03, errors


def test_a_bias_that_also_enters_the_gates_fails(tokens, monkeypatch):
    plain = moe_ops.route_top_k

    def biased_gates(logits, k, normalize=False, scoring="softmax",
                     bias=None, scale=1.0):
        _, experts, probs = plain(logits, k, normalize, scoring, bias, scale)
        scores = jax.nn.sigmoid(logits.astype(jnp.float32)) + bias
        gates = jnp.take_along_axis(scores, experts, axis=-1)
        gates = gates / gates.sum(-1, keepdims=True) * scale
        return gates, experts, probs

    monkeypatch.setattr(moe_ops, "route_top_k", biased_gates)
    wrong = build(small_config(compute_dtype="bfloat16"), tokens, "dots")
    errors, ok = wrong_system_against_the_reference(tokens, wrong)
    assert not ok, errors
    # the selection is the right one: only the arithmetic names it
    assert errors["choices"] <= 0.20, errors


def test_a_missing_scaling_factor_fails(tokens):
    wrong = build(
        small_config(compute_dtype="bfloat16", routed_scaling_factor=1.0),
        tokens, "dots")
    errors, ok = wrong_system_against_the_reference(tokens, wrong)
    assert not ok, errors
    assert errors["choices"] <= 0.20, errors


def test_a_selection_that_ignores_the_bias_fails_on_choices(
        tokens, monkeypatch):
    plain = moe_ops.route_top_k
    monkeypatch.setattr(
        moe_ops, "route_top_k",
        lambda logits, k, normalize=False, scoring="softmax", bias=None,
        scale=1.0: plain(logits, k, normalize, scoring, None, scale))
    wrong = build(small_config(compute_dtype="bfloat16"), tokens, "dots")
    errors, ok = wrong_system_against_the_reference(tokens, wrong)
    assert not ok and errors["choices"] > 0.20, errors


def test_the_zoo_reads_every_size_and_refuses_what_it_cannot_build():
    zoo = refcheck.load_by_path(
        "edlbench_zoo", os.path.join(MOONLIGHT, "zoo.py"))
    config = real_config()
    model = zoo.model_from_config(config, remat_policy="dots")
    assert (model.embed_dim, model.num_heads, model.num_experts,
            model.top_k, model.expert_dim, model.shared_experts) == (
                2048, 16, 64, 6, 1408, 2)
    assert model.latent == transformer.LatentDims(512, 128, 64, 128)
    assert (model.first_k_dense, model.dense_act, model.dense_dim) == (
        1, "swiglu", 11264)
    assert (model.scoring, model.gate_scale, model.normalize_gates) == (
        "sigmoid", 2.446, True)
    assert (model.rope_theta, model.norm, model.norm_eps) == (
        50000.0, "rmsnorm", 1e-5)
    assert (model.aux_loss_weight, model.z_loss_weight, model.seq_aux,
            model.bias_update_speed) == (0.001, 0.0, True, 0.001)
    assert model.embed_init_std == 1.0
    # AdamW under the warm-up: no update at step 0, 3e-4 / 2000 a step
    params = {"w": jnp.ones((2,))}
    opt = zoo.optimizer()
    state = opt.init(params)
    steps = []
    for _ in range(3):
        updates, state = opt.update({"w": jnp.ones((2,))}, state, params)
        steps.append(float(-updates["w"][0]))
    assert steps[0] == 0.0
    assert steps[2] == pytest.approx(2 * 3e-4 / 2000 * (1 + 0.01), rel=1e-3)
    assert (model.vocab_size, model.num_layers) == (
        config["vocab_size"], 2)
    assert model.dispatch_impl == "sorted" and model.moe_every == 1
    assert model.remat and model.remat_policy == "dots"
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("topk_group", 4), ("rope_scaling", {"type": "yarn"}),
                       ("scoring_func", "softmax"),
                       ("num_key_value_heads", 4)):
        with pytest.raises(ValueError):
            zoo.model_from_config(dict(config, **{key: value}))
