"""The repo's ``MoeTransformerLM`` as the Ouro zoo builds it against the
configuration's plain reference
(``benchmark/configs/ouro-2.6b-1chip/reference.py``), at a preset size
on the CPU with seeded weights (``preset/configs/tiny-ouro``): hidden
64, two sandwich-normed blocks of 4 heads of 16 and a SwiGLU of 96 run
four times over one set of weights, one gate, 128 tokens; in float32,
whole and over the last positions: every exit's logits, the exit
distribution, the loss, its named parts and the gradients. Every
variant of the system side that ``check.py`` names is outside its
tolerances."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark.lib import refcheck
from scripts.xing_precision import Rounded

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OURO = os.path.join(REPO, "benchmark", "configs", "ouro-2.6b-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-ouro",
    "config.json")
SEQ, VOCAB, PASSES = 128, 512, 4


def small_config(**changes):
    with open(TINY) as f:
        config = json.load(f)
    config["compute_dtype"] = ""
    config.update(changes)
    return config


def load(name):
    return refcheck.load_by_path(
        "edlbench_" + name, os.path.join(OURO, name + ".py"))


def build(config, tokens, remat_policy="none", last=None, model=None,
          reference_remat=False):
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(OURO, "zoo.py"),
        "reference": os.path.join(OURO, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy},
                 "last_positions": last,
                 "reference_remat": reference_remat},
    }
    return load("check").build(spec, tokens, model=model)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(1)
    return (rng.zipf(1.2, size=SEQ) % VOCAB).astype(np.int32)


def run(parts, tokens):
    """``lib/refcheck.py``'s order."""
    params = jax.jit(parts["init"])(jax.random.PRNGKey(5), tokens)
    got = jax.jit(parts["system"])(params, tokens)
    return params, got, jax.jit(parts["reference"])(params, tokens)


def system_side(model, params, tokens):
    """The system side of a stand-in ``model`` on the fixture's
    parameters (a stand-in's ``init`` is the zoo model's)."""
    return jax.jit(build(small_config(), tokens, model=model)["system"])(
        params, tokens)


@pytest.fixture(scope="module")
def reference(tokens):
    parts = build(small_config(), tokens)
    params, got, want = run(parts, tokens)
    return parts, params, got, want


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(OURO, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "flax" not in source.replace(
        "no flax", "")
    # the loop is a loop, the distribution a running product
    assert "for t in range(config[\"total_ut_steps\"])" in source
    assert "lax.scan" not in source and "log_sigmoid" not in source


def test_the_zoo_builds_the_published_block(reference):
    _, params, _, _ = reference
    assert set(params) == {"wte", "lm_head", "ln_f", "early_exit_gate",
                           "block_0", "block_1"}
    for block in ("block_0", "block_1"):
        assert set(params[block]) == {
            "attn", "ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out",
            "mlp_gate", "mlp_up", "mlp_down"}
        attn = params[block]["attn"]
        assert set(attn) == {"query", "key", "value", "out_proj"}
        assert attn["query"]["kernel"].shape == (64, 4, 16)
        assert params[block]["mlp_down"]["kernel"].shape == (96, 64)
        assert set(params[block]["ln_attn_out"]) == {"scale"}
    assert params["early_exit_gate"]["kernel"].shape == (64, 1)
    assert params["early_exit_gate"]["bias"].shape == (1,)
    assert params["lm_head"]["kernel"].shape == (64, VOCAB)
    zoo = load("zoo")
    model = zoo.model_from_config(small_config())
    assert (model.looped.passes, model.looped.beta) == (PASSES, 0.05)
    assert model.sandwich and model.first_k_dense == model.num_layers == 2
    assert (model.rope_theta, model.dense_act, model.dense_dim) == (
        1e6, "swiglu", 96)
    assert model.norm == "rmsnorm" and not model.tie_embeddings
    for key, value in (("hidden_act", "gelu"), ("rope_scaling", {}),
                       ("tie_word_embeddings", True),
                       ("early_exit_threshold", 0.5),
                       ("num_key_value_heads", 2),
                       ("use_sliding_window", True)):
        with pytest.raises(ValueError, match=key):
            zoo.model_from_config(small_config(**{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        zoo.model_from_config(small_config(
            layer_types=["sliding_attention"] * 4))


NAMES = {"logits", "exit_probs", "loss", "term:expected_ce",
         "term:exit_entropy", "span_ce"} | {
    "logits:exit_%d" % t for t in range(PASSES - 1)} | {
    "term:ce_exit_%d" % t for t in range(PASSES)}


@pytest.mark.parametrize("remat_policy", ["none", "flash"])
def test_reference_equals_the_model_in_float32(
        tokens, reference, remat_policy):
    parts, _, got, want = reference
    if remat_policy != "none":
        _, got, _ = run(build(small_config(), tokens, remat_policy), tokens)
    assert set(got) == NAMES | {
        "grad:" + leaf for leaf in small_config()["check_leaves"]}
    # the bias's one number stands beside a unit: its error is absolute
    assert got["grad:early_exit_gate/kernel"].shape == (64, 1)
    assert got["grad:early_exit_gate/bias"].shape == (2,)
    assert float(got["grad:early_exit_gate/bias"][1]) == 1.0
    # over ALL positions: the window's means where the window is the
    # sequence
    assert got["span_ce"].shape == (PASSES, SEQ - 1)
    # the head's gradient is the whole sequence's loss's
    assert got["grad:lm_head/kernel"].shape == (64, VOCAB)
    np.testing.assert_allclose(
        np.asarray(got["span_ce"]).mean(axis=1),
        [float(got["term:ce_exit_%d" % t]) for t in range(PASSES)],
        rtol=1e-5)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    # float32 against float32: the sums' order and nothing else
    assert errors["logits"] < 1e-4 and errors["loss"] < 1e-5, errors
    assert errors["exit_probs"] < 1e-5
    assert max(e for n, e in errors.items() if n.startswith("grad")) < 2e-3
    assert got["logits"].shape == got["logits:exit_0"].shape == (SEQ, VOCAB)
    assert got["exit_probs"].shape == (PASSES, SEQ)
    np.testing.assert_allclose(
        np.asarray(got["exit_probs"]).sum(axis=0), 1.0, atol=1e-5)
    # the loss is its parts
    assert float(got["loss"]) == pytest.approx(
        float(got["term:expected_ce"])
        - 0.05 * float(got["term:exit_entropy"]), rel=1e-5)
    # every exit says something else
    assert float(np.abs(np.asarray(got["logits"]) - np.asarray(
        got["logits:exit_0"])).max()) > 0.1


def test_the_last_positions_are_the_whole_run_s(tokens, reference):
    _, _, whole, _ = reference
    parts = build(small_config(), tokens, last=32)
    _, got, want = run(parts, tokens)
    assert got["logits"].shape == (32, VOCAB)
    assert got["exit_probs"].shape == (PASSES, 32)
    np.testing.assert_allclose(
        got["logits"], whole["logits"][-32:], atol=1e-4)
    np.testing.assert_allclose(
        got["exit_probs"], whole["exit_probs"][:, -32:], atol=1e-5)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and errors["logits"] < 1e-4, errors
    assert float(got["loss"]) != pytest.approx(float(whole["loss"]))
    # what runs over ALL positions does not move with the window
    for name in ("span_ce", "grad:lm_head/kernel"):
        np.testing.assert_allclose(
            got[name], whole[name], rtol=1e-5, atol=1e-7, err_msg=name)
    assert errors["span_ce"] < 1e-5, errors


def test_the_span_is_the_step_s_chunked_head(tokens, reference, monkeypatch):
    """The loss over ALL positions is ``ops/looped_exit.py``'s own call:
    at a chunk that does not divide the sequence (three chunks, the last
    padded) its cross-entropies, its value and the head's gradient,
    added up over the chunks, are the reference's."""
    from elasticdl_tpu.ops import looped_exit

    parts, params, whole, want = reference
    monkeypatch.setattr(looped_exit, "EXIT_CHUNK", 48)
    got = jax.jit(build(small_config(), tokens, last=32)["system"])(
        params, tokens)
    for name in ("span_ce", "grad:lm_head/kernel"):
        np.testing.assert_allclose(
            got[name], whole[name], rtol=2e-4, atol=1e-6, err_msg=name)
        assert float(refcheck.rel_rms(got[name], want[name])) < 1e-3, name


def test_a_gate_s_bias_left_untrained_is_refused(reference):
    """The bias's gradient is one number that may nearly cancel: it is
    held by its ABSOLUTE error (the pair (number, 1.0)), so a step that
    leaves the bias alone is outside, whatever the kernel's 64 numbers
    read."""
    parts, _, got, want = reference
    name = "grad:early_exit_gate/bias"
    assert abs(float(want[name][0])) > 2 * parts["tolerance"][name]
    errors, ok = refcheck.compare(
        dict(got, **{name: got[name].at[0].set(0.0)}), want,
        parts["tolerance"])
    assert not ok and errors[name] == pytest.approx(
        abs(float(want[name][0])), rel=0.02)
    assert errors[name] > parts["tolerance"][name]
    assert all(error <= refcheck.tolerance_of(other, parts["tolerance"])
               for other, error in errors.items() if other != name)


def test_the_reference_in_blocks_is_the_reference(tokens, reference):
    """``reference_remat`` is memory, not mathematics: the softmax a
    block of queries, the MLP a block of rows and the whole sequence's
    head a block of positions at a time, each block application under
    ``jax.checkpoint``, give what the whole arrays give."""
    import sys

    _, params, _, want = reference
    parts = build(small_config(), tokens, last=32, reference_remat=True)
    sys.modules["edlbench_reference"].QUERY_BLOCK = 32
    got = jax.jit(parts["reference"])(params, tokens)
    whole = jax.jit(build(small_config(), tokens, last=32)["reference"])(
        params, tokens)
    assert set(got) == set(whole)
    for name, value in whole.items():
        np.testing.assert_allclose(
            got[name], value, rtol=2e-4, atol=1e-6, err_msg=name)
    np.testing.assert_allclose(
        got["span_ce"], want["span_ce"], rtol=1e-5, atol=1e-6)


def test_the_pieces_are_the_model(tokens, reference):
    """``check.py:Pieces`` with nothing wrong is the model itself."""
    _, params, whole, _ = reference
    check = load("check")
    model = check.Pieces(load("zoo").model_from_config(small_config()))
    got = system_side(model, params, tokens)
    for name, value in whole.items():
        np.testing.assert_allclose(
            got[name], value, rtol=1e-4, atol=1e-6, err_msg=name)
    with pytest.raises(ValueError, match="one of"):
        check.Pieces(model.model, "anything else")


# what each variant has to move past its bound, at the least
MOVES = {
    "three_passes": {"logits", "grad:block_0/attn/query/kernel"},
    "untied": {"logits", "logits:exit_1", "grad:block_1/mlp_down/kernel"},
    "ln_f_once": {"logits:exit_0", "exit_probs", "loss"},
    "no_inner_norms": {"logits:exit_0", "logits",
                       "grad:block_1/ln_attn_out/scale"},
    "gated_last_exit": {"exit_probs", "loss", "term:exit_entropy",
                        "grad:early_exit_gate/kernel",
                        "grad:early_exit_gate/bias"},
    "float8_weights": {"logits", "logits:exit_0", "grad:lm_head/kernel"},
}


@pytest.mark.parametrize("variant", sorted(MOVES))
def test_a_wrong_step_is_outside_the_tolerances(tokens, reference, variant):
    parts, params, _, want = reference
    check = load("check")
    assert set(MOVES) == set(check.WRONG) | {"float8_weights"}
    model = load("zoo").model_from_config(small_config())
    # every parameter rounded to float8 e4m3 on the way in
    model = (Rounded(model, 4, 3) if variant == "float8_weights"
             else check.Pieces(model, variant))
    # the same parameters and the same reference: the system side alone
    got = system_side(model, params, tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok, errors
    outside = {name for name, error in errors.items()
               if not error <= refcheck.tolerance_of(
                   name, parts["tolerance"])}
    assert MOVES[variant] <= outside, (outside, errors)
    if variant == "three_passes":
        # the exits before the last are what they were
        assert errors["logits:exit_0"] < 1e-4
    if variant == "gated_last_exit":
        assert errors["logits"] < 1e-4
        assert float(np.asarray(got["exit_probs"]).sum(axis=0).max()) < 1


def test_bfloat16_compute_at_this_size(tokens):
    """The stated precision (bfloat16 operands, float32 accumulation,
    statistics, gate and loss) against the float32 reference at this
    small size. Widths of 16 to 96 average less than the cell's 128 to
    5632, so the small size's own bounds are wider than ``check.py``'s,
    which PERF.md Section 6 holds against the chip's readings."""
    parts = build(small_config(compute_dtype="bfloat16"), tokens)
    _, got, want = run(parts, tokens)
    stated, _ = refcheck.compare(got, want, parts["tolerance"])
    assert stated["logits"] < 0.1 and stated["logits:exit_0"] < 0.1, stated
    assert stated["loss"] < 0.01 and stated["exit_probs"] < 0.02, stated
    assert max(e for n, e in stated.items() if n.startswith("term")) < 0.01
