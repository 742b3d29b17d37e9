"""The repo's ``MoeTransformerLM`` as the SDAR zoo builds it, trained by
block diffusion, against the configuration's plain reference
(``benchmark/configs/sdar-30b-a3b-1chip/reference.py``), at a preset
size on the CPU with seeded weights (``preset/configs/tiny-sdar``):
hidden 64, four layers of grouped-query attention (8 query heads of 16
over 2 kv heads, a norm a head), 16 experts of 32 of which 4 are held,
top-3, no shared expert; 128 tokens in blocks of 4, so 256 positions a
layer; in float32 under every remat policy, whole and over the last
positions. And the check's names against faults of the kinds ISSUE 35's
equations rule out: a causal mask in the block mask's place, positions
that run on over the clean copy, a loss that is not weighted, another
chip's experts, a noise the two sides do not share."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.ops import block_diffusion
from elasticdl_tpu.ops import flash_attention as F

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SDAR = os.path.join(REPO, "benchmark", "configs", "sdar-30b-a3b-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-sdar",
    "config.json")
SEQ, VOCAB = 128, 512


def small_config(**changes):
    with open(TINY) as f:
        config = json.load(f)
    config["compute_dtype"] = ""
    config.update(changes)
    return config


def build(config, tokens, remat_policy="none", last=None, model=None,
          draw=None):
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(SDAR, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(SDAR, "zoo.py"),
        "reference": os.path.join(SDAR, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy},
                 "last_positions": last},
    }
    return check.build(spec, tokens, model=model, draw=draw)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(1)
    return (rng.zipf(1.2, size=SEQ) % VOCAB).astype(np.int32)


def run(parts, tokens, reference=None):
    """``lib/refcheck.py``'s order."""
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(5), tokens)
    got = jax.jit(parts["system"])(variables, tokens)
    want = jax.jit((reference or parts)["reference"])(variables, tokens)
    return variables, got, want


@pytest.fixture(scope="module")
def reference(tokens):
    parts = build(small_config(), tokens)
    variables, got, want = run(parts, tokens)
    return parts, variables, got, want


def zoo():
    return refcheck.sys.modules["edlbench_zoo"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(SDAR, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    # the mask is the issue's four cases, written out
    assert "(half_q == 0) & (half_k == 1) & (blk_k < blk_q)" in source


def test_the_reference_s_mask_is_the_layout_s(tokens):
    ref = refcheck.load_by_path(
        "edlbench_reference", os.path.join(SDAR, "reference.py"))
    pos = np.arange(2 * SEQ)
    np.testing.assert_array_equal(
        ref.may_see(pos[:, None], pos[None, :], SEQ, 4),
        F.BlockDiffusion(SEQ, 4).keep(pos[:, None], pos[None, :]))


def test_the_zoo_builds_the_published_block(reference):
    _, variables, _, _ = reference
    params = variables["params"]
    attn = params["block_3"]["attn"]
    assert attn["query"]["kernel"].shape == (64, 8, 16)
    assert attn["key"]["kernel"].shape == (64, 2, 16)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert set(params["block_0"]["moe_mlp"]) == {
        "router", "w_gate", "w_up", "w_down"}  # no shared expert
    assert params["block_0"]["moe_mlp"]["router"]["kernel"].shape == (64, 16)
    assert params["block_0"]["moe_mlp"]["w_gate"].shape == (4, 64, 32)
    model = zoo().model_from_config(small_config())
    assert (model.objective, model.bd_block, model.bd_mask_id,
            model.bd_t_min) == ("block_diffusion", 4, 511, 0.001)
    with pytest.raises(ValueError, match="held_experts says"):
        zoo().model_from_config(small_config(num_experts=8))
    with pytest.raises(ValueError, match="attention_bias"):
        zoo().model_from_config(small_config(attention_bias=True))
    cosine = dict(small_config()["assumed"], noise_schedule="cosine")
    with pytest.raises(ValueError, match="linear"):
        zoo().model_from_config(small_config(assumed=cosine))


@pytest.mark.parametrize("remat_policy", ["none", "dots", "flash", "full"])
def test_reference_equals_the_model_in_float32(
        tokens, reference, remat_policy):
    parts, _, _, want = reference
    _, got, _ = run(build(small_config(), tokens, remat_policy), tokens)
    assert set(got) == {
        "logits", "loss", "choices", "dropped_pairs_plus_one",
        "noisy_tokens", "weights"} | {
        "grad:" + leaf for leaf in small_config()["check_leaves"]}
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    # float32 against float32: the sums' order and nothing else
    assert errors["logits"] < 1e-4 and errors["loss"] < 1e-5, errors
    assert max(e for n, e in errors.items() if n.startswith("grad")) < 5e-4
    assert errors["choices"] == 0 and errors["dropped_pairs_plus_one"] == 0
    assert errors["noisy_tokens"] == 0 and errors["weights"] == 0
    # L positions of logits; both copies reach every expert layer
    assert got["logits"].shape == (SEQ, VOCAB)
    assert got["choices"].shape == (4, 2 * SEQ, 16)
    # about half the tokens are masked, and the weights average 1
    masked = np.asarray(got["weights"]) > 0
    assert 0.3 < masked.mean() < 0.7
    np.testing.assert_array_equal(
        np.asarray(got["noisy_tokens"])[masked], 1.0 + 511)


def test_the_last_positions_are_the_whole_run_s(tokens, reference):
    _, _, whole, _ = reference
    parts = build(small_config(), tokens, last=32)
    _, got, want = run(parts, tokens)
    assert got["logits"].shape == (32, VOCAB)
    np.testing.assert_allclose(
        got["logits"], whole["logits"][-32:], atol=1e-4)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and errors["logits"] < 1e-4, errors
    # the noise and the routing are compared over the whole run
    assert got["choices"].shape == (4, 2 * SEQ, 16)
    assert got["weights"].shape == (SEQ,)
    assert float(got["loss"]) != pytest.approx(float(whole["loss"]))


def test_a_dropped_pair_fails_the_check(tokens, reference):
    parts, _, _, _ = reference
    tight = small_config(expert_rows={"held_rows": 64})
    _, got, want = run(build(tight, tokens), tokens)
    assert float(got["dropped_pairs_plus_one"]) > 1
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok and errors["dropped_pairs_plus_one"] > 0


def test_a_noise_the_sides_do_not_share_fails_the_check(tokens, reference):
    """The reference draws from another key than the system did: the
    noise's own names fail, exactly, whatever the logits do."""
    parts, _, _, _ = reference

    def other(key, *args):
        return block_diffusion.noise(jax.random.fold_in(key, 1), *args)

    _, got, want = run(build(small_config(), tokens, draw=other), tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok
    assert errors["noisy_tokens"] > 0 and errors["weights"] > 0


def _wrong(tokens, reference, patch, **changes):
    """A system built wrong against the true configuration's
    reference."""
    parts, _, _, _ = reference
    model = zoo().model_from_config(small_config()).clone(**changes)
    with pytest.MonkeyPatch.context() as monkeypatch:
        patch(monkeypatch)
        wrong = build(small_config(), tokens, model=model)
        _, got, want = run(wrong, tokens, reference=parts)
    return refcheck.compare(got, want, parts["tolerance"])


def _causal(monkeypatch):
    monkeypatch.setattr(F, "BlockDiffusion", lambda half, block: F.CAUSAL)


def _running_positions(monkeypatch):
    monkeypatch.setattr(
        block_diffusion, "assemble", lambda noisy, clean: (
            jnp.concatenate([noisy, clean], -1),
            jnp.arange(2 * clean.shape[-1], dtype=jnp.int32)))


def _unweighted(monkeypatch):
    monkeypatch.setattr(
        block_diffusion, "weighted_loss",
        lambda targets, logits, weights:
        block_diffusion.sparse_softmax_cross_entropy(
            targets, logits).mean(-1))


@pytest.mark.parametrize("patch,changes,name", [
    (_causal, {}, "logits"),
    (_running_positions, {}, "logits"),
    (_unweighted, {}, "loss"),
    (lambda m: None, dict(held_experts=(0, 4)), "logits"),
    (lambda m: None, dict(bd_block=8), "logits"),
    (lambda m: None, dict(head_norm=None), None),
], ids=["causal-mask", "positions-run-on", "unweighted-loss",
        "another-chip-s-experts", "blocks-of-8", "no-head-norm"])
def test_a_wrong_step_is_outside_the_tolerances(
        tokens, reference, patch, changes, name):
    if name is None:
        # another parameter tree: the system cannot even read the
        # reference's parameters
        with pytest.raises(Exception):
            _wrong(tokens, reference, patch, **changes)
        return
    errors, ok = _wrong(tokens, reference, patch, **changes)
    assert not ok
    assert errors[name] > refcheck.tolerance_of(
        name, reference[0]["tolerance"]), errors


def test_bfloat16_compute_at_this_size(tokens):
    """The stated precision (bfloat16 operands, float32 accumulation
    and statistics) against the float32 reference at this small size:
    the noise exact, nothing dropped, the loss and the logits close.
    Widths of 16 to 64 average less than the cell's 128 to 2048, so the
    small size's own bounds are wider than ``check.py``'s, which PERF.md
    Section 6 holds against the chip's readings."""
    config = small_config(compute_dtype="bfloat16")
    parts = build(config, tokens)
    _, got, want = run(parts, tokens)
    stated, _ = refcheck.compare(got, want, parts["tolerance"])
    assert stated["dropped_pairs_plus_one"] == 0
    assert stated["noisy_tokens"] == 0 and stated["weights"] == 0
    assert stated["logits"] < 0.1 and stated["loss"] < 0.01, stated
