"""The repo's ``MoeTransformerLM`` as the Qwen3-Next zoo builds it
against the configuration's plain reference (``benchmark/configs/
qwen3-next-80b-a3b-1chip/reference.py``), at a small size on the CPU
with seeded weights: hidden 64, one period of three Gated DeltaNet
layers (2 key / 4 value heads of 16) and one gated grouped-query layer
(4 query heads of 32 over 2 kv heads, rotary on 8 lanes), 16 experts of
32 of which 4 are held, top-3, one shared expert behind its gate; in
float32 under every remat policy, whole and over the last positions.
And the check's names against faults of the kinds ISSUE 31's equations
rule out: a rule without its decay, rotary on the whole head, an
ungated shared expert, a layer that computes the wrong share."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.ops import gated_delta

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
QWEN = os.path.join(REPO, "benchmark", "configs", "qwen3-next-80b-a3b-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs",
    "tiny-qwen3next", "config.json")
SEQ, VOCAB = 128, 512


def small_config(**changes):
    with open(TINY) as f:
        config = json.load(f)
    config["compute_dtype"] = ""
    config.update(changes)
    return config


def build(config, tokens, remat_policy="none", last=None, model=None):
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(QWEN, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(QWEN, "zoo.py"),
        "reference": os.path.join(QWEN, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy},
                 "last_positions": last},
    }
    return check.build(spec, tokens, model=model)


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
    with open(os.path.join(QWEN, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(token" in source  # one token a step


def test_the_zoo_builds_the_published_pattern(reference):
    _, variables, _, _ = reference
    params = variables["params"]
    kinds = ["linear" if "A_log" in params["block_%d" % i]["attn"]
             else "full" for i in range(4)]
    assert kinds == ["linear", "linear", "linear", "full"]
    assert params["block_3"]["attn"]["query"]["kernel"].shape == (64, 4, 64)
    assert params["block_3"]["attn"]["key"]["kernel"].shape == (64, 2, 32)
    assert params["block_0"]["moe_mlp"]["router"]["kernel"].shape == (64, 16)
    assert params["block_0"]["moe_mlp"]["w_gate"].shape == (4, 64, 32)
    assert zoo().layer_kinds({"full_attention_interval": 4}) == (
        "linear", "linear", "linear", "full")
    with pytest.raises(ValueError, match="held_experts says"):
        zoo().model_from_config(small_config(num_experts=8))
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        zoo().model_from_config(small_config(decoder_sparse_step=2))


@pytest.mark.parametrize("remat_policy", ["none", "dots", "flash", "full"])
def test_reference_equals_the_model_in_float32(
        tokens, reference, remat_policy):
    parts, _, _, want = reference
    _, got, _ = run(build(small_config(), tokens, remat_policy), tokens)
    assert set(got) == {
        "logits", "loss", "choices", "dropped_pairs_plus_one"} | {
        "grad:" + leaf for leaf in small_config()["check_leaves"]}
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    # float32 against float32: the sums' order and nothing else
    assert errors["logits"] < 1e-4 and errors["loss"] < 1e-5, errors
    assert max(e for n, e in errors.items() if n.startswith("grad")) < 5e-4
    assert errors["choices"] == 0 and errors["dropped_pairs_plus_one"] == 0


def test_the_last_positions_are_the_whole_run_s(tokens, reference):
    _, _, whole, _ = reference
    parts = build(small_config(), tokens, last=32)
    _, got, want = run(parts, tokens)
    assert got["logits"].shape == (32, VOCAB)
    np.testing.assert_allclose(
        got["logits"], whole["logits"][-32:], atol=1e-4)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and errors["logits"] < 1e-4, errors
    # the routing is compared over the whole context either way
    assert got["choices"].shape == (4, SEQ, 16)


def test_a_dropped_pair_fails_the_check(tokens, reference):
    parts, _, _, _ = reference
    tight = small_config(expert_rows={"held_rows": 64})
    _, got, want = run(build(tight, tokens), tokens)
    assert float(got["dropped_pairs_plus_one"]) > 1
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok and errors["dropped_pairs_plus_one"] > 0


def _wrong(tokens, reference, **changes):
    """A system built wrong against the true configuration's
    reference."""
    parts, _, _, _ = reference
    model = zoo().model_from_config(small_config()).clone(**changes)
    wrong = build(small_config(), tokens, model=model)
    _, got, want = run(wrong, tokens, reference=parts)
    return refcheck.compare(got, want, parts["tolerance"])


@pytest.mark.parametrize("changes,name", [
    (dict(rotary_dim=None), "grad:block_3/attn/query/kernel"),
    (dict(shared_gate=False), None),
    (dict(output_gate=None), None),
    (dict(held_experts=(0, 4)), "logits"),
    (dict(head_norm=None), None),
], ids=["rotary-on-the-whole-head", "ungated-shared-expert",
        "ungated-attention", "another-chip-s-experts", "no-head-norm"])
def test_a_wrong_block_is_outside_the_tolerances(
        tokens, reference, changes, name):
    if name is None:
        # another parameter tree: the system cannot even read the
        # reference's parameters
        with pytest.raises(Exception):
            _wrong(tokens, reference, **changes)
        return
    errors, ok = _wrong(tokens, reference, **changes)
    assert not ok
    assert errors[name] > refcheck.tolerance_of(name, {
        "logits": 0.03, "grad": 0.04})


def test_a_rule_without_its_decay_is_outside_the_tolerances(
        tokens, reference, monkeypatch):
    parts, _, _, _ = reference
    rule = gated_delta.gated_delta_rule
    monkeypatch.setattr(
        gated_delta, "gated_delta_rule",
        lambda q, k, v, g, beta, **kw: rule(q, k, v, 0 * g, beta, **kw))
    _, got, want = run(build(small_config(), tokens), tokens, parts)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok and errors["logits"] > 0.03, errors


def test_bfloat16_decay_is_outside_the_tolerances_bfloat16_compute_inside(
        tokens, monkeypatch):
    """The stated precision (bfloat16 operands, float32 decay and state)
    against the float32 reference at this small size, and the same with
    the decay cumulated in bfloat16. Widths of 16 to 64 average less
    than the cell's 128 to 2048, so the small size's own bounds are
    wider than ``check.py``'s, which PERF.md Section 6 holds against the
    chip's readings."""
    config = small_config(compute_dtype="bfloat16")
    parts = build(config, tokens)
    _, got, want = run(parts, tokens)
    stated, _ = refcheck.compare(got, want, parts["tolerance"])
    rule = gated_delta.gated_delta_rule
    monkeypatch.setattr(
        gated_delta, "gated_delta_rule",
        lambda *a, **kw: rule(*a, decay_dtype=jnp.bfloat16, **kw))
    _, got, want = run(build(config, tokens), tokens, parts)
    rounded, _ = refcheck.compare(got, want, parts["tolerance"])
    assert stated["dropped_pairs_plus_one"] == 0
    assert stated["logits"] < 0.1 and stated["loss"] < 0.01, stated
    assert rounded["grad:block_0/attn/A_log"] > 1.5 * stated[
        "grad:block_0/attn/A_log"], (stated, rounded)
