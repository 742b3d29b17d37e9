"""The repo's ``MoeTransformerLM`` as the granite-4.0-h zoo builds it
against the configuration's plain reference (``benchmark/configs/
granite-4.0-h-micro-1chip/reference.py``), at a small size on the CPU
with seeded weights: hidden 64, three dense blocks (mamba, attention,
mamba; the ten-layer pattern is ``tests/test_granite_lm.py``'s and the
rehearsal's), Mamba-2 mixers of 8 heads of 16 over a state of 16,
attention of 4 / 2 heads of 16 that rotates nothing; in float32, with
and without the cell's remat policy. And the check's names against
faults of the kinds ISSUE 60's equations rule out: the gate after the
norm, a norm a head, another attention scale, rotated q and k, the
residual multiplier, the convolution's bias or ``D``'s skip left out."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.ops import ssd

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GRANITE = os.path.join(
    REPO, "benchmark", "configs", "granite-4.0-h-micro-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-granite",
    "config.json")
SEQ, VOCAB = 128, 512
BOUNDS = {"logits": 0.03, "grad": 0.05}
# three layers of the tiny preset's ten, and leaves of theirs
SHORT = dict(
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    check_leaves=[
        "wte/embedding", "block_0/attn/in_proj/kernel", "block_0/attn/A_log",
        "block_0/attn/conv_kernel", "block_0/attn/conv_bias",
        "block_2/attn/dt_bias", "block_2/attn/D",
        "block_2/attn/out_norm_scale", "block_2/mlp_down/kernel",
        "block_1/attn/key/kernel", "block_1/attn/out_proj/kernel"])


def small_config(**changes):
    with open(TINY) as f:
        config = json.load(f)
    config["compute_dtype"] = ""
    config.update(SHORT)
    config.update(changes)
    return config


def build(config, tokens, remat_policy="none", last=None, model=None,
          variants=None):
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(GRANITE, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(GRANITE, "zoo.py"),
        "reference": os.path.join(GRANITE, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy},
                 "last_positions": last},
    }
    return check.build(spec, tokens, model=model, variants=variants)


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
    with open(os.path.join(GRANITE, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(token" in source  # one token a step
    assert "jnp.exp(a_t)[:, None, None] * state" in source


def test_the_zoo_builds_the_published_pattern(reference):
    _, variables, got, want = reference
    params = variables["params"]
    kinds = ["mamba" if "A_log" in params["block_%d" % i]["attn"]
             else "full" for i in range(3)]
    assert kinds == ["mamba", "full", "mamba"]
    with open(TINY) as f:
        whole = zoo().model_from_config(json.load(f))
    assert whole.layer_kinds == ("mamba",) * 5 + ("full",) + ("mamba",) * 4
    assert params["block_0"]["attn"]["in_proj"]["kernel"].shape == (
        64, 2 * 128 + 2 * 16 + 8)
    assert params["block_0"]["attn"]["conv_kernel"].shape == (4, 160)
    assert params["block_1"]["attn"]["query"]["kernel"].shape == (64, 4, 16)
    assert params["block_1"]["attn"]["key"]["kernel"].shape == (64, 2, 16)
    assert all("moe_mlp" not in params["block_%d" % i] for i in range(3))
    assert "lm_head" not in params
    # ``init`` drew the skips and the gated norms' scales away from 1
    for name in ("D", "out_norm_scale"):
        leaf = np.asarray(params["block_0"]["attn"][name])
        assert leaf.min() >= 0.5 and leaf.max() <= 1.5 and leaf.std() > 0.1
    # float32 against float32: the sums' order and nothing else
    errors, ok = refcheck.compare(got, want, BOUNDS)
    assert ok and max(errors.values()) < 1e-4, errors
    for key, value in (("num_local_experts", 4), ("attention_bias", True),
                       ("position_embedding_type", "rope"),
                       ("tie_word_embeddings", False),
                       ("mamba_proj_bias", True), ("hidden_act", "gelu"),
                       ("mamba_expand", 3)):
        with pytest.raises(ValueError, match=key):
            zoo().model_from_config(small_config(**{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        zoo().model_from_config(small_config(layer_types=["mamba"] * 2))


def test_reference_equals_the_model_under_the_cell_s_remat(tokens, reference):
    """Under the cell's remat policy and over the last positions (the
    fixture runs neither)."""
    parts, _, _, _ = reference
    _, got, want = run(build(small_config(), tokens, "flash", 32), tokens)
    assert set(got) == {"logits"} | {
        "grad:" + leaf for leaf in small_config()["check_leaves"]}
    assert got["logits"].shape == (32, VOCAB)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and max(errors.values()) < 1e-4, errors


def _outside(errors, ok, name):
    assert not ok
    assert errors[name] > refcheck.tolerance_of(name, BOUNDS), errors


# the reference built wrong, against the system as it is
WRONG_REFERENCES = {
    "the_gate_after_the_norm": (
        {"mamba": {"gate_after_norm": True}}, "logits"),
    "a_norm_a_head": ({"mamba": {"norm_lanes": 16}}, "logits"),
    "the_attention_scale_of_sqrt": (
        {"full": {"scale": 16 ** -0.5}}, "grad:block_1/attn/key/kernel"),
    "q_and_k_rotated": (
        {"full": {"rotate": True}}, "grad:block_1/attn/key/kernel"),
    "no_residual_multiplier": ({"residual": 1.0}, "logits"),
    "no_convolution_bias": (
        {"mamba": {"conv_bias": False}}, "grad:block_0/attn/conv_bias"),
    "no_skip": ({"mamba": {"skip": False}}, "grad:block_2/attn/D"),
}


@pytest.mark.parametrize(
    "variants,name", list(WRONG_REFERENCES.values()),
    ids=list(WRONG_REFERENCES))
def test_a_reference_built_wrong_is_outside_the_tolerances(
        tokens, reference, variants, name):
    parts, variables, got, _ = reference
    wrong = build(small_config(), tokens, variants=variants)
    want = jax.jit(wrong["reference"])(variables, tokens)
    _outside(*refcheck.compare(got, want, parts["tolerance"]), name)


# the system built wrong, against the reference as it is
WRONG_SYSTEMS = {
    "rotated": (lambda model: model.clone(rotary=True),
                "grad:block_1/attn/key/kernel"),
    "the_default_scale": (lambda model: model.clone(attention_scale=None),
                          "grad:block_1/attn/key/kernel"),
    "no_residual_multiplier": (
        lambda model: model.clone(residual_scale=None), "logits"),
    "no_convolution_bias": (
        lambda model: model.clone(mamba=dataclasses.replace(
            model.mamba, conv_bias=False)), "logits"),
}


@pytest.mark.parametrize(
    "change,name", list(WRONG_SYSTEMS.values()), ids=list(WRONG_SYSTEMS))
def test_a_system_built_wrong_is_outside_the_tolerances(
        tokens, reference, change, name):
    parts, variables, _, want = reference
    model = change(zoo().model_from_config(small_config()))
    wrong = build(small_config(), tokens, model=model)
    got = jax.jit(wrong["system"])(variables, tokens)
    _outside(*refcheck.compare(got, want, parts["tolerance"]), name)


def test_bfloat16_compute_is_inside_the_small_size_s_bounds(
        tokens, monkeypatch):
    """The stated precision (bfloat16 operands, float32 decay and state)
    against the float32 reference at this small size. Widths of 16 to
    128 average less than the cell's 64 to 8192, so the small size's own
    bounds are wider than ``check.py``'s, which PERF.md Section 6 holds
    against the chip's readings; a decay cumulated in bfloat16 reads
    further off here too (what it costs the scan alone is
    ``tests/test_ssd_scan.py``'s and, at the cell's size,
    ``scripts/granite_precision.py``'s)."""
    config = small_config(compute_dtype="bfloat16")
    parts = build(config, tokens)
    variables, got, want = run(parts, tokens)
    stated, _ = refcheck.compare(got, want, parts["tolerance"])
    assert stated["logits"] < 0.05, stated
    assert max(e for n, e in stated.items() if n.startswith("grad")) < 0.3
    scan = ssd.ssd_scan
    monkeypatch.setattr(ssd, "ssd_scan", lambda *a, **kw: scan(
        *a, decay_dtype=jax.numpy.bfloat16, **kw))
    lowered = jax.jit(build(config, tokens)["system"])(variables, tokens)
    low, _ = refcheck.compare(lowered, want, parts["tolerance"])
    assert low["grad:block_0/attn/A_log"] > stated["grad:block_0/attn/A_log"]
