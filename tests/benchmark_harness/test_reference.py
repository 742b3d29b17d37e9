"""``reference.py`` against ``TransformerLM`` at a tiny size on the CPU,
the check's tolerance against the mistakes it has to catch, and the
second family's check (DeepFM, the preset's) through the same general
comparison."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from tests.benchmark_harness import _common as common

CONFIG = {
    "hidden_size": 64, "intermediate_size": 256, "num_attention_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 512,
    "check_leaves": ["wte/embedding", "block_0/attn/query/kernel",
                     "lm_head/kernel"],
}
PYTHIA = os.path.join(common.REPO, "benchmark", "configs", "pythia-1b")
DEEPFM = os.path.join(common.HERE, "preset", "configs", "tiny-deepfm")


def spec(compute_dtype="", **cell):
    return {
        "config": dict(CONFIG, compute_dtype=compute_dtype), "seed": 5,
        "zoo": os.path.join(PYTHIA, "zoo.py"),
        "reference": os.path.join(PYTHIA, "reference.py"),
        "cell": dict({"model_params": {"remat_policy": "dots"},
                      "last_positions": None, "reference_remat": False},
                     **cell),
    }


def load_check(directory):
    return refcheck.load_by_path(
        "edlbench_check", os.path.join(directory, "check.py"))


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(0)
    return (rng.zipf(1.2, size=96) % CONFIG["vocab_size"]).astype(np.int32)


def both_sides(spec_, sample, directory=PYTHIA):
    """(parts, params, system's output, reference's output)."""
    parts = load_check(directory).build(spec_, sample)
    params = jax.jit(parts["init"])(jax.random.PRNGKey(spec_["seed"]), sample)
    return (parts, params, jax.jit(parts["system"])(params, sample),
            jax.jit(parts["reference"])(params, sample))


@pytest.mark.parametrize("last, remat", [(None, False), (32, True)])
def test_reference_equals_the_model_in_float32(tokens, last, remat):
    parts, _, system, reference = both_sides(
        spec(last_positions=last, reference_remat=remat), tokens)
    assert set(system) == {"logits", "loss"} | {
        "grad:" + leaf for leaf in CONFIG["check_leaves"]}
    errors, ok = refcheck.compare(system, reference, parts["tolerance"])
    assert ok
    # same function, same precision: rounding order only
    assert max(errors.values()) < 1e-4, errors
    assert system["logits"].shape == (
        last or len(tokens), CONFIG["vocab_size"])


def test_bfloat16_system_path_is_inside_the_tolerance(tokens):
    parts, _, system, reference = both_sides(
        spec(compute_dtype="bfloat16"), tokens)
    errors, ok = refcheck.compare(system, reference, parts["tolerance"])
    assert ok, errors
    # and it is a different computation: the tolerance is not vacuous
    assert errors["logits"] > 1e-4


def rebuilt_reference(tokens):
    """The reference side built anew, and the module it loaded (to be
    patched before the side is traced)."""
    parts = load_check(PYTHIA).build(spec(), tokens)
    return parts, refcheck.sys.modules["edlbench_reference"]


def test_head_by_head_attention_is_the_same_function(tokens, monkeypatch):
    _, params, _, at_once = both_sides(spec(), tokens)
    parts, ref = rebuilt_reference(tokens)
    monkeypatch.setattr(ref, "SCORES_AT_ONCE", 1)
    by_head = jax.jit(parts["reference"])(params, tokens)
    errors, ok = refcheck.compare(by_head, at_once, parts["tolerance"])
    assert ok and max(errors.values()) < 1e-5, errors


def test_attention_without_the_causal_mask_fails(tokens, monkeypatch):
    _, params, _, good = both_sides(spec(), tokens)
    parts, ref = rebuilt_reference(tokens)

    def unmasked(q, k, v):
        q, k = ref.rotary(q), ref.rotary(k)
        scores = (q @ k.T) / jnp.sqrt(jnp.float32(q.shape[-1]))
        return jax.nn.softmax(scores, axis=-1) @ v

    monkeypatch.setattr(ref, "head_attention", unmasked)
    bad = jax.jit(parts["reference"])(params, tokens)
    errors, ok = refcheck.compare(bad, good, parts["tolerance"])
    assert not ok
    assert errors["logits"] > parts["tolerance"]["logits"]


def test_a_block_in_an_eight_bit_float_fails(tokens, monkeypatch):
    _, params, _, good = both_sides(spec(), tokens)
    parts, ref = rebuilt_reference(tokens)
    plain_block = ref.block

    def coarse(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def low_precision_block(x, p):
        p = jax.tree_util.tree_map(coarse, p)
        return coarse(plain_block(coarse(x), p))

    monkeypatch.setattr(ref, "block", low_precision_block)
    bad = jax.jit(parts["reference"])(params, tokens)
    errors, ok = refcheck.compare(bad, good, parts["tolerance"])
    assert not ok, errors


def test_the_comparison_is_general():
    """Names come from the check; a name nobody bounded fails, a
    ``kind:detail`` name falls back to its kind, and the two sides
    must return the same names."""
    a = {"x": jnp.ones(4), "grad:w": jnp.full(3, 2.0)}
    b = {"x": jnp.ones(4) * 1.01, "grad:w": jnp.full(3, 2.0)}
    errors, ok = refcheck.compare(a, b, {"x": 0.02, "grad": 1e-6})
    assert ok and errors["x"] == pytest.approx(0.01 / 1.01, rel=1e-3)
    assert not refcheck.compare(a, b, {"x": 0.005, "grad": 1e-6})[1]
    assert refcheck.tolerance_of("grad:w", {"grad": 1, "grad:w": 2}) == 2
    with pytest.raises(KeyError):
        refcheck.compare(a, b, {"x": 0.02})
    with pytest.raises(ValueError, match="different names"):
        refcheck.compare(a, {"x": jnp.ones(4)}, {"x": 0.02})


def test_second_family_deepfm_reference_equals_the_zoo_model():
    """The preset's DeepFM brings its own ``check.py``, ``reference.py``
    and generator ``sample``; ``lib/refcheck.py`` compares them as it
    compares the LM, and a wrong FM term is caught."""
    config = common.load(os.path.join(DEEPFM, "config.json"))
    generator = refcheck.load_by_path(
        "edlbench_traffic", os.path.join(
            common.HERE, "preset", "traffic", "zipf_ctr.py"))
    sample = generator.sample({"zipf_a": 1.2, "minibatch": 16}, config, 5)
    assert sample["ids"].shape == (16, config["fields"])
    spec_ = {"config": config, "seed": 5, "cell": {},
             "zoo": os.path.join(DEEPFM, "zoo.py"),
             "reference": os.path.join(DEEPFM, "reference.py")}
    parts, params, system, reference = both_sides(spec_, sample, DEEPFM)
    assert set(system) == {
        "logits", "loss", "grad:emb_rows", "grad:Dense_0/kernel"}
    errors, ok = refcheck.compare(system, reference, parts["tolerance"])
    assert ok, errors
    ref = refcheck.sys.modules["edlbench_reference"]
    wrong = ref.logits(params["dense"], 2.0 * params["emb_rows"],
                       params["linear_rows"],
                       sample["ids"] % config["check_rows"])
    assert float(refcheck.rel_rms(wrong, reference["logits"])) > 0.01
