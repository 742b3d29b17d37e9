"""The repo's ``MoeTransformerLM`` as the Keye-VL-2.0 zoo builds it
against the configuration's plain reference
(``benchmark/configs/keye-vl-2.0-30b-a3b-1chip/reference.py``), at a
preset size on the CPU with seeded weights
(``preset/configs/tiny-keye``): hidden 64, two expert layers, 4 query
heads of 16 over 2 kv heads with a norm a head, an indexer of 2 heads of
8 over one key a position that keeps 32 keys a query, 16 experts of 32
of which 4 are held, top-3, no shared expert; 128 tokens; in float32,
whole and over the last positions: logits, loss, ``indexer_loss``,
gradients, kept sets, scores and the experts' choices. The shares of
the expert layer add up to the uncut layer."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.models import moe_transformer as M

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYE = os.path.join(REPO, "benchmark", "configs", "keye-vl-2.0-30b-a3b-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-keye",
    "config.json")
SEQ, VOCAB, LAYERS, TOPK = 128, 512, 2, 32


def small_config(**changes):
    with open(TINY) as f:
        config = json.load(f)
    config["compute_dtype"] = ""
    config.update(changes)
    return config


def load(name):
    return refcheck.load_by_path(
        "edlbench_" + name, os.path.join(KEYE, name + ".py"))


def build(config, tokens, remat_policy="none", last=None, model=None):
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(KEYE, "zoo.py"),
        "reference": os.path.join(KEYE, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy},
                 "last_positions": last},
    }
    return load("check").build(spec, tokens, model=model)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(1)
    return (rng.zipf(1.2, size=SEQ) % VOCAB).astype(np.int32)


def run(parts, tokens):
    """``lib/refcheck.py``'s order."""
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(5), tokens)
    got = jax.jit(parts["system"])(variables, tokens)
    want = jax.jit(parts["reference"])(variables, tokens)
    return variables, got, want


@pytest.fixture(scope="module")
def reference(tokens):
    parts = build(small_config(), tokens)
    variables, got, want = run(parts, tokens)
    return parts, variables, got, want


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(KEYE, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "flax" not in source.replace(
        "no flax", "")
    # the selection is jax.lax.top_k itself, in one function
    assert source.count("jax.lax.top_k(scores") == 1
    assert "def kept_set(scores, topk)" in source


def test_the_zoo_builds_the_published_block(reference):
    _, variables, _, _ = reference
    params = variables["params"]
    assert set(params) == {"wte", "lm_head", "ln_f", "block_0", "block_1"}
    for block in ("block_0", "block_1"):
        attn = params[block]["attn"]
        assert set(attn) == {
            "query", "key", "value", "out_proj", "q_norm", "k_norm",
            "indexer_q", "indexer_k", "indexer_k_norm", "indexer_w"}
        assert attn["query"]["kernel"].shape == (64, 4, 16)
        assert attn["key"]["kernel"].shape == (64, 2, 16)
        assert attn["indexer_q"]["kernel"].shape == (64, 2, 8)
        assert attn["indexer_k"]["kernel"].shape == (64, 8)
        assert attn["indexer_w"]["kernel"].shape == (64, 2)
        assert attn["indexer_k_norm"]["bias"].shape == (8,)
        moe = params[block]["moe_mlp"]
        assert moe["router"]["kernel"].shape == (64, 16)  # ALL experts
        assert moe["w_gate"].shape == (4, 64, 32)         # the held ones
        assert set(moe) == {"router", "w_gate", "w_up", "w_down"}
    zoo = load("zoo")
    model = zoo.model_from_config(small_config())
    assert dataclasses.astuple(model.indexer) == (2, 8, TOPK)
    assert (model.rope_theta, model.head_norm, model.moe_every) == (
        1e7, "rmsnorm", 1)
    assert (model.scoring, model.shared_experts, model.first_k_dense) == (
        "softmax", 0, 0)
    assert (model.aux_loss_weight, model.indexer_loss_coef) == (0.001, 1.0)
    assert model.objective == "next_token" and model.held_experts == (4, 4)
    with pytest.raises(ValueError, match="held_experts says"):
        zoo.model_from_config(small_config(num_experts=8))
    with pytest.raises(ValueError, match="indexer_num_kv_heads"):
        zoo.model_from_config(small_config(sa_config=dict(
            small_config()["sa_config"], indexer_num_kv_heads=2)))
    with pytest.raises(ValueError, match="mrope_section"):
        zoo.model_from_config(small_config(rope_scaling={
            "mrope_section": [2, 3, 4], "rope_type": "default",
            "type": "default"}))
    with pytest.raises(ValueError, match="num_local_experts"):
        zoo.model_from_config(small_config(num_local_experts=4))
    with pytest.raises(ValueError, match="use_sliding_window"):
        zoo.model_from_config(small_config(use_sliding_window=True))


NAMES = {"logits", "loss", "indexer_loss", "choices", "kept", "scores",
         "kept_count", "kept_after_plus_one", "dropped_pairs_plus_one"}


@pytest.mark.parametrize("remat_policy", ["none", "flash"])
def test_reference_equals_the_model_in_float32(
        tokens, reference, remat_policy):
    parts, _, got, want = reference
    if remat_policy != "none":
        _, got, _ = run(build(small_config(), tokens, remat_policy), tokens)
    assert set(got) == NAMES | {
        "grad:" + leaf for leaf in small_config()["check_leaves"]}
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    # float32 against float32: the sums' order and nothing else
    assert errors["logits"] < 1e-4 and errors["loss"] < 1e-5, errors
    assert errors["indexer_loss"] < 1e-5 and errors["scores"] < 1e-4
    assert max(e for n, e in errors.items() if n.startswith("grad")) < 5e-3
    for name in ("choices", "kept", "kept_count", "kept_after_plus_one",
                 "dropped_pairs_plus_one"):
        assert errors[name] == 0, name
    assert got["logits"].shape == (SEQ, VOCAB)
    assert got["choices"].shape == (LAYERS, SEQ, 16)
    assert got["kept"].shape == got["scores"].shape == (LAYERS, SEQ, SEQ)
    # every query of every layer keeps exactly min(topk, t + 1) keys
    assert (np.asarray(got["kept_count"]) == np.minimum(
        TOPK, np.arange(SEQ) + 1)).all()
    assert (np.asarray(got["kept"]).sum(-1) == np.asarray(
        got["kept_count"])).all()
    # the routed and the indexer's leaves have bounds of their own
    tolerance = parts["tolerance"]
    assert tolerance["grad:block_1/moe_mlp/w_gate"] > tolerance["grad"]
    assert "grad:block_1/attn/indexer_q/kernel" in tolerance


def test_the_indexer_s_leaves_learn_from_its_term_alone(tokens, reference):
    """With the term's coefficient at 0 the three indexer leaves'
    gradients vanish on both sides, and nothing else's moves."""
    _, _, whole, _ = reference
    config = small_config()
    config["assumed"] = dict(config["assumed"], indexer_loss_coef=0.0)
    _, got, want = run(build(config, tokens), tokens)
    for name, value in got.items():
        if "/indexer_" in name:
            assert not np.asarray(value).any(), name
            assert not np.asarray(want[name]).any(), name
            assert np.asarray(whole[name]).any(), name
        elif name.startswith("grad:"):
            np.testing.assert_allclose(
                value, whole[name], rtol=1e-4, atol=1e-7, err_msg=name)
    assert float(got["indexer_loss"]) == pytest.approx(
        float(whole["indexer_loss"]))
    assert float(whole["loss"]) - float(got["loss"]) == pytest.approx(
        float(whole["indexer_loss"]), rel=1e-4)


def test_the_last_positions_are_the_whole_run_s(tokens, reference):
    _, _, whole, _ = reference
    parts = build(small_config(), tokens, last=32)
    _, got, want = run(parts, tokens)
    assert got["logits"].shape == (32, VOCAB)
    np.testing.assert_allclose(
        got["logits"], whole["logits"][-32:], atol=1e-4)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and errors["logits"] < 1e-4, errors
    # the selection and the routing are compared over the whole run
    assert got["choices"].shape == (LAYERS, SEQ, 16)
    assert got["kept_count"].shape == (LAYERS, SEQ)
    assert float(got["indexer_loss"]) == pytest.approx(
        float(whole["indexer_loss"]), rel=1e-5)
    assert float(got["loss"]) != pytest.approx(float(whole["loss"]))


def test_the_reference_s_selection_against_a_loop(reference):
    R = load("reference")
    scores = np.random.RandomState(3).randn(24, 24).astype(np.float32)
    scores[:, ::5] = 0.25  # ties
    causal = np.tril(np.ones((24, 24), bool))
    keep = np.asarray(R.kept_set(jnp.where(causal, scores, -jnp.inf), 6))
    for t in range(24):
        order = np.argsort(-scores[t, :t + 1], kind="stable")[:6]
        assert sorted(np.flatnonzero(keep[t])) == sorted(order)
    bits = R.pack(jnp.asarray(keep))
    assert bits.shape == (24, 3) and bits.dtype == jnp.uint8
    assert (np.asarray(R.unpack(bits)) == keep).all()
    assert (np.asarray(bits) == np.packbits(keep, axis=-1)).all()


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Eight chips hold experts 0-15, 16-31, ... of one layer of 128.
    Each share's part (the reference's, and the program's ``MoeMlp``
    told which experts it holds) add up to what the uncut reference
    gives for the whole layer; there is no shared expert to count
    once."""
    config = small_config(num_experts_per_tok=8)
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    lecun = jax.nn.initializers.lecun_normal(batch_axis=(0,))
    every = {
        "router": {"kernel": jax.random.normal(keys[3], (64, 128)) * 0.5},
        "w_gate": lecun(keys[0], (128, 64, 32)),
        "w_up": lecun(keys[1], (128, 64, 32)),
        "w_down": lecun(keys[2], (128, 32, 64)),
    }
    tokens = 32
    h = jax.random.normal(keys[4], (tokens, 64))
    R = load("reference")
    with jax.default_matmul_precision("highest"):
        uncut = R.expert_layer(h, every, config, (0, 128))[0]
        parts, program = [], []
        for first in range(0, 128, 16):
            share = dict(every, **{
                name: every[name][first:first + 16]
                for name in ("w_gate", "w_up", "w_down")})
            parts.append(R.expert_layer(h, share, config, (first, 16))[0])
            layer = M.MoeMlp(
                128, top_k=8, dispatch_impl="sorted", expert_dim=32,
                expert_act="swiglu", scoring="softmax",
                held_experts=(first, 16), held_rows=tokens * 8)
            y, aux = layer.apply({"params": share}, h[None])
            assert float(aux["routing"]["dropped"]) == 0
            program.append(y[0])
        for shares in (parts, program):
            np.testing.assert_allclose(sum(shares), uncut, atol=2e-5)
    # no share is the whole: each leaves the others' part out
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    assert float(jnp.abs(parts[0] - sum(parts)).max()) > 1e-3


def test_a_dropped_pair_fails_the_check(tokens, reference):
    parts, _, _, _ = reference
    tight = small_config(expert_rows={"held_rows": 32})
    _, got, want = run(build(tight, tokens), tokens)
    assert float(got["dropped_pairs_plus_one"]) > 1
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok and errors["dropped_pairs_plus_one"] > 0


WRONG = {
    # a causal mask in the selection's place
    "causal_mask": lambda m: m.clone(
        indexer=dataclasses.replace(m.indexer, topk=SEQ)),
    "rope_theta_10000": lambda m: m.clone(rope_theta=10000.0),
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_step_is_outside_the_tolerances(tokens, reference, variant):
    parts, _, _, _ = reference
    model = WRONG[variant](load("zoo").model_from_config(small_config()))
    _, got, want = run(build(small_config(), tokens, model=model), tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok, errors
    outside = {name for name, error in errors.items()
               if error > refcheck.tolerance_of(name, parts["tolerance"])}
    if variant == "causal_mask":
        assert {"kept", "kept_count"} <= outside, outside
    else:
        assert {"logits", "scores"} <= outside, outside


def test_bfloat16_compute_at_this_size(tokens):
    """The stated precision (bfloat16 operands, float32 accumulation,
    statistics, scores and selection) against the float32 reference at
    this small size: nothing dropped, every query's count exact, the
    loss and the logits close. Widths of 8 to 64 average less than the
    cell's 64 to 2048, so the small size's own bounds are wider than
    ``check.py``'s, which PERF.md Section 6 holds against the chip's
    readings."""
    config = small_config(compute_dtype="bfloat16")
    parts = build(config, tokens)
    _, got, want = run(parts, tokens)
    stated, _ = refcheck.compare(got, want, parts["tolerance"])
    assert stated["dropped_pairs_plus_one"] == 0
    assert stated["kept_count"] == 0 and stated["kept_after_plus_one"] == 0
    assert stated["logits"] < 0.1 and stated["loss"] < 0.01, stated
    assert stated["indexer_loss"] < 0.05 and stated["scores"] < 0.05, stated
    # near-ties between the 32nd and 33rd score flip, a few
    assert 0 < stated["kept"] < 0.5, stated
