"""The repo's ``MoeTransformerLM`` as the Laguna-XS.2 zoo builds it
against the configuration's plain reference
(``benchmark/configs/laguna-xs.2-1chip/reference.py``), at a preset
size on the CPU with seeded weights (``preset/configs/tiny-laguna``):
hidden 64, five layers (full and dense, then window, window, window,
full, all expert layers), 6 / 8 query heads of 16 over 2 kv heads, a
window of 24, the full layers' 8 of 16 lanes under YaRN over 32
positions, an output gate, 16 experts of 32 of which 4 are held, top-3,
one shared expert; 128 tokens; in float32, whole and over the last
positions. The shares of the expert layer add up to the uncut layer.
And the check's names against faults of the kinds ISSUE 42's equations
rule out are ``test_laguna_wrong_steps.py``'s (a file of its own, so
that the two run side by side)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.models import moe_transformer as M
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.ops import flash_attention as F

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAGUNA = os.path.join(REPO, "benchmark", "configs", "laguna-xs.2-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-laguna",
    "config.json")
SEQ, VOCAB = 128, 512


def small_config(**changes):
    with open(TINY) as f:
        config = json.load(f)
    config["compute_dtype"] = ""
    config.update(changes)
    return config


def build(config, tokens, remat_policy="none", last=None, model=None):
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(LAGUNA, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(LAGUNA, "zoo.py"),
        "reference": os.path.join(LAGUNA, "reference.py"),
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


def ref():
    return refcheck.sys.modules["edlbench_reference"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(LAGUNA, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "flax" not in source.replace(
        "no flax", "")


def test_the_zoo_builds_the_published_block(reference):
    _, variables, _, _ = reference
    params = variables["params"]
    # heads by kind: 6 in the full layers (0 and 4), 8 in the window
    # ones, a query and its gate a head, 2 kv heads everywhere
    for block, heads in (("block_0", 6), ("block_2", 8), ("block_4", 6)):
        attn = params[block]["attn"]
        assert attn["query"]["kernel"].shape == (64, heads, 32)
        assert attn["key"]["kernel"].shape == (64, 2, 16)
        assert attn["out_proj"]["kernel"].shape == (heads, 16, 64)
        assert set(attn) == {"query", "key", "value", "out_proj"}
    assert "moe_mlp" not in params["block_0"]  # the leading dense layer
    assert params["block_0"]["mlp_gate"]["kernel"].shape == (64, 96)
    moe = params["block_1"]["moe_mlp"]
    assert moe["router"]["kernel"].shape == (64, 16)  # ALL experts
    assert moe["w_gate"].shape == (4, 64, 32)         # the held ones
    assert moe["shared_gate"]["kernel"].shape == (64, 32)
    assert set(variables["moe_state"]) == {
        "block_1", "block_2", "block_3", "block_4"}
    model = zoo().model_from_config(small_config())
    assert model.layer_kinds == (
        "full", "window", "window", "window", "full")
    full, window = model.kind_fields["full"], model.kind_fields["window"]
    assert (full.num_heads, full.rope_theta, full.rotary_dim,
            full.window) == (6, 500000.0, 8, None)
    assert full.rope_scaling.factor == 64.0
    assert full.rope_scaling.mscale == pytest.approx(1.0)
    assert full.rope_scaling.mscale_all_dim == 0.0
    assert window == T.MixerKind(8, 10000.0, None, None, 24)
    assert (model.gate_scale, model.scoring) == (2.5, "sigmoid")
    assert (model.aux_loss_weight, model.first_k_dense) == (0.0, 1)
    with pytest.raises(ValueError, match="held_experts says"):
        zoo().model_from_config(small_config(num_experts=8))
    with pytest.raises(ValueError, match="gating"):
        zoo().model_from_config(small_config(gating=False))
    with pytest.raises(ValueError, match="one count a kind"):
        zoo().model_from_config(small_config(
            num_attention_heads_per_layer=[6, 8, 8, 4] * 2))
    with pytest.raises(ValueError, match="leading dense layers"):
        zoo().model_from_config(small_config(
            mlp_layer_types=["sparse", "dense"] + ["sparse"] * 6))
    linear = json.loads(json.dumps(small_config()["rope_parameters"]))
    linear["sliding_attention"]["rope_type"] = "linear"
    with pytest.raises(ValueError, match="'default' or 'yarn'"):
        zoo().model_from_config(small_config(rope_parameters=linear))


NAMES = {"logits", "loss", "choices", "dropped_pairs_plus_one"}


@pytest.mark.parametrize("remat_policy", ["none", "full"])
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
    assert max(e for n, e in errors.items() if n.startswith("grad")) < 5e-3
    assert errors["choices"] == 0 and errors["dropped_pairs_plus_one"] == 0
    assert got["logits"].shape == (SEQ, VOCAB)
    # four expert layers, over ALL 16 experts
    assert got["choices"].shape == (4, SEQ, 16)


def test_the_last_positions_are_the_whole_run_s(tokens, reference):
    _, _, whole, _ = reference
    parts = build(small_config(), tokens, last=32)
    _, got, want = run(parts, tokens)
    assert got["logits"].shape == (32, VOCAB)
    np.testing.assert_allclose(
        got["logits"], whole["logits"][-32:], atol=1e-4)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and errors["logits"] < 1e-4, errors
    # the routing is compared over the whole run
    assert got["choices"].shape == (4, SEQ, 16)
    assert float(got["loss"]) != pytest.approx(float(whole["loss"]))


def test_the_reference_s_band_in_blocks_of_rows_is_the_dense_mask():
    """``head_attention`` gives a block of queries only the keys its
    rows can see; against the whole (S, S) mask, with blocks shorter
    than, as long as and longer than the window, and against the
    program's layout."""
    R = ref()
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    seq, dim = 256, 16
    q, k, v = (jax.random.normal(key, (seq, dim)) for key in keys)
    pos = np.arange(seq)
    for window, block in ((24, 64), (64, 64), (100, 32), (300, 64),
                          (1, 128), (24, 256)):
        allowed = (pos[None, :] <= pos[:, None]) & (
            pos[:, None] - pos[None, :] < window)
        np.testing.assert_array_equal(
            allowed, F.Band(window).keep(pos[:, None], pos[None, :]))
        scores = jnp.where(allowed, (q @ k.T) / 4.0, -jnp.inf)
        want = jax.nn.softmax(scores, axis=-1) @ v
        R.QUERY_BLOCK, kept = block, R.QUERY_BLOCK
        try:
            got = R.head_attention(q, k, v, window=window)
        finally:
            R.QUERY_BLOCK = kept
        np.testing.assert_allclose(got, want, atol=2e-6)
    causal = jnp.where(pos[None, :] <= pos[:, None], (q @ k.T) / 4.0,
                       -jnp.inf)
    np.testing.assert_allclose(
        R.head_attention(q, k, v), jax.nn.softmax(causal, axis=-1) @ v,
        atol=2e-6)


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer. Each
    share's routed part (the reference's, and the program's ``MoeMlp``
    told which experts it holds), with the shared expert counted once,
    add up to what the uncut reference gives for the whole layer."""
    _, variables, _, _ = reference
    config = small_config()
    held = variables["params"]["block_1"]
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    lecun = jax.nn.initializers.lecun_normal(batch_axis=(0,))
    every = dict(held["moe_mlp"])  # router, shared: every chip's alike
    every["w_gate"] = lecun(keys[0], (16, 64, 32))
    every["w_up"] = lecun(keys[1], (16, 64, 32))
    every["w_down"] = lecun(keys[2], (16, 32, 64))
    bias = jax.random.uniform(keys[3], (16,), jnp.float32, -0.1, 0.1)
    h = jax.random.normal(keys[4], (SEQ, 64))
    R = ref()
    with jax.default_matmul_precision("highest"):
        shared = R.shared_expert(h, every)
        uncut = R.expert_layer(h, every, bias, config, (0, 16))[0] + shared
        parts, program = [], []
        for first in (0, 4, 8, 12):
            share = dict(every, **{
                name: every[name][first:first + 4]
                for name in ("w_gate", "w_up", "w_down")})
            parts.append(R.expert_layer(
                h, share, bias, config, (first, 4))[0])
            layer = M.MoeMlp(
                16, top_k=3, dispatch_impl="sorted", expert_dim=32,
                expert_act="swiglu", scoring="sigmoid", gate_scale=2.5,
                bias_update_speed=0.001, shared_experts=1,
                held_experts=(first, 4), held_rows=SEQ * 3)
            y, aux = layer.apply(
                {"params": share, "moe_state": {
                    "e_score_correction_bias": bias}}, h[None])
            assert float(aux["routing"]["dropped"]) == 0
            program.append(y[0] - shared)
        for routed in (parts, program):
            np.testing.assert_allclose(
                sum(routed) + shared, uncut, atol=2e-5)
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


def test_bfloat16_compute_at_this_size(tokens):
    """The stated precision (bfloat16 operands, float32 accumulation
    and statistics) against the float32 reference at this small size:
    nothing dropped, the loss and the logits close. Widths of 16 to 64
    average less than the cell's 128 to 8192, so the small size's own
    bounds are wider than ``check.py``'s, which PERF.md Section 6 holds
    against the chip's readings."""
    config = small_config(compute_dtype="bfloat16")
    parts = build(config, tokens)
    _, got, want = run(parts, tokens)
    stated, _ = refcheck.compare(got, want, parts["tolerance"])
    assert stated["dropped_pairs_plus_one"] == 0
    assert stated["logits"] < 0.1 and stated["loss"] < 0.01, stated
