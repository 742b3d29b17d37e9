"""The repo's ``MoeTransformerLM`` as the LFM2-8B-A1B zoo builds it
against the configuration's plain reference
(``benchmark/configs/lfm2-8b-a1b-1chip/reference.py``), at a preset size
on the CPU with seeded weights (``preset/configs/tiny-lfm2``): hidden
64, six layers (conv, conv, full, conv, conv, conv: the first two with a
dense SwiGLU of 96, the others expert layers), a gated short convolution
of 3 taps, 4 query heads of 16 over 2 kv heads with a norm a head, 16
experts of 32 of which 4 are held, top-3, no shared expert, the head
tied to the embedding; 128 tokens; in float32, whole and over the last
positions. The shares of the expert layer add up to the uncut layer."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.models import moe_transformer as M
from elasticdl_tpu.ops import short_conv

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LFM2 = os.path.join(REPO, "benchmark", "configs", "lfm2-8b-a1b-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-lfm2",
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
        "edlbench_check", os.path.join(LFM2, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(LFM2, "zoo.py"),
        "reference": os.path.join(LFM2, "reference.py"),
        "cell": {"model_params": {"remat_policy": remat_policy},
                 "last_positions": last},
    }
    return check.build(spec, tokens, model=model)


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


def zoo():
    return refcheck.load_by_path("edlbench_zoo", os.path.join(LFM2, "zoo.py"))


def ref():
    return refcheck.load_by_path(
        "edlbench_reference", os.path.join(LFM2, "reference.py"))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(LFM2, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "flax" not in source.replace(
        "no flax", "")
    assert "conv_general_dilated" not in source


def test_the_zoo_builds_the_published_block(reference):
    _, variables, _, _ = reference
    params = variables["params"]
    for block in ("block_0", "block_1", "block_3", "block_4", "block_5"):
        attn = params[block]["attn"]
        assert set(attn) == {"in_proj", "conv_kernel", "proj_out"}
        assert attn["in_proj"]["kernel"].shape == (64, 192)
        assert attn["conv_kernel"].shape == (3, 64)
        assert attn["proj_out"]["kernel"].shape == (64, 64)
    attn = params["block_2"]["attn"]
    assert set(attn) == {
        "query", "key", "value", "out_proj", "q_norm", "k_norm"}
    assert attn["query"]["kernel"].shape == (64, 4, 16)
    assert attn["key"]["kernel"].shape == (64, 2, 16)
    assert attn["q_norm"]["scale"].shape == (16,)
    for block in ("block_0", "block_1"):  # the leading dense layers
        assert "moe_mlp" not in params[block]
        assert params[block]["mlp_gate"]["kernel"].shape == (64, 96)
    moe = params["block_2"]["moe_mlp"]
    assert moe["router"]["kernel"].shape == (64, 16)  # ALL experts
    assert moe["w_gate"].shape == (4, 64, 32)         # the held ones
    assert set(moe) == {"router", "w_gate", "w_up", "w_down"}
    assert set(variables["moe_state"]) == {
        "block_2", "block_3", "block_4", "block_5"}
    assert "lm_head" not in params  # tied to the embedding
    model = zoo().model_from_config(small_config())
    assert model.layer_kinds == (
        "conv", "conv", "full", "conv", "conv", "conv")
    assert (model.conv.taps, model.rope_theta, model.head_norm) == (
        3, 1e6, "rmsnorm")
    assert (model.gate_scale, model.scoring, model.shared_experts) == (
        1.0, "sigmoid", 0)
    assert (model.aux_loss_weight, model.first_k_dense) == (0.0, 2)
    eight = zoo().model_from_config(small_config(num_hidden_layers=8))
    assert eight.layer_kinds[6:] == ("full", "conv")
    with pytest.raises(ValueError, match="held_experts says"):
        zoo().model_from_config(small_config(num_experts=8))
    with pytest.raises(ValueError, match="conv_bias"):
        zoo().model_from_config(small_config(conv_bias=True))
    with pytest.raises(ValueError, match="head_dim is hidden_size"):
        zoo().model_from_config(small_config(head_dim=32))
    untied = small_config()
    untied["assumed"] = dict(untied["assumed"], tie_word_embeddings=False)
    assert not zoo().model_from_config(untied).tie_embeddings


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
    # the routed leaves have the bound of their own
    assert parts["tolerance"]["grad:block_5/moe_mlp/w_gate"] > parts[
        "tolerance"]["grad"]


def test_an_untied_head_is_compared_too(tokens):
    config = small_config()
    config["assumed"] = dict(config["assumed"], tie_word_embeddings=False)
    parts = build(config, tokens)
    variables, got, want = run(parts, tokens)
    assert "lm_head" in variables["params"]
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and errors["logits"] < 1e-4, errors


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


def test_the_reference_s_convolution_is_the_op_and_a_loop():
    """``reference.short_conv``'s three shifted copies against the
    program's op and against a loop over positions."""
    R = ref()
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    seq, width = 40, 8
    x = jax.random.normal(keys[0], (seq, width))
    p = {"in_proj": {"kernel": jax.random.normal(keys[1], (width, 24))},
         "conv_kernel": jax.random.normal(keys[2], (3, width)),
         "proj_out": {"kernel": jnp.eye(width)}}
    config = {"conv_L_cache": 3, "conv_bias": False}
    with jax.default_matmul_precision("highest"):
        got = R.short_conv(x, p, config)
        bcx = x @ p["in_proj"]["kernel"]
    np.testing.assert_allclose(
        got, short_conv.gated_short_conv(bcx, p["conv_kernel"]), atol=1e-5)
    b, c, u = (np.asarray(bcx[:, i * width:(i + 1) * width])
               for i in range(3))
    w, z, want = np.asarray(p["conv_kernel"]), b * u, np.zeros((seq, width))
    for t in range(seq):
        for j in range(3):
            if t - 2 + j >= 0:
                want[t] += w[j] * z[t - 2 + j]
    np.testing.assert_allclose(got, c * want, atol=1e-4)
    with pytest.raises(ValueError, match="taps over"):
        R.short_conv(x, dict(p, conv_kernel=p["conv_kernel"][:2]), config)


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer. Each
    share's part (the reference's, and the program's ``MoeMlp`` told
    which experts it holds) add up to what the uncut reference gives for
    the whole layer; there is no shared expert to count once."""
    _, variables, _, _ = reference
    config = small_config()
    held = variables["params"]["block_2"]
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    lecun = jax.nn.initializers.lecun_normal(batch_axis=(0,))
    every = dict(held["moe_mlp"])  # the router: every chip's alike
    every["w_gate"] = lecun(keys[0], (16, 64, 32))
    every["w_up"] = lecun(keys[1], (16, 64, 32))
    every["w_down"] = lecun(keys[2], (16, 32, 64))
    bias = jax.random.uniform(keys[3], (16,), jnp.float32, -0.1, 0.1)
    h = jax.random.normal(keys[4], (SEQ, 64))
    R = ref()
    with jax.default_matmul_precision("highest"):
        uncut = R.expert_layer(h, every, bias, config, (0, 16))[0]
        parts, program = [], []
        for first in (0, 4, 8, 12):
            share = dict(every, **{
                name: every[name][first:first + 4]
                for name in ("w_gate", "w_up", "w_down")})
            parts.append(R.expert_layer(
                h, share, bias, config, (first, 4))[0])
            layer = M.MoeMlp(
                16, top_k=3, dispatch_impl="sorted", expert_dim=32,
                expert_act="swiglu", scoring="sigmoid", gate_scale=1.0,
                bias_update_speed=0.001, held_experts=(first, 4),
                held_rows=SEQ * 3)
            y, aux = layer.apply(
                {"params": share, "moe_state": {
                    "e_score_correction_bias": bias}}, h[None])
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


def test_bfloat16_compute_at_this_size(tokens):
    """The stated precision (bfloat16 operands, float32 accumulation,
    statistics and gates) against the float32 reference at this small
    size: nothing dropped, the loss and the logits close. Widths of 16
    to 64 average less than the cell's 64 to 7168, so the small size's
    own bounds are wider than ``check.py``'s, which PERF.md Section 6
    holds against the chip's readings."""
    config = small_config(compute_dtype="bfloat16")
    parts = build(config, tokens)
    _, got, want = run(parts, tokens)
    stated, _ = refcheck.compare(got, want, parts["tolerance"])
    assert stated["dropped_pairs_plus_one"] == 0
    assert stated["logits"] < 0.1 and stated["loss"] < 0.01, stated
