"""The repo's ``MoeTransformerLM`` as the Xing4.0 zoo builds it against
the configuration's plain reference
(``benchmark/configs/xing4.0-29b-a4b-1chip/reference.py``), at a preset
size on the CPU with seeded weights (``preset/configs/tiny-xing``):
hidden 64, four streams, one dense and two expert blocks and the
prediction module's, latent attention (4 heads of 16 | 8, v 16, a q
latent of 24) under YaRN, 16 experts of 32 of which 4 are held, top-3,
one shared expert; 128 tokens; in float32, whole and over the last
positions. The shares of the expert layer add up to the uncut layer.
And the check's names against faults of the kinds ISSUE 37's equations
rule out are ``test_xing_wrong_steps.py``'s (a file of its own, so that
the two run side by side)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.models import moe_transformer as M
from elasticdl_tpu.models import transformer as T

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
XING = os.path.join(REPO, "benchmark", "configs", "xing4.0-29b-a4b-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-xing",
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
        "edlbench_check", os.path.join(XING, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(XING, "zoo.py"),
        "reference": os.path.join(XING, "reference.py"),
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
    with open(os.path.join(XING, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "flax" not in source.replace(
        "no flax", "")


def test_the_zoo_builds_the_published_block(reference):
    _, variables, _, _ = reference
    params = variables["params"]
    attn = params["block_2"]["attn"]
    assert attn["q_down"]["kernel"].shape == (64, 24)
    assert attn["q_proj"]["kernel"].shape == (24, 4, 24)
    assert attn["kv_up"]["kernel"].shape == (32, 4, 32)
    assert params["block_1"]["hc_attn"]["p_res"].shape == (4, 64, 16)
    assert "moe_mlp" not in params["block_0"]  # the leading dense layer
    moe = params["block_1"]["moe_mlp"]
    assert moe["router"]["kernel"].shape == (64, 16)  # ALL experts
    assert moe["w_gate"].shape == (4, 64, 32)         # the held ones
    assert moe["shared_gate"]["kernel"].shape == (64, 32)
    assert params["mtp_proj"]["kernel"].shape == (128, 64)
    assert set(variables["moe_state"]) == {"block_1", "block_2", "mtp_block"}
    model = zoo().model_from_config(small_config())
    assert model.hc == T.HyperDims(4, 20, 1e-6, (-30.0, 30.0))
    assert model.rope_scaling.factor == 64.0 and model.mtp_layers == 1
    assert model.latent.q_lora_rank == 24 and model.gate_scale == 2.0
    assert (model.aux_loss_weight, model.seq_aux) == (0.0, False)
    with pytest.raises(ValueError, match="held_experts says"):
        zoo().model_from_config(small_config(n_routed_experts=8))
    with pytest.raises(ValueError, match="topk_group"):
        zoo().model_from_config(small_config(topk_group=2))
    linear = dict(small_config()["rope_scaling"], type="linear")
    with pytest.raises(ValueError, match="YaRN only"):
        zoo().model_from_config(small_config(rope_scaling=linear))
    with pytest.raises(ValueError, match="sequence-wise"):
        zoo().model_from_config(small_config(seq_aux=True))


NAMES = {"logits", "mtp_logits", "loss", "mtp_loss", "choices",
         "dropped_pairs_plus_one", "h_res:first", "h_res:last",
         "row_err_plus_one:first", "row_err_plus_one:last",
         "col_err_plus_one:first", "col_err_plus_one:last"}


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
    assert errors["logits"] < 1e-4 and errors["mtp_logits"] < 1e-4, errors
    assert errors["loss"] < 1e-5 and errors["mtp_loss"] < 1e-5, errors
    assert max(e for n, e in errors.items() if n.startswith("grad")) < 5e-3
    assert max(e for n, e in errors.items() if n.startswith("h_res")) < 1e-5
    assert errors["choices"] == 0 and errors["dropped_pairs_plus_one"] == 0
    assert got["logits"].shape == got["mtp_logits"].shape == (SEQ, VOCAB)
    # two expert layers and the module's block, over ALL 16 experts
    assert got["choices"].shape == (3, SEQ, 16)
    assert got["h_res:first"].shape == (SEQ, 4, 4)
    # the second loss is in the first at the assumed weight
    main = float(got["loss"]) - 0.1 * float(got["mtp_loss"])
    assert 0 < main < float(got["loss"])


def test_the_last_positions_are_the_whole_run_s(tokens, reference):
    _, _, whole, _ = reference
    parts = build(small_config(), tokens, last=32)
    _, got, want = run(parts, tokens)
    assert got["logits"].shape == got["mtp_logits"].shape == (32, VOCAB)
    np.testing.assert_allclose(
        got["logits"], whole["logits"][-32:], atol=1e-4)
    np.testing.assert_allclose(
        got["mtp_logits"], whole["mtp_logits"][-32:], atol=1e-4)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and errors["logits"] < 1e-4, errors
    # the routing and the coefficients are compared over the whole run
    assert got["choices"].shape == (3, SEQ, 16)
    assert got["h_res:last"].shape == (SEQ, 4, 4)
    assert float(got["loss"]) != pytest.approx(float(whole["loss"]))


def test_without_the_module_the_loss_is_the_plain_one(tokens):
    """``num_nextn_predict_layers`` 0, what the rule would have left had
    memory refused the module: both sides drop it."""
    config = small_config(
        num_nextn_predict_layers=0,
        check_leaves=[leaf for leaf in small_config()["check_leaves"]
                      if not leaf.startswith("mtp_")])
    parts = build(config, tokens)
    variables, got, want = run(parts, tokens)
    assert "mtp_logits" not in got and float(got["mtp_loss"]) == 0.0
    assert not any(k.startswith("mtp_") for k in variables["params"])
    assert got["choices"].shape == (2, SEQ, 16)
    # both sides' mtp_loss is 0: leave 0 / 0 out of the comparison
    got.pop("mtp_loss"), want.pop("mtp_loss")
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok and errors["logits"] < 1e-4, errors


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer. Each
    share's routed part (the reference's, and the program's ``MoeMlp``
    told which experts it holds), with the shared expert and the
    residual mix counted once, add up to what the uncut reference gives
    for the whole sublayer: ``X' = H_res X + H_post^T (sum of the
    shares + shared)``."""
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
    streams = jax.random.normal(keys[4], (SEQ, 4, 64))
    R = ref()
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = R.hyper_coefficients(
            streams, held["hc_mlp"], config)
        h = R.rms_norm(
            jnp.einsum("sn,snc->sc", h_pre, streams),
            held["ln_mlp"]["scale"], 1e-6)
        shared = R.shared_expert(h, every)
        uncut, _, _ = R.hyper_connected(
            streams, held["hc_mlp"], config,
            lambda u: R.expert_layer(
                R.rms_norm(u, held["ln_mlp"]["scale"], 1e-6), every, bias,
                config, (0, 16))[0] + shared)
        parts, program = [], []
        for first in (0, 4, 8, 12):
            share = dict(every, **{
                name: every[name][first:first + 4]
                for name in ("w_gate", "w_up", "w_down")})
            parts.append(R.expert_layer(
                h, share, bias, config, (first, 4))[0])
            layer = M.MoeMlp(
                16, top_k=3, dispatch_impl="sorted", expert_dim=32,
                expert_act="swiglu", scoring="sigmoid", gate_scale=2.0,
                bias_update_speed=0.001, shared_experts=1,
                held_experts=(first, 4), held_rows=SEQ * 3)
            y, aux = layer.apply(
                {"params": share, "moe_state": {
                    "e_score_correction_bias": bias}}, h[None])
            assert float(aux["routing"]["dropped"]) == 0
            program.append(y[0] - shared)
        for routed in (parts, program):
            total = (jnp.einsum("smn,snc->smc", h_res, streams)
                     + h_post[:, :, None] * (sum(routed) + shared)[:, None])
            np.testing.assert_allclose(total, uncut, atol=2e-5)
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
    statistics and coefficients) against the float32 reference at this
    small size: nothing dropped, the coefficients' sums as the
    reference's, the losses and the logits close. Widths of 8 to 64
    average less than the cell's 64 to 3584, so the small size's own
    bounds are wider than ``check.py``'s, which PERF.md Section 6 holds
    against the chip's readings."""
    config = small_config(compute_dtype="bfloat16")
    parts = build(config, tokens)
    _, got, want = run(parts, tokens)
    stated, _ = refcheck.compare(got, want, parts["tolerance"])
    assert stated["dropped_pairs_plus_one"] == 0
    assert max(e for n, e in stated.items()
               if n.startswith(("row_err", "col_err"))) < 1e-4, stated
    assert max(e for n, e in stated.items() if n.startswith("h_res")) < 0.01
    assert stated["logits"] < 0.1 and stated["mtp_logits"] < 0.1, stated
    assert stated["loss"] < 0.01 and stated["mtp_loss"] < 0.01, stated
