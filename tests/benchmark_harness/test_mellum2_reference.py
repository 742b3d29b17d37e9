"""The repo's ``MoeTransformerLM`` as the Mellum2 zoo builds it, OVER
``ep=4`` on four virtual devices, against the configuration's plain
reference (``benchmark/configs/mellum2-12b-a2.5b-ep4/reference.py``),
through the configuration's ``check.py`` in ``lib/refcheck.py``'s
order, at a preset size on the CPU with seeded weights
(``preset/configs/tiny-mellum2``): hidden 64, one period (window,
window, window, full), 8 query heads of 16 over 2 kv heads, a window of
24, the full layer under YaRN over 32 positions, 8 experts of 32 spread
two a rank, top-2; four sequences of 128 tokens, one a rank; in
float32, whole and over the last positions; and the check's names
against faults of the kinds ISSUE 45's equations rule out."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MELLUM = os.path.join(REPO, "benchmark", "configs", "mellum2-12b-a2.5b-ep4")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-mellum2",
    "config.json")
SEQ, VOCAB = 128, 512
NAMES = {"logits", "loss", "choices", "dropped_pairs_plus_one"}


def small_config(**changes):
    with open(TINY) as f:
        config = json.load(f)
    config.update(changes)
    return config


def mesh():
    return build_mesh(MeshConfig(ep=4), num_devices=4)


def build(config, tokens, remat_policy="none", last=None, model=None):
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(MELLUM, "check.py"))
    spec = {
        "config": config, "seed": 5,
        "zoo": os.path.join(MELLUM, "zoo.py"),
        "reference": os.path.join(MELLUM, "reference.py"),
        "cell": {"mesh": "ep=4", "last_positions": last,
                 "model_params": {"remat_policy": remat_policy}},
    }
    return check.build(spec, tokens, model=model, mesh=mesh())


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
def compared(tokens):
    parts = build(small_config(), tokens)
    return (parts,) + run(parts, tokens)


def zoo():
    return refcheck.sys.modules["edlbench_zoo"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(MELLUM, "reference.py")) as f:
        source = f.read()
    assert "import elasticdl_tpu" not in source
    assert "from elasticdl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    for word in ("pallas", "shard_map", "all_to_all", "psum", "Mesh("):
        assert word not in source, word


def test_the_zoo_builds_the_published_block(compared):
    _, variables, _, _ = compared
    params = variables["params"]
    for block in ("block_0", "block_3"):
        attn = params[block]["attn"]
        assert attn["query"]["kernel"].shape == (64, 8, 16)
        assert attn["key"]["kernel"].shape == (64, 2, 16)
        assert attn["out_proj"]["kernel"].shape == (8, 16, 64)
        assert set(attn) == {"query", "key", "value", "out_proj"}
        moe = params[block]["moe_mlp"]
        # ALL the experts: nothing is held back, no shared expert
        assert moe["router"]["kernel"].shape == (64, 8)
        assert moe["w_gate"].shape == (8, 64, 32)
        assert set(moe) == {"router", "w_gate", "w_up", "w_down"}
    assert params["lm_head"]["kernel"].shape == (64, VOCAB)
    # the batch: the harness's sample and three other orders of it
    batch = np.asarray(variables["batch"])
    assert batch.shape == (4, SEQ)
    assert all(sorted(row) == sorted(batch[0]) for row in batch)
    assert len({row.tobytes() for row in batch}) == 4
    model = zoo().model_from_config(small_config())
    assert model.layer_kinds == ("window", "window", "window", "full")
    full, window = model.kind_fields["full"], model.kind_fields["window"]
    assert (full.num_heads, full.rope_theta, full.rotary_dim,
            full.window) == (8, 500000.0, None, None)
    assert full.rope_scaling.factor == 16.0
    assert full.rope_scaling.mscale == pytest.approx(1.0)
    assert window == T.MixerKind(8, 500000.0, None, None, 24)
    assert (model.scoring, model.normalize_gates, model.shared_experts,
            model.held_experts) == ("softmax", True, 0, None)
    assert (model.aux_loss_weight, model.dispatch_impl) == (0.001, "sorted")
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        zoo().model_from_config(small_config(tie_word_embeddings=True))
    with pytest.raises(ValueError, match="every built layer is sparse"):
        zoo().model_from_config(small_config(
            mlp_layer_types=["dense"] + ["sparse"] * 7))
    linear = json.loads(json.dumps(small_config()["rope_parameters"]))
    linear["sliding_attention"]["rope_type"] = "linear"
    with pytest.raises(ValueError, match="'default' or 'yarn'"):
        zoo().model_from_config(small_config(rope_parameters=linear))


def test_the_system_over_ep_equals_the_reference_in_float32(compared):
    parts, _, got, want = compared
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    assert set(errors) == NAMES | {
        "grad:" + path for path in small_config()["check_leaves"]}
    assert errors["choices"] == 0.0
    assert errors["dropped_pairs_plus_one"] == 0.0
    assert max(errors.values()) < 2e-4, errors
    # both sides saw the whole batch: (layers, B, S, E), 2 choices each
    assert got["choices"].shape == (4, 4, SEQ, 8)
    assert float(got["choices"].sum()) == 4 * 4 * SEQ * 2
    assert got["grad:block_1/moe_mlp/w_gate"].shape == (8, 64, 32)
    # every rank's experts got a gradient through the exchange
    per_expert = jnp.abs(got["grad:block_1/moe_mlp/w_gate"]).sum((1, 2))
    assert bool((per_expert > 0).all())


@pytest.mark.parametrize("remat_policy", ["flash", "full"])
def test_over_the_last_positions_and_under_remat(tokens, remat_policy):
    parts = build(small_config(), tokens, remat_policy, last=32)
    _, got, want = run(parts, tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert ok, errors
    assert got["logits"].shape == (4, 32, VOCAB)
    assert max(errors.values()) < 2e-4, errors


def wrong(config, **changes):
    """The zoo's model of a config that differs, on the cell's mesh."""
    return zoo().model_from_config(
        small_config(**changes), mesh=mesh(), attention_impl="xla")


@pytest.mark.parametrize("fault,failing", [
    # the band ignored in the window layers
    (dict(sliding_window=SEQ), "logits"),
    # the band off by a quarter
    (dict(sliding_window=32), "logits"),
    # the gates left as the softmax gave them
    (dict(norm_topk_prob=False), "logits"),
])
def test_the_check_fails_what_the_equations_rule_out(
        tokens, compared, fault, failing):
    base = small_config()
    parts = build(base, tokens, last=32, model=wrong(base, **fault))
    _, got, want = run(parts, tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok
    assert errors[failing] > refcheck.tolerance_of(
        failing, parts["tolerance"]), errors


def test_yarn_on_the_full_layer_alone_is_checked(tokens):
    """The full layer under the window layers' plain table: its own W_q
    and W_k read over the gradients' bound."""
    base = small_config()
    plain = json.loads(json.dumps(base["rope_parameters"]))
    plain["full_attention"] = dict(plain["sliding_attention"])
    parts = build(base, tokens, last=32,
                  model=wrong(base, rope_parameters=plain))
    _, got, want = run(parts, tokens)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok
    assert errors["grad:block_3/attn/query/kernel"] > refcheck.tolerance_of(
        "grad:block_3/attn/query/kernel", parts["tolerance"]), errors
