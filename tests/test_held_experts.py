"""One chip's share of an expert layer (ISSUE 31, the first half of
expert parallelism): ``ops/moe.py``'s ``sort_held`` / ``dispatch_held``
/ ``combine_held`` and ``MoeMlp`` with ``held_experts``, at small sizes
on the CPU. The router runs over ALL experts; only the pairs of the
held experts get a row; the shares of all the chips of a layer, the
shared expert counted once, add up to the whole layer (the test that
ties the share to the model)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.models.moe_transformer import MoeMlp, MoeTransformerLM
from elasticdl_tpu.models.transformer import GatedDeltaDims
from elasticdl_tpu.ops import moe as moe_ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sort_held_groups_the_held_pairs_and_counts_all_loads():
    experts = jnp.asarray(
        [[0, 5], [6, 1], [5, 7], [2, 5], [4, 6], [5, 4]], jnp.int32)
    pairs, valid, sizes, loads, held, dropped = moe_ops.sort_held(
        experts, 8, first=4, count=3, buffer_rows=8)
    # experts 4, 5, 6 are held: pairs (token * 2 + choice) by expert
    assert list(np.asarray(loads)) == [1, 1, 1, 0, 2, 4, 2, 1]
    assert int(held) == 8 and int(dropped) == 0
    assert list(np.asarray(pairs)) == [8, 11, 1, 4, 7, 10, 2, 9]
    assert bool(valid.all())
    assert list(np.asarray(sizes)) == [2, 4, 2]


def test_a_buffer_with_room_gives_its_spare_rows_to_the_last_group():
    experts = jnp.asarray([[0, 5], [6, 1], [5, 7], [2, 3]], jnp.int32)
    pairs, valid, sizes, loads, held, dropped = moe_ops.sort_held(
        experts, 8, first=4, count=3, buffer_rows=6)
    assert int(held) == 3 and int(dropped) == 0
    assert list(np.asarray(valid)) == [True] * 3 + [False] * 3
    assert list(np.asarray(pairs))[:3] == [1, 4, 2]
    # whole buffers are computed; the combine adds nothing for the rest
    assert list(np.asarray(sizes)) == [0, 2, 4] and int(sizes.sum()) == 6
    # a buffer larger than all pairs is cut to them
    assert moe_ops.sort_held(experts, 8, 4, 3, 100)[0].shape == (8,)


def test_a_pair_without_a_row_is_counted_not_hidden():
    experts = jnp.asarray([[4, 5], [4, 5], [4, 6], [5, 6]], jnp.int32)
    pairs, valid, sizes, _, held, dropped = moe_ops.sort_held(
        experts, 8, first=4, count=3, buffer_rows=5)
    assert int(held) == 8 and int(dropped) == 3
    assert bool(valid.all()) and list(np.asarray(sizes)) == [3, 2, 0]
    stats = moe_ops.routing_stats(
        jnp.full((4, 8), 0.125), jnp.asarray([0, 0, 0, 0, 3, 3, 2, 0]), 2,
        held=held, dropped=dropped)
    assert float(stats["held"]) == 8 and float(stats["dropped"]) == 3
    # the dropless path's counters are what they were
    whole = moe_ops.routing_stats(
        jnp.full((4, 8), 0.125), jnp.asarray([1] * 8), 2)
    assert "held" not in whole and float(whole["dropped"]) == 0


def test_dispatch_and_combine_are_each_other_s_transpose():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6, 4), jnp.float32)
    experts = jnp.asarray(rng.randint(0, 8, (6, 2)), jnp.int32)
    gates = jnp.asarray(rng.rand(6, 2), jnp.float32)
    pairs, valid, _, _, held, _ = moe_ops.sort_held(experts, 8, 2, 3, 8)
    rows = moe_ops.dispatch_held(x, pairs, 2)
    np.testing.assert_array_equal(rows, x[np.asarray(pairs) // 2])
    y = moe_ops.combine_held(rows, gates, pairs, valid)
    here = (np.asarray(experts) >= 2) & (np.asarray(experts) < 5)
    want = (np.asarray(gates) * here).sum(1)[:, None] * np.asarray(x)
    np.testing.assert_allclose(y, want, atol=1e-6)
    # the gradient of a gather is a scatter-add and the other way round
    g = jax.grad(lambda x: moe_ops.combine_held(
        moe_ops.dispatch_held(x, pairs, 2), gates, pairs, valid).sum())(x)
    np.testing.assert_allclose(
        g, np.broadcast_to((np.asarray(gates) * here).sum(1)[:, None],
                           x.shape), atol=1e-6)


def _layer(held, rows=64, **kw):
    return MoeMlp(
        16, top_k=3, dispatch_impl="sorted", expert_dim=8,
        expert_act="swiglu", normalize_gates=True, shared_experts=1,
        shared_gate=True, held_experts=held, held_rows=rows, **kw)


def _reference_of(configuration):
    path = os.path.join(
        REPO, "benchmark", "configs", configuration, "reference.py")
    spec = importlib.util.spec_from_file_location(
        configuration.replace("-", "_") + "_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# (the configuration whose reference gives the uncut layer, experts,
# top-k, shared experts behind a gate, the ways the layer is shared)
SHARE_CASES = {
    # Qwen3-Next's layer at a small size: 16 experts, top-3, one
    # shared expert behind its gate, over 16 / 4 / 2 chips
    "qwen3next-16-top3-shared": (
        "qwen3-next-80b-a3b-1chip", 16, 3, 1, (16, 4, 2)),
    # SDAR's layer at its published counts: 128 experts, top-8, no
    # shared expert, the deployment's eight shares (16 experts a chip)
    "sdar-128-top8-eight-shares": (
        "sdar-30b-a3b-1chip", 128, 8, 0, (8,)),
}


@pytest.mark.parametrize(
    "case", list(SHARE_CASES.values()), ids=list(SHARE_CASES))
def test_the_sixteen_shares_add_up_to_the_uncut_layer(case):
    """Each chip's ``MoeMlp`` holds its own experts' kernels (rows of
    ONE seeded stack), routes over all the experts and returns its
    part; the parts, a shared expert counted once, sum to what the
    uncut reference gives for the whole layer."""
    configuration, experts, top_k, shared_experts, ways = case
    ref = _reference_of(configuration)
    config = {"num_experts_per_tok": top_k, "norm_topk_prob": True,
              "published": {"num_experts": experts}}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16))
    flat = x.reshape(24, 16)

    def layer(held):
        return MoeMlp(
            experts, top_k=top_k, dispatch_impl="sorted", expert_dim=8,
            expert_act="swiglu", normalize_gates=True,
            shared_experts=shared_experts, shared_gate=bool(shared_experts),
            held_experts=held, held_rows=24 * top_k)

    whole = layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    assert params["w_gate"].shape == (experts, 16, 8)
    want, want_balance, _ = ref.expert_layer(
        flat, params, config, (0, experts))
    got, aux = whole.apply({"params": params}, x)
    np.testing.assert_allclose(got.reshape(24, 16), want, atol=1e-5)
    shared = ref.shared_expert(flat, params) if shared_experts else 0.0
    for chips in ways:
        count = experts // chips
        total = 0.0
        for chip in range(chips):
            first = chip * count
            mine = dict(params, **{
                name: params[name][first:first + count]
                for name in ("w_gate", "w_up", "w_down")})
            part, part_aux = layer((first, count)).apply(
                {"params": mine}, x)
            # the reference is given the same share
            ref_part, _, _ = ref.expert_layer(
                flat, mine, config, (first, count))
            np.testing.assert_allclose(
                part.reshape(24, 16), ref_part, atol=1e-5)
            # every chip sees every expert's load and the same loss
            np.testing.assert_allclose(
                part_aux["load_balancing"], want_balance, rtol=1e-5)
            assert float(part_aux["routing"]["dropped"]) == 0
            total = total + part.reshape(24, 16) - shared
        np.testing.assert_allclose(total + shared, want, atol=2e-5)
        assert float(jnp.abs(total).max()) > 1e-2


def test_a_share_holds_its_experts_kernels_only():
    x = jnp.zeros((1, 8, 16))
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape,
        _layer((4, 4)).init(jax.random.PRNGKey(0), x)["params"])
    assert shapes["w_gate"] == shapes["w_up"] == (4, 16, 8)
    assert shapes["w_down"] == (4, 8, 16)
    assert shapes["router"]["kernel"] == (16, 16)  # all experts
    assert shapes["shared_expert_gate"]["kernel"] == (16, 1)


def test_held_counters_reach_the_model_s_routing():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 16))
    layer = _layer((0, 4), rows=8)
    variables = layer.init(jax.random.PRNGKey(3), x)
    _, aux = layer.apply(variables, x)
    routing = aux["routing"]
    held = float(routing["held"])
    assert float(routing["load_mean"]) == 24 * 3 / 16  # over all experts
    assert float(routing["dropped"]) == max(held - 8, 0) > 0
    merged = moe_transformer.merge_routing(
        [routing, dict(routing, held=routing["held"] + 5)])
    assert float(merged["held"]) == held + 5
    assert float(merged["dropped"]) == 2 * float(routing["dropped"])


def test_held_experts_refuse_what_they_do_not_build():
    x = jnp.zeros((1, 8, 16))
    with pytest.raises(ValueError, match="held_rows"):
        _layer((0, 4), rows=None).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="outside 16 experts"):
        _layer((14, 4)).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="sorted"):
        MoeMlp(16, held_experts=(0, 4), held_rows=8).init(
            jax.random.PRNGKey(0), x)


def _small_lm(**kw):
    fields = dict(
        vocab_size=97, num_layers=4, num_heads=4, embed_dim=32,
        num_experts=16, top_k=3, expert_dim=8, expert_act="swiglu",
        normalize_gates=True, moe_every=1, norm="zero_centred_rmsnorm",
        dispatch_impl="sorted", shared_experts=1, shared_gate=True,
        held_experts=(4, 4), held_rows=128, attention_impl="xla",
        layer_kinds=("linear", "linear", "linear", "full"),
        linear=GatedDeltaDims(2, 4, 8, 8, 4, chunk=16), head_dim=16,
        num_kv_heads=2, head_norm="zero_centred_rmsnorm", rotary_dim=4,
        output_gate="sigmoid", embed_init_std=1.0)
    fields.update(kw)
    return MoeTransformerLM(**fields)


def test_the_layer_kinds_are_a_pattern_with_a_period():
    model = _small_lm(num_layers=6)
    tokens = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    linear = [i for i in range(6)
              if "in_proj_qkvz" in params["block_%d" % i]["attn"]]
    full = [i for i in range(6) if "query" in params["block_%d" % i]["attn"]]
    assert linear == [0, 1, 2, 4, 5] and full == [3]
    with pytest.raises(ValueError, match="layer_kinds"):
        _small_lm(layer_kinds=("linear", "window")).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="layer_kinds"):
        _small_lm(linear=None).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="dense block"):
        _small_lm(first_k_dense=1).init(jax.random.PRNGKey(0), tokens)


def test_the_model_trains_and_reports_its_share():
    model = _small_lm()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 97)
    variables = model.init(jax.random.PRNGKey(0), tokens, training=False)
    out = model.apply(variables, tokens, training=True)
    assert out["logits"].shape == (2, 48, 97)
    assert set(out["routing"]) == {
        "load_max", "load_mean", "entropy", "dropped", "held"}
    assert float(out["routing"]["dropped"]) == 0
    assert 0 < float(out["routing"]["held"]) <= 96 * 3
    grads = jax.grad(lambda p: moe_transformer.loss(
        tokens, model.apply({"params": p}, tokens, training=True)).mean())(
            variables["params"])
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert bool(jnp.isfinite(leaf).all()), path
        assert float(jnp.abs(leaf).max()) > 0, path
    # eval returns bare logits, as every model of the family
    assert model.apply(variables, tokens).shape == (2, 48, 97)


def test_no_parameter_of_the_new_model_falls_to_the_catch_all_rule():
    model = _small_lm()
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((2, 32), int)))
    rules = moe_transformer.moe_sharding_rules()
    hits = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            variables["params"]):
        name = "/".join(p.key for p in path)
        pattern = next(
            pat.pattern for pat, _ in rules._rules if pat.search(name))
        assert pattern != ".*", name
        spec = rules.spec_for(name)
        assert len(spec) <= len(leaf.shape), (name, spec, leaf.shape)
        hits[name] = spec
    P = jax.sharding.PartitionSpec
    assert hits["block_0/attn/in_proj_qkvz/kernel"] == P("fsdp", "tp")
    assert hits["block_0/attn/in_proj_ba/kernel"] == P("fsdp", "tp")
    assert hits["block_0/attn/conv_kernel"] == P(None, "tp")
    assert hits["block_0/attn/A_log"] == hits["block_0/attn/dt_bias"] == P()
    assert hits["block_0/attn/out_norm/scale"] == P()
    assert hits["block_0/attn/out_proj/kernel"] == P("tp", None, "fsdp")
    assert hits["block_3/attn/query/kernel"] == P("fsdp", "tp", None)
    assert hits["block_3/attn/q_norm/scale"] == P()
    assert hits["block_3/moe_mlp/shared_expert_gate/kernel"] == P()
    assert hits["block_3/moe_mlp/w_gate"] == P("ep", "fsdp", "tp")
