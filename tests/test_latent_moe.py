"""What Moonlight-16B-A3B's (DeepSeek-V3's) block added to the program
(ISSUE 29), at small sizes on the CPU with seeded weights:

- the flash kernels with a q / k width that differs from v's (192 /
  128: one and a half lane tiles), against ``xla_attention``, in
  interpret mode, under both backward schedules;
- ``LatentAttention`` against its equations written out;
- ``route_top_k``'s sigmoid scoring, selection bias and scaling;
- the balancing bias through ``make_train_step``: it moves toward the
  under-loaded experts, receives no gradient, and the optimizer never
  sees it;
- the shared experts, the dense-first layer pattern, the sequence-wise
  balance loss;
- ``moe_sharding_rules``: no parameter of the new model falls to the
  catch-all rule.

``tests/benchmark_harness/test_moonlight_reference.py`` holds the whole
model against ``reference.py`` and the three wrong variants.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.models import moe_transformer, transformer
from elasticdl_tpu.models.moe_transformer import MoeMlp, MoeTransformerLM
from elasticdl_tpu.models.transformer import LatentAttention, LatentDims
from elasticdl_tpu.ops import attention as attention_ops
from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops.attention import xla_attention
from elasticdl_tpu.train.optimizers import create_optimizer
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import create_train_state
from tests.kernel_common import traced_flash

DIMS = LatentDims(
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)


# ---------------------------------------------------------------- flash


def _qkv(seq, qk_dim, v_dim, dtype, heads=2, seed=0):
    rng = np.random.RandomState(seed)
    make = lambda d: jnp.asarray(
        rng.randn(1, heads, seq, d) * 0.5, dtype)
    return make(qk_dim), make(qk_dim), make(v_dim)


def _value_and_grads(attention, q, k, v):
    def loss(q, k, v):
        out = attention(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out,) + grads


@functools.lru_cache(maxsize=None)
def _xla_reference(widths, dtype):
    """(q, k, v, the XLA attention's forward and gradients): it knows
    of no backward schedule, so once for both."""
    q, k, v = _qkv(512, *widths, dtype)
    return (q, k, v), jax.jit(functools.partial(
        _value_and_grads, functools.partial(xla_attention, causal=True)))(
            q, k, v)


@pytest.mark.parametrize("schedule", ["fused", "split"])
@pytest.mark.parametrize("widths,dtype,blocks", [
    ((192, 128), jnp.float32, (128, 128)),   # MLA's widths
    ((192, 128), jnp.bfloat16, (256, 128)),
    ((24, 16), jnp.float32, (128, 256)),     # the small test model's
    ((128, 256), jnp.float32, (128, 128)),   # v the wider one
], ids=["192-128-f32", "192-128-bf16", "24-16", "128-256"])
def test_flash_with_two_head_widths_matches_xla(
        widths, dtype, blocks, schedule, monkeypatch):
    """o and dv are v's width, dq and dk the q / k width; forward, dq,
    dk, dv against the XLA reference, causal, interpret mode."""
    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    (q, k, v), want = _xla_reference(widths, dtype)
    flash = lambda q, k, v: F.flash_attention(
        q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
        interpret=True)
    # one trace: the names are read from the program that runs
    names, got = traced_flash(functools.partial(_value_and_grads, flash), (q, k, v))
    assert names == (
        ["flash_bwd", "flash_fwd"] if schedule == "fused"
        else ["flash_dkv", "flash_dq", "flash_fwd"])
    assert [g.shape[-1] for g in got] == [
        widths[1], widths[0], widths[0], widths[1]]
    tol = 6e-2 if dtype == jnp.bfloat16 else 3e-4
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol)


def test_the_scale_is_the_q_width_and_q_k_must_agree():
    q, k, v = _qkv(256, 192, 128, jnp.float32)
    a = F.flash_attention(q, k, v, causal=True, interpret=True)
    b = F.flash_attention(
        q, k, v, causal=True, sm_scale=192 ** -0.5, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="share a width"):
        F.flash_attention(q, v, v, causal=True, interpret=True)


def test_shape_functions_take_both_widths():
    bf16 = jnp.bfloat16
    # the cell: 8192 x 192 / 128 takes the tall q-block and stays fused
    assert F._blocks(8192, 8192, 192, bf16, None, None, v_dim=128) == (
        1024, 1024)
    assert F.backward_schedule(8192, 8192, 192, bf16, v_dim=128) == "fused"
    # VMEM holds whole lane tiles: 192 counts as 256 on the q / k side,
    # v's 128 as it is, so the count lies between the equal-width ones
    count = lambda hd, vd: F.fused_bwd_vmem_bytes(8192, hd, 1024, 1024, 2, vd)
    assert count(128, None) < count(192, 128) < count(256, None)
    assert count(192, 128) == count(256, 128)
    assert count(256, None) == count(256, 256)
    # the schedule follows dq's accumulator, which is q-wide
    assert F.backward_schedule(65536, 65536, 192, bf16, v_dim=128) == "split"
    # kimi-linear48b-s32k: 77.25 MiB with the two buffers of dq's
    # output block the count assumes by default, 61.25 with one (PR 61)
    assert [F.fused_bwd_vmem_bytes(32768, 192, 512, 1024, 2, 128, buffers)
            / 2**20 for buffers in (2, 1)] == [77.25, 61.25]
    assert F.fused_dq_buffers(32768, 32768, 192, bf16, v_dim=128) == 1
    assert F.backward_schedule(32768, 32768, 192, bf16, v_dim=128) == "fused"
    # equal widths: what the parent counted (PR 26: 47 MiB at 16k x 256)
    assert round(
        F.fused_bwd_vmem_bytes(16384, 256, 512, 1024, 2) / 2**20) == 47


def test_the_attention_line_names_both_widths_and_the_layout():
    q, k, v = _qkv(8192, 192, 128, jnp.bfloat16, heads=1)
    facts = attention_ops._flash_facts(q, k, v, True, None, None)
    assert facts.startswith(
        "head q/k=192 v=128 layout=whole, flash backward=fused, pairs run=")
    same = attention_ops._flash_facts(q, k, q, True, None, None)
    assert same.startswith("flash backward=")  # the other cells' line
    assert attention_ops._pallas_refusal(q, k, v, None, None) == ""


# ----------------------------------------------------- latent attention


def _rotate_halves(x, base):
    seq, dim = x.shape[-2], x.shape[-1]
    half = dim // 2
    angle = np.arange(seq)[:, None] * base ** (-np.arange(half) / half)
    a, b = x[..., :half], x[..., half:]
    return np.concatenate(
        [a * np.cos(angle) - b * np.sin(angle),
         b * np.cos(angle) + a * np.sin(angle)], axis=-1)


def test_latent_attention_against_its_equations():
    layer = LatentAttention(
        4, DIMS, attention_impl="xla", rope_theta=50000.0, norm_eps=1e-5)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 64, 48), jnp.float32)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    p = jax.tree_util.tree_map(np.asarray, variables["params"])
    shapes = {
        "/".join(k.key for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(p)}
    assert shapes == {
        "q_proj/kernel": (48, 4, 24), "kv_down/kernel": (48, 40),
        "kv_norm/scale": (32,), "kv_up/kernel": (32, 4, 32),
        "out_proj/kernel": (4, 16, 48)}
    got = np.asarray(jax.jit(layer.apply)(variables, x))

    xs = np.asarray(x, np.float64)
    q = np.einsum("bsd,dhk->bhsk", xs, p["q_proj"]["kernel"])
    c = xs @ p["kv_down"]["kernel"]
    c_kv = c[..., :32]
    c_kv = c_kv / np.sqrt((c_kv ** 2).mean(-1, keepdims=True) + 1e-5)
    c_kv = c_kv * p["kv_norm"]["scale"]
    kv = np.einsum("bsr,rhk->bhsk", c_kv, p["kv_up"]["kernel"])
    k_rope = _rotate_halves(c[:, None, :, 32:], 50000.0)  # ONE head
    q = np.concatenate(
        [q[..., :16], _rotate_halves(q[..., 16:], 50000.0)], -1)
    k = np.concatenate(
        [kv[..., :16], np.broadcast_to(k_rope, kv.shape[:3] + (8,))], -1)
    scores = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(24.0)
    scores = np.where(np.tril(np.ones((64, 64), bool)), scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bhkv->bqhv", probs, kv[..., 16:])
    want = np.einsum("bqhv,hvd->bqd", out, p["out_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_latent_attention_refuses_what_it_does_not_build():
    x = jnp.zeros((1, 128, 48))
    with pytest.raises(ValueError, match="one device's sequence"):
        jax.eval_shape(
            LatentAttention(4, DIMS, attention_impl="ring").init,
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="no qk_norm"):
        transformer.make_attention(4, DIMS, qk_norm=True)
    assert isinstance(
        transformer.make_attention(4, None, qk_norm=True),
        transformer.Attention)


def test_the_scopes_are_in_the_lowered_step():
    """Every matmul of the layer under its ``mla/`` scope, what needs no
    FLOPs under ``mla/assemble``, the shared experts under
    ``moe/shared``: the names the per-layer metrics read."""
    model = _small_lm()
    tokens = jnp.zeros((2, 128), jnp.int32)
    # lowering reads shapes and dtypes: no parameter is initialised
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))
    text = jax.jit(
        lambda v, t: model.apply(v, t, training=False)
    ).lower(variables, tokens).as_text(debug_info=True)
    for scope in ("mla/q_proj", "mla/kv_down", "mla/kv_up", "mla/assemble",
                  "mla/out_proj", "moe/shared", "moe/router", "moe/experts"):
        assert scope in text, scope


# -------------------------------------------------------------- routing


def test_sigmoid_routing_selects_by_bias_and_gates_without_it():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(64, 8), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.3, 0.3, 8), jnp.float32)
    gates, experts, probs = moe_ops.route_top_k(
        logits, 3, normalize=True, scoring="sigmoid", bias=bias, scale=2.446)
    scores = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(experts), want)
    picked = np.take_along_axis(scores, want, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates),
        picked / picked.sum(-1, keepdims=True) * 2.446, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(probs), scores / scores.sum(-1, keepdims=True), rtol=1e-5)
    # the bias changed the selection, so this test can see it
    unbiased = np.argsort(-scores, axis=-1)[:, :3]
    assert (np.sort(unbiased, -1) != np.sort(want, -1)).any()
    # unnormalised and unscaled: the scores themselves
    gates, _, _ = moe_ops.route_top_k(logits, 3, scoring="sigmoid")
    np.testing.assert_allclose(
        np.asarray(gates), np.take_along_axis(scores, unbiased, -1),
        rtol=1e-5)
    # no gradient reaches the bias
    grad = jax.grad(lambda b: moe_ops.route_top_k(
        logits, 3, True, "sigmoid", b, 2.446)[0].sum())(bias)
    np.testing.assert_array_equal(np.asarray(grad), 0.0)


def test_softmax_routing_refuses_a_bias_or_a_scale():
    logits = jnp.zeros((4, 8))
    with pytest.raises(ValueError, match="sigmoid"):
        moe_ops.route_top_k(logits, 2, bias=jnp.zeros(8))
    with pytest.raises(ValueError, match="sigmoid"):
        moe_ops.route_top_k(logits, 2, scale=2.0)
    with pytest.raises(ValueError, match="scoring"):
        moe_ops.route_top_k(logits, 2, scoring="tanh")


def test_sequence_balance_loss_by_hand():
    rng = np.random.RandomState(1)
    probs = rng.dirichlet(np.ones(4), size=12).astype(np.float32)
    experts = rng.randint(0, 4, size=(12, 2)).astype(np.int32)
    got = float(moe_ops.sequence_balance_loss(
        jnp.asarray(probs), jnp.asarray(experts), 3))
    want = 0.0
    for s in range(3):  # three sequences of four tokens
        p, e = probs[4 * s:4 * s + 4], experts[4 * s:4 * s + 4]
        f = np.bincount(e.reshape(-1), minlength=4) * 4 / (2 * 4)
        want += float((f * p.mean(0)).sum()) / 3
    assert got == pytest.approx(want, rel=1e-5)
    # a uniform router scores 1 whatever it chose
    uniform = jnp.full((12, 4), 0.25)
    assert float(moe_ops.sequence_balance_loss(
        uniform, jnp.asarray(experts), 3)) == pytest.approx(1.0)


def test_bias_update_is_a_sign_step_toward_the_mean_load():
    bias = jnp.asarray([0.0, 0.5, -0.5, 0.1])
    sizes = jnp.asarray([10, 2, 6, 6], jnp.int32)  # mean 6
    np.testing.assert_allclose(
        np.asarray(moe_ops.balancing_bias_update(bias, sizes, 0.01)),
        [-0.01, 0.51, -0.5, 0.1], rtol=1e-6)


# ------------------------------------------------- the model, the step


def _small_lm(**changes):
    fields = dict(
        vocab_size=256, num_layers=3, num_heads=4, embed_dim=48,
        num_experts=8, top_k=3, expert_dim=32, expert_act="swiglu",
        moe_every=1, norm="rmsnorm", norm_eps=1e-5, dispatch_impl="sorted",
        aux_loss_weight=0.001, latent=DIMS, rope_theta=50000.0,
        first_k_dense=1, dense_act="swiglu", dense_dim=80,
        scoring="sigmoid", gate_scale=2.446, bias_update_speed=0.001,
        seq_aux=True, shared_experts=2, attention_impl="xla")
    fields.update(changes)
    return MoeTransformerLM(**fields)


def test_the_first_k_blocks_are_dense_and_the_rest_share_experts():
    model = _small_lm()
    tokens = jnp.zeros((2, 128), jnp.int32)
    # names, shapes and dtypes are read: nothing is drawn
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))
    params = variables["params"]
    assert set(params["block_0"]) == {
        "attn", "ln_attn", "ln_mlp", "mlp_gate", "mlp_up", "mlp_down"}
    assert params["block_0"]["mlp_gate"]["kernel"].shape == (48, 80)
    for name in ("block_1", "block_2"):
        moe = params[name]["moe_mlp"]
        assert set(moe) == {
            "router", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down"}
        # two shared experts of width 32 side by side
        assert moe["shared_gate"]["kernel"].shape == (48, 64)
        assert moe["shared_down"]["kernel"].shape == (64, 48)
        assert set(params[name]["attn"]) == {
            "q_proj", "kv_down", "kv_norm", "kv_up", "out_proj"}
    # the bias: 8 floats an expert layer, in a collection of its own
    assert jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype.name), dict(variables["moe_state"])) == {
            name: {"moe_mlp": {"e_score_correction_bias": ((8,), "float32")}}
            for name in ("block_1", "block_2")}


@pytest.mark.parametrize("std,want", [(None, 48 ** -0.5), (1.0, 1.0)])
def test_the_embedding_is_drawn_at_the_stated_deviation(std, want):
    """Flax's 1 / sqrt(embed_dim) unless the model states another: at
    that default a seeded model's routers all see the context's mean
    (PERF.md Section 6, PR 29); the zoo of Moonlight states 1."""
    model = _small_lm(embed_init_std=std)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))()["params"]
    assert float(params["wte"]["embedding"].std()) == pytest.approx(
        want, rel=0.02)
    # nothing else is drawn differently
    assert float(params["lm_head"]["kernel"].std()) == pytest.approx(
        48 ** -0.5, rel=0.05)


def test_shared_experts_are_added_to_the_routed_output():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 128, 48), jnp.float32)
    common = dict(
        top_k=3, dispatch_impl="sorted", expert_dim=32, expert_act="swiglu",
        scoring="sigmoid", gate_scale=2.446)
    with_shared = MoeMlp(8, shared_experts=2, **common)
    variables = jax.jit(with_shared.init)(jax.random.PRNGKey(0), x)
    params = dict(variables["params"])
    y_both, _ = jax.jit(with_shared.apply)({"params": params}, x)
    routed = {k: v for k, v in params.items() if not k.startswith("shared")}
    y_routed, _ = jax.jit(MoeMlp(8, **common).apply)({"params": routed}, x)
    gate, up, down = (
        params["shared_" + n]["kernel"] for n in ("gate", "up", "down"))
    shared = (jax.nn.silu(x @ gate) * (x @ up)) @ down
    np.testing.assert_allclose(
        np.asarray(y_both), np.asarray(y_routed + shared), atol=1e-5)
    assert float(jnp.abs(shared).max()) > 1e-2


def test_the_bias_moves_toward_underloaded_experts_and_gets_no_gradient():
    """Three steps through ``make_train_step``: the bias leaves zero by
    ``speed`` a step against the sign of each expert's excess load, the
    optimizer state holds nothing for it, the routing counters report
    its magnitude, and a step's selection used the bias it was given."""
    model = _small_lm(num_layers=2)
    tx = create_optimizer("AdamW", learning_rate=3e-4, weight_decay=0.01)
    tokens = jnp.asarray(
        np.random.RandomState(0).zipf(1.2, (2, 128)) % 256, jnp.int32)
    state = jax.jit(lambda: create_train_state(
        model, tx, jax.random.PRNGKey(0), tokens))()
    path = ("moe_state", "block_1", "moe_mlp", "e_score_correction_bias")
    bias_of = lambda st: np.asarray(
        st.model_state[path[0]][path[1]][path[2]][path[3]])
    np.testing.assert_array_equal(bias_of(state), 0.0)
    assert "moe_state" not in state.params
    slots = jax.tree_util.tree_leaves_with_path(state.opt_state)
    assert not any("e_score_correction_bias" in str(p) for p, _ in slots)
    step = jax.jit(make_train_step(model, moe_transformer.loss, tx,
                                   health=True))
    batch = {"features": tokens, "labels": tokens, MASK_KEY: jnp.ones((2,))}

    @jax.jit
    def chosen(st):
        _, sown = model.apply(
            {"params": st.params, **st.model_state}, tokens, training=True,
            mutable=["intermediates"])
        return sown["intermediates"]["block_1"]["moe_mlp"]["experts"][0]

    def loads(st):
        """Pairs per expert that ``st``'s parameters and bias choose."""
        return np.bincount(
            np.asarray(chosen(st)).reshape(-1), minlength=8)

    for n in range(1, 4):
        load, before = loads(state), bias_of(state)
        state, _, scalars = step(state, batch)
        after = bias_of(state)
        np.testing.assert_allclose(
            after - before, 0.001 * np.sign(load.mean() - load), atol=1e-7)
        assert float(scalars["routing"]["bias_abs_max"]) == pytest.approx(
            np.abs(after).max())
        assert np.abs(after).max() <= 0.001 * n + 1e-7
    # under-loaded experts gained, over-loaded ones lost
    assert (after[load < load.mean()] > before[load < load.mean()]).all()
    assert (after[load > load.mean()] < before[load > load.mean()]).all()
    # no gradient: differentiating the loss with respect to the bias
    def loss_of(bias):
        out = model.apply(
            {"params": state.params, "moe_state": {"block_1": {"moe_mlp": {
                "e_score_correction_bias": bias}}}}, tokens, training=True)
        return moe_transformer.loss(tokens, out).mean()

    np.testing.assert_array_equal(
        np.asarray(jax.jit(jax.grad(loss_of))(jnp.asarray(after))), 0.0)


def test_eval_and_init_leave_the_bias_alone():
    model = _small_lm(num_layers=2)
    tokens = jnp.zeros((2, 128), jnp.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    np.testing.assert_array_equal(
        np.asarray(variables["moe_state"]["block_1"]["moe_mlp"][
            "e_score_correction_bias"]), 0.0)
    # a training call that may not write the collection reads it
    out = jax.jit(lambda v: model.apply(v, tokens, training=True))(variables)
    assert set(out) == {"logits", "aux_loss", "routing"}
    assert set(out["routing"]) == {
        "load_max", "load_mean", "entropy", "dropped", "bias_abs_max"}
    logits = jax.jit(lambda v: model.apply(v, tokens, training=False))(
        variables)
    assert logits.shape == (2, 128, 256)


def test_the_legacy_and_olmoe_models_keep_their_counters_and_state():
    """A model without a balancing bias has no ``moe_state`` and no
    ``bias_abs_max``: it compiles the program it compiled before."""
    model = MoeTransformerLM(
        vocab_size=64, num_layers=1, num_heads=2, embed_dim=32,
        num_experts=4, top_k=2, expert_dim=16, expert_act="swiglu",
        moe_every=1, dispatch_impl="sorted", normalize_gates=False,
        attention_impl="xla")
    tokens = jnp.zeros((1, 128), jnp.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    assert set(variables) == {"params"}
    out = jax.jit(lambda v: model.apply(v, tokens, training=True))(variables)
    assert set(out["routing"]) == {
        "load_max", "load_mean", "entropy", "dropped"}


def test_sigmoid_needs_the_sorted_dispatch():
    x = jnp.zeros((1, 128, 32))
    with pytest.raises(ValueError, match="sorted"):
        jax.eval_shape(
            MoeMlp(4, scoring="sigmoid").init, jax.random.PRNGKey(0), x)


def test_no_parameter_of_the_new_model_falls_to_the_catch_all_rule():
    model = _small_lm()
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((2, 128), int)))
    rules = moe_transformer.moe_sharding_rules()
    leaves = jax.tree_util.tree_leaves_with_path(variables["params"])
    assert len(leaves) > 30
    hits = {}
    for path, leaf in leaves:
        name = "/".join(p.key for p in path)
        pattern = next(
            pat.pattern for pat, _ in rules._rules if pat.search(name))
        assert pattern != ".*", name
        spec = rules.spec_for(name)
        assert len(spec) <= len(leaf.shape), (name, spec, leaf.shape)
        hits[name] = spec
    P = jax.sharding.PartitionSpec
    assert hits["block_1/attn/q_proj/kernel"] == P("fsdp", "tp", None)
    assert hits["block_1/attn/kv_up/kernel"] == P("fsdp", "tp", None)
    assert hits["block_1/attn/kv_down/kernel"] == P("fsdp", None)
    assert hits["block_0/mlp_gate/kernel"] == P("fsdp", "tp")
    assert hits["block_1/moe_mlp/shared_down/kernel"] == P("tp", "fsdp")
    assert hits["block_1/moe_mlp/w_gate"] == P("ep", "fsdp", "tp")
    assert hits["block_1/attn/kv_norm/scale"] == P()
