"""The gated short convolution (``ops/short_conv.py``) and the mixer
built on it (``models/transformer.py:ShortConv``): against a loop over
positions, its gradients against that loop's, causality, float32 and
bfloat16; and what ``MoeTransformerLM`` runs the ``conv`` kind beside
and what it refuses, each by name."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import moe_transformer as M
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh
from elasticdl_tpu.train import step_fns

BATCH, SEQ, WIDTH = 2, 24, 8


def operands(taps=3, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    bcx = jax.random.normal(keys[0], (BATCH, SEQ, 3 * WIDTH), jnp.float32)
    w = jax.random.normal(keys[1], (taps, WIDTH), jnp.float32)
    return bcx.astype(dtype), w.astype(dtype)


def by_loop(bcx, w):
    """``y[t] = C[t] sum_j w[j] (B X)[t - (K - 1) + j]``, position by
    position, in jax so that it differentiates."""
    width, taps = w.shape[1], w.shape[0]
    b, c, x = (bcx[..., i * width:(i + 1) * width] for i in range(3))
    z, rows = b * x, []
    for t in range(bcx.shape[1]):
        total = jnp.zeros_like(z[:, 0])
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                total = total + w[j] * z[:, t - (taps - 1) + j]
        rows.append(c[:, t] * total)
    return jnp.stack(rows, axis=1)


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_op_is_the_loop_over_positions(taps):
    bcx, w = operands(taps)
    np.testing.assert_allclose(
        short_conv.gated_short_conv(bcx, w), by_loop(bcx, w), atol=1e-5)


def test_the_gradients_are_the_loop_s():
    bcx, w = operands()
    weights = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, WIDTH))
    loss = lambda fn: lambda bcx, w: (fn(bcx, w) * weights).sum()
    got = jax.grad(loss(short_conv.gated_short_conv), argnums=(0, 1))(bcx, w)
    want = jax.grad(loss(by_loop), argnums=(0, 1))(bcx, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    # the backward keeps the two operands and forms the rest again
    from jax._src.ad_checkpoint import saved_residuals

    saved = saved_residuals(short_conv.gated_short_conv, bcx, w)
    assert sorted(r[0].shape for r in saved) == sorted(
        [w.shape, bcx.shape])


def test_a_change_at_t_moves_no_output_before_t():
    bcx, w = operands()
    base = short_conv.gated_short_conv(bcx, w)
    for t in (0, 5, SEQ - 1):
        moved = short_conv.gated_short_conv(
            bcx.at[:, t].add(1.0), w)
        changed = np.abs(np.asarray(moved - base)).max(axis=(0, 2)) > 0
        assert not changed[:t].any()
        # itself and the K - 1 positions after it, and no further
        assert changed[t:t + 3].all() and not changed[t + 3:].any()
    # and the gradient of y[t] reaches nothing after t
    grad = jax.grad(
        lambda bcx: short_conv.gated_short_conv(bcx, w)[:, 9].sum())(bcx)
    reached = np.abs(np.asarray(grad)).max(axis=(0, 2)) > 0
    assert reached[7:10].all() and not reached[10:].any()
    assert not reached[:7].any()


def test_bfloat16_operands_float32_arithmetic():
    bcx, w = operands(dtype=jnp.bfloat16)
    got = short_conv.gated_short_conv(bcx, w)
    assert got.dtype == jnp.bfloat16
    # one rounding of the float32 result: half a bfloat16 step
    want = by_loop(bcx.astype(jnp.float32), w.astype(jnp.float32))
    np.testing.assert_array_equal(got, want.astype(jnp.bfloat16))
    grads = jax.grad(
        lambda bcx, w: short_conv.gated_short_conv(bcx, w).astype(
            jnp.float32).sum(), argnums=(0, 1))(bcx, w)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.bfloat16]
    want = jax.grad(
        lambda bcx, w: by_loop(bcx, w).sum(), argnums=(0, 1))(
            bcx.astype(jnp.float32), w.astype(jnp.float32))
    for a, b in zip(grads, want):
        np.testing.assert_allclose(
            a.astype(jnp.float32), b, rtol=2e-2, atol=2e-2)


def test_the_projection_is_three_times_the_taps_wide():
    bcx, w = operands()
    with pytest.raises(ValueError, match=r"B \| C \| X"):
        short_conv.gated_short_conv(bcx[..., :2 * WIDTH], w)


def test_the_mixer_s_tree_and_scopes():
    mixer = T.ShortConv(T.ShortConvDims(taps=3))
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, SEQ, WIDTH))
    variables = mixer.init(jax.random.PRNGKey(0), x)
    shapes = jax.tree_util.tree_map(lambda a: a.shape, variables["params"])
    assert shapes == {
        "in_proj": {"kernel": (WIDTH, 3 * WIDTH)},
        "conv_kernel": (3, WIDTH),
        "proj_out": {"kernel": (WIDTH, WIDTH)}}
    p = variables["params"]
    want = by_loop(x @ p["in_proj"]["kernel"], p["conv_kernel"]) @ p[
        "proj_out"]["kernel"]
    np.testing.assert_allclose(mixer.apply(variables, x), want, atol=1e-5)
    text = jax.jit(jax.grad(
        lambda v: mixer.apply(v, x).sum())).lower(variables).as_text(
            debug_info=True)
    for scope in ("short_conv/in_proj", "short_conv/gate",
                  "short_conv/out_proj"):
        assert scope in text
    for field in ("mask", "rope_scaling"):
        with pytest.raises(ValueError, match="has no " + field):
            T.make_attention(4, conv=T.ShortConvDims(3), norm_eps=1e-5,
                             **{field: object()})


def model(**changes):
    fields = dict(
        vocab_size=64, num_layers=6, num_heads=4, embed_dim=32,
        layer_kinds=("conv", "conv", "full", "conv", "conv", "conv"),
        conv=T.ShortConvDims(3), head_dim=8, num_kv_heads=2,
        head_norm="rmsnorm", first_k_dense=2, dense_act="swiglu",
        dense_dim=48, num_experts=8, held_experts=(0, 4), held_rows=256,
        top_k=2, expert_dim=16, expert_act="swiglu", moe_every=1,
        norm="rmsnorm", norm_eps=1e-5, scoring="sigmoid",
        bias_update_speed=0.001, dispatch_impl="sorted",
        aux_loss_weight=0.0, rope_theta=1e6, tie_embeddings=True)
    fields.update(changes)
    return M.MoeTransformerLM(**fields)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (2, 32)), jnp.int32)


def test_conv_in_dense_and_in_expert_blocks(tokens):
    lm = model()
    variables = lm.init(jax.random.PRNGKey(0), tokens, training=False)
    params = variables["params"]
    conv = {"in_proj", "conv_kernel", "proj_out"}
    assert set(params["block_0"]["attn"]) == conv  # a dense block's
    assert "mlp_gate" in params["block_0"]
    assert set(params["block_3"]["attn"]) == conv  # an expert block's
    assert "moe_mlp" in params["block_3"]
    assert "query" in params["block_2"]["attn"]
    assert "lm_head" not in params
    outputs, _ = lm.apply(
        variables, tokens, training=True, mutable=["moe_state"])
    assert outputs["logits"].shape == (2, 32, 64)
    # what the model is made of is read from its fields, once, and
    # nothing leaves the step for it
    assert lm.mixer_kinds() == {
        "conv_layers": 5, "full_layers": 1, "dense_layers": 2,
        "conv_taps": 3, "conv_channels": 32, "head_dim": 8, "kv_heads": 2}
    assert "mixers" not in outputs
    assert step_fns.facts_of(outputs).keys() == {"routing"}
    # the tied head is the embedding, transposed
    eval_logits = lm.apply(variables, tokens)
    untied = model(tie_embeddings=False)
    other = untied.init(jax.random.PRNGKey(0), tokens, training=False)
    assert other["params"]["lm_head"]["kernel"].shape == (32, 64)
    put = dict(other["params"], lm_head={
        "kernel": params["wte"]["embedding"].T})
    put.update({k: v for k, v in params.items() if k != "lm_head"})
    np.testing.assert_allclose(
        untied.apply(dict(other, params=put), tokens), eval_logits,
        atol=1e-5)
    # causal: a later token moves no earlier position's logits
    moved = lm.apply(variables, tokens.at[:, 20].set(5))
    np.testing.assert_allclose(moved[:, :20], eval_logits[:, :20], atol=1e-5)
    assert float(jnp.abs(moved[:, 20:] - eval_logits[:, 20:]).max()) > 1e-4


def test_remat_and_the_scope_of_the_leading_mlps(tokens):
    plain = model()
    variables = plain.init(jax.random.PRNGKey(0), tokens, training=False)

    def grads(lm):
        def loss(params):
            out, _ = lm.apply(
                dict(variables, params=params), tokens, training=True,
                mutable=["moe_state"])
            return M.loss(tokens, out).mean()
        return jax.jit(jax.grad(loss))

    want = grads(plain)(variables["params"])
    for policy in ("full", "dots"):
        got = grads(model(remat=True, remat_policy=policy))(
            variables["params"])
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, atol=2e-5)
    text = grads(plain).lower(variables["params"]).as_text(debug_info=True)
    assert "/dense_mlp/" in text and "short_conv/gate" in text
    # a dense block's MLP has the scope whatever the model's mixers
    # are, and a model without the kind says nothing of kinds
    no_conv = model(layer_kinds=None, conv=None)
    others = no_conv.init(jax.random.PRNGKey(0), tokens, training=False)
    assert "/dense_mlp/" in jax.jit(
        lambda v: no_conv.apply(v, tokens)).lower(others).as_text(
            debug_info=True)
    assert no_conv.mixer_kinds() is None


def test_conv_under_a_data_axis(tokens):
    mesh = build_mesh(MeshConfig(dp=2), num_devices=2)
    lm = model(mesh=mesh)
    variables = model().init(jax.random.PRNGKey(0), tokens, training=False)
    np.testing.assert_allclose(
        jax.jit(lambda v: lm.apply(v, tokens))(variables),
        model().apply(variables, tokens), atol=1e-5)


REFUSED = [
    ("block_diffusion", dict(objective="block_diffusion", bd_mask_id=63,
                             first_k_dense=0)),
    ("kind_fields", dict(kind_fields={"full": T.MixerKind(4)})),
    ("latent attention", dict(
        latent=T.LatentDims(16, 8, 4, 8), head_dim=None, num_kv_heads=None,
        head_norm=None)),
    ("Gated DeltaNet", dict(linear=T.GatedDeltaDims(2, 2, 8, 8, 4))),
    ("hyper-connections", dict(hc=T.HyperDims(2))),
    ("prediction module", dict(mtp_layers=1)),
    ("ring", dict(attention_impl="ring")),
]


@pytest.mark.parametrize("what,changes", REFUSED, ids=[r[0] for r in REFUSED])
def test_what_conv_was_not_built_beside_is_refused_by_name(
        tokens, what, changes):
    with pytest.raises(ValueError, match="a 'conv' layer .* beside .*%s"
                       % what.split()[0]):
        model(**changes).init(jax.random.PRNGKey(0), tokens, training=False)


def test_conv_beside_ep_is_refused(tokens):
    mesh = build_mesh(MeshConfig(ep=2), num_devices=2)
    with pytest.raises(ValueError, match="spread over ep"):
        model(mesh=mesh, held_experts=None).init(
            jax.random.PRNGKey(0), tokens, training=False)


def test_the_kinds_are_checked_by_name(tokens):
    with pytest.raises(
            ValueError, match="'kda' and 'mamba' need their mixer's sizes"):
        model(conv=None).init(jax.random.PRNGKey(0), tokens, training=False)
    with pytest.raises(ValueError, match="each is 'full', 'window', 'linear'"):
        model(layer_kinds=("conv", "mamba")).init(
            jax.random.PRNGKey(0), tokens, training=False)
    # a dense block takes softmax, latent or conv, and no Gated DeltaNet
    with pytest.raises(
            ValueError, match="Mamba-2 mixer; layer 0 asks for a Gated"):
        model(layer_kinds=("linear", "full"), conv=None,
              linear=T.GatedDeltaDims(2, 2, 8, 8, 4)).init(
                  jax.random.PRNGKey(0), tokens, training=False)


def test_a_64_wide_head_s_tiles_and_schedule_are_chosen_on_purpose():
    """``_blocks`` at LFM2's shape keeps the rule's 1024 x 1024 (its
    docstring has the chip's table), VMEM holds a 64-wide row on 128
    lanes, and nothing moved at 128 or 256."""
    bf16 = jnp.bfloat16
    for backward in (False, True):
        assert F._blocks(32768, 32768, 64, bf16, None, None,
                         backward=backward) == (1024, 1024)
        assert F._blocks(32768, 32768, 128, bf16, None, None,
                         backward=backward) == (1024, 1024)
    assert F._blocks(32768, 32768, 256, bf16, None, None) == (512, 1024)
    assert F._blocks(2048, 2048, 256, bf16, None, None,
                     backward=True) == (512, 512)
    assert [F._lanes(w) for w in (64, 128, 192, 256)] == [128, 128, 256, 256]
    count = lambda seq, width: F.fused_bwd_vmem_bytes(
        seq, width, 1024, 1024, 2)
    assert count(32768, 64) == count(32768, 128)
    assert count(32768, 64) == 52 * 2**20
    assert F.backward_schedule(32768, 32768, 64, bf16) == "fused"
    # past the two-buffer count dq's output block gets one (PR 61)
    assert F.fused_dq_buffers(32768, 32768, 64, bf16) == 2
    assert F.fused_dq_buffers(65536, 65536, 64, bf16) == 1
    assert F.fused_dq_buffers(32768, 32768, 256, bf16) == 1
    assert F.backward_schedule(131072, 131072, 64, bf16) == "split"
    assert F.backward_schedule(65536, 65536, 256, bf16) == "split"
