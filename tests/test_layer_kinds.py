"""Softmax layers of several KINDS in one model (PR 42):
``Attention`` with YaRN on the lanes that rotate against a direct
formula, ``Block`` (the dense block) with the grouped-query fields,
``MoeTransformerLM.layer_kinds`` with ``window`` and ``kind_fields``
(heads, rotary table, YaRN and mask by kind; the scopes and the
attention line), the refusals by name, and the models built without a
kind of their own against what they were."""

import hashlib
import logging
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.models import moe_transformer, transformer
from elasticdl_tpu.models.moe_transformer import MoeTransformerLM
from elasticdl_tpu.models.transformer import (
    Attention,
    Block,
    GatedDeltaDims,
    LatentDims,
    MixerKind,
    TransformerLM,
    YarnScaling,
    make_attention,
)
from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import create_train_state

YARN = YarnScaling(factor=64.0, original_max_position_embeddings=32,
                   beta_fast=4.0, beta_slow=1.0, mscale=1.0,
                   mscale_all_dim=0.0)
FULL = MixerKind(6, 500000.0, 8, YARN)
WINDOW = MixerKind(8, 10000.0, None, None, 24)


def direct_rotary(x, lanes, base, scaling=None):
    """(H, S, D) rotated as ISSUE 42 writes it: of the first ``lanes``
    lanes, lane i with lane i + lanes / 2, by pos x f_i; under YaRN f
    the blend and cos, sin times 0.1 mscale ln(factor) + 1; the other
    lanes pass through."""
    half = lanes // 2
    freqs = base ** (-2.0 * np.arange(half) / lanes)
    amplitude = 1.0
    if scaling is not None:
        turns = lambda r: lanes * math.log(
            scaling.original_max_position_embeddings / (r * 2 * math.pi)
        ) / (2 * math.log(base))
        low = max(math.floor(turns(scaling.beta_fast)), 0)
        high = min(math.ceil(turns(scaling.beta_slow)), lanes - 1)
        ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
        freqs = freqs * (1 - ramp) + freqs / scaling.factor * ramp
        amplitude = 0.1 * scaling.mscale * math.log(scaling.factor) + 1.0
    angle = np.arange(x.shape[1])[:, None] * freqs[None]
    cos, sin = np.cos(angle) * amplitude, np.sin(angle) * amplitude
    a, b = x[..., :half], x[..., half:lanes]
    return np.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., lanes:]], axis=-1)


def direct_attention(x, params, heads, kv_heads, dim, lanes, base, scaling,
                     window):
    """One sequence (S, d) through the gated grouped-query mixer, in
    numpy float64."""
    x = np.asarray(x, np.float64)
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    qg = np.einsum("sd,dhk->hsk", x, p["query"]["kernel"])
    q, gate = qg[..., :dim], qg[..., dim:]
    k = np.einsum("sd,dhk->hsk", x, p["key"]["kernel"])
    v = np.einsum("sd,dhk->hsk", x, p["value"]["kernel"])
    q = direct_rotary(q, lanes, base, scaling)
    k = direct_rotary(k, lanes, base, scaling)
    group = heads // kv_heads
    pos = np.arange(x.shape[0])
    allowed = pos[None, :] <= pos[:, None]
    if window is not None:
        allowed &= pos[:, None] - pos[None, :] < window
    out = np.zeros((heads, x.shape[0], dim))
    for h in range(heads):
        s = q[h] @ k[h // group].T / math.sqrt(dim)
        s = np.where(allowed, s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[h] = (w / w.sum(-1, keepdims=True)) @ v[h // group]
    out = out / (1.0 + np.exp(-gate))
    return np.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])


@pytest.mark.parametrize("kind,own", [("full", FULL), ("window", WINDOW)])
def test_attention_by_kind_against_the_direct_formula(kind, own):
    """``Attention`` with a kind's fields: YaRN's blended table and its
    amplitude on the 8 of 16 lanes that rotate (the others neither
    rotated nor scaled, the softmax scale 1 / sqrt(16)); the whole head
    at another base under a band."""
    mask = F.Band(own.window) if own.window else None
    mixer = Attention(
        own.num_heads, attention_impl="xla", head_dim=16, num_kv_heads=2,
        output_gate="sigmoid", rope_theta=own.rope_theta,
        rotary_dim=own.rotary_dim, rope_scaling=own.rope_scaling,
        mask=mask, kind_scope="attn_" + kind)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 96, 64))
    params = jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"]
    assert params["query"]["kernel"].shape == (64, own.num_heads, 32)
    got = jax.jit(mixer.apply)({"params": params}, x)[0]
    want = direct_attention(
        x[0], params, own.num_heads, 2, 16, own.rotary_dim or 16,
        own.rope_theta, own.rope_scaling, own.window)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_yarn_without_its_parts_is_another_function():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 96, 64))

    def out(**fields):
        mixer = Attention(4, attention_impl="xla", head_dim=16,
                          rotary_dim=8, rope_theta=500000.0, **fields)
        params = jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"]
        return jax.jit(mixer.apply)({"params": params}, x)

    import dataclasses

    stated = out(rope_scaling=YARN)
    for other in (None, dataclasses.replace(YARN, mscale=0.0),
                  dataclasses.replace(YARN, factor=8.0)):
        assert float(jnp.abs(stated - out(rope_scaling=other)).max()) > 1e-3
    # ``mscale_all_dim`` divides the amplitude and touches no scale
    same = dataclasses.replace(YARN, mscale=2.0, mscale_all_dim=2.0)
    bare = dataclasses.replace(YARN, mscale=0.0)
    np.testing.assert_allclose(
        out(rope_scaling=same), out(rope_scaling=bare), atol=1e-6)


def test_make_attention_hands_each_mixer_its_own():
    assert make_attention(
        4, norm_eps=1e-6, rope_scaling=YARN).rope_scaling == YARN
    latent = make_attention(
        4, LatentDims(32, 16, 8, 16), norm_eps=1e-6, rope_scaling=YARN,
        mask=None, kind_scope=None)
    assert latent.rope_scaling == YARN
    with pytest.raises(ValueError, match="latent attention has no mask"):
        make_attention(4, LatentDims(32, 16, 8, 16), norm_eps=1e-6,
                       mask=F.Band(8))
    linear = GatedDeltaDims(2, 4, 16, 16, 4)
    for name, value in (("mask", F.Band(8)), ("rope_scaling", YARN)):
        with pytest.raises(ValueError, match="Gated DeltaNet mixer has no "
                           + name):
            make_attention(4, None, linear, norm_eps=1e-6, **{name: value})


def test_the_dense_block_takes_the_mixer_s_fields():
    """Laguna's layer 0: 6 heads of 16 over 2 kv heads with a gate and
    partial rotary under YaRN, then a dense SwiGLU; at the fields'
    defaults the tree is the one it always was."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64))
    block = Block(
        dict(num_heads=6, attention_impl="xla", head_dim=16,
             num_kv_heads=2, rotary_dim=8, output_gate="sigmoid",
             rope_theta=500000.0, rope_scaling=YARN,
             kind_scope="attn_full"),
        norm="rmsnorm", mlp_act="swiglu", mlp_dim=96)
    params = jax.jit(block.init)(jax.random.PRNGKey(1), x)["params"]
    assert params["attn"]["query"]["kernel"].shape == (64, 6, 32)
    assert params["attn"]["key"]["kernel"].shape == (64, 2, 16)
    assert params["attn"]["out_proj"]["kernel"].shape == (6, 16, 64)
    assert params["mlp_gate"]["kernel"].shape == (64, 96)
    y, aux = jax.jit(block.apply)({"params": params}, x)
    assert y.shape == x.shape and bool(jnp.isfinite(y).all()) and aux == {}
    h = jax.jit(transformer.make_norm("rmsnorm", 1e-6, "n").apply)(
        {"params": params["ln_attn"]}, x)
    want = direct_attention(
        h[0], params["attn"], 6, 2, 16, 8, 500000.0, YARN, None)
    mixed = jax.jit(Attention(
        6, attention_impl="xla", head_dim=16, num_kv_heads=2, rotary_dim=8,
        output_gate="sigmoid", rope_theta=500000.0, rope_scaling=YARN,
    ).apply)({"params": params["attn"]}, h)
    np.testing.assert_allclose(mixed[0], want, atol=2e-5)
    # a band in a dense block
    banded = Block(dict(num_heads=4, attention_impl="xla", mask=F.Band(8)))
    plain = Block(dict(num_heads=4, attention_impl="xla"))
    p = jax.jit(plain.init)(jax.random.PRNGKey(1), x)
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(
        jax.eval_shape(banded.init, jax.random.PRNGKey(1), x))
    assert float(jnp.abs(jax.jit(plain.apply)(p, x)[0]
                         - jax.jit(banded.apply)(p, x)[0]).max()) > 1e-3
    assert set(p["params"]["attn"]) == {"query", "key", "value", "out_proj"}


def laguna_like(**changes):
    fields = dict(
        vocab_size=128, num_layers=5, num_heads=6, embed_dim=64,
        layer_kinds=("full", "window", "window", "window", "full"),
        kind_fields={"full": FULL, "window": WINDOW},
        head_dim=16, num_kv_heads=2, output_gate="sigmoid",
        first_k_dense=1, dense_act="swiglu", dense_dim=96,
        num_experts=8, held_experts=(0, 4), held_rows=512, top_k=2,
        expert_dim=32, expert_act="swiglu", shared_experts=1, moe_every=1,
        norm="rmsnorm", scoring="sigmoid", gate_scale=2.5,
        bias_update_speed=0.001, dispatch_impl="sorted",
        aux_loss_weight=0.0, attention_impl="xla")
    fields.update(changes)
    return MoeTransformerLM(**fields)


TOKENS = jnp.asarray(
    np.random.RandomState(0).randint(0, 128, size=(2, 128)), jnp.int32)


def test_layer_kinds_with_a_window_kind():
    model = laguna_like()
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), TOKENS)
    params = variables["params"]
    for block, heads in enumerate((6, 8, 8, 8, 6)):
        attn = params["block_%d" % block]["attn"]
        assert attn["query"]["kernel"].shape == (64, heads, 32)
        assert attn["key"]["kernel"].shape == (64, 2, 16)
        assert attn["out_proj"]["kernel"].shape == (heads, 16, 64)
    assert "mlp_gate" in params["block_0"] and "moe_mlp" in params["block_1"]
    # what a kind decides, stated once
    of_the_model = dict(
        latent=None, linear=None, conv=None, attention_impl="xla",
        qk_norm=False, head_dim=16, num_kv_heads=2, head_norm=None,
        output_gate="sigmoid", indexer=None)
    assert model._mixer("window") == dict(
        num_heads=8, rope_theta=10000.0, rotary_dim=None, rope_scaling=None,
        mask=F.Band(24), kind_scope="attn_window", **of_the_model)
    assert model._mixer("full") == dict(
        num_heads=6, rope_theta=500000.0, rotary_dim=8, rope_scaling=YARN,
        mask=None, kind_scope="attn_full", **of_the_model)
    logits = jax.jit(model.apply)(variables, TOKENS)
    assert logits.shape == (2, 128, 128)
    # the window decides: another window, another function; a window
    # that holds the whole prefix is the causal model
    wide = {"full": FULL,
            "window": MixerKind(8, 10000.0, None, None, 2 ** 20)}
    causal = jax.jit(laguna_like(kind_fields=wide).apply)(variables, TOKENS)
    assert float(jnp.abs(logits - causal).max()) > 1e-3
    # a token inside every window reads the same either way
    np.testing.assert_allclose(logits[:, :24], causal[:, :24], atol=1e-4)


def test_the_scopes_and_the_lines_by_kind(caplog):
    model = laguna_like(remat=True, remat_policy="full")
    tx = moe_transformer.optimizer()
    state = jax.jit(lambda: create_train_state(
        model, tx, jax.random.PRNGKey(0), TOKENS))()
    batch = {"features": TOKENS, "labels": TOKENS,
             MASK_KEY: jnp.ones((2,), jnp.float32)}
    step = jax.jit(make_train_step(
        model, moe_transformer.loss, tx, jnp.bfloat16, health=True))
    moe_transformer._log_kinds_once.cache_clear()
    with caplog.at_level(logging.INFO):
        lowered = step.lower(state, batch)
    text = lowered.as_text(debug_info=True)
    for kind in ("attn_full", "attn_window"):
        for part in ("qkv", "rotary", "flash", "gate", "out_proj"):
            assert "%s/%s" % (kind, part) in text
            assert re.search(r"transpose\(jvp\([^\n]*%s/%s" % (kind, part),
                             text), (kind, part)
    lines = [r.getMessage() for r in caplog.records]
    assert any(
        line == "layer kinds: full x2 (heads=6 theta=500000 rotary=8 "
        "yarn=64), window x3 (heads=8 theta=10000 window=24)"
        for line in lines), lines
    # the program whose text was read, compiled and run
    _, loss, scalars = lowered.compile()(state, batch)
    assert np.isfinite(float(loss))
    assert {"held", "dropped", "rows_run"} <= set(scalars["routing"])


def test_the_attention_line_by_kind(monkeypatch, caplog):
    """One line a distinct mixer, with the kind's heads, rotary lanes,
    YaRN and mask on it (the resolution is ``auto`` on a TPU; here the
    backend is said to be one and the kernels run interpreted)."""
    from elasticdl_tpu.ops import attention as A

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    A._log_auto_once.cache_clear()
    x = jnp.zeros((1, 256, 64), jnp.bfloat16)
    with caplog.at_level(logging.INFO):
        for kind, own in (("full", FULL), ("window", MixerKind(
                8, 10000.0, None, None, 128))):
            mixer = Attention(
                own.num_heads, head_dim=16, num_kv_heads=2,
                output_gate="sigmoid", rope_theta=own.rope_theta,
                rotary_dim=own.rotary_dim, rope_scaling=own.rope_scaling,
                mask=F.Band(own.window) if own.window else None,
                kind_scope="attn_" + kind)
            jax.eval_shape(
                lambda: mixer.init(jax.random.PRNGKey(0), x))
    lines = [r.getMessage() for r in caplog.records
             if "resolved to pallas" in r.getMessage()]
    assert any(
        "q=(1, 6, 256, 16) float32, heads=6 gate=sigmoid rotary=8/16 "
        "yarn=64, kv_heads=2 group=3, flash backward=" in line
        and "mask=" not in line for line in lines), lines
    assert any(
        "q=(1, 8, 256, 16) float32, heads=8 gate=sigmoid rotary=16/16, "
        "kv_heads=2 group=4, flash backward=fused, mask=window(128) pairs "
        "run=" in line and "blocks=256x256" in line
        for line in lines), lines


@pytest.mark.parametrize("changes,match", [
    (dict(layer_kinds=("full", "banded")), "each is 'full', 'window', 'linear'"),
    (dict(kind_fields={"full": FULL}), "needs kind_fields\\['window'\\]"),
    (dict(kind_fields={"full": FULL, "window": MixerKind(8)}),
     "needs kind_fields\\['window'\\]"),
    (dict(kind_fields={"full": WINDOW, "window": WINDOW}),
     "no other kind takes one"),
    (dict(layer_kinds=("full",)), "needs kind_fields\\['window'\\]"),
    (dict(kind_fields={"full": FULL, "window": WINDOW, "linear": FULL}),
     "only the softmax kinds"),
    (dict(objective="block_diffusion", bd_mask_id=3, first_k_dense=0),
     "block_diffusion"),
    (dict(latent=LatentDims(32, 16, 8, 16), head_dim=None,
          num_kv_heads=None, output_gate=None), "latent"),
    (dict(mtp_layers=1), "mtp_layers"),
    (dict(attention_impl="ring"), "'ring' / 'ulysses'"),
], ids=["unknown-kind", "window-without-fields", "window-without-a-window",
        "a-window-in-full", "fields-without-the-kind", "fields-for-linear",
        "block-diffusion", "latent", "prediction-module", "ring"])
def test_the_refusals_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        # raised while the model is traced: no operation need run
        jax.eval_shape(
            laguna_like(**changes).init, jax.random.PRNGKey(0), TOKENS)


def test_a_linear_layer_in_a_dense_block_is_refused():
    model = MoeTransformerLM(
        vocab_size=128, num_layers=2, num_heads=4, embed_dim=64,
        layer_kinds=("linear", "full"),
        linear=GatedDeltaDims(2, 4, 16, 16, 4), first_k_dense=1,
        moe_every=1, attention_impl="xla")
    with pytest.raises(ValueError, match="layer 0 asks for a Gated DeltaNet"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0), TOKENS)


# --- what was there is what it was -----------------------------------


def _sha(text):
    return hashlib.sha256(
        re.sub(r" at 0x[0-9a-f]+", "", text).encode()).hexdigest()[:16]


# (sha256 of the jaxpr of the train step, of the parameter tree's paths
# and shapes) of models built WITHOUT a kind of their own, recorded on
# the parent of PR 42 (4c389d9) with the pinned jax: the legacy zoo
# model; gated grouped-query heads beside linear layers with a held
# share (Qwen3-Next's fields); latent attention behind a leading dense
# layer with sigmoid routing (Moonlight's); OLMoE's. ``kind_fields``,
# ``Block``'s mixer fields, ``Attention.rope_scaling`` / ``kind_scope``
# and the refusals' new homes left their trees and their programs what
# they were.
OLDER_MODELS = {
    "legacy": (dict(), "423f2470f6a6929e", "cf40237cf087e5c0"),
    "gated-gqa-with-linear-kinds": (dict(
        layer_kinds=("linear", "full"),
        linear=GatedDeltaDims(2, 4, 16, 16, 4), head_dim=16,
        num_kv_heads=2, head_norm="zero_centred_rmsnorm", rotary_dim=8,
        output_gate="sigmoid", norm="zero_centred_rmsnorm", moe_every=1,
        dispatch_impl="sorted", expert_act="swiglu", expert_dim=32,
        num_experts=8, held_experts=(0, 4), held_rows=512,
        shared_experts=1, shared_gate=True, remat=True,
        remat_policy="full"), "84f2a5f97e10185f", "b349e6a544673c40"),
    "latent-dense-first": (dict(
        latent=LatentDims(32, 16, 8, 16), first_k_dense=1,
        dense_act="swiglu", dense_dim=96, moe_every=1, norm="rmsnorm",
        scoring="sigmoid", gate_scale=2.0, bias_update_speed=0.001,
        shared_experts=2, dispatch_impl="sorted", expert_act="swiglu",
        expert_dim=32, aux_loss_weight=0.0),
        "8e18d8d843bc2208", "9c70581e46bc28f0"),
    "olmoe-like": (dict(
        norm="rmsnorm", qk_norm=True, expert_act="swiglu", expert_dim=32,
        moe_every=1, dispatch_impl="sorted", normalize_gates=False,
        z_loss_weight=0.001, remat=True, remat_policy="dots"),
        "0a75b149340b3ac8", "63e64abb991129e5"),
}


def _step_and_tree(model, zoo, tokens):
    tx = zoo.optimizer()
    # shapes alone are read: the state is never drawn
    state = jax.eval_shape(lambda: create_train_state(
        model, tx, jax.random.PRNGKey(0), tokens))
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jnp.ones((2,), jnp.float32)}
    step = make_train_step(model, zoo.loss, tx, jnp.bfloat16, health=True)
    tree = sorted(
        jax.tree_util.keystr(k) + str(v.shape) for k, v in
        jax.tree_util.tree_flatten_with_path(state.params)[0])
    return (_sha(str(jax.make_jaxpr(step)(state, batch))),
            _sha("\n".join(tree)))


@pytest.mark.parametrize(
    "case", list(OLDER_MODELS.values()), ids=list(OLDER_MODELS))
def test_a_model_without_a_kind_of_its_own_is_what_it_was(case):
    fields, want_step, want_tree = case
    fields = dict(fields)
    model = MoeTransformerLM(
        vocab_size=128, num_layers=2, num_heads=4, embed_dim=64, top_k=2,
        num_experts=fields.pop("num_experts", 4), attention_impl="xla",
        **fields)
    step, tree = _step_and_tree(
        model, moe_transformer, jnp.zeros((2, 128), jnp.int32))
    assert tree == want_tree
    assert step == want_step


def test_the_dense_lm_is_what_it_was():
    model = TransformerLM(
        vocab_size=128, num_layers=2, num_heads=4, embed_dim=64,
        attention_impl="xla", remat=True, remat_policy="dots")
    step, _ = _step_and_tree(
        model, transformer, jnp.zeros((2, 128), jnp.int32))
    assert step == "4dcd5cedb63465b4"
