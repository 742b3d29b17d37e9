"""Block diffusion's objective (``ops/block_diffusion.py``) and the model
that trains by it (``MoeTransformerLM(objective="block_diffusion")``),
at small sizes on the CPU: the noise is a pure function of its key, the
one 2 L pass is the objective's definition block by block, the step
hands the model a ``noise`` stream, and a ``next_token`` model is what
it was."""

import dataclasses
import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.models.moe_transformer import MoeTransformerLM
from elasticdl_tpu.models.transformer import Block
from elasticdl_tpu.ops import block_diffusion as bd
from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.train.step_fns import make_train_step, step_rngs
from elasticdl_tpu.train.train_state import create_train_state

LENGTH, BLOCK, VOCAB, MASK_ID = 32, 4, 64, 63


def _tokens(seed=0, batch=2, length=LENGTH):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, VOCAB - 1, size=(batch, length)), jnp.int32)


# --- the noise -------------------------------------------------------


def test_noise_is_a_pure_function_of_its_key():
    tokens = _tokens()
    key = jax.random.PRNGKey(7)
    x_t, w = bd.noise(key, tokens, BLOCK, MASK_ID, 1e-3)
    again = bd.noise(key, tokens, BLOCK, MASK_ID, 1e-3)
    np.testing.assert_array_equal(x_t, again[0])
    np.testing.assert_array_equal(w, again[1])
    jitted = jax.jit(bd.noise, static_argnums=(2, 3, 4))(
        key, tokens, BLOCK, MASK_ID, 1e-3)
    np.testing.assert_array_equal(x_t, jitted[0])
    np.testing.assert_array_equal(w, jitted[1])
    other = bd.noise(jax.random.PRNGKey(8), tokens, BLOCK, MASK_ID, 1e-3)
    assert not np.array_equal(x_t, other[0])
    assert x_t.dtype == tokens.dtype and w.dtype == jnp.float32


def test_noise_masks_where_it_weighs_and_never_touches_the_clean_copy():
    tokens = _tokens(1, batch=4, length=64)
    before = np.asarray(tokens).copy()
    x_t, w = bd.noise(jax.random.PRNGKey(3), tokens, BLOCK, MASK_ID, 1e-3)
    np.testing.assert_array_equal(tokens, before)
    masked = np.asarray(w) > 0
    assert masked.any() and not masked.all()
    np.testing.assert_array_equal(
        np.asarray(x_t), np.where(masked, MASK_ID, before))
    # one noise level a block: the masked tokens of a block weigh alike,
    # 1 / t with t in [t_min, 1]
    blocks = np.asarray(w).reshape(4, -1, BLOCK)
    for row in blocks.reshape(-1, BLOCK):
        assert len(set(row[row > 0])) <= 1
    assert blocks[blocks > 0].min() >= 1.0
    assert blocks.max() <= 1e3 * (1 + 1e-6)
    levels = bd.noise_levels(jax.random.PRNGKey(3), (4,), 16, 1e-3)
    np.testing.assert_allclose(
        blocks.max(-1)[blocks.max(-1) > 0],
        1.0 / np.asarray(levels)[blocks.max(-1) > 0], rtol=1e-6)
    with pytest.raises(ValueError, match="do not divide"):
        bd.noise(jax.random.PRNGKey(0), tokens[:, :63], BLOCK, MASK_ID, 1e-3)


def test_noise_masks_a_share_t_a_block_and_its_weights_average_one():
    """Over many keys: the share of a block's tokens that are masked
    follows its level, and ``sum(w) / L`` averages 1 (the weight of a
    masked token is the inverse of its probability)."""
    tokens = jnp.zeros((64, 256), jnp.int32)

    @jax.jit
    def draw(key):
        _, w = bd.noise(key, tokens, BLOCK, MASK_ID, 1e-3)
        levels = bd.noise_levels(key, (64,), 64, 1e-3)
        share = (w > 0).reshape(64, 64, BLOCK).mean(-1)
        return w.mean(), share, levels, bd.noise_facts(
            key, w, BLOCK, 1e-3)

    means, shares, levels = [], [], []
    for seed in range(40):
        mean, share, level, facts = draw(jax.random.PRNGKey(seed))
        means.append(float(mean))
        shares.append(np.asarray(share).ravel())
        levels.append(np.asarray(level).ravel())
        assert float(facts["weight_mean"]) == pytest.approx(float(mean))
        assert float(facts["masked_share"]) == pytest.approx(
            float(np.mean(np.asarray(share))))
        assert float(facts["mean_t"]) == pytest.approx(
            float(np.mean(np.asarray(level))))
    # 40 x 16,384 tokens: sd of sum(w) / L about sqrt(5.9 / 655,360)
    assert np.mean(means) == pytest.approx(1.0, abs=0.012)
    shares, levels = np.concatenate(shares), np.concatenate(levels)
    assert 1e-3 <= levels.min() and levels.max() <= 1.0
    assert np.mean(levels) == pytest.approx(0.5005, abs=0.005)
    for lo in (0.0, 0.25, 0.5, 0.75):
        inside = (levels >= lo) & (levels < lo + 0.25)
        assert np.mean(shares[inside]) == pytest.approx(
            np.mean(levels[inside]), abs=0.01)


def test_assemble_puts_the_copies_side_by_side():
    clean = _tokens(2)
    noisy = clean.at[:, ::3].set(MASK_ID)
    inputs, positions = bd.assemble(noisy, clean)
    np.testing.assert_array_equal(inputs[:, :LENGTH], noisy)
    np.testing.assert_array_equal(inputs[:, LENGTH:], clean)
    np.testing.assert_array_equal(
        positions, np.arange(2 * LENGTH) % LENGTH)
    x = jnp.arange(2 * 2 * LENGTH * 3.0).reshape(2, 2 * LENGTH, 3)
    np.testing.assert_array_equal(bd.noisy_half(x), x[:, :LENGTH])


def test_the_weighted_loss_is_position_aligned():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 8, VOCAB))
    targets = _tokens(3, length=8)
    weights = jnp.asarray(np.random.RandomState(0).rand(2, 8), jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    want = -(weights * np.take_along_axis(
        np.asarray(logp), np.asarray(targets)[..., None], -1)[..., 0]
    ).mean(-1)
    np.testing.assert_allclose(
        bd.weighted_loss(targets, logits, weights), want, rtol=1e-5)
    outputs = {"logits": logits, "aux_loss": 0.25, "weights": weights}
    np.testing.assert_allclose(
        moe_transformer.loss(targets, outputs), want + 0.25, rtol=1e-5)


# --- the model -------------------------------------------------------

FIELDS = dict(
    vocab_size=VOCAB, num_layers=2, num_heads=4, embed_dim=32, head_dim=8,
    num_kv_heads=2, head_norm="rmsnorm", rope_theta=1e4, num_experts=8,
    held_experts=(2, 4), held_rows=2 * 2 * LENGTH * 2, top_k=2,
    expert_dim=16, expert_act="swiglu", moe_every=1, norm="rmsnorm",
    dispatch_impl="sorted", attention_impl="xla", embed_init_std=1.0,
    aux_loss_weight=0.001)


def _model(**changes):
    return MoeTransformerLM(**{
        **FIELDS, "objective": "block_diffusion", "bd_block": BLOCK,
        "bd_mask_id": MASK_ID, **changes})


def _training(model, params, tokens, noisy, weights):
    """The training call's outputs, one program (a new one a call: the
    model is traced under what the test has patched by then)."""
    return jax.jit(lambda params: model.apply(
        {"params": params}, tokens, training=True, noisy=noisy,
        weights=weights))(params)


@pytest.fixture(scope="module")
def trained():
    tokens = _tokens(5)
    model = _model()
    params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)["params"]
    noisy, weights = bd.noise(
        jax.random.PRNGKey(2), tokens, BLOCK, MASK_ID, 1e-3)
    outputs = _training(model, params, tokens, noisy, weights)
    return model, params, tokens, noisy, weights, outputs


@dataclasses.dataclass(frozen=True)
class BlockCausal:
    """The definition's own mask: a position sees the blocks up to its
    own, whole (its block in both directions). Only the XLA path, which
    reads ``keep`` alone."""

    block: int

    def keep(self, q_pos, k_pos):
        return k_pos // self.block <= q_pos // self.block


class Definition(nn.Module):
    """The model's own blocks, norms and head over ONE sequence under
    the block-causal mask: what the objective says a block's logits
    are, with no second copy and no layout of two halves."""

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(VOCAB, FIELDS["embed_dim"], name="wte")(tokens)
        for i in range(FIELDS["num_layers"]):
            x, _ = Block(
                dict({k: FIELDS[k] for k in (
                    "num_heads", "rope_theta", "head_dim", "num_kv_heads",
                    "head_norm", "attention_impl")},
                    mask=BlockCausal(BLOCK)),
                {k: FIELDS[k] for k in (
                    "num_experts", "top_k", "dispatch_impl", "expert_dim",
                    "expert_act", "held_experts", "held_rows")},
                norm=FIELDS["norm"], name="block_%d" % i)(x)
        x = nn.RMSNorm(epsilon=1e-6, name="ln_f")(x)
        return nn.Dense(VOCAB, use_bias=False, name="lm_head")(x)


def test_the_one_pass_is_the_definition_block_by_block(trained):
    """For each block b, the model run on ``[clean blocks < b ; noisy
    block b]`` gives block b's logits of the one 2 L pass."""
    _, params, tokens, noisy, _, outputs = trained
    assert outputs["logits"].shape == (2, LENGTH, VOCAB)
    # one program for the eight: under the definition's own mask a
    # position sees no block after its own, so the blocks after b are
    # there for the length alone and block b's logits are those of the
    # sequence that ends with it
    definition = jax.jit(
        lambda sequence: Definition().apply({"params": params}, sequence))
    for b in range(LENGTH // BLOCK):
        lo, hi = b * BLOCK, (b + 1) * BLOCK
        sequence = jnp.concatenate(
            [tokens[:, :lo], noisy[:, lo:hi], tokens[:, hi:]], axis=1)
        want = definition(sequence)[:, lo:hi]
        np.testing.assert_allclose(
            outputs["logits"][:, lo:hi], want, atol=2e-5, rtol=2e-5)


def test_the_training_outputs_and_the_eval_surface(trained):
    model, params, tokens, noisy, weights, outputs = trained
    assert set(outputs) == {"logits", "aux_loss", "routing", "weights"}
    np.testing.assert_array_equal(outputs["weights"], weights)
    assert float(outputs["routing"]["dropped"]) == 0
    # 2 L positions reach every expert layer
    assert float(outputs["routing"]["load_mean"]) == (
        2 * 2 * LENGTH * FIELDS["top_k"] / FIELDS["num_experts"])
    # the same noise passed to an eval call: bare logits, the same ones
    logits = jax.jit(lambda params: model.apply(
        {"params": params}, tokens, noisy=noisy, weights=weights))(params)
    np.testing.assert_allclose(logits, outputs["logits"], atol=1e-6)
    # an eval call that brings none is a function of its tokens
    bare = jax.jit(lambda params: model.apply({"params": params}, tokens))
    a = bare(params)
    np.testing.assert_array_equal(a, bare(params))
    assert a.shape == (2, LENGTH, VOCAB)
    # a training call draws from the stream it is handed, and says what
    drawn, sown = jax.jit(lambda params: model.apply(
        {"params": params}, tokens, training=True,
        rngs={"noise": jax.random.PRNGKey(9)},
        mutable=["intermediates"]))(params)
    assert set(drawn["noise"]) == {"masked_share", "mean_t", "weight_mean"}
    key = sown["intermediates"]["noise_key"][0]
    want = bd.noise(key, tokens, BLOCK, MASK_ID, 1e-3)
    np.testing.assert_array_equal(sown["intermediates"]["noisy"][0], want[0])
    np.testing.assert_array_equal(drawn["weights"], want[1])
    assert float(drawn["noise"]["weight_mean"]) == pytest.approx(
        float(want[1].mean()))


def test_the_mask_and_the_positions_matter(trained):
    """A causal mask in the layout's place, or positions that run on
    over the clean copy, is another function."""
    model, params, tokens, noisy, weights, outputs = trained
    real = F.BlockDiffusion

    def run():
        return _training(model, params, tokens, noisy, weights)["logits"]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(F, "BlockDiffusion", lambda half, block: F.CAUSAL)
        assert float(jnp.abs(run() - outputs["logits"]).max()) > 0.05
    assert F.BlockDiffusion is real
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            bd, "assemble", lambda noisy, clean: (
                jnp.concatenate([noisy, clean], -1),
                jnp.arange(2 * clean.shape[-1], dtype=jnp.int32)))
        assert float(jnp.abs(run() - outputs["logits"]).max()) > 0.05


def test_what_the_block_cannot_express_is_refused():
    tokens = _tokens()
    for changes, match in [
        (dict(bd_mask_id=None), "bd_mask_id"),
        (dict(bd_mask_id=VOCAB), "bd_mask_id"),
        (dict(moe_every=2), "expert blocks"),
        (dict(first_k_dense=1), "expert blocks"),
        (dict(objective="denoise"), "objective must be"),
    ]:
        with pytest.raises(ValueError, match=match):
            _model(**changes).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="come together"):
        _model().init(jax.random.PRNGKey(0), tokens, noisy=tokens)
    with pytest.raises(ValueError, match="only block diffusion"):
        MoeTransformerLM(**FIELDS).init(
            jax.random.PRNGKey(0), tokens, noisy=tokens, weights=tokens)


# --- the step --------------------------------------------------------


def test_the_step_hands_the_model_a_noise_stream_folded_from_the_step():
    rngs = step_rngs(jnp.int32(3))
    assert set(rngs) == {"dropout", "noise"}
    assert not np.array_equal(rngs["noise"], rngs["dropout"])
    assert not np.array_equal(
        rngs["noise"], step_rngs(jnp.int32(4))["noise"])
    np.testing.assert_array_equal(
        rngs["dropout"], jax.random.fold_in(jax.random.PRNGKey(0), 3))

    tokens = _tokens(6)
    model = _model()
    tx = moe_transformer.optimizer()
    new_state = jax.jit(lambda: create_train_state(
        model, tx, jax.random.PRNGKey(0), tokens))
    state = new_state()
    step = jax.jit(make_train_step(
        model, moe_transformer.loss, tx, health=True))
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jnp.ones((2,), jnp.float32)}
    seen = []
    for _ in range(3):
        state, loss, scalars = step(state, batch)
        assert np.isfinite(float(loss))
        assert set(scalars["noise"]) == {
            "masked_share", "mean_t", "weight_mean"}
        assert "routing" in scalars
        seen.append(float(scalars["noise"]["mean_t"]))
    # new noise every step, the same noise for the same step
    assert len(set(seen)) == 3
    again = new_state()
    _, _, scalars = step(again, batch)
    assert float(scalars["noise"]["mean_t"]) == seen[0]
    # without the health scalars the step returns what it always did
    bare = make_train_step(model, moe_transformer.loss, tx)
    assert len(jax.eval_shape(bare, again, batch)) == 2


# --- what was there is what it was -----------------------------------


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _next_token_step_jaxpr():
    model = MoeTransformerLM(**dict(FIELDS, remat=True,
                                    remat_policy="dots"))
    tokens = _tokens(7)
    tx = moe_transformer.optimizer()
    state = jax.jit(lambda: create_train_state(
        model, tx, jax.random.PRNGKey(0), tokens))()
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jnp.ones((2,), jnp.float32)}
    step = make_train_step(
        model, moe_transformer.loss, tx, jnp.bfloat16, health=True)
    # the text is read from the trace that runs
    traced = jax.jit(step).trace(state, batch)
    return state, batch, traced, str(traced.jaxpr)


def test_a_next_token_model_traces_no_part_of_the_objective():
    """The default objective's step: no second copy, no noise drawn, no
    ``bd/`` scope, the causal mask; its only new operation is the
    unused ``noise`` key's ``fold_in`` beside ``dropout``'s, which the
    compiler drops."""
    state, batch, traced, text = _next_token_step_jaxpr()
    assert "bd/" not in text and "random_bits" not in text
    assert text.count("random_fold_in") + text.count("threefry2x32") <= 4
    _, _, scalars = traced.lower().compile()(state, batch)
    assert "noise" not in scalars and "routing" in scalars
    # the parameters are the ones a next_token model always had
    assert set(state.params["block_0"]["attn"]) == {
        "query", "key", "value", "q_norm", "k_norm", "out_proj"}


# sha256 of the jaxpr (kernel bodies and index maps included) of the
# causal flash call's gradient at the five cells' shapes, recorded on
# the parent of PR 35 (ff36309) with the pinned jax: the diagonal's
# kernels are what they were. A change to the kernels changes these
# knowingly: since PR 61 32,768 x 256 traces ``flash_bwd`` with dq's
# output block in one buffer where it traced the split pair (bdb00a75...).
CAUSAL_CALLS = {
    "pythia1b-s2k": ((4, 8, 2048, 256), 8, 256, "2bcfa253fd64326f"),
    "pythia1b-s16k": ((1, 8, 16384, 256), 8, 256, "e41c6909a11789f4"),
    "olmoe1b7b-s4k": ((8, 16, 4096, 128), 16, 128, "02c2fb4f97787774"),
    "moonlight16b-s8k": ((2, 16, 8192, 192), 16, 128, "fc9b9be8ca36bd1b"),
    "qwen3next80b-s32k": ((1, 16, 32768, 256), 2, 256, "731ab0f65612c0f5"),
}


@pytest.mark.parametrize(
    "case", list(CAUSAL_CALLS.values()), ids=list(CAUSAL_CALLS))
def test_a_causal_call_s_kernels_are_what_they_were(case):
    q_shape, kv_heads, v_dim, want = case
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(
        (q_shape[0], kv_heads) + q_shape[2:], jnp.bfloat16)
    v = jax.ShapeDtypeStruct(
        (q_shape[0], kv_heads, q_shape[2], v_dim), jnp.bfloat16)

    def loss(q, k, v):
        return F.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert _sha(text) == want
