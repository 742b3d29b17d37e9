"""A looped stack (``MoeTransformerLM.looped``, ``Block.sandwich``,
``ops/looped_exit.py``; Ouro-2.6B's, PR 55) at a small size on the CPU,
seeded weights: the system against the configuration's plain reference
in float32 and in bfloat16; the loop tied to the stack of copies it
stands for; what one pass, a shut gate and an eval call reduce to; the
chunked head against the whole one; every refusal by its name; and an
older model's tree and program free of the new names."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.models import moe_transformer as M
from elasticdl_tpu.models.transformer import (
    Block,
    GatedDeltaDims,
    HyperDims,
    IndexerDims,
    LoopedDims,
    ShortConvDims,
    make_norm,
)
from elasticdl_tpu.ops import looped_exit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OURO = os.path.join(REPO, "benchmark", "configs", "ouro-2.6b-1chip")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-ouro",
    "config.json")
SEQ, VOCAB, LAYERS, PASSES = 96, 256, 2, 3
FIELDS = dict(
    vocab_size=VOCAB, num_layers=LAYERS, num_heads=4, embed_dim=64,
    head_dim=16, first_k_dense=LAYERS, dense_act="swiglu", dense_dim=96,
    norm="rmsnorm", rope_theta=1e6, sandwich=True, embed_init_std=1.0)


def looped(passes=PASSES, beta=0.05, **changes):
    return M.MoeTransformerLM(
        **dict(FIELDS, looped=LoopedDims(passes, beta), **changes))


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(1)
    return jnp.asarray((rng.zipf(1.2, size=(2, SEQ)) % VOCAB).astype(
        np.int32))


@pytest.fixture(scope="module")
def params(tokens):
    tree = jax.jit(looped().init)(jax.random.PRNGKey(0), tokens)["params"]
    # a gate that tells positions apart, and norms that are not 1
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    return jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, tree)


def training(model, params, tokens):
    """The training call, one program (an operation at a time the
    model is a thousand dispatches)."""
    return jax.jit(lambda params, tokens: model.apply(
        {"params": params}, tokens, training=True))(params, tokens)


@pytest.fixture(scope="module")
def trained(tokens, params):
    """The looped model's training outputs, made once for the tests
    that read them."""
    return training(looped(), params, tokens)


# -- the system against the plain reference ---------------------------------


def check_parts(compute_dtype):
    with open(TINY) as f:
        config = json.load(f)
    config["compute_dtype"] = compute_dtype
    spec = {"config": config, "seed": 5,
            "zoo": os.path.join(OURO, "zoo.py"),
            "reference": os.path.join(OURO, "reference.py"),
            "cell": {"model_params": {"remat_policy": "flash"},
                     "last_positions": None}}
    sample = (np.random.RandomState(2).zipf(1.2, size=128) % 512).astype(
        np.int32)
    check = refcheck.load_by_path(
        "edlbench_check", os.path.join(OURO, "check.py"))
    parts = check.build(spec, sample)
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(5), sample)
    got = jax.jit(parts["system"])(variables, sample)
    want = jax.jit(parts["reference"])(variables, sample)
    return refcheck.compare(got, want, parts["tolerance"])


@pytest.mark.parametrize("compute_dtype", ["", "bfloat16"])
def test_the_system_against_the_reference(compute_dtype):
    """Every exit's logits, the exit distribution, the loss, its named
    parts and the gradients (each block kernel's a sum over the
    passes), whole sequence."""
    errors, ok = check_parts(compute_dtype)
    assert {"logits", "logits:exit_0", "logits:exit_2", "exit_probs",
            "loss", "term:expected_ce", "term:exit_entropy",
            "term:ce_exit_3", "grad:early_exit_gate/kernel",
            "grad:early_exit_gate/bias", "span_ce",
            "grad:block_1/ln_attn_out/scale"} <= set(errors)
    if compute_dtype:
        # widths of 16 to 96 average less than the cell's
        assert max(errors.values()) < 0.2, errors
        assert errors["loss"] < 0.01 and errors["exit_probs"] < 0.02
    else:
        assert ok and max(errors.values()) < 2e-3, errors
        assert errors["logits"] < 1e-4 and errors["loss"] < 1e-5


# -- the loop tied to the stack ---------------------------------------------


def test_the_loop_is_the_stack_of_copies(tokens, params):
    """A looped model of d layers and T passes gives the logits of T x d
    layers whose layer t x d + i is a copy of layer i, with ``ln_f``
    between the copies; a shared kernel's gradient is the sum of its
    copies' gradients."""
    model = looped()
    block = Block(
        model._mixer("full"), norm="rmsnorm", mlp_act="swiglu", mlp_dim=96,
        sandwich=True)
    ln_f = make_norm("rmsnorm", model.norm_eps, None)

    def stack_loss(copies, rest):
        x = rest["wte"]["embedding"][tokens]
        for j, copy in enumerate(copies):
            x, _ = block.apply({"params": copy}, x, False)
            if (j + 1) % LAYERS == 0:
                x = ln_f.apply({"params": rest["ln_f"]}, x)
        logits = x @ rest["lm_head"]["kernel"]
        return M.loss(tokens, logits).mean(), logits

    def loop_loss(params):
        logits = model.apply({"params": params}, tokens)
        return M.loss(tokens, logits).mean(), logits

    copies = [params["block_%d" % (j % LAYERS)]
              for j in range(PASSES * LAYERS)]
    (_, want), of_copies = jax.jit(jax.value_and_grad(
        stack_loss, has_aux=True))(copies, params)
    (_, got), of_loop = jax.jit(jax.value_and_grad(
        loop_loss, has_aux=True))(params)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for i in range(LAYERS):
        summed = jax.tree_util.tree_map(
            lambda *g: sum(g), *of_copies[i::LAYERS])
        for got_leaf, want_leaf in zip(
                jax.tree_util.tree_leaves(of_loop["block_%d" % i]),
                jax.tree_util.tree_leaves(summed)):
            np.testing.assert_allclose(
                got_leaf, want_leaf, rtol=1e-4, atol=1e-7)
        # no copy's gradient is the sum
        one = of_copies[i]["attn"]["query"]["kernel"]
        assert float(jnp.abs(
            one - of_loop["block_%d" % i]["attn"]["query"]["kernel"]
        ).max()) > 1e-6


def test_one_pass_is_the_plain_model(tokens):
    """``passes=1`` with the entropy's weight at 0: the tree and the
    loss of the model whose stack is walked once."""
    plain = M.MoeTransformerLM(**FIELDS)
    once = looped(passes=1, beta=0.0)
    tree = jax.jit(plain.init)(jax.random.PRNGKey(0), tokens)["params"]
    own = jax.jit(once.init)(jax.random.PRNGKey(0), tokens)["params"]
    assert jax.tree_util.tree_structure(own) == (
        jax.tree_util.tree_structure(tree))
    for a, b in zip(jax.tree_util.tree_leaves(own),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(a, b)
    want = M.loss(tokens, training(plain, tree, tokens))
    got, terms = M.loss(tokens, training(once, tree, tokens))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(terms["ce_exit_0"], want, rtol=1e-5)
    assert not np.asarray(terms["exit_entropy"]).any()
    np.testing.assert_allclose(
        jax.jit(once.apply)({"params": tree}, tokens),
        jax.jit(plain.apply)({"params": tree}, tokens), atol=1e-5)


# -- the exits --------------------------------------------------------------


def test_the_exit_distribution_sums_to_one(tokens, trained):
    out = trained
    log_p = out["exit_log_probs"]
    assert log_p.shape == (PASSES,) + tokens.shape
    assert log_p.dtype == jnp.float32
    p = np.exp(np.asarray(log_p, np.float64))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    # the last entry is the remainder
    np.testing.assert_allclose(p[-1], 1.0 - p[:-1].sum(axis=0), atol=1e-6)
    assert 0.01 < p.min() and p.std(axis=(1, 2)).min() > 0.01
    facts = out["looped"]
    np.testing.assert_allclose(facts["p_mean"], p.mean(axis=(1, 2)),
                               rtol=1e-5)
    assert float(facts["p_mean"].sum()) == pytest.approx(1.0, abs=1e-5)
    assert facts["lambda_mean"].shape == (PASSES - 1,)
    assert float(facts["passes"]) == PASSES
    assert float(facts["entropy"]) == pytest.approx(
        float(-(p * np.log(p)).sum(axis=0).mean()), rel=1e-5)
    assert len(out["exits"]) == PASSES
    assert out["exits"][0].shape == tokens.shape + (64,)


def test_a_shut_gate_leaves_the_last_exit_s_cross_entropy(tokens, params):
    """``beta = 0`` and the gate's bias far below 0: all mass on the
    last exit, and the loss is its cross-entropy."""
    shut = dict(params, early_exit_gate=dict(
        params["early_exit_gate"], bias=jnp.full((1,), -60.0)))
    model = looped(beta=0.0)
    got, terms = M.loss(tokens, training(model, shut, tokens))
    want = M.loss(tokens, jax.jit(model.apply)({"params": shut}, tokens))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(
        terms["ce_exit_%d" % (PASSES - 1)], want, rtol=1e-5)
    assert np.isfinite(np.asarray(terms["exit_entropy"])).all()
    assert float(jnp.abs(terms["exit_entropy"]).max()) < 1e-6


def test_an_eval_call_returns_the_last_pass_s_bare_logits(
        tokens, params, trained):
    logits = jax.jit(looped().apply)({"params": params}, tokens)
    assert logits.shape == tokens.shape + (VOCAB,)
    out = trained
    np.testing.assert_allclose(
        logits, out["exits"][-1] @ out["head_kernel"], atol=1e-5)
    assert float(jnp.abs(
        logits - out["exits"][0] @ out["head_kernel"]).max()) > 1e-2


def test_the_chunked_head_is_the_whole_head(monkeypatch):
    """Values and gradients at a length that is no multiple of the
    chunk: 2 exits of 150 positions in chunks of 64."""
    monkeypatch.setattr(looped_exit, "EXIT_CHUNK", 64)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    exits = tuple(jax.random.normal(k, (2, 150, 32)) for k in keys[:2])
    kernel = jax.random.normal(keys[2], (32, 200)) * 0.3
    labels = jax.random.randint(keys[3], (2, 150), 0, 200)
    log_p = jax.nn.log_softmax(
        jax.random.normal(keys[4], (2, 2, 150)), axis=0)

    def whole(exits, kernel, log_p):
        logp = jax.nn.log_softmax(jnp.stack(exits) @ kernel, axis=-1)
        ce = -jnp.take_along_axis(
            logp[:, :, :-1], labels[None, :, 1:, None], axis=-1)[..., 0]
        p = jnp.exp(log_p[:, :, :-1])
        entropy = -(p * log_p[:, :, :-1]).sum(axis=0)
        return ((p * ce).sum(axis=0) - 0.1 * entropy).mean(axis=-1), ce

    def chunked(exits, kernel, log_p):
        loss, terms = looped_exit.expected_loss(
            labels, exits, kernel, log_p, 0.1)
        return loss, terms

    want, ce = jax.jit(whole)(exits, kernel, log_p)
    # the values are the trace's that is read below
    traced = jax.jit(chunked).trace(exits, kernel, log_p)
    got, terms = traced.lower().compile()(exits, kernel, log_p)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(
        terms["ce_exit_1"], ce[1].mean(axis=-1), rtol=1e-5)
    grads = lambda f: jax.jit(jax.grad(
        lambda *args: f(*args)[0].sum(), argnums=(0, 1, 2)))(
            exits, kernel, log_p)
    for a, b in zip(jax.tree_util.tree_leaves(grads(chunked)),
                    jax.tree_util.tree_leaves(grads(whole))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # nothing as wide as the vocabulary leaves a chunk
    jaxpr = str(traced.jaxpr)
    assert "150,200]" not in jaxpr and "192,200]" not in jaxpr


def test_the_step_hands_out_the_loop_s_facts(tokens, params):
    """``train/step_fns.py``: the ``looped`` fact with the loss's
    ``ce_exit_<t>`` as ``ce``, and the named terms."""
    import optax

    from elasticdl_tpu.data.pipeline import MASK_KEY
    from elasticdl_tpu.train import step_fns
    from elasticdl_tpu.train.train_state import TrainState

    model, tx = looped(), optax.sgd(0.0)
    state = TrainState(step=jnp.int32(0), params=params, model_state={},
                       opt_state=tx.init(params))
    step = jax.jit(step_fns.make_train_step(
        model, M.loss, tx, health=True))
    _, loss, scalars = step(state, {
        "features": tokens, "labels": tokens,
        MASK_KEY: jnp.ones((2,), jnp.float32)})
    facts = step_fns.facts_of(scalars)
    assert set(facts) == {"looped", "loss_terms"}
    (row,) = [f for f in step_fns.FACTS if f.key == "looped"]
    event = row.journal(facts["looped"])
    assert set(event) == {"passes", "p_mean", "entropy", "lambda_mean"}
    assert len(event["p_mean"]) == PASSES
    assert len(event["lambda_mean"]) == PASSES - 1
    assert sum(event["p_mean"]) == pytest.approx(1.0, abs=1e-5)
    # the cross-entropy an exit is the loss's own terms', same step
    terms = facts["loss_terms"]
    assert {"ce_exit_%d" % t for t in range(PASSES)} <= set(terms)
    assert float(loss) == pytest.approx(float(
        terms["expected_ce"] - 0.05 * terms["exit_entropy"]), rel=1e-5)


# -- what it was not built beside -------------------------------------------


@pytest.mark.parametrize("what,fields", [
    ("an expert block", dict(first_k_dense=1, moe_every=1)),
    ("hyper-connections", dict(hc=HyperDims(streams=2))),
    ("the prediction module", dict(mtp_layers=1)),
    ("block_diffusion", dict(objective="block_diffusion", bd_mask_id=0)),
    ("a learned indexer", dict(indexer=IndexerDims(2, 8, 16))),
    ("a 'linear', 'conv', 'mamba' or 'kda' mixer", dict(
        layer_kinds=("conv", "full"), conv=ShortConvDims(taps=3))),
    ("a 'linear', 'conv', 'mamba' or 'kda' mixer", dict(
        layer_kinds=("linear", "full"), linear=GatedDeltaDims(2, 2, 16, 16, 4))),
    ("tied to the embedding", dict(tie_embeddings=True)),
    ("at least one pass", dict(passes=0)),
])
def test_what_a_loop_was_not_built_beside_is_refused(tokens, what, fields):
    model = looped(**fields)
    with pytest.raises(ValueError, match="looped passes.*" + what):
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens))


def test_a_sandwich_under_hyper_connections_is_refused(tokens):
    model = M.MoeTransformerLM(**dict(FIELDS, hc=HyperDims(streams=2)))
    with pytest.raises(ValueError, match="sandwich"):
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens))


# -- an older model ---------------------------------------------------------


@pytest.mark.parametrize("preset", ["tiny-moe"])
def test_an_older_model_has_none_of_it(preset):
    """A zoo model the benchmark has, as its zoo builds it: no leaf and
    no operation of the new names, and the loss it always had."""
    config_path = os.path.join(
        REPO, "tests", "benchmark_harness", "preset", "configs", preset,
        "config.json")
    with open(config_path) as f:
        config = json.load(f)
    zoo = refcheck.load_by_path(
        "edlbench_zoo_" + preset.replace("-", "_"),
        os.path.join(REPO, config["zoo"]))
    model = zoo.model_from_config(config)
    assert model.looped is None and model.sandwich is False
    sample = jnp.zeros((2, 64), jnp.int32)
    tree = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample))["params"]
    names = {"/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert not [n for n in names if "_out/" in n or "early_exit" in n]

    def step(params):
        out = model.apply({"params": params}, sample, training=True)
        return zoo.loss(sample, out).mean()

    program = str(jax.make_jaxpr(jax.grad(step))(tree))
    for word in ("looped", "exit/", "exit_norm", "ln_attn_out",
                 "optimization_barrier"):
        assert word not in program, word
    # the same fields spelled out change nothing
    spelled = model.clone(looped=None, sandwich=False)
    assert str(jax.make_jaxpr(jax.grad(
        lambda p: zoo.loss(sample, spelled.apply(
            {"params": p}, sample, training=True)).mean()))(tree)) == program
