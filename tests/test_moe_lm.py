"""The sorted dropless dispatch in the whole ``MoeTransformerLM``: its
loss curve against the one-hot dispatch's, under a dp mesh against one
device, what it refuses, what its train step must never hold (a
capacity, a one-hot), and its routing counters' way out of the step.
The routing and the dispatch themselves are ``test_moe.py``'s, whose
file this was part of."""

import jax
import numpy as np
import pytest

from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh
from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer
from elasticdl_tpu.train.optimizers import create_optimizer
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import create_train_state
from tests.test_moe import _batch, _small_moe


def _lm_losses(model, batch, steps=3):
    tx = create_optimizer("Adam", learning_rate=0.01)
    init_rng, _ = jax.random.split(jax.random.PRNGKey(0))
    state = jax.jit(lambda: create_train_state(
        model, tx, init_rng, batch["features"]))()
    step = jax.jit(make_train_step(model, moe_transformer.loss, tx))
    losses = []
    for _ in range(steps):
        state, loss = step(state, batch)
        losses.append(float(loss))
    return losses


def test_moe_lm_sorted_matches_onehot_losses():
    """Full MoeTransformerLM trained with dispatch_impl="sorted" vs
    "onehot" (ample capacity: nothing dropped) gives the same loss
    curve on one device. The auxiliary loss is left out: one-hot
    balances first choices (Switch), sorted all k (OLMoE)."""
    batch = _batch()
    losses = {
        impl: _lm_losses(
            _small_moe(attention_impl="xla", dispatch_impl=impl,
                       aux_loss_weight=0.0),
            batch)
        for impl in ("onehot", "sorted")
    }
    np.testing.assert_allclose(
        losses["sorted"], losses["onehot"], rtol=1e-4)
    assert losses["sorted"][-1] < losses["sorted"][0]


def test_sorted_dispatch_under_dp_mesh_matches_single_device():
    """The sorted path must also compile and stay correct when tokens
    are dp-sharded over a mesh with ep=1 (one global sort: the
    partitioner gathers what it needs)."""
    batch = _batch(batch=8)
    kwargs = dict(attention_impl="xla", dispatch_impl="sorted")
    expected = _lm_losses(_small_moe(**kwargs), batch)
    mesh = build_mesh(MeshConfig(dp=8))
    trainer = SpmdTrainer(
        model=_small_moe(mesh=mesh, **kwargs),
        loss_fn=moe_transformer.loss,
        optimizer=create_optimizer("Adam", learning_rate=0.01),
        mesh=mesh,
        seed=0,
        sharding_rules=moe_transformer.sharding_rules(),
        batch_spec=moe_transformer.batch_spec(),
    )
    state = trainer.create_state(batch["features"])
    got = []
    for _ in range(3):
        state, loss = trainer.train_step(state, batch)
        got.append(float(loss))
    np.testing.assert_allclose(got, expected, atol=1e-4, rtol=1e-4)


def _trace_init(model):
    """A refusal is raised while the model is traced: ``eval_shape``
    traces the init and runs no operation."""
    return jax.eval_shape(
        model.init, jax.random.PRNGKey(0), _batch()["features"])


def test_sorted_dispatch_refuses_what_its_exchange_does_not_divide():
    """Over ``ep`` the sorted path exchanges rows in a region manual
    over the whole mesh (tests/test_moe_exchange.py); an axis it
    divides nothing over is refused by name, never served by a silent
    fallback."""
    mesh = build_mesh(MeshConfig(dp=2, tp=2, ep=2))
    model = _small_moe(
        attention_impl="xla", mesh=mesh, dispatch_impl="sorted")
    with pytest.raises(ValueError, match=r"nothing divides over \['tp'\]"):
        _trace_init(model)
    with pytest.raises(ValueError, match="dispatch_impl"):
        _trace_init(_small_moe(dispatch_impl="compact"))


def test_onehot_dispatch_refuses_unnormalised_gates():
    """``top_k_routing`` always renormalises: the field must not be
    taken and ignored."""
    with pytest.raises(ValueError, match="normalize_gates=False needs"):
        _trace_init(_small_moe(normalize_gates=False))
    _trace_init(_small_moe(normalize_gates=False, dispatch_impl="sorted"))


def _avals(jaxpr):
    """Every array shape a jaxpr computes, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if hasattr(var.aval, "shape"):
                yield tuple(var.aval.shape)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _avals(inner)


def test_no_capacity_and_no_onehot_in_the_sorted_step():
    """The sorted path's train step holds no array over (tokens or
    sequence, experts, anything more): no (G, S, E, C) dispatch tensor
    and no capacity. The one-hot path's does, which is what the test can see."""
    # sizes that occur nowhere else: 5 experts, sequence 24, batch 3
    batch = _batch(batch=3, seq=24)
    experts, tokens = 5, 3 * 24

    def step_shapes(impl):
        model = moe_transformer.MoeTransformerLM(
            vocab_size=128, num_layers=2, num_heads=4, embed_dim=32,
            num_experts=experts, top_k=2, attention_impl="xla",
            dispatch_impl=impl)
        tx = create_optimizer("Adam", learning_rate=0.01)
        # the trace reads shapes: no parameter is initialised
        state = jax.eval_shape(lambda: create_train_state(
            model, tx, jax.random.PRNGKey(0), batch["features"]))
        step = make_train_step(model, moe_transformer.loss, tx)
        return set(_avals(jax.make_jaxpr(step)(state, batch).jaxpr))

    def over_tokens_and_experts(shape):
        # larger than the router's own (tokens, experts) probabilities
        return experts in shape and (24 in shape or tokens in shape) and (
            np.prod(shape) > tokens * experts)

    assert any(map(over_tokens_and_experts, step_shapes("onehot")))
    assert not any(map(over_tokens_and_experts, step_shapes("sorted")))


def test_routing_counters_leave_the_step_only_for_a_model_that_has_them():
    """``make_train_step(health=True)`` hands the sorted MoE LM's
    routing counters out beside the health scalars (``JaxTrainer``
    keeps them on the device for the worker's logged steps); a model
    without them gets the scalars it always got."""
    from elasticdl_tpu.models import transformer
    from elasticdl_tpu.worker.trainer import JaxTrainer

    batch = _batch()
    tx = create_optimizer("Adam", learning_rate=0.01)
    for accum in (1, 2):
        trainer = JaxTrainer(
            _small_moe(attention_impl="xla", dispatch_impl="sorted"),
            moe_transformer.loss, tx, grad_accum_steps=accum)
        trainer.train_step(None, batch)
        routing = {
            k: float(v) for k, v in trainer.facts["routing"].items()}
        assert set(routing) == {
            "load_max", "load_mean", "entropy", "dropped"}
        assert routing["dropped"] == 0.0
        # 2 expert layers... each sees every token twice over 4 experts
        tokens = batch["features"].size // accum
        assert routing["load_mean"] == tokens * 2 / 4
        assert routing["load_max"] >= routing["load_mean"]
        assert 0.0 < routing["entropy"] <= np.log(4) + 1e-6
    for model, loss in (
        (_small_moe(attention_impl="xla"), moe_transformer.loss),
        (transformer.TransformerLM(
            vocab_size=128, num_layers=1, num_heads=4, embed_dim=32,
            attention_impl="xla"), transformer.loss),
    ):
        trainer = JaxTrainer(model, loss, tx)
        trainer.train_step(None, batch)
        assert trainer.facts == {}
