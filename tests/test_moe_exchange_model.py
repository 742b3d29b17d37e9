"""A tiny Mellum2 with its experts spread over ``ep=4``
(``MoeMlp._sorted_over_ep`` in the whole model), on the CPU's virtual
devices: against its plain reference and against the program on ONE
device, the rows the regrouping ran, and the counters' way to the
journal through ``SpmdTrainer``. The exchange's own arithmetic and the
layer alone are ``test_moe_exchange.py``'s, whose file this was part
of."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer
from elasticdl_tpu.train import step_fns
from elasticdl_tpu.train.optimizers import create_optimizer
from tests.test_moe_exchange import TINY, _assert_trees_close, ep_mesh, load

# --- a tiny Mellum2 ---------------------------------------------------


def tiny_config():
    with open(TINY) as f:
        config = json.load(f)
    # the file's one period (three layers under the window, one
    # without; what is held against what here is the exchange, a layer
    # at a time), a window of 8
    config.update(sliding_window=8)
    assert config["num_hidden_layers"] == 4
    return config


@functools.lru_cache(maxsize=None)
def _tiny_inputs():
    """(tokens, the tiny model's parameters), the parameters initialised
    once, one program and not an operation at a time."""
    config = tiny_config()
    tokens = jnp.asarray(np.random.RandomState(4).randint(
        0, config["vocab_size"], (4, 32)), jnp.int32)
    one = load("zoo").model_from_config(config, attention_impl="xla")
    return tokens, jax.jit(lambda: one.init(
        jax.random.PRNGKey(5), tokens, training=False))()["params"]


@functools.lru_cache(maxsize=None)
def _one_device():
    """Loss, logits and gradients of the program on ONE device with all
    the experts, once: two tests hold the mesh's against them."""
    zoo = load("zoo")
    one = zoo.model_from_config(tiny_config(), attention_impl="xla")
    tokens, params = _tiny_inputs()
    return _system(zoo, one, tokens)(params)


def _tiny_case(mesh=None):
    zoo = load("zoo")
    config = tiny_config()
    model = zoo.model_from_config(config, mesh=mesh, attention_impl="xla")
    return (zoo, config, model) + _tiny_inputs()


def _system(zoo, model, tokens):
    def loss(params):
        outputs, sown = model.apply(
            {"params": params}, tokens, training=True,
            mutable=["intermediates"])
        return zoo.loss(tokens, outputs).mean(), (
            outputs["logits"], outputs["routing"], sown)

    return jax.jit(jax.value_and_grad(loss, has_aux=True))


def test_tiny_mellum2_over_ep_is_its_reference_and_the_one_device_program():
    zoo, config, model, tokens, params = _tiny_case(ep_mesh())
    (loss, (logits, routing, sown)), grads = _system(zoo, model, tokens)(
        params)
    assert float(routing["dropped"]) == 0
    # the program on ONE device with all the experts
    (loss_one, (logits_one, _, _)), grads_one = _one_device()
    np.testing.assert_allclose(loss, loss_one, rtol=1e-5)
    np.testing.assert_allclose(logits, logits_one, rtol=1e-3, atol=1e-4)
    _assert_trees_close(grads, grads_one, rtol=1e-4, atol=1e-5)
    # the plain reference: all experts in one place, no mesh
    ref = load("reference")

    def reference_loss(params):
        logits, loss, chosen = ref.logits_loss_and_choices(
            params, tokens, config)
        return loss, (logits, chosen)

    (loss_ref, (logits_ref, chosen)), grads_ref = jax.jit(
        jax.value_and_grad(reference_loss, has_aux=True))(params)
    experts = jnp.stack([
        sown["intermediates"]["block_%d" % i]["moe_mlp"]["experts"][0]
        for i in range(config["num_hidden_layers"])])
    np.testing.assert_array_equal(
        np.sort(experts, axis=-1), np.sort(chosen, axis=-1))
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-5)
    np.testing.assert_allclose(logits, logits_ref, rtol=1e-3, atol=1e-4)
    _assert_trees_close(grads, grads_ref, rtol=1e-4, atol=1e-5)


def test_the_counters_reach_the_journal_s_fields_from_the_spmd_trainer():
    """``SpmdTrainer`` keeps the step's facts (``FACTS``): the
    ``moe_routing`` event's fields exist on a mesh, the exchange's
    among them, and a model without facts adds nothing."""
    zoo, config, model, tokens, _ = _tiny_case(ep_mesh())
    trainer = SpmdTrainer(
        model=model, loss_fn=zoo.loss,
        optimizer=create_optimizer("AdamW", learning_rate=1e-3),
        mesh=ep_mesh(), sharding_rules=zoo.sharding_rules(),
        batch_spec=zoo.batch_spec())
    batch = {"features": np.asarray(tokens), "labels": np.asarray(tokens),
             "_mask": np.ones((4,), np.float32)}
    state, loss = trainer.train_step(None, batch)
    assert np.isfinite(float(loss))
    (fact,) = [f for f in step_fns.FACTS if f.key == "routing"]
    fields = fact.journal(trainer.facts["routing"])
    for name in ("tokens_per_expert_max", "dropped_pairs", "sent_pairs",
                 "received_pairs_max", "received_pairs_mean",
                 "exchange_bytes"):
        assert name in fields, name
    assert fields["dropped_pairs"] == 0
    assert fields["received_pairs_mean"] == 32 * 2
    # no stated buffer: all the ranks' pairs, which 4,096 does not
    # divide, so the regrouping runs it whole
    assert (fields["received_rows_run"] == fields["received_rows_buffer"]
            == 4 * 32 * 2)
    # the experts' state is divided over ep and nothing else's is
    specs = {
        "/".join(str(k.key) for k in path): leaf.sharding.spec
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.params)}
    assert specs["block_0/moe_mlp/w_gate"][0] == "ep"
    assert specs["lm_head/kernel"][0] == ("fsdp", "ep")
    assert "ep" not in str(specs["block_0/attn/query/kernel"])


def test_the_rows_the_regrouping_ran_reach_the_moe_routing_event(
        monkeypatch):
    """``received_rows_run`` of ``received_rows_buffer``: the busiest
    rank's received rows, in the layer where it received the most,
    rounded up to a chunk (16 rows here, so that the tiny buffer has
    chunks to stop at), from a training step over ``ep`` whose loss and
    gradients are the one-device program's."""
    monkeypatch.setattr(moe_ops, "HELD_CHUNK_ROWS", 16)
    zoo, config, model, tokens, params = _tiny_case(ep_mesh())
    (loss, (_, routing, _)), grads = _system(zoo, model, tokens)(params)
    # stopping short of the buffer changes nothing that is read
    (loss_one, _), grads_one = _one_device()
    np.testing.assert_allclose(loss, loss_one, rtol=1e-5)
    _assert_trees_close(grads, grads_one, rtol=1e-4, atol=1e-5)
    (fact,) = [f for f in step_fns.FACTS if f.key == "routing"]
    fields = fact.journal(routing)
    buffer_rows = 4 * 32 * 2
    assert fields["received_rows_buffer"] == buffer_rows
    busiest = fields["received_pairs_max"]
    assert 32 * 2 <= busiest <= buffer_rows
    assert fields["received_rows_run"] == -(-busiest // 16) * 16
    assert fields["received_rows_run"] <= fields["received_rows_buffer"]
