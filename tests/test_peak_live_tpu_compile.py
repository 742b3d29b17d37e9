"""``peak_live`` against the TPU compiler's own count (ISSUE 47): a
small rematerialised two-block train step compiled for a v5e that is
described, not attached, as ``tests/test_moe_tpu_compile.py`` compiles
its layers. The walk over the scheduled module has to land on the
compiler's ``peak_memory_in_bytes`` (``walk_over_compiler`` inside
0.85-1.15, the band ``peak_live_named_share`` reports in), name the
instruction at the peak, and find what the policy saved under the
forward's scopes.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.models.transformer import TransformerLM
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import abstract_train_state

BATCH, SEQ, VOCAB, DIM = 4, 1024, 8192, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def compiled(topo):
    """The step of a two-block model under the ``dots`` policy, as
    ``JaxTrainer`` jits it (bfloat16 compute, the state donated)."""
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    model = TransformerLM(
        vocab_size=VOCAB, num_layers=2, num_heads=4, embed_dim=DIM,
        mlp_ratio=4, attention_impl="xla", remat=True,
        remat_policy="dots")
    tx = optax.adamw(3e-4)

    def loss(labels, logits):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(axis=-1)

    tokens = on_chip((BATCH, SEQ), jnp.int32)
    state = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        abstract_train_state(model, tx, jax.random.PRNGKey(0), tokens))
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: on_chip((BATCH,), jnp.float32)}
    step = make_train_step(model, loss, tx, jnp.bfloat16, health=True)
    return jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile()


def test_the_walk_lands_on_the_compiler_s_peak(compiled):
    memory = device_obs.compiled_memory(compiled)
    assert memory["peak_from"] == "compiler"
    # the state is donated: every output but the loss and the health
    # scalars shares an argument's buffer
    assert memory["aliased"] > 0.99 * memory["outputs"]
    assert memory["peak"] > memory["arguments"]
    text = compiled.as_text()
    assert "is_scheduled=true" in text.split("\n", 1)[0]
    live = device_obs.peak_live(text, memory["peak"])
    assert 0.85 <= live["walk_over_compiler"] <= 1.15
    assert 0 < live["position"] < live["instructions"]
    assert live["op_name"].startswith("jit(train_step)/")
    groups = live["groups"]
    assert len(groups) <= device_obs.PEAK_GROUPS_MAX
    assert sum(g["bytes"] for g in groups) == live["walk_peak"]
    by_scope = {(g["scope"], g["direction"]): g for g in groups}
    # AdamW's two moments and the parameters, float32
    params = by_scope["state.params", "argument"]["bytes"]
    assert by_scope["state.opt_state", "argument"]["bytes"] == (
        pytest.approx(2 * params, rel=0.01))
    assert memory["arguments"] == pytest.approx(3 * params, rel=0.01)


def test_what_the_policy_saved_lies_under_the_forward_s_scopes(compiled):
    memory = device_obs.compiled_memory(compiled)
    live = device_obs.peak_live(compiled.as_text(), memory["peak"])
    kept = [
        g for g in live["groups"]
        if g["scope"].startswith("forward/TransformerLM/block_*")
        and g["direction"] in ("forward", "recompute")
    ]
    # the peak lies in the backward, where both blocks' saved products
    # are still held: at least one buffer a block in some group
    assert kept and max(g["buffers"] for g in kept) >= 2
    # an activation of the up projection a block, bfloat16
    up = BATCH * SEQ * 4 * DIM * 2
    assert sum(g["bytes"] for g in kept) >= 2 * up
    # nothing the walk met was left without the program's names
    named = sum(
        g["bytes"] for g in live["groups"]
        if g["scope"] not in ("other", "unnamed"))
    assert named > 0.9 * live["walk_peak"]
