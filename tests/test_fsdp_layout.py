"""fsdp means ZeRO-3 (ISSUE 24): the transformer pins its activations
to the data axes, so under an ``fsdp`` extent the compiled step moves
weights and never a full-batch activation; and the compile ledger
counts the collectives that say so.

Counts from the CPU backend's HLO, never a speed. That backend carries
bfloat16 collectives as float32 and reduces gradients with all-reduce
where the TPU's reduce-scatters, so the byte bound is stated in
float32."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.models import transformer
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import events
from elasticdl_tpu.parallel.mesh import DATA_AXES, MeshConfig, build_mesh
from elasticdl_tpu.parallel.sharding import constrain
from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer
from elasticdl_tpu.train.optimizers import create_optimizer

BATCH, SEQ, VOCAB = 12, 64, 256


def _mesh(**axes):
    sizes = dict(dp=1, **axes)
    return build_mesh(MeshConfig(**sizes), num_devices=int(np.prod(
        list(sizes.values()))))


def _trainer(mesh, remat_policy="none"):
    model = transformer.TransformerLM(
        vocab_size=VOCAB, num_layers=2, num_heads=4, embed_dim=64,
        attention_impl="xla", mesh=mesh,
        remat=remat_policy != "none",
        remat_policy="full" if remat_policy == "none" else remat_policy,
    )
    return SpmdTrainer(
        model=model,
        loss_fn=transformer.loss,
        optimizer=create_optimizer("AdamW", learning_rate=0.01),
        compute_dtype="bfloat16",
        mesh=mesh,
        seed=0,
        sharding_rules=transformer.sharding_rules(),
        batch_spec=transformer.batch_spec(),
    )


def _batch():
    tokens = np.random.RandomState(0).randint(
        0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
    return {
        "features": tokens,
        "labels": tokens,
        "_mask": np.ones((BATCH,), np.float32),
    }


# ---------------------------------------------------------------------
# the helper


@pytest.mark.parametrize(
    "make_mesh", [lambda: None, _mesh], ids=["no-mesh", "one-device"])
def test_constrain_is_identity_without_a_mesh_to_shard_over(make_mesh):
    x = jnp.ones((4, 8))
    spec = P(DATA_AXES, None)
    assert constrain(x, make_mesh(), spec) is x
    jaxpr = jax.make_jaxpr(lambda a: constrain(a, make_mesh(), spec))(x)
    assert "sharding_constraint" not in str(jaxpr)


def test_constrain_is_identity_inside_a_manual_region():
    mesh = _mesh(fsdp=4)
    seen = []

    def body(a):
        out = constrain(a, mesh, P(DATA_AXES, None))
        seen.append(out is a)
        return out * 2

    fn = jax_compat.shard_map(
        body, mesh=mesh, in_specs=P(DATA_AXES, None),
        out_specs=P(DATA_AXES, None),
    )
    out = jax.jit(fn)(jnp.ones((8, 4)))
    assert seen == [True]
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((8, 4)))


def test_constrain_pins_the_layout_on_a_mesh():
    mesh = _mesh(fsdp=4)
    spec = P(DATA_AXES, None)
    fn = jax.jit(lambda a: constrain(a * 2, mesh, spec))
    assert "sharding_constraint" in str(jax.make_jaxpr(fn)(jnp.ones((8, 4))))
    out = fn(jnp.ones((8, 4)))
    assert out.sharding.shard_shape(out.shape) == (2, 4)


# ---------------------------------------------------------------------
# the compiled step under fsdp=4


def _full_batch_activations(arrays):
    """Of a collective's result, the arrays that carry the global
    batch as their leading dimension and are an activation: anything
    but a rank-1 / rank-2 array or an integer or predicate one
    (labels, masks, a scalar per token)."""
    return [
        (dtype, dims) for dtype, dims in arrays
        if dims[:1] == (BATCH,) and len(dims) > 2 and dtype[0] not in "sup"
    ]


@pytest.mark.parametrize("remat_policy", ["none", "dots"])
def test_fsdp_step_moves_weights_not_the_batch(remat_policy):
    trainer = _trainer(_mesh(fsdp=4), remat_policy)
    batch = _batch()
    state = trainer.create_state(batch["features"])
    trainer._build_steps(batch)
    hlo = trainer._train_step.lower(
        state, trainer.shard_batch(batch)).compile().as_text()
    collectives = device_obs.hlo_collectives(hlo)
    assert any(kind == "all-gather" for kind, _, _ in collectives)
    full_batch = [
        (kind, arrays) for kind, arrays, _ in collectives
        if _full_batch_activations(arrays)
    ]
    assert not full_batch, (
        "collectives over a full-batch activation: %s" % full_batch)
    params = sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(state.params))
    moved = sum(nbytes for _, _, nbytes in collectives)
    # ZeRO-3: the weights gathered for the forward, once more where the
    # backward recomputes, and their gradients reduced: three passes
    # over the parameters at most (float32 here, see the docstring)
    assert moved <= 3 * 4 * params, (moved, params)


# ---------------------------------------------------------------------
# the counter


def _compile_events(tmp_path, monkeypatch, mesh):
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(tmp_path))
    journal = events.configure("worker-0")
    try:
        trainer = _trainer(mesh)
        trainer.train_step(None, _batch())
        with open(journal.path, encoding="utf-8") as f:
            records = [json.loads(line) for line in f if line.strip()]
    finally:
        events._reset_for_tests()
    found = [
        r for r in records
        if r["event"] == "xla_compile" and r["fn"] == "spmd_train_step"
    ]
    assert len(found) == 1 and found[0]["compiles"] == 1
    assert found[0]["collectives"] == trainer.program_collectives
    return found[0]["collectives"]


def test_compile_event_carries_the_collectives_of_an_fsdp_step(
        tmp_path, monkeypatch):
    stats = _compile_events(tmp_path, monkeypatch, _mesh(fsdp=4))
    assert set(stats["by_kind"]) == set(device_obs.COLLECTIVE_KINDS)
    gathers = stats["by_kind"]["all-gather"]
    assert gathers["count"] > 0 and gathers["bytes"] > 0
    assert stats["bytes"] == sum(
        entry["bytes"] for entry in stats["by_kind"].values())
    assert 0 < stats["largest"]["bytes"] <= stats["bytes"]
    assert stats["largest"]["kind"] in device_obs.COLLECTIVE_KINDS


def test_compile_event_of_a_one_device_step_carries_zeros(
        tmp_path, monkeypatch):
    stats = _compile_events(tmp_path, monkeypatch, _mesh())
    assert stats["bytes"] == 0 and stats["largest"] is None
    assert all(
        entry == {"count": 0, "bytes": 0}
        for entry in stats["by_kind"].values())


TPU_HLO = """
HloModule jit_train_step
%fused_continue (p: bf16[512,128]) -> bf16[2048,128] {
  %p = bf16[512,128]{1,0} parameter(0)
  ROOT %all-gather.9 = bf16[2048,128]{1,0:T(8,128)(2,1)} all-gather(%p), channel_id=7, dimensions={0}
}
%wrapped (q: f32[4,8]) -> f32[1,8] {
  %q = f32[4,8]{1,0} parameter(0)
  ROOT %reduce-scatter.1 = f32[1,8]{1,0} reduce-scatter(%q), channel_id=3
}
ENTRY %main {
  %all-gather.8 = bf16[2048,128]{1,0:T(8,128)(2,1)} all-gather(%x), channel_id=7, dimensions={0}
  %cps = (bf16[512,8]{1,0}, bf16[512,8]{1,0}, u32[], u32[]) collective-permute-start(%w), channel_id=4
  %cpd = bf16[512,8]{1,0} collective-permute-done(%cps)
  %ar = (f32[8]{0}, pred[3]{0}, f32[2]{0}, /*index=3*/f32[]) all-reduce(%a, %b, %c, %d), channel_id=5, to_apply=%add
  %add.5 = f32[8]{0} add(%all-gather.8, %y), metadata={op_name="all-gather(x)"}
  %rs = f32[1,8]{1,0} async-done(%rs-start), calls=%wrapped
}
"""


def test_hlo_collectives_counts_each_collective_once():
    """Text as the TPU compiler leaves it: an asynchronous all-gather
    repeated in the fusion it continues through (one ``channel_id``),
    a start / done pair, a wrapped reduce-scatter, a combined
    all-reduce; an operand or an ``op_name`` that holds a collective's
    name is not one."""
    combined = [("f32", (8,)), ("pred", (3,)), ("f32", (2,)), ("f32", ())]
    assert device_obs.hlo_collectives(TPU_HLO) == [
        ("all-gather", [("bf16", (2048, 128))], 2048 * 128 * 2),
        ("reduce-scatter", [("f32", (1, 8))], 32),
        ("collective-permute", [("bf16", (512, 8))], 512 * 8 * 2),
        ("all-reduce", combined, 32 + 3 + 8 + 4),
    ]
    assert device_obs._result_text(combined) == (
        "f32[8], pred[3], f32[2], +1 more")
    stats = device_obs.collective_stats(TPU_HLO)
    assert stats["by_kind"]["all-to-all"] == {"count": 0, "bytes": 0}
    assert stats["largest"] == {
        "kind": "all-gather", "result": "bf16[2048,128]",
        "bytes": 2048 * 128 * 2,
    }
    assert device_obs.collectives_text(stats).startswith(
        "0.5 MB: all-gather x1 0.5 MB, all-reduce x1 0.0 MB")
