"""Dense data plane: collective helpers, reduction plan, and the
bit-exactness contract of the SPMD trainer against the single-chip
trainer at mesh=1."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.parallel.collectives import (
    CollectiveBytes,
    mesh_all_gather,
    mesh_pmean,
    mesh_psum,
    mesh_reduce_scatter,
    track_collective_bytes,
)
from elasticdl_tpu.parallel.dense_plane import plan_dense_plane
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh
from elasticdl_tpu.parallel.sharding import ShardingRules

from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat


@pytest.mark.parametrize("check_vma", [True, False])
def test_mesh_psum_values_and_grad_inside_shard_map(check_vma):
    """mesh_psum reduces like lax.psum AND its vjp taken INSIDE the
    manual region is correct — the transpose of an all-reduce whose
    cotangent is replicated is the identity, not another psum (bare
    lax.psum gets this wrong by a factor of the axis size under
    check_vma=False; see parallel/collectives.py)."""
    mesh = build_mesh(MeshConfig(dp=1, tp=4, devices=jax.devices()[:4]))

    def body(w, x):
        # w varies over tp (a different shard everywhere); the stage
        # reduces the partial products and the loss differentiates
        # through the reduction in-body
        def loss(w_, x_):
            return jnp.sum(mesh_psum(w_ * x_, "tp") ** 2)

        val, grad = jax.value_and_grad(loss)(w, x)
        return val, grad

    wrapped = jax_compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("tp"), P()),
        out_specs=(P(), P("tp")),
        check_vma=check_vma,
    )
    w = jnp.arange(4, dtype=jnp.float32) + 1.0  # shards: 1,2,3,4
    x = jnp.ones((), jnp.float32)

    def reference(w_, x_):
        return jnp.sum(jnp.sum(w_ * x_) ** 2)

    val, grad = jax.jit(wrapped)(w, x)
    ref_val, ref_grad = jax.value_and_grad(reference)(w, x)
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val))
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad))


def test_mesh_pmean_and_gather_scatter_roundtrip():
    mesh = build_mesh(MeshConfig(dp=4, devices=jax.devices()[:4]))

    def body(x):
        mean = mesh_pmean(x, "dp", mesh=mesh)
        # a full-size per-device value (like a gradient): each device
        # holds a different scaling of the same vector
        scale = (jax.lax.axis_index("dp") + 1).astype(jnp.float32)
        v = scale * jnp.arange(8, dtype=jnp.float32)
        scattered = mesh_reduce_scatter(v, "dp")
        gathered = mesh_all_gather(scattered, "dp")
        return mean, gathered

    # an all_gather result is typed VARYING over the gathered axis
    # (every device holds its own copy), so it leaves the region
    # stacked over dp — which also shows each device's copy is whole
    wrapped = jax_compat.shard_map(
        body, mesh=mesh, in_specs=(P("dp"),), out_specs=(P(), P("dp"))
    )
    x = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    mean, gathered = jax.jit(wrapped)(x)
    np.testing.assert_allclose(
        np.asarray(mean), np.asarray(x.sum(0, keepdims=True) / 4.0)
    )
    # reduce-scatter sums the 4 scalings (1+2+3+4 = 10) and leaves each
    # device its slice; the all-gather re-materializes the full sum on
    # every device
    np.testing.assert_allclose(
        np.asarray(gathered).reshape(4, 8),
        np.tile(10.0 * np.arange(8, dtype=np.float32), (4, 1)),
    )


def test_axis_size_product_resolves_or_raises():
    """Sizes come from mesh= or the enclosing manual region; an axis
    neither binds is an error, not a silent 1 (mesh_pmean would
    otherwise return the SUM)."""
    from elasticdl_tpu.parallel.collectives import axis_size_product

    mesh = build_mesh(MeshConfig(dp=4, devices=jax.devices()[:4]))
    assert axis_size_product(("dp", "tp"), mesh) == 4

    def body(x):
        # no mesh= : the size must come from the manual region
        return mesh_pmean(x, "dp")

    wrapped = jax_compat.shard_map(
        body, mesh=mesh, in_specs=(P("dp"),), out_specs=P()
    )
    x = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    np.testing.assert_allclose(
        np.asarray(jax.jit(wrapped)(x)),
        np.asarray(x.sum(0, keepdims=True) / 4.0),
    )
    with pytest.raises(ValueError, match="nope"):
        axis_size_product("nope")


def test_track_collective_bytes_ring_costs():
    mesh = build_mesh(MeshConfig(dp=4, devices=jax.devices()[:4]))
    x = jnp.zeros((128,), jnp.float32)  # 512 payload bytes

    with track_collective_bytes() as acc:

        def body(v):
            return (
                mesh_psum(v, "dp", mesh=mesh),
                mesh_reduce_scatter(v, "dp", mesh=mesh),
                mesh_all_gather(v, "dp", mesh=mesh),
            )

        jax.eval_shape(
            jax_compat.shard_map(
                body, mesh=mesh,
                in_specs=(P("dp"),),
                out_specs=(P(), P("dp"), P("dp")),
            ),
            x,
        )
    # per-shard payload is 32 floats = 128 bytes; ring cost B(n-1)/n
    ring = 128 * 3 // 4
    assert acc.all_reduce == 2 * ring
    assert acc.reduce_scatter == ring
    assert acc.all_gather == ring
    assert acc.total == 4 * ring
    assert acc.calls == 3


def test_track_collective_bytes_nested_and_size1_axis():
    mesh = build_mesh(MeshConfig(dp=1, devices=jax.devices()[:1]))
    with track_collective_bytes() as outer:
        with track_collective_bytes() as inner:
            # size-1 axis: no traffic, no call recorded
            mesh_psum(jnp.ones((4,)), "dp", mesh=mesh)
        assert inner.total == 0 and inner.calls == 0
    assert outer.total == 0


def test_plan_reduce_scatter_vs_psum_fallback():
    """fsdp-sharded params reduce-scatter; small/replicated params fall
    back to a psum; tp-sharded params reduce only over the data
    extent."""
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2, devices=jax.devices()[:8]))
    params = {
        "big": jax.ShapeDtypeStruct((1024, 64), jnp.float32),
        "tiny": jax.ShapeDtypeStruct((8,), jnp.float32),
        "tpw": jax.ShapeDtypeStruct((64, 64), jnp.float32),
    }
    rules = ShardingRules(
        rules=[
            (r"^big$", P("fsdp", None)),
            (r"^tpw$", P(None, "tp")),
        ],
        default_spec=P(),
    )
    plan = plan_dense_plane(params, mesh, rules)
    modes = {p.path: p for p in plan.params}
    assert modes["big"].mode == "reduce_scatter"
    assert modes["tiny"].mode == "psum"
    assert modes["tpw"].mode == "psum"
    big, tiny, tpw = modes["big"], modes["tiny"], modes["tpw"]
    # big: RS over fsdp=2 then all-reduce of the half over dp=2
    assert big.grad_bytes_per_step == (
        big.nbytes // 2 + 2 * ((big.nbytes // 2) // 2)
    )
    # tiny: plain all-reduce over dp*fsdp=4
    assert tiny.grad_bytes_per_step == 2 * (tiny.nbytes * 3 // 4)
    # tpw: each tp shard all-reduces over dp*fsdp=4 only
    assert tpw.grad_bytes_per_step == 2 * ((tpw.nbytes // 2) * 3 // 4)
    summary = plan.summary()
    assert summary["mesh_shape"] == "dp=2,fsdp=2,tp=2"
    assert summary["reduce_scatter_params"] == 1
    assert summary["psum_params"] == 2
    assert summary["collective_bytes_per_step"] == (
        big.grad_bytes_per_step
        + tiny.grad_bytes_per_step
        + tpw.grad_bytes_per_step
    )


def test_plan_single_chip_is_all_local():
    mesh = build_mesh(MeshConfig(dp=1, devices=jax.devices()[:1]))
    params = {"w": jax.ShapeDtypeStruct((32, 32), jnp.float32)}
    plan = plan_dense_plane(params, mesh)
    assert all(p.mode == "local" for p in plan.params)
    assert plan.collective_bytes_per_step == 0
    assert plan.mesh_shape_str() == "dp=1"


def _mnist_batch(rng, n=16):
    return {
        "features": rng.randn(n, 28, 28, 1).astype(np.float32),
        "labels": rng.randint(0, 10, size=n).astype(np.int32),
        "_mask": np.ones((n,), np.bool_),
    }


@pytest.mark.slow
def test_spmd_trainer_bit_exact_at_mesh1():
    """The dense-plane acceptance contract: at mesh=1 the SPMD trainer
    computes bit-identical step state to the single-chip JaxTrainer —
    the sharding annotations change WHERE tensors live, never what
    they hold."""
    from elasticdl_tpu.models import mnist
    from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer
    from elasticdl_tpu.worker.trainer import JaxTrainer

    mesh = build_mesh(MeshConfig(dp=1, devices=[jax.devices()[0]]))
    make = lambda: dict(
        model=mnist.custom_model(),
        loss_fn=mnist.loss,
        optimizer=mnist.optimizer(),
        seed=7,
    )
    spmd = SpmdTrainer(mesh=mesh, **make())
    single = JaxTrainer(health=False, **make())

    rng = np.random.RandomState(3)
    batches = [_mnist_batch(rng) for _ in range(3)]
    s_state = d_state = None
    for batch in batches:
        s_state, s_loss = single.train_step(s_state, dict(batch))
        d_state, d_loss = spmd.train_step(d_state, dict(batch))
    assert float(s_loss) == float(d_loss)
    for a, b in zip(
        jax.tree_util.tree_leaves(s_state.params),
        jax.tree_util.tree_leaves(d_state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree_util.tree_leaves(s_state.opt_state),
        jax.tree_util.tree_leaves(d_state.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the plan is derived and exported for telemetry
    assert spmd.dense_plan is not None
    assert spmd.mesh_shape_str == "dp=1"
    assert spmd.collective_bytes_per_step == 0.0


def test_spmd_trainer_dense_plan_on_fsdp_mesh():
    from elasticdl_tpu.models import mnist
    from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer

    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, devices=jax.devices()[:2]))
    trainer = SpmdTrainer(
        model=mnist.custom_model(),
        loss_fn=mnist.loss,
        optimizer=mnist.optimizer(),
        mesh=mesh,
        seed=0,
    )
    batch = _mnist_batch(np.random.RandomState(0))
    state, loss = trainer.train_step(None, batch)
    assert np.isfinite(float(loss))
    plan = trainer.dense_plan
    assert plan is not None
    # the conv/dense kernels are big enough to shard; biases fall back
    modes = {p.path: p.mode for p in plan.params}
    assert "reduce_scatter" in modes.values()
    assert trainer.collective_bytes_per_step > 0
    assert trainer.mesh_shape_str == "fsdp=2"
