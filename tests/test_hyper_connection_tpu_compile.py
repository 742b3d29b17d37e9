"""The hyper-connection's four kernels compiled for a v5e that is
described, not attached (the TPU compiler is installed here), at the
Xing4.0 cell's shape and at its float32 and two-stream variants: what
interpret mode cannot see (the chip's tiling, its VMEM). And the
sublayer's gradient around a stand-in F, as a TPU backend gets it, must
hold each kernel once, named as the trace reader and the compile ledger
find them.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.ops import hyper_connection as H

# xing4-29b-s4k: one sequence of 4,096 tokens, four streams of 3,584
SEQ, DIM = 4096, 3584
KERNELS = ("mhc_pre_fwd", "mhc_post_fwd", "mhc_post_bwd", "mhc_pre_bwd")


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n,dtype,seq", [
    (4, jnp.bfloat16, SEQ), (4, jnp.float32, 512), (2, jnp.bfloat16, 512),
], ids=["the-cell", "float32", "two-streams"])
def test_the_four_kernels_compile(chip, n, dtype, seq):
    assert H.vmem_bytes(n, DIM, jnp.dtype(dtype).itemsize) <= H._VMEM_BUDGET
    _, padded = H.coef_rows(n)
    on = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=chip)
    x, y = on((1, n, seq, DIM), dtype), on((1, seq, DIM), dtype)
    kt, gb = on((n, padded, DIM), dtype), on((2, padded, 1), jnp.float32)
    coef = on((1, padded, seq), jnp.float32)
    dims = (n, 20, 1e-6, (-30.0, 30.0))
    for lowered in (
            H.mhc_pre_fwd.lower(x, kt, gb, dims=dims),
            H.mhc_post_fwd.lower(x, y, coef),
            H.mhc_post_bwd.lower(x, y, coef),
            H.mhc_pre_bwd.lower(x, x, y, kt, gb, coef, coef, coef,
                                dims=dims)):
        assert "tpu_custom_call" in lowered.compile().as_text()


def test_a_sublayer_s_gradient_holds_each_kernel_once(chip, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    module = T.HyperConnection(T.HyperDims(4))
    x = jax.ShapeDtypeStruct((1, 4, 512, DIM), jnp.bfloat16, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))["params"])

    def loss(params, x):
        u, write, _ = module.apply({"params": params}, x)
        # a loss that needs X' itself, or the forward mix is dead code
        return (write(jnp.tanh(u)).astype(jnp.float32) ** 2).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert device_obs.pallas_kernels(hlo) == dict.fromkeys(KERNELS, 1)
