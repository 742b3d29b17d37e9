"""The Gated DeltaNet layer's two ``qkv_conv_*`` kernels compiled for a
v5e that is described, not attached (the TPU compiler is installed
here), at the Qwen3-Next cell's shape (1 x 32,768 x 12,288 bfloat16, 16
key / 32 value heads of 128) and at float32 and one-to-one heads: what
interpret mode cannot see (the chip's tiling, its VMEM, a slice that is
not aligned). And the layer's gradient, as a TPU backend gets it, must
hold each kernel once, under its name and under the scope the trace
reader charges: ``gdn/conv``, never ``gdn/scan``.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.lib import gdn_trace
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.ops import qkv_conv as Q

# qwen3next80b-s32k: one sequence of 32,768 tokens
SEQ, TAPS = 32768, 4
KERNELS = ("qkv_conv_fwd", "qkv_conv_bwd")


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("hk,hv,dtype,seq", [
    (16, 32, jnp.bfloat16, SEQ), (16, 32, jnp.float32, 2048),
    (2, 2, jnp.bfloat16, 384),
], ids=["the-cell", "float32", "one-to-one-at-the-smallest-tile"])
def test_both_kernels_compile(chip, hk, hv, dtype, seq):
    heads = (hk, hv, 128)
    on = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=chip)
    conv_dim = (2 * hk + hv) * 128
    qkvz = on((1, seq, conv_dim + hv * 128), dtype)
    taps = on((TAPS, conv_dim), dtype)
    dq, dv = on((1, hk, seq, 128), dtype), on((1, hv, seq, 128), dtype)
    for lowered in (
            Q.qkv_conv_fwd.lower(qkvz, taps, heads=heads),
            Q.qkv_conv_bwd.lower(qkvz, taps, dq, dq, dv, heads=heads)):
        assert "tpu_custom_call" in lowered.compile().as_text()


def _kernel_instructions(hlo):
    """(kernel name, instruction text up to its metadata, op_name) of
    every Mosaic call of the compiled module."""
    found = []
    for line in hlo.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" not in line:
            continue
        names = device_obs.pallas_kernels(line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        found.append((next(iter(names)), line, op_name.group(1)))
    return found


def test_the_layer_s_gradient_holds_each_kernel_once_under_its_scope(
        chip, monkeypatch):
    """At the cell's shape, the rule's own kernels and the gated
    norm's pair (``ops/gated_norm.py``, PR 65) beside them: the two
    calls are there once each, and
    ``benchmark.lib.gdn_trace.classify`` reads both, the backward's
    inside the VJP too, as ``gdn/conv``, the rule's as ``gdn/scan``,
    and ``gated_norm_fwd`` / ``gated_norm_bwd``, once each, as
    ``gdn/out_norm``: their names hold none of ``gdn``, ``kda`` or a
    leading ``ssd``, by which the readers charge a kernel to the
    SCAN."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dims = T.GatedDeltaDims(
        num_key_heads=16, num_value_heads=32, key_head_dim=128,
        value_head_dim=128, conv_kernel_dim=TAPS)
    layer = T.GatedDeltaNet(dims)
    x = jax.ShapeDtypeStruct((1, SEQ, 2048), jnp.bfloat16, sharding=chip)
    assert Q.conv_impl(jnp.bfloat16, 128, 128, SEQ, TAPS) == "pallas"
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))["params"])

    def loss(params, x):
        return (layer.apply({"params": params}, x).astype(
            jnp.float32) ** 2).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    counts = device_obs.pallas_kernels(hlo)
    norm = ("gated_norm_fwd", "gated_norm_bwd")
    assert {name: counts.get(name) for name in KERNELS + norm
            } == dict.fromkeys(KERNELS + norm, 1)
    for name in norm:
        assert "gdn" not in name and "kda" not in name
        assert not name.startswith("ssd")
    for name, text, op_name in _kernel_instructions(hlo):
        want = ("gdn/conv" if name in KERNELS
                else "gdn/out_norm" if name in norm else "gdn/scan")
        assert gdn_trace.classify(text, op_name) == want, (name, op_name)
        assert ("transpose(" in op_name) == name.endswith("_bwd") or (
            want == "gdn/scan")
