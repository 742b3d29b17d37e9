"""The gated short convolution's two ``short_conv_*`` kernels compiled
for a v5e that is described, not attached (the TPU compiler is
installed here), at the LFM2 cell's shape (1 x 32,768 x 6,144 bfloat16,
3 taps) and at float32: what interpret mode cannot see (the chip's
tiling, its VMEM, a slice that is not aligned). And the mixer's
gradient, as a TPU backend gets it, must hold each kernel under its
name and under the scope the trace reader charges, ``short_conv/gate``.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.lib import conv_trace
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.ops import short_conv as S

# lfm2-8b-s32k: one sequence of 32,768 tokens, 2,048 channels
SEQ, CHANNELS, TAPS = 32768, 2048, 3
KERNELS = ("short_conv_fwd", "short_conv_bwd")


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype,batch,seq,channels,taps", [
    (jnp.bfloat16, 1, SEQ, CHANNELS, TAPS),
    (jnp.float32, 1, 4096, CHANNELS, TAPS),
    (jnp.bfloat16, 2, 384, 128, 1),
    (jnp.bfloat16, 1, 2048, 1536, 9),
], ids=["the-cell", "float32", "one-tap-at-the-smallest-tile",
        "nine-taps-twelve-lane-rows"])
def test_both_kernels_compile(chip, dtype, batch, seq, channels, taps):
    on = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=chip)
    bcx = on((batch, seq, 3 * channels), dtype)
    w = on((taps, channels), dtype)
    dy = on((batch, seq, channels), dtype)
    for lowered in (S.short_conv_fwd.lower(bcx, w),
                    S.short_conv_bwd.lower(bcx, w, dy)):
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


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_mixer_s_gradient_holds_each_kernel_under_its_scope(
        chip, monkeypatch, remat):
    """At the cell's shape: the backward once, the forward once and,
    where the block is rematerialised, at most twice; and
    ``benchmark.lib.conv_trace.classify`` charges every one, the
    backward's inside the VJP too, to ``short_conv/gate``. Beside the
    two calls nothing turns, copies or widens an array of the
    projection's or the result's size."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mixer = T.ShortConv(T.ShortConvDims(TAPS))
    x = jax.ShapeDtypeStruct((1, SEQ, CHANNELS), jnp.bfloat16, sharding=chip)
    assert S.conv_choice(jnp.bfloat16, CHANNELS, SEQ, TAPS) == ("pallas", 512)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(lambda: mixer.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))["params"])

    def apply(params, x):
        return mixer.apply({"params": params}, x)

    if remat:
        apply = jax.checkpoint(apply)

    def loss(params, x):
        return (apply(params, x).astype(jnp.float32) ** 2).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    counts = device_obs.pallas_kernels(hlo)
    assert counts.get("short_conv_bwd") == 1
    assert counts.get("short_conv_fwd") in ((1, 2) if remat else (1,))
    for name, text, op_name in _kernel_instructions(hlo):
        assert conv_trace.classify(text, op_name) == [conv_trace.GATE], (
            name, op_name)
        if name == "short_conv_bwd":
            assert "transpose(" in op_name
    # no pass of XLA's over (S, 3 C) or (S, C) under the scope: the
    # kernels read the projection's result and write out_proj's operand
    big = re.compile(r"\[1,%d,(%d|%d)\]" % (SEQ, CHANNELS, 3 * CHANNELS))
    for line in hlo.splitlines():
        op_name = re.search(r'op_name="([^"]*)"', line)
        if (op_name and "short_conv/gate" in op_name.group(1)
                and "tpu_custom_call" not in line and " fusion(" in line):
            shape = line.split("=", 1)[1].split("fusion(")[0]
            assert not big.search(shape), line[:300]
