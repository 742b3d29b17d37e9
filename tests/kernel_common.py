"""Shared by the kernels' test files. For the compile-only ones
(``test_flash_tpu_compile.py``, ``test_gated_delta_tpu_compile.py``,
``test_moe_tpu_compile.py``, ``test_moe_cells_tpu_compile.py``): the
described v5e:2x2 and its first chip as module-scoped fixtures, which a
file takes by importing their names (still one topology a file, in the
process that runs it: on-chip-measurement guide, section 2), and the
Mosaic kernels a compiled program holds. For the interpret-mode ones
(``test_attention_ops.py``, ``test_grouped_query_attention.py``,
``test_latent_moe.py``): the flash kernels' names and the value from one
trace. For the band's three files (``test_flash_band.py``: the layout;
``test_flash_band_kernels.py``; ``test_flash_band_grid.py``): ISSUE 42's
equation and the kernels' cases."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def chip(topology):
    return SingleDeviceSharding(topology.devices[0])


def mosaic_kernels(hlo):
    """op_name of every Mosaic kernel in the program."""
    return [
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in hlo.splitlines() if "tpu_custom_call" in line
    ]


def flash_names(jaxpr):
    """Names of the flash pallas_calls in a jaxpr, sorted."""
    return sorted(set(re.findall(
        r"name=(flash_(?:fwd|bwd|dq|dkv))\b", str(jaxpr))))


def traced_flash(fn, args):
    """(names of the flash pallas_calls ``fn`` traces to, its value)
    from ONE trace: the jaxpr is read and then compiled, where
    ``make_jaxpr`` and a call would trace the kernels twice."""
    traced = jax.jit(fn).trace(*args)
    return flash_names(traced.jaxpr), traced.lower().compile()(*args)


def dq_block_buffers(jaxpr):
    """Of every fused flash backward in ``jaxpr`` (a ``Traced.jaxpr``),
    how many buffers dq's whole-head output block has: the mode the
    block states (``pl.Buffered(1)``), 2 where it states none."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(inner)

    modes = [
        params["grid_mapping"].block_mappings[-1].pipeline_mode
        for params in calls(jaxpr.jaxpr)
        if re.fullmatch(r"flash_(?:\w+_)?bwd", params["name"])]
    return [2 if mode is None else mode.buffer_count for mode in modes]


def dense_band(seq, window):
    """ISSUE 42's equation, position by position, in numpy: a query
    sees itself and the ``window - 1`` keys before it."""
    pos = np.arange(seq)
    return (pos[None, :] <= pos[:, None]) & (
        pos[:, None] - pos[None, :] < window)


# (seq, window, heads, kv heads, width, block_q, block_k, dtype)
BAND_KERNEL_CASES = {
    "group-6-float32": (512, 100, 6, 1, 64, 128, 128, jnp.float32),
    "group-8-bfloat16": (512, 128, 8, 1, 64, 128, 256, jnp.bfloat16),
    "group-8-256-128": (512, 200, 16, 2, 32, 256, 128, jnp.float32),
    "group-1-window-over-a-block": (512, 300, 2, 2, 64, 128, 128,
                                    jnp.float32),
    "group-6-window-of-one": (256, 1, 6, 1, 32, 128, 128, jnp.float32),
    "window-of-one-128-256": (512, 1, 2, 1, 32, 128, 256, jnp.float32),
    "window-is-the-sequence": (512, 512, 2, 2, 32, 128, 128, jnp.float32),
    "window-over-the-sequence": (512, 5000, 8, 1, 32, 256, 128,
                                 jnp.float32),
    "group-8-window-is-a-block": (768, 128, 8, 1, 32, 128, 128,
                                  jnp.bfloat16),
    "group-8-two-kv-heads-128-256": (512, 130, 16, 2, 32, 128, 256,
                                     jnp.float32),
}
