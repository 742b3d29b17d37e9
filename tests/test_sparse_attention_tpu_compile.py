"""The kernels of ``ops/sparse_attention.py`` compiled for a v5e that is
described, not attached (the TPU compiler is installed here), at the
Keye cell's shape (32 query heads over 4 kv heads x 32,768 x 128, an
indexer of 16 heads of 64, top 2,048, bfloat16): what interpret mode
cannot see (the chip's tiling, its VMEM, an int8 mask tile, a loop of
dynamic length, a branch on a vector's maximum). The gradient of the
whole call must hold each kernel once under its name
(``benchmark/metrics/flash_time_share.py`` finds the two with "flash" in
theirs) and under the scope ``benchmark/lib/dsa_trace.py`` charges it
to.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.lib import dsa_trace
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.ops import sparse_attention as S

HEADS, KV_HEADS, SEQ, HEAD_DIM = 32, 4, 32768, 128
IDX_HEADS, IDX_DIM, TOPK = 16, 64, 2048
KERNELS = {"dsa_select": "dsa/select", "dsa_mask": "dsa/scores",
           "flash_sparse_fwd": "dsa/attend", "flash_sparse_bwd": "dsa/attend",
           "dsa_indexer_loss": "dsa/indexer_loss"}


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def operands(chip, seq, dtype, batch=1):
    on = lambda shape, kind=dtype: jax.ShapeDtypeStruct(
        shape, kind, sharding=chip)
    return (on((batch, HEADS, seq, HEAD_DIM)),
            on((batch, KV_HEADS, seq, HEAD_DIM)),
            on((batch, KV_HEADS, seq, HEAD_DIM)),
            on((batch, IDX_HEADS, seq, IDX_DIM)),
            on((batch, seq, IDX_DIM)),
            on((batch, seq, IDX_HEADS), jnp.float32))


@functools.lru_cache(maxsize=None)
def gradient_hlo(chip, seq, dtype, batch=1, remat=False):
    def call(*args):
        return S.dsa_attention(*args, TOPK, impl="pallas")[:2]

    if remat:
        call = jax.checkpoint(
            call, policy=jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse", *S.DSA_SAVE_NAMES))

    def loss(*args):
        out, kl = call(*args)
        return out.astype(jnp.float32).sum() + kl.sum()

    return jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *operands(chip, seq, dtype, batch)).compile().as_text()


@pytest.mark.parametrize("seq,dtype,batch", [
    (SEQ, jnp.bfloat16, 1), (4096, jnp.float32, 2),
], ids=["the-cell", "two-sequences-in-float32"])
def test_the_call_s_gradient_compiles_to_each_kernel_once(
        chip, seq, dtype, batch):
    # by position, as every caller: ``lru_cache`` keys on how an
    # argument was given, and the cell's program is read by two tests
    hlo = gradient_hlo(chip, seq, dtype, batch, False)
    assert device_obs.pallas_kernels(hlo) == dict.fromkeys(KERNELS, 1)
    assert hlo.count("tpu_custom_call") == len(
        device_obs._PALLAS_KERNEL_RE.findall(hlo))


def test_under_the_flash_policy_nothing_of_the_selection_is_made_again(chip):
    """A block's backward under ``remat_policy="flash"``: the kept set
    in bits, the flash outputs and the indexer's term with its
    cotangents are saved, so every kernel runs once; and the kept set
    is nowhere a byte a pair."""
    hlo = gradient_hlo(chip, SEQ, jnp.bfloat16, 1, True)
    assert device_obs.pallas_kernels(hlo) == dict.fromkeys(KERNELS, 1)
    assert "s8[1,%d,%d]" % (SEQ, SEQ) not in hlo
    assert "s8[1,%d,%d]" % (SEQ, SEQ // 8) in hlo


def test_the_trace_reader_charges_every_kernel_to_its_scope(chip):
    hlo = gradient_hlo(chip, SEQ, jnp.bfloat16, 1, False)
    seen = set()
    for line in hlo.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" not in line:
            continue
        name = next(iter(device_obs.pallas_kernels(line)))
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        # by its name, wherever it was called, and by its scope alike
        assert dsa_trace.classify(line.strip(), "") == [KERNELS[name]], name
        assert KERNELS[name] in op_name, (name, op_name)
        seen.add(name)
    assert seen == set(KERNELS)


def test_a_sequence_the_tiles_do_not_divide_is_refused_by_name():
    q = jnp.zeros((1, HEADS, 1000, HEAD_DIM), jnp.bfloat16)
    assert "not whole tiles" in S._refusal(q)
    q = jnp.zeros((1, HEADS, SEQ, HEAD_DIM), jnp.bfloat16)
    assert S._refusal(q) == ""
