"""``jax.grad`` of the flash kernel compiled at the benchmark's four
shapes for a v5e that is described, not attached (the TPU compiler is
installed here): what interpret mode cannot see. The chip's compiler
must accept the fused backward (its dq accumulator and whole-``bh`` dq
block need more VMEM than the default scoped limit), the program must
hold one forward and one backward kernel, both named ``flash...``
(``benchmark/metrics/flash_time_share.py`` finds them by that word),
and a shape over the budget must compile to the split pair.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.ops import flash_attention as F


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def flash_kernels(chip, shape):
    """The Mosaic kernels, as the compile ledger names and counts them,
    of the compiled causal attention's gradient at (batch, heads, seq,
    head width), bfloat16."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert hlo.count("tpu_custom_call") == len(
        device_obs._PALLAS_KERNEL_RE.findall(hlo))
    return device_obs.pallas_kernels(hlo)


@pytest.mark.parametrize("shape", [
    (4, 8, 2048, 256),    # pythia1b-s2k
    (1, 8, 16384, 256),   # pythia1b-s16k: 16 MB of dq accumulator
    (3, 8, 2048, 256),    # pythia1b-fsdp4-s2k, a chip's shard
    (8, 16, 4096, 128),   # olmoe1b7b-s4k
], ids=["s2k-b4", "s16k-b1", "s2k-b3", "s4k-b8"])
def test_the_cells_compile_to_one_forward_and_one_backward(chip, shape):
    assert F.backward_schedule(
        shape[2], shape[2], shape[3], jnp.bfloat16) == "fused"
    assert flash_kernels(chip, shape) == {"flash_fwd": 1, "flash_bwd": 1}


def test_over_the_budget_compiles_to_the_split_pair(chip):
    shape = (1, 2, 32768, 256)
    assert F.backward_schedule(
        shape[2], shape[2], shape[3], jnp.bfloat16) == "split"
    assert flash_kernels(chip, shape) == {
        "flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}


@pytest.mark.parametrize("mib,blocks", [
    (57, (1024, 1024)),  # what _blocks gives the cell: it counts 56
    (48, (512, 1024)),   # a budget the tall block is over: it counts 47
])
def test_the_count_is_above_what_the_compiler_needs(
        chip, monkeypatch, mib, blocks):
    """``fused_bwd_vmem_bytes`` counts generously: with the stated
    limit just above its count for the largest cell, the compiler
    still accepts the kernel; and ``_blocks`` takes 1024 q-rows only
    where the fused backward fits its budget with them."""
    monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", mib * 2**20)
    assert F._blocks(16384, 16384, 256, jnp.bfloat16, None, None) == blocks
    assert (mib - 1) * 2**20 <= F.fused_bwd_vmem_bytes(
        16384, 256, *blocks, 2) < mib * 2**20
    assert flash_kernels(chip, (1, 1, 16384, 256)) == {
        "flash_fwd": 1, "flash_bwd": 1}


@pytest.mark.parametrize("shape,kv_heads,kernels", [
    # qwen3next80b-s32k: 16 query heads over 2 kv heads, the split pair
    ((1, 16, 32768, 256), 2,
     {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}),
    # a grouped call under the fused backward's budget
    ((2, 8, 4096, 128), 2, {"flash_fwd": 1, "flash_bwd": 1}),
], ids=["s32k-16-over-2", "s4k-8-over-2"])
def test_grouped_query_heads_compile_without_a_copy_of_k_or_v(
        chip, shape, kv_heads, kernels):
    """k and v keep their own head count up to the kernels' operands:
    the compiled program holds no array of the kv width at the query
    heads' count in the kernels' dtype but q, o, do and dq themselves
    (dk and dv leave a query head in float32 and are summed after)."""
    batch, heads, seq, dim = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct(
        (batch, kv_heads, seq, dim), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert device_obs.pallas_kernels(hlo) == kernels
    merged = "bf16[%d,%d,%d]" % (batch * kv_heads, seq, dim)
    forward = [line for line in hlo.splitlines()
               if "flash_fwd" in line and "custom-call(" in line]
    assert len(forward) == 1 and forward[0].count(merged) >= 2
    # the gradients come back at the kv heads' count
    assert "f32[%d,%d,%d]" % (batch * heads, seq, dim) in hlo


def test_the_chunked_rule_compiles_at_the_cell_s_shape(chip):
    """``gated_delta_rule``'s gradient at 32,768 tokens, 16 key and 32
    value heads of 128, chunk 64, for a described v5e: the segments'
    ``jax.checkpoint`` keeps its temporaries under 2.5 GB (4 GB and a
    refused step without it, PERF.md Section 6)."""
    from elasticdl_tpu.ops import gated_delta

    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)
    args = (
        struct((1, 16, 32768, 128), jnp.bfloat16),
        struct((1, 16, 32768, 128), jnp.bfloat16),
        struct((1, 32, 32768, 128), jnp.bfloat16),
        struct((1, 32, 32768), jnp.float32),
        struct((1, 32, 32768), jnp.float32),
    )
    compiled = jax.jit(jax.grad(
        lambda *a: gated_delta.gated_delta_rule(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))
    ).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2**30
    assert "while" in compiled.as_text()
