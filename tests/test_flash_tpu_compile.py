"""``jax.grad`` of the flash kernel compiled at the benchmark's four
shapes for a v5e that is described, not attached (the TPU compiler is
installed here): what interpret mode cannot see. The chip's compiler
must accept the fused backward (its dq accumulator and whole-``bh`` dq
block need more VMEM than the default scoped limit), the program must
hold one forward and one backward kernel, both named ``flash...``
(``benchmark/metrics/flash_time_share.py`` finds them by that word),
the two 32,768-token cells' shapes must compile to the fused kernel
with dq's output block in one buffer (PR 61), and a shape over the
budget even so must compile to the split pair.

The gated delta rule's kernels for the same described chip are
``tests/test_gated_delta_tpu_compile.py``'s.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.ops import flash_attention as F
from tests.kernel_common import chip, topology  # noqa: F401 (fixtures)


def flash_kernels(chip, shape, v_dim=None, dtype=jnp.bfloat16):
    """The Mosaic kernels, as the compile ledger names and counts them,
    of the compiled causal attention's gradient at (batch, heads, seq,
    head width), bfloat16; ``v_dim``: v's own width."""
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    v = jax.ShapeDtypeStruct(
        shape[:3] + (v_dim or shape[3],), dtype, sharding=chip)

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, v).compile().as_text()
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
    # dq's float32 accumulator alone is the 64 MiB
    shape = (1, 2, 65536, 256)
    assert F.backward_schedule(
        shape[2], shape[2], shape[3], jnp.bfloat16) == "split"
    assert flash_kernels(chip, shape) == {
        "flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}


@pytest.mark.parametrize("shape,v_dim,dtype,mib", [
    ((1, 2, 32768, 256), None, jnp.bfloat16, (79.0, 63.0)),
    ((1, 2, 32768, 192), 128, jnp.bfloat16, (77.25, 61.25)),
    # a model's float32 init trace at pythia1b-s16k's shape
    ((1, 2, 16384, 256), None, jnp.float32, (68.0, 52.0)),
], ids=["qwen3next80b-s32k", "kimi-linear48b-s32k", "s16k-float32"])
def test_one_buffer_of_dq_s_block_keeps_the_fused_kernel(
        chip, shape, v_dim, dtype, mib):
    """Where the count with the pipeline's two buffers of dq's
    whole-head output block is over the budget and the count with one
    is not, the block is ``pl.Buffered(1)`` and the chip's compiler
    takes the fused kernel under the budget it states (PR 61: with two
    buffers it refuses 32,768 x 256 under 72 MiB; with one it takes it
    under 59 and 32,768 x 192 / 128 under 56)."""
    seq, dim = shape[2:]
    blocks = F._blocks(
        seq, seq, dim, dtype, None, None, backward=True, v_dim=v_dim)
    assert blocks == (512, 1024)
    assert tuple(
        F.fused_bwd_vmem_bytes(
            seq, dim, *blocks, jnp.dtype(dtype).itemsize, v_dim, buffers)
        / 2**20 for buffers in (2, 1)) == mib
    assert F.fused_dq_buffers(seq, seq, dim, dtype, v_dim=v_dim) == 1
    assert flash_kernels(chip, shape, v_dim, dtype) == {
        "flash_fwd": 1, "flash_bwd": 1}


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
    # qwen3next80b-s32k: 16 query heads over 2 kv heads, the fused
    # kernel with dq's output block in one buffer (PR 61; the pair before)
    ((1, 16, 32768, 256), 2, {"flash_fwd": 1, "flash_bwd": 1}),
    # a grouped call under the fused backward's budget
    ((2, 8, 4096, 128), 2, {"flash_fwd": 1, "flash_bwd": 1}),
    # lfm2-8b-s32k: 32 query heads over 8 kv heads at a 64-wide head,
    # whose dq accumulator (half a 128-wide head's) fits the fused one
    ((1, 32, 32768, 64), 8, {"flash_fwd": 1, "flash_bwd": 1}),
], ids=["s32k-16-over-2", "s4k-8-over-2", "s32k-32-over-8-at-64"])
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


@pytest.mark.parametrize("half_len,block,blocks", [
    (8192, 4, (None, None)),   # sdar30b-bd-s8k: 1024 x 1024 tiles
    (8192, 4, (512, 512)),     # tiles under the default
    (6144, 6, (None, None)),   # a block length that is no power of two
], ids=["the-cell", "512-512", "blocks-of-6"])
def test_the_block_diffusion_layout_compiles(chip, half_len, block, blocks):
    """The mask as a layout (PR 35) under Mosaic, which interpret mode
    cannot stand in for (a select between two boolean vectors was
    refused here: ``BlockDiffusion.keep`` compares integers): 32 query
    heads of 128 over 4 kv heads, 2 x ``half_len`` positions, one
    forward and one fused backward."""
    q = jax.ShapeDtypeStruct(
        (1, 32, 2 * half_len, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct(
        (1, 4, 2 * half_len, 128), jnp.bfloat16, sharding=chip)
    layout = F.BlockDiffusion(half_len, block)

    def loss(q, k, v):
        out = F.flash_attention(
            q, k, v, mask=layout, block_q=blocks[0], block_k=blocks[1])
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert device_obs.pallas_kernels(hlo) == {"flash_fwd": 1, "flash_bwd": 1}


@pytest.mark.parametrize("heads,mask,kernels", [
    (64, F.Band(512), {"flash_band_fwd": 1, "flash_band_bwd": 1}),
    (48, None, {"flash_fwd": 1, "flash_bwd": 1}),
    (64, F.Band(700), {"flash_band_fwd": 1, "flash_band_bwd": 1}),
], ids=["window-64-over-8", "full-48-over-8", "window-no-multiple"])
def test_the_band_layout_compiles_at_the_cell_s_shapes(
        chip, heads, mask, kernels):
    """``laguna-xs2-s32k``'s two kinds of layer (PR 42) under Mosaic:
    32,768 positions, heads of 128, groups of 8 and of 6 over 8 kv
    heads, the fused backward (33.5 MB of dq accumulator), the band's
    kernels under names of their own (``Band.keep`` compares integers
    and ands two compares, as the block-diffusion layout's does)."""
    q = jax.ShapeDtypeStruct(
        (1, heads, 32768, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct(
        (1, 8, 32768, 128), jnp.bfloat16, sharding=chip)
    assert F.backward_schedule(
        32768, 32768, 128, jnp.bfloat16, layout=mask) == "fused"

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True, mask=mask)
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert device_obs.pallas_kernels(hlo) == kernels


@pytest.mark.parametrize("blocks,schedule,kernels", [
    ((1024, 1024), "fused", {"flash_band_fwd": 1, "flash_band_bwd": 1}),
    ((512, 1024), "fused", {"flash_band_fwd": 1, "flash_band_bwd": 1}),
    ((1024, 512), "fused", {"flash_band_fwd": 1, "flash_band_bwd": 1}),
    ((256, 256), "fused", {"flash_band_fwd": 1, "flash_band_bwd": 1}),
    ((None, None), "split",
     {"flash_band_fwd": 1, "flash_band_dq": 1, "flash_band_dkv": 1}),
    ((512, 1024), "split",
     {"flash_band_fwd": 1, "flash_band_dq": 1, "flash_band_dkv": 1}),
], ids=lambda value: str(value) if isinstance(value, (tuple, str)) else "")
def test_the_band_s_grid_of_runs_compiles(
        chip, monkeypatch, blocks, schedule, kernels):
    """The band's four kernels on a grid as long as the band (PR 43)
    under Mosaic, at the cell's window layers' shapes: the inner index
    becomes a block by scalar arithmetic in the index maps and in the
    kernels (``_grid_step``: a division, a maximum, a minimum), at
    equal and unequal tiles (runs of different lengths: slots to
    spare), and under the split schedule, which no cell reaches with a
    band."""
    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    q = jax.ShapeDtypeStruct(
        (1, 64, 32768, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct(
        (1, 8, 32768, 128), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        out = F.flash_attention(
            q, k, v, mask=F.Band(512), block_q=blocks[0],
            block_k=blocks[1])
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert device_obs.pallas_kernels(hlo) == kernels
