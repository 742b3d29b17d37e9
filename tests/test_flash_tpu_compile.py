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

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.ops import flash_attention as F


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


def _square_float32_dots(hlo, size=64):
    """The dots of a compiled program whose operands and result are all
    float32 [..., size, size]: the product form's and its VJP's."""
    types = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", hlo))
    square = re.compile(r"f32\[(?:\d+,)*%d,%d\]$" % (size, size))
    found = []
    for out, operands in re.findall(
            r"= (\w+\[[\d,]*\])\S* convolution\(([^)]*)\)", hlo):
        names = re.findall(r"%([\w.\-]+)", operands)
        if all(square.match(t) for t in
               [out] + [types.get(n, "") for n in names]):
            found.append(out)
    return found


@pytest.mark.parametrize("impl", ["xla", "pallas", "inverse"])
def test_the_chunked_rule_compiles_at_the_cell_s_shape(
        chip, monkeypatch, impl):
    """``gated_delta_rule``'s gradient at 32,768 tokens, 16 key and 32
    value heads of 128, chunk 64, for a described v5e: the segments'
    ``jax.checkpoint`` keeps its temporaries under 2.5 GB (4 GB and a
    refused step without it, PERF.md Section 6). With the backend a TPU
    (``pallas``: what the chip gets, ISSUE 32, 34 and 39) a segment is
    four kernels, the chunks' operands with the inverses in them and the
    chunk-to-chunk scan, every ``tpu_custom_call`` named, no float32 64 x
    64 dot, no 64 x 64 float32 array of the chunks' and no loop over a
    segment's chunks is left outside them, and the VMEM they ask for is
    under the limits they state. ``inverse``: the same with the
    operands' chooser held to XLA's lines, PR 34's program, in which
    the inverses are kernels of their own (their operands row-major, 64
    lanes padded to 128, where XLA kept some of its own matrices with
    the chunks on the lanes: the rule alone reads 2.67 GiB for 2.45)."""
    from elasticdl_tpu.ops import gated_delta

    if impl != "xla":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if impl == "inverse":
        monkeypatch.setattr(
            gated_delta, "prepare_impl", lambda *a, **kw: "xla")
    chosen = "xla" if impl == "xla" else "pallas"
    assert gated_delta.inverse_impl(jnp.float32, 64) == chosen
    assert gated_delta.scan_impl(jnp.bfloat16, 64, 128, 128) == chosen
    assert gated_delta.prepare_impl(
        jnp.bfloat16, 64, 128, 128, 2, 128) == (
            "pallas" if impl == "pallas" else "xla")
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
    hlo = compiled.as_text()
    assert "while" in hlo
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    if impl == "xla":
        assert temporaries < 2.5 * 2**30
        assert "tpu_custom_call" not in hlo
        # the step's forward, the segment's again, and the VJP's two
        assert len(_square_float32_dots(hlo)) == 22
        return
    # the forward in the step and in the segment's recompute; a kernel
    # inside a loop body counts once
    if impl == "pallas":
        # four padded float32 matrix arrays a segment fewer, ``T`` at
        # half its padded size
        assert temporaries < 2.3 * 2**30
        assert device_obs.pallas_kernels(hlo) == {
            "gdn_prepare_fwd": 2, "gdn_prepare_bwd": 1,
            "gdn_scan_fwd": 2, "gdn_scan_bwd": 1}
        assert not re.search(r"f32\[[\d,]*,64,64\]", hlo)
    else:
        assert temporaries < 2.7 * 2**30
        assert device_obs.pallas_kernels(hlo) == {
            "gdn_inverse_fwd": 2, "gdn_inverse_bwd": 1,
            "gdn_scan_fwd": 2, "gdn_scan_bwd": 1}
    assert hlo.count("tpu_custom_call") == 6
    assert not _square_float32_dots(hlo)
    # the chunk-to-chunk recurrence is inside the scan's kernels (ISSUE
    # 34): the two loops left are the forward's and the backward's over
    # the four segments, none over a segment's 128 chunks
    assert hlo.count(" while(") == 2
    # the compiler held each kernel to the limit it states (it refuses
    # a body that needs more), and the blocks of a segment's 4,096
    # matrices and of a grid step's heads and chunks, operands and
    # results double-buffered, count under it
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    limits = {"gdn_scan": gated_delta._SCAN_VMEM_LIMIT,
              "gdn_prepare": gated_delta._PREPARE_VMEM_LIMIT,
              "gdn_inverse": gated_delta._INVERSE_VMEM_LIMIT}
    for line in calls:
        # the call's own name; its source locations name its callers
        name, = device_obs.pallas_kernels(line)
        assert '"size":"%d"' % limits[name[:name.rindex("_")]] in line
    for arrays in (2, 3):
        block = gated_delta.inverse_block(4096, 64, arrays)
        assert gated_delta.inverse_vmem_bytes(
            block, 64, arrays) < gated_delta._INVERSE_VMEM_LIMIT
    for kind in ("fwd", "fwd_residuals", "bwd"):
        block, step = gated_delta.scan_block(32, 128, 64, 128, 128, 2, kind)
        assert gated_delta.scan_vmem_bytes(
            block, step, 64, 128, 128, 2, kind) < gated_delta._SCAN_VMEM_LIMIT
        step = gated_delta.prepare_block(2, 128, 64, 128, 128, 2)
        assert gated_delta.prepare_vmem_bytes(
            2, step, 64, 128, 128, 2, kind) < gated_delta._PREPARE_VMEM_LIMIT


@pytest.mark.parametrize("chunk,rep,heads,chunks,dtype", [
    (64, 2, 16, 128, "bfloat16"),   # the cell's segment
    (128, 2, 16, 64, "bfloat16"),
    (64, 1, 4, 16, "float32"),
    (128, 1, 2, 3, "float32"),      # a block of all the chunks, no tile
], ids=lambda v: str(v))
def test_the_operands_kernels_compile(chip, chunk, rep, heads, chunks, dtype):
    """``gdn_prepare_fwd`` (with and without ``T``) and
    ``gdn_prepare_bwd`` alone for a described v5e: the lane-row
    concatenations, the masked sums over lanes and rows, the one-row
    loads of ``g`` and the transposed products are what the interpreter
    never refuses."""
    from elasticdl_tpu.ops import gated_delta

    dtype = jnp.dtype(dtype)
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)
    args = (
        struct((1, heads, 1, chunks, chunk, 128), dtype),
        struct((1, heads, 1, chunks, chunk, 128), dtype),
        struct((1, heads, rep, chunks, chunk, 128), dtype),
        struct((1, heads, rep, chunks, chunk), jnp.float32),
        struct((1, heads, rep, chunks, chunk), jnp.float32),
    )
    for residuals in (False, True):
        hlo = jax.jit(functools.partial(
            gated_delta.gdn_prepare_fwd, residuals=residuals)).lower(
                *args).compile().as_text()
        assert device_obs.pallas_kernels(hlo) == {"gdn_prepare_fwd": 1}
    outs = jax.eval_shape(functools.partial(
        gated_delta.gdn_prepare_fwd, residuals=True), *args)
    *operands, u, inverse = [struct(o.shape, o.dtype) for o in outs]
    hlo = jax.jit(gated_delta.gdn_prepare_bwd).lower(
        *args, inverse, *operands, struct(u.shape, dtype)
    ).compile().as_text()
    assert device_obs.pallas_kernels(hlo) == {"gdn_prepare_bwd": 1}


def test_the_chunked_rule_stays_partitionable_over_a_mesh(
        topology, monkeypatch):
    """A ``pallas_call`` has no GSPMD partitioning rule, and the rule
    opens no ``shard_map``: with its inputs sharded by the batch over
    the four chips of a described v5e:2x2 (data parallel or FSDP) the
    inverses stay XLA's product form, and the gradient compiles with
    nothing gathered. Told of no mesh the same call takes the kernels,
    which jax refuses to partition. The model's layer hands the rule
    its mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.models.transformer import (
        GatedDeltaDims,
        make_attention,
    )
    from elasticdl_tpu.ops import gated_delta

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topology.devices), ("data",))
    assert gated_delta.inverse_impl(jnp.float32, 64, mesh) == "xla"
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P("data")))
    args = (
        struct((4, 2, 1024, 128), jnp.bfloat16),
        struct((4, 2, 1024, 128), jnp.bfloat16),
        struct((4, 4, 1024, 128), jnp.bfloat16),
        struct((4, 4, 1024), jnp.float32),
        struct((4, 4, 1024), jnp.float32),
    )
    grad = lambda mesh: jax.jit(jax.grad(
        lambda *a: gated_delta.gated_delta_rule(*a, mesh=mesh).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))
    hlo = grad(mesh).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in hlo
    assert "all-gather" not in hlo and "all-to-all" not in hlo
    with pytest.raises(NotImplementedError, match="shard_map"):
        grad(None).lower(*args)

    layer = make_attention(4, linear=GatedDeltaDims(
        num_key_heads=2, num_value_heads=4, key_head_dim=128,
        value_head_dim=128, conv_kernel_dim=4), norm_eps=1e-6, mesh=mesh)
    x = struct((4, 1024, 256), jnp.bfloat16)
    variables = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    hlo = jax.jit(jax.grad(
        lambda v, x: layer.apply(v, x).astype(jnp.float32).sum())).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=NamedSharding(mesh, P())),
                variables), x).compile().as_text()
    assert "tpu_custom_call" not in hlo
