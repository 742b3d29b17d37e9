"""The gated delta rule (``ops/gated_delta.py``) compiled for a v5e
that is described, not attached (the TPU compiler is installed here):
what interpret mode cannot see. The rule at the cell's shape by each of
its paths and the rule sharded over the four chips of a described
v5e:2x2 (the operands' kernels alone are
``tests/test_gated_delta_operands_tpu_compile.py``'s). Every shape here is one the
assertion is about (the cell's segment, a block the kernels' budget
chooses, the mesh's shards): that THIS shape compiles inside the VMEM
the kernels state. The flash kernels' are
``tests/test_flash_tpu_compile.py``'s.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.observability import device as device_obs
from tests.kernel_common import chip, topology  # noqa: F401 (fixtures)


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


@pytest.mark.parametrize("impl", ["xla", "pallas", "mixed"])
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
    under the limits they state. ``mixed``: the operands' chooser held
    to XLA's lines (what a segment whose chunks fit no block gets): the
    scan's kernels alone, the inverses the product form by XLA, no
    kernel of their own."""
    from elasticdl_tpu.ops import gated_delta

    if impl != "xla":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if impl == "mixed":
        monkeypatch.setattr(
            gated_delta, "prepare_impl", lambda *a, **kw: "xla")
    chosen = "xla" if impl == "xla" else "pallas"
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
        assert hlo.count("tpu_custom_call") == 6
        assert not _square_float32_dots(hlo)
    else:
        assert temporaries < 2.7 * 2**30
        assert device_obs.pallas_kernels(hlo) == {
            "gdn_scan_fwd": 2, "gdn_scan_bwd": 1}
        assert hlo.count("tpu_custom_call") == 3
        assert "gdn_inverse" not in hlo and "gdn_prepare" not in hlo
        # the inverses are XLA's products, as on the CPU
        assert len(_square_float32_dots(hlo)) == 22
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
              "gdn_prepare": gated_delta._PREPARE_VMEM_LIMIT}
    for line in calls:
        # the call's own name; its source locations name its callers
        name, = device_obs.pallas_kernels(line)
        assert '"size":"%d"' % limits[name[:name.rindex("_")]] in line
    for kind in ("fwd", "fwd_residuals", "bwd"):
        block, step = gated_delta.scan_block(32, 128, 64, 128, 128, 2, kind)
        assert gated_delta.scan_vmem_bytes(
            block, step, 64, 128, 128, 2, kind) < gated_delta._SCAN_VMEM_LIMIT
        step = gated_delta.prepare_block(2, 128, 64, 128, 128, 2)
        assert gated_delta.prepare_vmem_bytes(
            2, step, 64, 128, 128, 2, kind) < gated_delta._PREPARE_VMEM_LIMIT


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
    assert not jax_compat.kernels_can_run(mesh)
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
