"""The gated output norm's two ``gated_norm_*`` kernels compiled for a
v5e that is described, not attached (the TPU compiler is installed
here), at the cells' shapes: what interpret mode cannot see (the chip's
tiling, its VMEM, a block of heads written to lanes ``h D ...``, a
group's channels walked on the sublanes, the gate read at an offset in
an array 8,512 columns wide). The
mixers' own programs, with each kernel under its scope, are
``tests/test_ssd_tpu_compile.py``'s, ``test_kda_tpu_compile.py``'s and
``test_gated_delta_tpu_compile.py``'s.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.ops import gated_norm as G
from tests.kernel_common import chip, topology  # noqa: F401 - fixtures


@pytest.mark.parametrize(
    "form,x,z,scale,lanes,z_offset,rows,block", [
        ("silu_norm", (1, 32, 4096, 256), (1, 8512, 8192), 4096, 4096, 0,
         256, (256, 1)),
        ("silu_norm", (1, 32, 4096, 256), (1, 10304, 8192), 4096, 512, 0,
         256, (256, 8)),
        ("norm_silu", (8, 1, 32, 4096, 128), (1, 32768, 12288), 128, 128,
         8192, None, (1024, 4)),
        ("norm_sigmoid", (1, 2, 6, 384, 256), (2, 384, 1536), 256, 256, 0,
         None, (128, 2)),
    ], ids=["granite4h-micro-s8k", "nemotron3-nano-s8k", "qwen3next80b-s32k",
            "sigmoid-256-wide-heads-at-the-smallest-tile"])
def test_both_kernels_compile(
        chip, form, x, z, scale, lanes, z_offset, rows, block):  # noqa: F811
    """Each is ONE Mosaic kernel with nothing of XLA's beside it but
    the scale's widening, and keeps no temporary: by columns (the two
    Mamba-2 cells: the scan's chunks of 256 rows with the rows in the
    lanes, the gate's rows in a projection 8,512 and 10,304 columns
    wide, a group's 4,096 or 512 channels walked twice) and by heads
    (Qwen3-Next's 32 heads read by the rule's 8 segments)."""
    on = lambda shape, kind=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, kind, sharding=chip)
    columns = rows is not None
    seq = z[2] if columns else z[1]
    inner = x[2] if columns else x[2] * x[4]
    assert G._block(
        lanes, inner // lanes, seq, 2, z_offset, rows, x[0]) == block
    static = (form, lanes, 1e-6, z_offset, columns)
    result = (z[0], inner, seq) if columns else (z[0], seq, inner)
    fwd = G.gated_norm_fwd.lower(
        on(x), on(z), on((scale,)), *static).compile()
    assert fwd.as_text().count("tpu_custom_call") == 1
    assert fwd.memory_analysis().temp_size_in_bytes < 2**20
    bwd = G.gated_norm_bwd.lower(
        on(x), on(z), on((scale,)), on(result), *static).compile()
    hlo = bwd.as_text()
    assert hlo.count("tpu_custom_call") == 1
    partial = (z[0], inner, 128) if columns else (
        z[0], seq // block[0], 8, inner)
    assert "f32[%s]" % ",".join(map(str, partial)) in hlo
