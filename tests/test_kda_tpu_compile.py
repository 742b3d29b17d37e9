"""The delta rule with a decay a channel (Kimi Delta Attention) and its
mixer compiled for a v5e that is described, not attached (the TPU
compiler is installed here): what a CPU run cannot see. The rule's
gradient at the cell's shape, four Mosaic kernels a segment (the
operands' pair ``kda_prepare_*``, ISSUE 59, and the scan's pair with
the state transposed), inside the memory the cell's step leaves it; and
the mixer at the cell's widths, whose convolution is the kernel pair
under the mixer's own scope and whose gated norm is ``gated_norm_*``
under ``kda/out_norm`` (PR 65).

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import jax
import jax.numpy as jnp

from elasticdl_tpu.observability import device as device_obs
from tests.kernel_common import chip, topology  # noqa: F401 (fixtures)


def test_the_vector_rule_compiles_at_the_cell_s_shape(chip, monkeypatch):
    """``gated_delta_rule``'s gradient at 32,768 tokens, 32 heads of
    128, chunk 64, segments of 64 chunks, a decay a channel, with the
    backend a TPU: the operands' chooser says ``pallas`` by the
    operand's rank and the kernels' own account of a block, the program
    holds exactly ``kda_prepare_fwd``, ``kda_prepare_bwd``,
    ``kda_scan_fwd`` and ``kda_scan_bwd`` (Mosaic takes both new bodies:
    the rolls over the sublanes, the sums over the lanes, the loop over
    a block's chunks), the segments are loops, and the temporaries stay
    under 2.6 GiB as the compiler counts them (2.43 GiB read; 4.44 GB
    with the operands XLA's lines: no (B, H, N, 64, 64) float32 array
    and no group of decays is left in HBM)."""
    from elasticdl_tpu.ops import gated_delta

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gated_delta.scan_impl(jnp.bfloat16, 64, 128, 128) == "pallas"
    assert gated_delta.prepare_impl(
        jnp.bfloat16, 64, 128, 128, 1, 64,
        decay_rank=gated_delta.VECTOR_DECAY) == "pallas"
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)
    wide = (1, 32, 32768, 128)
    args = (struct(wide, jnp.bfloat16),) * 3 + (
        struct(wide, jnp.float32), struct(wide[:3], jnp.float32))
    compiled = jax.jit(jax.grad(
        lambda *a: gated_delta.gated_delta_rule(*a, segment=64).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))
    ).lower(*args).compile()
    hlo = compiled.as_text()
    assert set(device_obs.pallas_kernels(hlo)) == {
        "kda_prepare_fwd", "kda_prepare_bwd", "kda_scan_fwd", "kda_scan_bwd"}
    assert hlo.count(" while(") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6 * 2**30


def test_the_mixer_s_convolution_is_the_kernel_pair(chip, monkeypatch):
    """``KimiDeltaAttention`` at the cell's widths over one segment of
    tokens: ``conv_impl`` says ``pallas`` (32 / 32 heads of 128 are
    whole 128-lane rows), both its kernels sit under ``kda/conv``, the
    rule's four (the operands' pair and the scan's) under ``kda/scan``,
    the gated norm's two (``ops/gated_norm.py``, PR 65) under
    ``kda/out_norm``, the backward's inside the VJP, and none under the
    Gated DeltaNet's scope: eight kernels. Neither of the norm's is
    named as ``benchmark/lib/kda_trace.py`` names the scan's."""
    from elasticdl_tpu.models.transformer import KdaDims, KimiDeltaAttention
    from tests.kernel_common import mosaic_kernels

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = KimiDeltaAttention(KdaDims(32, 128, 4, 128, segment=64))
    x = jax.ShapeDtypeStruct((1, 4096, 2304), jnp.bfloat16, sharding=chip)
    variables = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))
    placed = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, jnp.bfloat16, sharding=chip), variables)
    hlo = jax.jit(jax.grad(
        lambda v, x: layer.apply(v, x)[0].astype(jnp.float32).sum())
    ).lower(placed, x).compile().as_text()
    kernels = mosaic_kernels(hlo)
    assert sum("kda/conv" in k for k in kernels) == 2
    under_norm = sorted((k for k in kernels if "kda/out_norm" in k), key=len)
    assert [k.split("/")[-2] for k in under_norm] == [
        "gated_norm_fwd", "gated_norm_bwd"]
    assert "transpose(" in under_norm[1] and "transpose(" not in under_norm[0]
    assert all("kda/conv" in k or "kda/scan" in k or k in under_norm
               for k in kernels)
    assert not any("gdn/" in k for k in kernels)
    names = device_obs.pallas_kernels(hlo)
    assert set(names) == {
        "qkv_conv_fwd", "qkv_conv_bwd", "kda_prepare_fwd", "kda_prepare_bwd",
        "kda_scan_fwd", "kda_scan_bwd", "gated_norm_fwd", "gated_norm_bwd"}
    assert names["gated_norm_fwd"] == names["gated_norm_bwd"] == 1
    for word in ("gdn", "kda"):
        assert word not in "gated_norm_fwd gated_norm_bwd"
    under_scan = [k for k in kernels if "kda/scan" in k]
    assert len(under_scan) == len(kernels) - 4 >= 4
