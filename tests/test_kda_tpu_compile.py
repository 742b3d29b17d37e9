"""The delta rule with a decay a channel (Kimi Delta Attention) and its
mixer compiled for a v5e that is described, not attached (the TPU
compiler is installed here): what a CPU run cannot see. The rule's
gradient at the cell's shape, whose operands are XLA's lines on a TPU
too and whose chunk-to-chunk recurrence is the scan's kernel pair with
the state transposed, inside the memory the cell's step leaves it; and
the mixer at the cell's widths, whose convolution is the kernel pair
under the mixer's own scope.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import jax
import jax.numpy as jnp

from elasticdl_tpu.observability import device as device_obs
from tests.kernel_common import chip, topology  # noqa: F401 (fixtures)


def test_the_vector_rule_compiles_at_the_cell_s_shape(chip, monkeypatch):
    """``gated_delta_rule``'s gradient at 32,768 tokens, 32 heads of
    128, chunk 64, segments of 64 chunks, a decay a channel, with the
    backend a TPU: the operands' chooser says ``xla`` by the operand's
    rank (the ``gdn_prepare_*`` kernels compute the scalar rule's), the
    recurrence is ``kda_scan_fwd`` / ``kda_scan_bwd`` (Mosaic takes the
    transposed state's products), the segments and the diagonals'
    groups are loops, and the temporaries stay under 4.5 GiB as the
    compiler counts them (4.44 GB; it counts a loop's body more than
    once: the cell's whole step, 9.6 GB of state beside them, compiles
    at a peak of 15.02 GB where the ``lax.scan`` took 15.26)."""
    from elasticdl_tpu.ops import gated_delta

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gated_delta.scan_impl(jnp.bfloat16, 64, 128, 128) == "pallas"
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
        "kda_scan_fwd", "kda_scan_bwd"}
    assert hlo.count(" while(") >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < 4.5 * 2**30


def test_the_mixer_s_convolution_is_the_kernel_pair(chip, monkeypatch):
    """``KimiDeltaAttention`` at the cell's widths over one segment of
    tokens: ``conv_impl`` says ``pallas`` (32 / 32 heads of 128 are
    whole 128-lane rows), both its kernels sit under ``kda/conv``, the
    scan's pair under ``kda/scan`` and none under the Gated DeltaNet's
    scope."""
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
    assert all("kda/conv" in k or "kda/scan" in k for k in kernels)
    assert not any("gdn/" in k for k in kernels)
    assert set(device_obs.pallas_kernels(hlo)) == {
        "qkv_conv_fwd", "qkv_conv_bwd", "kda_scan_fwd", "kda_scan_bwd"}
