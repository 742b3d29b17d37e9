"""The chunked selective scan (Mamba-2's SSD, ``ops/ssd.py``) and its
mixer compiled for a v5e that is described, not attached (the TPU
compiler is installed here): what a CPU run cannot see. The scan's
gradient at the cell's shape inside the memory the cell's step leaves
it, its segments a loop; and the mixer at the cell's widths with every
operation under a ``mamba/`` scope and, since PR 65, exactly two Mosaic
kernels in it: the gated norm's pair under ``mamba/out_norm``.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp

from tests.kernel_common import chip, mosaic_kernels, topology  # noqa: F401


def test_the_scan_compiles_at_the_cell_s_shape(chip):
    """``ssd_scan``'s gradient at 8,192 tokens, 64 heads of 64 over a
    state of 128, one group, chunks of 256 in segments of 8: the
    segments are a loop (forward and backward), no kernel is in the
    program (``scan_impl`` says ``xla``), and the temporaries stay under
    1.5 GiB as the compiler counts them: a segment's decay masks (134 MB
    in float32) and their cotangents, never the layer's 537 MB."""
    from elasticdl_tpu.ops import ssd

    assert ssd.scan_impl(jnp.bfloat16, 64, 128, 256) == "xla"
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)
    args = (
        struct((1, 8192, 64, 64), jnp.bfloat16),
        struct((1, 8192, 64), jnp.float32),
        struct((1, 8192, 64), jnp.float32),
        struct((1, 8192, 1, 128), jnp.bfloat16),
        struct((1, 8192, 1, 128), jnp.bfloat16),
        struct((64,), jnp.float32))
    compiled = jax.jit(jax.grad(
        lambda *a: ssd.ssd_scan(*a, chunk=256, segment=8).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4, 5))
    ).lower(*args).compile()
    hlo = compiled.as_text()
    assert not mosaic_kernels(hlo)
    assert hlo.count(" while(") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2**30


def test_the_mixer_s_operations_lie_under_its_scopes(chip, monkeypatch):
    """``Mamba2Mixer`` at the cell's widths over one segment of tokens,
    with the backend a TPU: its matmuls, the convolution, the gates,
    the scan and the gated norm each under its ``mamba/`` scope,
    forward and backward; the scan stays XLA's lines, the gated norm is
    ``gated_norm_fwd`` and ``gated_norm_bwd`` (``ops/gated_norm.py``,
    PR 65), both under ``mamba/out_norm``, the backward's inside the
    VJP, neither named as ``benchmark/lib/ssm_trace.py`` names the
    scan's."""
    from elasticdl_tpu.models.transformer import Mamba2Dims, Mamba2Mixer
    from elasticdl_tpu.observability import device as device_obs
    from elasticdl_tpu.ops import gated_norm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gated_norm.gated_norm_impl(
        jnp.bfloat16, 4096, 1, 2048, rows=256) == "pallas"
    layer = Mamba2Mixer(Mamba2Dims(64, 64, 128, 1, 4), norm_eps=1e-5)
    x = jax.ShapeDtypeStruct((1, 2048, 2048), jnp.bfloat16, sharding=chip)
    variables = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))
    assert variables["params"]["in_proj"]["kernel"].shape == (2048, 8512)
    assert variables["params"]["conv_kernel"].shape == (4, 4352)
    placed = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, jnp.bfloat16, sharding=chip), variables)
    hlo = jax.jit(jax.grad(
        lambda v, x: layer.apply(v, x)[0].astype(jnp.float32).sum())
    ).lower(placed, x).compile().as_text()
    assert device_obs.pallas_kernels(hlo) == {
        "gated_norm_fwd": 1, "gated_norm_bwd": 1}
    forward, backward = sorted(mosaic_kernels(hlo), key=len)
    assert "mamba/out_norm" in forward and "transpose(" not in forward
    assert "mamba/out_norm" in backward and "transpose(" in backward
    assert not any(name.startswith("ssd") for name in (
        "gated_norm_fwd", "gated_norm_bwd"))
    scopes = set(re.findall(r"mamba/(\w+)", hlo))
    assert scopes == {
        "in_proj", "conv", "gates", "scan", "out_norm", "out_proj"}
    # both passes: the backward's operations carry the scope too
    assert re.search(r"transpose\(jvp\([^\"]*mamba/scan", hlo)
