"""The gated delta rule's operands' kernels (``gdn_prepare_fwd`` /
``gdn_prepare_bwd``, ``ops/gated_delta.py``) alone, compiled for a v5e
that is described, not attached: a block the kernels' budget chooses
compiles inside the VMEM the kernels state. The rule by them at the
cell's shape is ``tests/test_gated_delta_tpu_compile.py``'s, whose file
this was part of until it summed past the rule's 100 s (``ROADMAP.md``
Queue 3 item 12): three Mosaic compiles a case, each another program.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import functools

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.observability import device as device_obs
from tests.kernel_common import chip, topology  # noqa: F401 (fixtures)


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
