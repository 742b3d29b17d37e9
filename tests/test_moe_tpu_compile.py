"""The sorted MoE path compiled at OLMoE's and at Moonlight's published
widths for a v5e that is described, not attached (the TPU compiler is
installed here): what the CPU's interpret-free tests cannot see. Both
grouped matmuls must be accepted by the chip's compiler (the tiles
``ops/moe.py:gmm_tiles`` chose inside its VMEM, no unaligned slice), a
tile its byte count calls too large must be refused by the compiler
too, the Pallas one must be what a TPU backend gets, and the compiled
expert layer must hold no capacity and no one-hot. The layers that
hold a share of their experts or spread them over ``ep``, at their
cells' real sizes, are ``tests/test_moe_cells_tpu_compile.py``'s.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.models.moe_transformer import MoeMlp
from elasticdl_tpu.ops import moe as moe_ops
from tests.kernel_common import (  # noqa: F401 (fixtures)
    chip, mosaic_kernels as kernels, topology)

# OLMoE-1B-7B's expert layer; a quarter of the cell's 32,768 tokens
TOKENS, DIM, WIDTH, EXPERTS, TOP_K = 8192, 2048, 1024, 64, 8
# (expert width, top-k): OLMoE-1B-7B's and Moonlight-16B-A3B's, whose
# 1408 = 11 x 128 no tile of 1024 divides
LAYERS = {"olmoe": (WIDTH, TOP_K), "moonlight": (1408, 6)}


def compile_layer(chip, width=WIDTH, top_k=TOP_K):
    """HLO text of the expert layer's loss and gradients."""
    layer = MoeMlp(
        EXPERTS, top_k=top_k, dispatch_impl="sorted", expert_dim=width,
        expert_act="swiglu", normalize_gates=False)
    x = jax.ShapeDtypeStruct((2, TOKENS // 2, DIM), jnp.bfloat16,
                             sharding=chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))["params"]
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=chip),
        params)

    def loss(params, x):
        y, aux = layer.apply({"params": params}, x)
        return (y.astype(jnp.float32) ** 2).mean() + aux["load_balancing"]

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    return compiled.as_text()


def assert_no_capacity(hlo):
    """No array over (experts, more than the router's own columns) with
    a token axis: shapes like [.., 64, C] beside 4096 or 8192."""
    for shape in set(re.findall(r"\[([\d,]+)\]", hlo)):
        dims = [int(d) for d in shape.split(",")]
        over_tokens = TOKENS in dims or TOKENS // 2 in dims
        if EXPERTS in dims and over_tokens:
            assert len(dims) <= 3 and max(
                d for d in dims if d not in (TOKENS, TOKENS // 2)
            ) == EXPERTS, shape


@pytest.mark.parametrize("model", sorted(LAYERS))
def test_pallas_grouped_matmul_is_what_a_tpu_backend_compiles(
        chip, monkeypatch, model):
    # here the default backend is the CPU: steer the one question the
    # code asks (the guide: "it does so in the test")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    width, top_k = LAYERS[model]
    hlo = compile_layer(chip, width, top_k)
    names = kernels(hlo)
    # gate, up, down: forward, the rows' gradient, the kernels' gradient
    assert len(names) == 9, names
    assert all("moe/experts" in n and "gmm" in n for n in names), names
    assert sum("transpose(" in n for n in names) == 6
    assert sum("jit(tgmm)" in n for n in names) == 3
    assert "ragged-dot" not in hlo
    # ``tgmm`` is handed its lhs swapped and swaps it back: the pair
    # cancels, no row-sized array is transposed ahead of the kernel
    rows = TOKENS * top_k
    assert "[%d,%d]" % (DIM, rows) not in hlo
    assert "[%d,%d]" % (width, rows) not in hlo
    if model == "olmoe":
        assert_no_capacity(hlo)


# Nemotron-3-Nano's two projections (rows, k, n): d 2688 = 21 x 128 and
# the experts' 1856 = 14.5 x 128, the first width that is no multiple of
# the lane width; 8 held experts over a buffer of 8,192 rows
NEMOTRON = {"up": (8192, 2688, 1856), "down": (8192, 1856, 2688)}


@pytest.mark.parametrize("projection", sorted(NEMOTRON))
def test_the_three_calls_compile_at_2688_by_1856(chip, projection):
    """The backend's ``gmm`` / ``tgmm`` take an N or a K of 1856 as it
    stands: forward, the rows' gradient and the weights' gradient of a
    projection compile for the described v5e at the tiles the rule
    chose, which cover 1856 by three tiles of 640 (1920: 96.7% needed
    work) and 2688 by three of 896 exactly."""
    rows, k, n = NEMOTRON[projection]
    tiles = moe_ops.projection_tiles(rows, k, n, jnp.bfloat16)
    covered = lambda dim, tile: -(-dim // tile) * tile
    for call, (_, tk, tn) in tiles.items():
        inner, outer = (n, k) if call == "d_rows" else (k, n)
        assert {covered(inner, tk), covered(outer, tn)} == {2688, 1920}, call
    assert moe_ops.projection_fill(k, n, tiles) == pytest.approx(
        1856 / 1920)
    shape = lambda *dims: jax.ShapeDtypeStruct(
        dims, jnp.bfloat16, sharding=chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=chip)

    def loss(x, w, sizes):
        out = moe_ops.pallas_grouped_matmul(x, w, sizes)
        return (out.astype(jnp.float32) ** 2).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        shape(rows, k), shape(8, k, n), sizes).compile().as_text()
    names = kernels(hlo)
    assert len(names) == 3 and sum("tgmm" in n for n in names) == 1, names
    # the weights are stored at 1856, not padded to the tiles' 1920
    assert "1920" not in "".join(re.findall(r"bf16\[[\d,]+\]", hlo))


def test_a_tile_the_byte_count_refuses_the_compiler_refuses_too(chip):
    """(512, 1024, 1408) for the gate's weight gradient pads nothing
    and is what the rule would take if it fitted: 16.8 MiB by
    ``gmm_vmem_bytes``, "Scoped allocation with size 16.79M and limit
    16.00M" by the compiler. The tile the rule takes in its place
    compiles."""
    rows, k, n = 98304, DIM, 1408
    shape = lambda *dims: jax.ShapeDtypeStruct(
        dims, jnp.bfloat16, sharding=chip)
    sizes = jax.ShapeDtypeStruct((EXPERTS,), jnp.int32, sharding=chip)
    backend = moe_ops._gmm_backend()

    def compile_at(tiles):
        jax.jit(lambda x, dy, sizes: backend.tgmm(
            x.swapaxes(0, 1), dy, sizes, jnp.bfloat16, tiles,
            num_actual_groups=EXPERTS,
        )).lower(shape(rows, k), shape(rows, n), sizes).compile()

    too_large = (512, 1024, 1408)
    assert moe_ops.gmm_vmem_bytes(
        "tgmm", too_large, jnp.bfloat16) > moe_ops.GMM_VMEM_BYTES
    with pytest.raises(Exception, match="vmem"):
        compile_at(too_large)
    chosen = moe_ops.gmm_tiles(rows, k, n, jnp.bfloat16, "tgmm")
    assert chosen != too_large and moe_ops.gmm_fill(k, n, chosen) == 1.0
    compile_at(chosen)


def test_ragged_dot_is_the_other_path_and_compiles_too(chip):
    hlo = compile_layer(chip)
    names = [n for n in kernels(hlo) if "metadata" not in n]
    # XLA's own kernels keep no scope in op_name (lib/moe_trace.py
    # charges them to the expert layer by their name)
    assert len(names) == 9 and all(
        n.startswith("ragged-dot") for n in names), names
    assert_no_capacity(hlo)


def test_the_pallas_kernel_is_not_chosen_where_it_cannot_run(monkeypatch):
    resolve = moe_ops.resolve_grouped_matmul
    assert resolve(1024, jnp.bfloat16) == "ragged_dot"  # a CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve(1024, jnp.bfloat16) == "pallas_gmm"
    # a mesh of several devices, float32 rows, a ragged row tile
    assert resolve(1024, jnp.bfloat16, one_device=False) == "ragged_dot"
    assert resolve(1024, jnp.float32) == "ragged_dot"
    assert resolve(1000, jnp.bfloat16) == "ragged_dot"
