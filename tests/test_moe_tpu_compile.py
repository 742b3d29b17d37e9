"""The sorted MoE path compiled at OLMoE's and at Moonlight's published
widths for a v5e that is described, not attached (the TPU compiler is
installed here): what the CPU's interpret-free tests cannot see. Both
grouped matmuls must be accepted by the chip's compiler (the tiles
``ops/moe.py:gmm_tiles`` chose inside its VMEM, no unaligned slice), a
tile its byte count calls too large must be refused by the compiler
too, the Pallas one must be what a TPU backend gets, and the compiled
expert layer must hold no capacity and no one-hot. And a layer that
holds a share of its experts (``sdar30b-bd-s8k``'s, at its real size)
must compile with the loop and the branches that let a step run the
rows that carry a pair.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.models.moe_transformer import MoeMlp
from elasticdl_tpu.ops import moe as moe_ops

# OLMoE-1B-7B's expert layer; a quarter of the cell's 32,768 tokens
TOKENS, DIM, WIDTH, EXPERTS, TOP_K = 8192, 2048, 1024, 64, 8
# (expert width, top-k): OLMoE-1B-7B's and Moonlight-16B-A3B's, whose
# 1408 = 11 x 128 no tile of 1024 divides
LAYERS = {"olmoe": (WIDTH, TOP_K), "moonlight": (1408, 6)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


def kernels(hlo):
    """op_name of every Mosaic kernel in the program."""
    return [
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in hlo.splitlines() if "tpu_custom_call" in line
    ]


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


def test_a_held_layer_compiles_with_its_loop_and_its_prefixes(
        chip, monkeypatch):
    """``sdar30b-bd-s8k``'s expert layer at its real size (16,384
    positions, 128 experts top-8, 16 held of width 768, a buffer of
    49,152 rows): the chip's compiler takes the dispatch's gather as a
    loop with a trip count the step decides, the two scatter-adds as
    branches over the buffer's eighths and quarters, and the same nine
    kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = MoeMlp(
        128, top_k=8, dispatch_impl="sorted", expert_dim=768,
        expert_act="swiglu", normalize_gates=True, held_experts=(0, 16),
        held_rows=49152)
    x = jax.ShapeDtypeStruct((1, 16384, DIM), jnp.bfloat16, sharding=chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))["params"]
    assert params["w_gate"].shape == (16, DIM, 768)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=chip),
        params)

    def loss(params, x):
        y, aux = layer.apply({"params": params}, x)
        return (y.astype(jnp.float32) ** 2).mean() + aux["load_balancing"]

    step = jax.jit(jax.grad(loss, argnums=(0, 1)))
    compiled = step.lower(params, x).compile()
    hlo = compiled.as_text()
    names = kernels(hlo)
    assert len(names) == 9, names
    assert all("moe/experts" in n and "gmm" in n for n in names), names
    # the combine's scatter-add and the dispatch's transpose: a branch
    # for each eighth, and for each quarter, of the buffer
    branches = {
        re.search(r"moe/(dispatch|combine)", line).group(0): len(
            re.search(r"branch_computations=\{([^}]*)\}", line).group(1)
            .split(","))
        for line in hlo.splitlines()
        if " conditional(" in line and "branch_computations" in line}
    assert branches == {
        "moe/combine": moe_ops.HELD_PREFIXES,
        "moe/dispatch": moe_ops.HELD_BACKWARD_PREFIXES}, branches
    # and the gather's loop under the dispatch's scope
    assert any(
        " while(" in line and "moe/dispatch" in line
        for line in hlo.splitlines())


def test_the_layer_over_ep_compiles_for_the_four_chips(topo, monkeypatch):
    """``mellum2-ep4-s8k``'s expert layer at its real size (4 x 8,192
    tokens, 64 experts of 896 top-8 over ``ep=4``, a receive buffer of
    131,072 rows) for the four described chips: the chip's compiler
    takes the exchange as ``ragged-all-to-all`` (the dispatch's and the
    combine's, and their transposes, under the ``moe/exchange`` scope),
    the grouped matmuls inside the manual region are the same nine
    Pallas kernels, at tiles of 2304 and 896's own, and the regrouping
    is loops of gathers over chunks of the buffer whose peak memory is
    no higher than one gather's over the whole of it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticdl_tpu.parallel.mesh import DATA_AXES, MeshConfig, build_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(ep=4, devices=list(topo.devices)))
    dim, width = 2304, 896
    layer = MoeMlp(
        64, top_k=8, dispatch_impl="sorted", expert_dim=width,
        expert_act="swiglu", normalize_gates=True, mesh=mesh,
        exchange_rows=131072)
    x = jax.ShapeDtypeStruct(
        (4, TOKENS, dim), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(DATA_AXES)))
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))["params"]
    assert params["w_gate"].shape == (64, dim, width)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16, sharding=NamedSharding(
                mesh, P("ep") if a.ndim == 3 else P())),
        params)

    def loss(params, x):
        y, aux = layer.apply({"params": params}, x)
        return (y.astype(jnp.float32) ** 2).mean() + aux["load_balancing"]

    step = jax.jit(jax.grad(loss, argnums=(0, 1)))
    compiled = step.lower(params, x).compile()
    hlo = compiled.as_text()
    names = kernels(hlo)
    assert len(names) == 9, names
    assert all("moe/experts" in n and "gmm" in n for n in names), names
    assert "ragged-dot" not in hlo
    exchanges = [
        line for line in hlo.splitlines() if " ragged-all-to-all(" in line]
    assert len(exchanges) == 4, len(exchanges)
    assert all("moe/exchange" in line for line in exchanges)
    assert sum("transpose(" in line for line in exchanges) == 2
    # a rank's rows and what it can receive: no array over all the
    # ranks' 262,144 pairs
    assert "[131072,%d]" % dim in hlo and "[262144," not in hlo
    assert moe_ops.projection_tiles(131072, dim, width, jnp.bfloat16) == {
        "fwd": (512, 1152, 896), "d_rows": (512, 896, 1152),
        "d_weights": (512, 1152, 896)}
    # the regrouping (two permutes and their transposes) is four loops
    # over chunks of 4,096 rows, each body a gather, and no gather runs
    # the receive buffer whole
    loops = [line for line in hlo.splitlines()
             if " while(" in line and "_gather_carried" in line]
    assert len(loops) == 4, len(loops)
    assert sum("moe/dispatch" in line for line in loops) == 2
    assert sum("moe/combine" in line for line in loops) == 2
    assert "bf16[4096,%d]" % dim in hlo
    whole = re.compile(r"= bf16\[131072,%d\]\S* gather\(" % dim)
    assert not whole.search(hlo)

    # ... and the loops update their buffers in place: the compiler's
    # peak is no higher than under one ``take`` over the whole buffer
    # (the form before PR 46)
    @jax.custom_vjp
    def take_whole(rows, index, inverse, carried):
        return jnp.take(rows, index, axis=0)

    take_whole.defvjp(
        lambda rows, index, inverse, carried: (
            jnp.take(rows, index, axis=0), inverse),
        lambda inverse, d_rows: (
            jnp.take(d_rows, inverse, axis=0), None, None, None))
    monkeypatch.setattr(moe_ops, "permute_rows", take_whole)
    before = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    assert whole.search(before.as_text())
    assert (compiled.memory_analysis().peak_memory_in_bytes
            <= before.memory_analysis().peak_memory_in_bytes)


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
