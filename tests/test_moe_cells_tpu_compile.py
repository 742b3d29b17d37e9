"""The sorted MoE layer compiled at two cells' real sizes for a v5e
that is described, not attached (the TPU compiler is installed here):
a layer that holds a share of its experts (``sdar30b-bd-s8k``'s) must
compile with the loop and the branches that let a step run the rows
that carry a pair, and a layer whose experts are spread over ``ep``
(``mellum2-ep4-s8k``'s) with the exchange and the regrouping's loops.
Each shape is its cell's: that THIS layer compiles, with these kernels
and this peak, is the assertion. The grouped matmuls alone are
``tests/test_moe_tpu_compile.py``'s.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp

from elasticdl_tpu.models.moe_transformer import MoeMlp
from elasticdl_tpu.ops import moe as moe_ops
from tests.kernel_common import (  # noqa: F401 (fixtures)
    chip, mosaic_kernels as kernels, topology)

DIM = 2048  # sdar30b's width


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


def test_the_layer_over_ep_compiles_for_the_four_chips(
        topology, monkeypatch):
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
    mesh = build_mesh(MeshConfig(ep=4, devices=list(topology.devices)))
    dim, width = 2304, 896
    layer = MoeMlp(
        64, top_k=8, dispatch_impl="sorted", expert_dim=width,
        expert_act="swiglu", normalize_gates=True, mesh=mesh,
        exchange_rows=131072)
    x = jax.ShapeDtypeStruct(
        (4, 8192, dim), jnp.bfloat16,
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
