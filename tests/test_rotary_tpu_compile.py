"""The rotation's two ``rotary_*`` kernels compiled for a v5e that is
described, not attached (the TPU compiler is installed here), at the
cells' shapes: what interpret mode cannot see (the chip's tiling, its
VMEM, a roll on the lanes). And ``Attention``'s gradient, as a TPU
backend gets it, must hold each kernel under its name and under the
scope ``lib/window_trace.py`` charges, ``<kind>/rotary``, with no pass
of XLA's over q or k beside them: the kernels write over their operands.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import re

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.ops import rotary as R
from tests.kernel_common import chip, topology  # noqa: F401 - fixtures


@pytest.mark.parametrize("dtype,batch,heads,kv_heads,seq,dim,lanes", [
    (jnp.bfloat16, 1, 16, 16, 16384, 128, 128),
    (jnp.bfloat16, 1, 8, 8, 16384, 256, 64),
    (jnp.bfloat16, 1, 48, 8, 32768, 128, 64),
    (jnp.bfloat16, 1, 64, 8, 32768, 128, 128),
    (jnp.bfloat16, 1, 32, 4, 16384, 128, 128),
    (jnp.float32, 1, 16, 2, 4096, 256, 64),
    (jnp.bfloat16, 8, 16, 16, 4096, 128, 128),
    (jnp.bfloat16, 2, 2, 2, 384, 256, 256),
], ids=["ouro2.6b-s16k", "pythia1b-s16k", "laguna-full", "laguna-window",
        "sdar30b-bd-s8k", "qwen3next-float32", "olmoe1b7b-s4k",
        "a-256-wide-head-whole-at-the-smallest-tile"])
def test_both_kernels_compile(
        chip, dtype, batch, heads, kv_heads, seq, dim, lanes):  # noqa: F811
    on = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=chip)
    q, k = on((batch, heads, seq, dim), dtype), on(
        (batch, kv_heads, seq, dim), dtype)
    table = on((seq, R.lane_groups(lanes)), jnp.float32)
    for kernel in (R.rotary_fwd, R.rotary_bwd):
        hlo = kernel.lower((q, k), table, table, lanes).compile().as_text()
        assert "tpu_custom_call" in hlo and "output_to_operand_aliasing" in hlo


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_attention_s_gradient_holds_each_kernel_under_its_scope(
        chip, monkeypatch, remat):  # noqa: F811
    """At ouro2.6b-s16k's heads over 4,096 tokens: the backward once,
    the forward once and, where the block is rematerialised, at most
    twice, each under ``attn_full/rotary``, the backward's inside the
    VJP too; beside them nothing of XLA's under the scope reads or
    writes an array of q's size."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq, heads, dim = 4096, 16, 128
    mixer = T.Attention(
        num_heads=heads, attention_impl="pallas", kind_scope="attn_full")
    x = jax.ShapeDtypeStruct((1, seq, heads * dim), jnp.bfloat16,
                             sharding=chip)
    assert R.rotary_impl(jnp.bfloat16, dim, dim, seq) == "pallas"
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=chip),
        jax.eval_shape(lambda: mixer.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))["params"])

    def apply(params, x):
        return mixer.apply({"params": params}, x)

    if remat:
        apply = jax.checkpoint(apply)

    def loss(params, x):
        return (apply(params, x).astype(jnp.float32) ** 2).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    counts = device_obs.pallas_kernels(hlo)
    assert counts.get("rotary_bwd") == 1
    assert counts.get("rotary_fwd") in ((1, 2) if remat else (1,))
    big = re.compile(r"\[1,%d,%d,%d\]" % (heads, seq, dim))
    for line in hlo.splitlines():
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not op_name:
            continue
        op_name = op_name.group(1)
        if "tpu_custom_call" in line:
            if "rotary_" in op_name:
                assert "attn_full/rotary" in op_name
                assert "transpose(" in op_name or "rotary_bwd" not in op_name
        elif "attn_full/rotary" in op_name and (
                " fusion(" in line or " copy(" in line):
            shape = line.split("=", 1)[1].split("(")[0]
            assert not big.search(shape), line[:300]
