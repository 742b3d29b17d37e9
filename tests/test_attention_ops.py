"""Flash kernel vs XLA oracle; ring/ulysses SP vs full attention.

Kernel runs in Pallas interpret mode on CPU (compiled on real TPU); the
SP schedules run on the 8-virtual-device mesh from conftest.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.attention import xla_attention
from elasticdl_tpu.ops.flash_attention import flash_attention
from elasticdl_tpu.ops.ring_attention import (
    ring_attention,
    ulysses_attention,
)
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh


def _inputs(batch=2, heads=2, seq=256, dim=64, seed=0):
    rng = np.random.RandomState(seed)
    shape = (batch, heads, seq, dim)
    mk = lambda s: jnp.asarray(rng.normal(size=shape, scale=0.5), jnp.float32)
    return mk(0), mk(1), mk(2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_xla(causal):
    q, k, v = _inputs()
    expected = xla_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_xla(causal):
    q, k, v = _inputs(seq=128)

    def loss_ref(q, k, v):
        out = xla_attention(q, k, v, causal=causal)
        return jnp.sum(out * jnp.cos(out))

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
        )
        return jnp.sum(out * jnp.cos(out))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = build_mesh(MeshConfig(dp=2, sp=4))
    q, k, v = _inputs(seq=64, dim=16)
    expected = xla_attention(q, k, v, causal=causal)

    ring = jax.jit(
        lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal)
    )
    np.testing.assert_allclose(
        ring(q, k, v), expected, atol=2e-5, rtol=2e-5
    )


def test_ring_attention_grads_match_full():
    mesh = build_mesh(MeshConfig(dp=2, sp=4))
    q, k, v = _inputs(seq=32, dim=8)

    def loss_full(q, k, v):
        return jnp.sum(jnp.square(xla_attention(q, k, v, causal=True)))

    def loss_ring(q, k, v):
        return jnp.sum(
            jnp.square(ring_attention(q, k, v, mesh, causal=True))
        )

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    mesh = build_mesh(MeshConfig(dp=2, sp=4))
    q, k, v = _inputs(heads=4, seq=64, dim=16)
    expected = xla_attention(q, k, v, causal=causal)

    uly = jax.jit(
        lambda q, k, v: ulysses_attention(q, k, v, mesh, causal=causal)
    )
    np.testing.assert_allclose(
        uly(q, k, v), expected, atol=2e-5, rtol=2e-5
    )


def test_ring_attention_sp1_falls_back():
    mesh = build_mesh(MeshConfig(dp=8, sp=1))
    q, k, v = _inputs(seq=32, dim=8)
    expected = xla_attention(q, k, v, causal=True)
    got = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_ring_matches_full(causal):
    """Ring fold with the Pallas kernel as block compute (VERDICT.md
    round-1 item #6): per-device work is true flash attention, output
    matches full single-device attention."""
    mesh = build_mesh(MeshConfig(dp=2, sp=4))
    q, k, v = _inputs(seq=256, dim=32)
    expected = xla_attention(q, k, v, causal=causal)
    got = jax.jit(
        lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal,
            block_impl="flash", interpret=True,
        )
    )(q, k, v)
    np.testing.assert_allclose(got, expected, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_ring_grads_match_full(causal):
    mesh = build_mesh(MeshConfig(dp=2, sp=4))
    q, k, v = _inputs(seq=256, dim=32, seed=5)

    def loss_ref(q, k, v):
        out = xla_attention(q, k, v, causal=causal)
        return jnp.sum(out * jnp.cos(out))

    def loss_ring(q, k, v):
        out = ring_attention(
            q, k, v, mesh, causal=causal,
            block_impl="flash", interpret=True,
        )
        return jnp.sum(out * jnp.cos(out))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_flash_ring_agrees_with_einsum_ring():
    """The two block computes are different executions of the same
    math: outputs must agree tightly."""
    mesh = build_mesh(MeshConfig(dp=2, sp=4))
    q, k, v = _inputs(seq=256, dim=32, seed=9)
    a = jax.jit(
        lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=True, block_impl="flash", interpret=True
        )
    )(q, k, v)
    b = jax.jit(
        lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=True, block_impl="einsum"
        )
    )(q, k, v)
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_rotary_seq_axis_variants_agree():
    """rotary_embedding(seq_axis=1) on (B, S, H, d) must equal the
    transposed seq_axis=2 result on (B, H, S, d)."""
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.models.transformer import rotary_embedding

    x = jnp.asarray(
        np.random.RandomState(3).randn(2, 32, 4, 16), jnp.float32
    )
    seq_first = rotary_embedding(x, seq_axis=1)
    heads_first = rotary_embedding(
        x.transpose(0, 2, 1, 3), seq_axis=2
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(seq_first), np.asarray(heads_first), atol=1e-6
    )


def test_attention_rejects_unknown_impl():
    from elasticdl_tpu.ops.attention import dot_product_attention

    q = jnp.zeros((1, 2, 16, 8), jnp.float32)
    with pytest.raises(ValueError, match="unknown attention impl"):
        dot_product_attention(q, q, q, impl="flash")


def test_pallas_attention_sharded_over_mesh_matches_oracle():
    """With mesh= the kernel runs per shard (batch over the data axes,
    heads over tp) inside a shard_map — a pallas_call in a plain jit
    has no GSPMD rule and would run replicated. Values and gradients
    must equal the unsharded oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.ops.attention import dot_product_attention
    from elasticdl_tpu.parallel.mesh import (
        DATA_AXES, MeshConfig, build_mesh,
    )

    mesh = build_mesh(MeshConfig(dp=2, tp=2, devices=jax.devices()[:4]))
    spec = P(DATA_AXES, "tp", None, None)
    rng = np.random.RandomState(5)
    q, k, v = [
        jnp.asarray(rng.randn(4, 2, 128, 16), jnp.float32)
        for _ in range(3)
    ]

    def loss(impl, **kw):
        def fn(q, k, v):
            out = dot_product_attention(
                q, k, v, causal=True, impl=impl, **kw
            )
            return jnp.sum(out * out), out
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out), grads = loss(
        "pallas", interpret=True, mesh=mesh, spec=spec
    )(q, k, v)
    (_, ref), ref_grads = loss("xla")(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5
    )
    for g, g_ref in zip(grads, ref_grads):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_ref), atol=1e-4
        )
    # the output leaves the manual region sharded the way it went in
    # (size-1 fsdp is normalized out of the spec)
    assert out.sharding.spec[0] in ("dp", ("dp", "fsdp"))
    assert out.sharding.spec[1] == "tp"
    # the ring of one (sp=1) hands its mesh and spec to the same wrap
    from elasticdl_tpu.ops.ring_attention import ring_attention

    ring_out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True, block_impl="flash", interpret=True
    ))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ring_out), np.asarray(ref), atol=1e-5
    )
    assert ring_out.sharding.spec[1] == "tp"
    # a batch the data axes do not divide is an error, not a silent
    # replicated (on a chip: refused) kernel
    import pytest

    with pytest.raises(ValueError, match="does not divide"):
        dot_product_attention(
            q[:3], k[:3], v[:3], causal=True, impl="pallas",
            interpret=True, mesh=mesh, spec=spec,
        )


# The fused backward (flash_bwd: dq accumulated beside dk and dv) against
# XLA's autodiff of the plain attention and against the split
# flash_dq / flash_dkv pair it falls back to above its VMEM budget.
# (causal, heads' width, seq_q, seq_k, block_q, block_k, dtype)
FUSED_BACKWARD_CASES = {
    "causal-several-blocks-d128":
        (True, 128, 512, 512, 128, 256, jnp.float32),
    "full-several-blocks-d128":
        (False, 128, 512, 512, 128, 256, jnp.float32),
    "causal-one-block-each-d256":
        (True, 256, 256, 256, 256, 256, jnp.float32),
    "full-several-blocks-d256":
        (False, 256, 256, 256, 128, 128, jnp.float32),
    # the ring's call: a block of another rank's keys, never causal
    "full-seq-q-shorter-than-seq-k":
        (False, 128, 256, 512, 128, 128, jnp.float32),
    "full-seq-q-longer-than-seq-k":
        (False, 128, 512, 256, 128, 256, jnp.float32),
    # the pythia cells' class of shape: causal, head 256, several blocks
    "causal-several-blocks-d256":
        (True, 256, 512, 512, 128, 256, jnp.float32),
    "causal-several-blocks-bfloat16-d256":
        (True, 256, 512, 512, 128, 256, jnp.bfloat16),
    "causal-bfloat16-d128":
        (True, 128, 512, 512, 128, 256, jnp.bfloat16),
    "causal-block-k-below-block-q":
        (True, 128, 512, 512, 256, 128, jnp.float32),
}


def _flash_grads(case):
    from elasticdl_tpu.ops.attention import dot_product_attention

    causal, dim, seq_q, seq_k, block_q, block_k, dtype = case
    rng = np.random.RandomState(7)

    def mk(seq):
        return jnp.asarray(
            rng.normal(size=(2, 2, seq, dim), scale=0.5), dtype)

    q, k, v = mk(seq_q), mk(seq_k), mk(seq_k)

    def loss(impl, **kw):
        def fn(q, k, v):
            out = dot_product_attention(
                q, k, v, causal=causal, impl=impl, **kw
            ).astype(jnp.float32)
            return jnp.sum(out * jnp.cos(out))
        return jax.grad(fn, argnums=(0, 1, 2))

    flash = loss(
        "pallas", block_q=block_q, block_k=block_k, interpret=True
    )
    return flash, loss("xla"), (q, k, v)


def _kernel_names(fn, args):
    """Names of the pallas_calls a function traces to."""
    import re

    return sorted(set(re.findall(
        r"name=(flash_(?:fwd|bwd|dq|dkv))\b",
        str(jax.make_jaxpr(fn)(*args)),
    )))


@pytest.mark.parametrize(
    "case", list(FUSED_BACKWARD_CASES.values()),
    ids=list(FUSED_BACKWARD_CASES),
)
def test_fused_backward_matches_xla_and_the_split_pair(case, monkeypatch):
    from elasticdl_tpu.ops import flash_attention as F

    flash, xla, args = _flash_grads(case)
    assert _kernel_names(flash, args) == ["flash_bwd", "flash_fwd"]
    fused = flash(*args)
    bfloat16 = case[-1] == jnp.bfloat16
    for got, ref in zip(fused, xla(*args)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        tol = 5e-2 if bfloat16 else 3e-4
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol,
        )
    # a budget nothing fits: the same call falls back to the pair (a
    # new function: jax keeps the traces of the old one)
    monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    flash, _, _ = _flash_grads(case)
    assert _kernel_names(flash, args) == [
        "flash_dkv", "flash_dq", "flash_fwd"]
    dq, dk, dv = (np.asarray(g, np.float32) for g in flash(*args))
    # dk and dv are the pair's statements unchanged; dq's terms arrive
    # in ascending k in both schedules
    np.testing.assert_array_equal(np.asarray(fused[1], np.float32), dk)
    np.testing.assert_array_equal(np.asarray(fused[2], np.float32), dv)
    np.testing.assert_allclose(
        np.asarray(fused[0], np.float32), dq, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("shape,dtype,schedule", [
    # the benchmark's cells: pythia-1b at 2k and 16k, OLMoE at 4k
    ((2048, 2048, 256), jnp.bfloat16, "fused"),
    ((16384, 16384, 256), jnp.bfloat16, "fused"),
    ((4096, 4096, 128), jnp.bfloat16, "fused"),
    # dq's accumulator alone is the whole budget
    ((32768, 32768, 256), jnp.bfloat16, "split"),
    ((65536, 65536, 128), jnp.bfloat16, "split"),
    # a ring block: dq's size follows seq_q, not seq_k
    ((4096, 65536, 128), jnp.bfloat16, "fused"),
    # float32 doubles dq's output block (a model's init trace at 16k)
    ((16384, 16384, 256), jnp.float32, "split"),
    ((2048, 2048, 256), jnp.float32, "fused"),
])
def test_backward_schedule_is_chosen_from_the_shapes(shape, dtype, schedule):
    from elasticdl_tpu.ops import flash_attention as F

    assert F.backward_schedule(*shape, dtype) == schedule


def test_attention_log_line_says_which_backward(monkeypatch, caplog):
    """``benchmark/lib/logs.py:ATTENTION_RE`` reads the first word after
    "resolved to"; the backward's schedule rides inside the
    parentheses."""
    import logging

    from elasticdl_tpu.ops import attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        attention._flash, "flash_attention", lambda q, k, v, **kw: q)
    attention._log_auto_once.cache_clear()
    q = jnp.zeros((1, 2, 2048, 128), jnp.bfloat16)
    with caplog.at_level(logging.INFO, logger=attention.logger.name):
        attention.dot_product_attention(q, q, q, causal=True)
        attention.dot_product_attention(q, q[:, :, :1000], q[:, :, :1000])
    attention._log_auto_once.cache_clear()
    lines = [r.getMessage() for r in caplog.records]
    assert lines[0] == (
        "attention impl=auto resolved to pallas (backend=tpu, "
        "q=(1, 2, 2048, 128) bfloat16, flash backward=fused)")
    assert "resolved to xla" in lines[1] and "backward" not in lines[1]
