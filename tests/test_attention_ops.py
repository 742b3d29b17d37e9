"""Flash kernel vs XLA oracle; ring/ulysses SP vs full attention.

Kernel runs in Pallas interpret mode on CPU (compiled on real TPU); the
SP schedules run on the 8-virtual-device mesh from conftest. The flash
kernels' backward schedules, the causal call's pair classes and the
blocks chosen from shapes are ``test_flash_backward.py``'s: one file
summed past the rule's 100 s (``ROADMAP.md`` Queue 3 item 12).
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
