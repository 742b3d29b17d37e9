"""Grouped-query attention (ISSUE 31): the flash kernels with fewer k /
v heads than q heads, read through the index maps and never copied,
against ``xla_attention`` with k and v repeated (interpret mode, both
backward schedules); a call of equal head counts still traces what it
traced; and ``Attention`` with a head width of its own, 2 kv heads, a
norm a head, partial rotary and a sigmoid output gate against its
equations."""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.transformer import (
    Attention,
    ZeroCentredRMSNorm,
    make_attention,
    make_norm,
)
from elasticdl_tpu.ops import attention as attention_ops
from elasticdl_tpu.ops import flash_attention as F
from elasticdl_tpu.ops.attention import xla_attention
from tests.kernel_common import traced_flash


def _qkv(seq, heads, kv_heads, dim, dtype, seed=0):
    rng = np.random.RandomState(seed)
    make = lambda h: jnp.asarray(rng.randn(1, h, seq, dim) * 0.5, dtype)
    return make(heads), make(kv_heads), make(kv_heads)


def _value_and_grads(attention, q, k, v):
    def loss(q, k, v):
        out = attention(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out,) + grads


@functools.lru_cache(maxsize=None)
def _repeated(heads, kv_heads, dtype):
    """(q, k, v, the XLA reference on k and v repeated over the group,
    the XLA path on the unrepeated ones): neither knows of a backward
    schedule, so once for both."""
    q, k, v = _qkv(256, heads, kv_heads, 32, dtype)
    group = heads // kv_heads

    def repeated(q, k, v):
        return xla_attention(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
            causal=True)

    return (q, k, v), jax.jit(functools.partial(
        _value_and_grads, repeated))(q, k, v), jax.jit(functools.partial(
            xla_attention, causal=True))(q, k, v)


@pytest.mark.parametrize("schedule", ["fused", "split"])
@pytest.mark.parametrize("heads,kv_heads,dtype", [
    (16, 2, jnp.float32),   # Qwen3-Next's 16 over 2
    (4, 2, jnp.bfloat16),
    (4, 1, jnp.float32),    # one kv head for all
], ids=["16-2", "4-2-bf16", "4-1"])
def test_flash_reads_kv_head_h_over_group(
        heads, kv_heads, dtype, schedule, monkeypatch):
    """o, dq at the query heads; dk, dv at the kv heads, summed over the
    group; against the XLA reference on repeated k, v; causal."""
    if schedule == "split":
        monkeypatch.setattr(F, "_FUSED_VMEM_BYTES", 0)
    (q, k, v), want, unrepeated = _repeated(heads, kv_heads, dtype)
    flash = lambda q, k, v: F.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    # one trace: the names are read from the program that runs
    names, got = traced_flash(functools.partial(_value_and_grads, flash), (q, k, v))
    assert names == (
        ["flash_bwd", "flash_fwd"] if schedule == "fused"
        else ["flash_dkv", "flash_dq", "flash_fwd"])
    assert [g.shape[1] for g in got] == [heads, heads, kv_heads, kv_heads]
    tol = 6e-2 if dtype == jnp.bfloat16 else 3e-4
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol)
    # the XLA path takes the same unrepeated operands
    np.testing.assert_allclose(
        np.asarray(unrepeated, np.float32),
        np.asarray(want[0], np.float32), atol=1e-6)


def test_k_and_v_are_never_copied_to_the_query_heads():
    """No array of the kv width at the QUERY heads' count enters a
    kernel: the forward's k / v operands are the 2-head arrays."""
    q, k, v = _qkv(256, 16, 2, 32, jnp.float32)
    text = str(jax.make_jaxpr(lambda q, k, v: F.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True
    ))(q, k, v))
    (call,) = [line for line in text.splitlines() if "name=flash_fwd" in line
               ] or [text]
    assert "repeat" not in text and "broadcast_in_dim" not in text.split(
        "pallas_call")[0]
    assert "f32[2,256,32]" in text


@pytest.mark.parametrize("k_outer", [False, True])
def test_equal_head_counts_keep_their_index_maps(k_outer):
    """At group 1 the kv-ish map IS the k-ish map (one function object),
    so a call of equal head counts traces the module it always traced;
    at group 8 it divides the merged head index."""
    _, k_idx, _ = F._index_maps(True, 128, 128, 4, k_outer=k_outer)
    assert F._kv_index_map(k_idx, 1) is k_idx
    kv_idx = F._kv_index_map(k_idx, 8)
    assert kv_idx is not k_idx
    head, block, lane = kv_idx(jnp.int32(19), jnp.int32(3), jnp.int32(1))
    assert (int(head), int(lane)) == (2, 0)
    assert int(block) == int(k_idx(19, jnp.int32(3), jnp.int32(1))[1])


def test_equal_head_counts_trace_no_division_and_no_float32_dk():
    q, k, v = _qkv(256, 4, 4, 32, jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda *a: _value_and_grads(
        lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True),
        *a))(q, k, v))
    kernels = text.split("pallas_call")
    assert len(kernels) == 3  # flash_fwd, flash_bwd
    # dk and dv leave the kernel in k's dtype, as they always did
    assert "f32[4,256,32]" not in kernels[2].split("name=flash_bwd")[-1][:400]
    grouped = str(jax.make_jaxpr(lambda *a: _value_and_grads(
        lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True),
        *a))(*_qkv(256, 4, 2, 32, jnp.bfloat16)))
    assert grouped.count("reduce_sum") > text.count("reduce_sum")


def test_head_counts_must_divide():
    q, k, v = _qkv(256, 4, 3, 32, jnp.float32)
    with pytest.raises(ValueError, match="divides q's"):
        F.flash_attention(q, k, v, interpret=True)


def test_the_attention_line_names_the_group_and_the_note(caplog):
    # the line reads shapes and dtypes: the cell's, with no array made
    q = jax.ShapeDtypeStruct((1, 16, 32768, 256), jnp.bfloat16)
    k = v = jax.ShapeDtypeStruct((1, 2, 32768, 256), jnp.bfloat16)
    facts = attention_ops._flash_facts(q, k, v, True, None, None)
    # 32,768 x 256 fits the fused backward's budget with dq's
    # whole-head output block in one buffer (PR 61; the pair before)
    assert facts.startswith(
        "kv_heads=2 group=8, flash backward=fused dq_buffers=1, pairs run=")
    same = attention_ops._flash_facts(q, q, q, True, None, None)
    assert same.startswith("flash backward=")  # the other cells' line
    attention_ops._log_auto_once.cache_clear()
    with caplog.at_level(logging.INFO):
        attention_ops._log_auto_once(
            "tpu", "pallas", "", (1, 16, 32768, 256), "bfloat16",
            "gate=sigmoid rotary=64/256, " + facts)
    assert ("bfloat16, gate=sigmoid rotary=64/256, kv_heads=2 group=8, "
            "flash backward=fused dq_buffers=1, pairs run=") in caplog.text


# ------------------------------------------------------------ the module


def test_zero_centred_norm_starts_at_one_and_scales_by_one_plus_w():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16)) * 4
    norm = make_norm("zero_centred_rmsnorm", 1e-6, "n")
    assert isinstance(norm, ZeroCentredRMSNorm)
    variables = norm.init(jax.random.PRNGKey(1), x)
    assert bool((variables["params"]["scale"] == 0).all())
    rms = lambda t: np.sqrt(np.mean(np.square(t), -1))
    np.testing.assert_allclose(rms(norm.apply(variables, x)), 1, atol=1e-5)
    doubled = {"params": {"scale": jnp.ones(16)}}
    np.testing.assert_allclose(
        norm.apply(doubled, x), 2 * norm.apply(variables, x), rtol=1e-6)
    with pytest.raises(ValueError, match="zero_centred_rmsnorm"):
        make_norm("batchnorm", 1e-6, "n")


def _rotate_halves(x, base):
    seq, dim = x.shape[-2], x.shape[-1]
    half = dim // 2
    angle = np.arange(seq)[:, None] * base ** (-np.arange(half) / half)
    a, b = x[..., :half], x[..., half:]
    return np.concatenate(
        [a * np.cos(angle) - b * np.sin(angle),
         b * np.cos(angle) + a * np.sin(angle)], axis=-1)


def test_gated_grouped_query_attention_against_its_equations():
    """ISSUE 31's equations in numpy float64: 4 query heads of 16 over a
    model width of 32, 2 kv heads, the zero-centred norm a head, rotary
    on 4 of 16 lanes, a sigmoid gate."""
    heads, kv, dim, lanes, base = 4, 2, 16, 4, 1e7
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32))
    layer = Attention(
        heads, attention_impl="xla", rope_theta=base, head_dim=dim,
        num_kv_heads=kv, head_norm="zero_centred_rmsnorm", rotary_dim=lanes,
        output_gate="sigmoid")
    variables = layer.init(jax.random.PRNGKey(6), x)
    p = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float64), variables["params"])
    assert p["query"]["kernel"].shape == (32, heads, 2 * dim)
    assert p["key"]["kernel"].shape == (32, kv, dim)
    assert p["q_norm"]["scale"].shape == (dim,)
    assert p["out_proj"]["kernel"].shape == (heads, dim, 32)
    # seeded scales, so that the norm's (1 + w) shows
    p["q_norm"]["scale"] = np.linspace(-0.5, 0.5, dim)
    p["k_norm"]["scale"] = np.linspace(0.3, -0.3, dim)
    got = layer.apply({"params": jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), p)}, x)[0]
    xs = np.asarray(x[0], np.float64)
    qg = np.einsum("sd,dhk->hsk", xs, p["query"]["kernel"])
    q, gate = qg[..., :dim], qg[..., dim:]
    k = np.einsum("sd,dhk->hsk", xs, p["key"]["kernel"])
    v = np.einsum("sd,dhk->hsk", xs, p["value"]["kernel"])
    norm = lambda t, w: t / np.sqrt(
        (t * t).mean(-1, keepdims=True) + 1e-6) * (1 + w)
    q, k = norm(q, p["q_norm"]["scale"]), norm(k, p["k_norm"]["scale"])
    turn = lambda t: np.concatenate(
        [_rotate_halves(t[..., :lanes], base), t[..., lanes:]], -1)
    q, k = turn(q), turn(k)
    out = np.zeros((heads, 24, dim))
    for h in range(heads):
        scores = q[h] @ k[h // 2].T / np.sqrt(dim)
        scores = np.where(np.tril(np.ones((24, 24), bool)), scores, -np.inf)
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        out[h] = weights / weights.sum(-1, keepdims=True) @ v[h // 2]
    out = out / (1 + np.exp(-gate))
    want = np.einsum("hsv,hvd->sd", out, p["out_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_defaults_are_the_older_blocks():
    """Without the new fields the parameters are the GPT-NeoX block's:
    no norm a head, no gate, heads of width / heads."""
    x = jnp.zeros((1, 8, 32))
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape,
        Attention(4, attention_impl="xla").init(
            jax.random.PRNGKey(0), x)["params"])
    assert shapes == {
        "query": {"kernel": (32, 4, 8)}, "key": {"kernel": (32, 4, 8)},
        "value": {"kernel": (32, 4, 8)}, "out_proj": {"kernel": (4, 8, 32)}}


def test_attention_refuses_what_it_does_not_build():
    x = jnp.zeros((1, 8, 32))
    with pytest.raises(ValueError, match="output_gate"):
        Attention(4, output_gate="tanh").init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="head_norm"):
        Attention(4, qk_norm=True, head_dim=16).init(
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="equal head counts"):
        Attention(4, attention_impl="ring", num_kv_heads=2).init(
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="latent attention has no"):
        make_attention(4, latent=object(), num_kv_heads=2)
