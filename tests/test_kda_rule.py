"""The delta rule whose decay is a vector a head (Kimi Delta Attention:
``ops/gated_delta.py`` with ``g`` of rank 4), against the per-token
recurrence in float64 on the CPU: forward, the gradients of q, k, v, g,
beta and of the entering state; decays of -50 a token on some channels
and 0 on others, where a factorised ``(K e^G)(K e^-G)^T`` overflows; a
vector that is constant over the channels against the scalar rule;
chunks of 64 and 128 over several segments; the choosers, which read
the decay's rank and say ``pallas`` for a vector decay's operands and
for its chunk-to-chunk recurrence where they say so for the scalar
rule's (the operands' kernels themselves are
``test_kda_operands.py``'s); and the scan's kernels carrying a decay a
channel (``kda_scan_fwd`` / ``kda_scan_bwd``, the state transposed)
against the ``lax.scan``, in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gated_delta
from tests.gdn_common import _force_pallas, x64  # noqa: F401 (fixture)

HEADS, DIM = 2, 8
TOLERANCE = 1e-12


def _operands(seq, regime, seed=0, dim=DIM, dtype=jnp.float64):
    """q, k, v (1, H, S, D), g (1, H, S, D), beta (1, H, S). ``regime``:
    ``drawn`` (g = -exp(normal)), ``hard`` (-50 a token on the even
    channels, 0 on the odd ones) or ``mixed`` (drawn, with a run of -50
    in the middle of the sequence)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (1, HEADS, seq, dim)
    q = jax.random.normal(keys[0], shape, dtype) * dim ** -0.5
    k = jax.random.normal(keys[1], shape, dtype)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], shape, dtype)
    g = -jnp.exp(jax.random.normal(keys[3], shape, dtype))
    even = jnp.arange(dim) % 2 == 0
    if regime == "hard":
        g = jnp.broadcast_to(jnp.where(even, -50.0, 0.0), shape).astype(dtype)
    elif regime == "mixed":
        run = (jnp.arange(seq) >= seq // 3) & (jnp.arange(seq) < seq // 2)
        g = jnp.where(run[:, None] & even, -50.0, g)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3], dtype))
    return q, k, v, g, beta


@functools.lru_cache(maxsize=None)
def _value_and_grads(f, argnums):
    """One program a function: cases that hand the same function
    object operands of the same shapes (a shape's three regimes are
    values) trace and compile it once."""
    return jax.jit(lambda weight, *a: (f(*a), jax.grad(
        lambda *b: (f(*b) * weight).sum(), argnums=argnums)(*a)))


def _both(fn, oracle, args, argnums):
    """(value, gradients) of ``fn`` and of ``oracle`` under one seeded
    weighting of the output."""
    weight = jax.random.normal(
        jax.random.PRNGKey(9), jax.eval_shape(oracle, *args).shape,
        jnp.float64)
    return (_value_and_grads(fn, argnums)(weight, *args),
            _value_and_grads(oracle, argnums)(weight, *args))


@functools.lru_cache(maxsize=None)
def _rule(chunk, segment):
    return lambda *a: gated_delta.gated_delta_rule(
        *a, chunk=chunk, segment=segment)


def _close(got, want, names):
    for name, a, b in zip(names, got, want):
        assert bool(jnp.isfinite(a).all()), name
        scale = max(1.0, float(jnp.abs(b).max()))
        np.testing.assert_allclose(
            a, b, rtol=0, atol=TOLERANCE * scale, err_msg=name)


@pytest.mark.parametrize("regime", ["drawn", "hard", "mixed"])
@pytest.mark.parametrize("chunk,segment,seq", [
    (64, 2, 320), (128, 1, 384), (16, 2, 80), (64, 128, 100),
], ids=["chunk64-3segments", "chunk128-3segments", "one-sub-block",
        "padded"])
def test_the_chunked_vector_rule_is_the_recurrence(x64, regime, chunk,
                                                   segment, seq):
    args = _operands(seq, regime)
    (o, grads), (want, want_grads) = _both(
        _rule(chunk, segment), gated_delta.gated_delta_recurrence, args,
        (0, 1, 2, 3, 4))
    _close((o,) + grads, (want,) + want_grads,
           ("o", "dq", "dk", "dv", "dg", "dbeta"))
    if regime == "hard":
        # the decay did decay: the even channels forget at once
        assert float(jnp.abs(grads[3]).max()) > 0


@pytest.mark.parametrize("regime", ["drawn", "hard"])
def test_a_segment_from_an_entering_state(x64, regime):
    """``_chunks`` from a non-zero state: the output and the gradient
    that reaches the state a segment starts from."""
    seq, chunk = 128, 64
    q, k, v, g, beta = _operands(seq, regime, seed=3)
    state = 0.3 * jax.random.normal(
        jax.random.PRNGKey(4), (1, HEADS, DIM, DIM), jnp.float64)
    split = lambda x: x.reshape(
        (1, HEADS, 1, seq // chunk, chunk) + x.shape[3:])

    def segment(state, q, k, v, g, beta):
        _, o = gated_delta._chunks(
            state[:, :, None], *map(split, (q, k, v, g, beta)),
            jnp.float64, "xla", "xla")
        return o.reshape(1, HEADS, seq, DIM)

    oracle = lambda state, *a: gated_delta.gated_delta_recurrence(
        *a, state=state)
    (o, grads), (want, want_grads) = _both(
        segment, oracle, (state, q, k, v, g, beta), (0, 1, 2, 3, 4, 5))
    _close((o,) + grads, (want,) + want_grads,
           ("o", "dstate", "dq", "dk", "dv", "dg", "dbeta"))
    assert float(jnp.abs(grads[0]).max()) > 1e-3


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_vector_constant_over_the_channels_is_the_scalar_rule(x64, chunk):
    q, k, v, g, beta = _operands(160, "drawn", seed=1)
    scalar = g[..., 0]
    rule = lambda g: jax.jit(lambda *a: gated_delta.gated_delta_rule(
        *a, chunk=chunk, segment=2))(q, k, v, g, beta)
    np.testing.assert_allclose(
        rule(jnp.broadcast_to(scalar[..., None], g.shape)), rule(scalar),
        rtol=0, atol=TOLERANCE)


def test_the_diagonal_s_decays_go_by_groups_of_chunks(x64, monkeypatch):
    """``_CUBE_BYTES`` small enough that the VJP of the sub-blocks on
    the diagonals runs a chunk at a time (the cell's segment runs in
    four groups): the same floats."""
    args = _operands(256, "mixed", seed=2)
    rule = lambda *a: gated_delta.gated_delta_rule(*a, chunk=64, segment=4)
    weight = jax.random.normal(
        jax.random.PRNGKey(9), args[2].shape, jnp.float64)
    whole = _value_and_grads(rule, (0, 1, 2, 3, 4))(weight, *args)
    monkeypatch.setattr(gated_delta, "_CUBE_BYTES", 64 * 1024)
    # a new function object: jax keeps a function's traces
    grouped = _value_and_grads(lambda *a: rule(*a), (0, 1, 2, 3, 4))(
        weight, *args)
    _close((grouped[0],) + grouped[1], (whole[0],) + whole[1],
           ("o", "dq", "dk", "dv", "dg", "dbeta"))


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_a_vector_decay_s_operands_are_chosen_by_its_rank(
        monkeypatch, caplog, backend):
    """By the operand's rank and not by a flag: on a (described) TPU
    the scalar rule at the cell's shapes is the four ``gdn_*`` kernels
    and the vector rule the four ``kda_*`` ones (``kda_prepare_*`` make
    its operands, by their own account of a block), on the CPU both are
    XLA's lines, and the rule's line says so."""
    import logging

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    pallas = "pallas" if backend == "tpu" else "xla"
    shape = (jnp.bfloat16, 64, 128, 128)
    assert gated_delta.scan_impl(*shape) == pallas
    assert gated_delta.prepare_impl(*shape, 1, 128) == pallas
    assert gated_delta.prepare_impl(
        *shape, 1, 128, decay_rank=gated_delta.SCALAR_DECAY) == pallas
    assert gated_delta.prepare_impl(
        *shape, 1, 128, decay_rank=gated_delta.VECTOR_DECAY) == pallas
    # each rank by its own kernels' VMEM: 4 value heads a key head fit
    # the scalar rule's blocks and not the vector rule's
    assert gated_delta.prepare_impl(*shape, 4, 128) == pallas
    assert gated_delta.prepare_impl(
        *shape, 4, 128, decay_rank=gated_delta.VECTOR_DECAY) == "xla"
    # and the rule hands the operands' chooser its operand's rank
    seen = []
    prepare = gated_delta.prepare_impl
    monkeypatch.setattr(
        gated_delta, "prepare_impl",
        lambda *a, **kw: seen.append(a[-1]) or prepare(*a, **kw))
    gated_delta._log_once.cache_clear()
    struct = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    with caplog.at_level(logging.INFO):
        # a new function object: jax keeps a function's traces
        out = jax.eval_shape(
            lambda *a: gated_delta.gated_delta_rule(*a),
            struct(1, 2, 128, 128),
            struct(1, 2, 128, 128), struct(1, 2, 128, 128),
            jax.ShapeDtypeStruct((1, 2, 128, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, 2, 128), jnp.float32))
    assert out.shape == (1, 2, 128, 128) and out.dtype == jnp.bfloat16
    assert seen == [gated_delta.VECTOR_DECAY]
    assert ("linear attention heads k=2 v=2 dim=128 chunk=64 impl=%s "
            "scan=%s prep=%s (tokens=128) decay=vector" % ((pallas,) * 3)
            ) in caplog.text


def _segment_by_channel(chunk, rep, dtype, hk=2, num=2, seed=0):
    """A segment's operands as ``_chunk_operands_by_channel`` builds
    them at 128-wide heads (batch 1, two chunks: one grid step shows the
    state handed from a chunk to the next) and a non-zero entering
    state."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    key_like, value_like = (1, hk, 1, num, chunk), (1, hk, rep, num, chunk)
    q = unit(jax.random.normal(keys[0], key_like + (128,))) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], key_like + (128,)))
    v = jax.random.normal(keys[2], value_like + (128,))
    g = -0.3 * jnp.exp(jax.random.normal(keys[3], value_like + (128,)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], value_like))
    operands = jax.jit(
        lambda *a: gated_delta._chunk_operands_by_channel(*a, jnp.float32))(
            *(x.astype(dtype) for x in (q, k, v)), g, beta)
    state = 0.3 * jax.random.normal(keys[5], (1, hk, rep, 128, 128))
    return (state,) + operands


# an interpreted kernel costs by the trace: the cell's chunk in the
# cell's dtype, and the other chunk and a key head's two value heads in
# the dtype that compares to rounding
@pytest.mark.parametrize("chunk,rep,hk,dtype", [
    (64, 1, 2, jnp.bfloat16), (128, 2, 1, jnp.float32),
], ids=["64-rep1-bfloat16", "128-rep2-float32"])
def test_the_scan_s_kernels_carry_a_decay_a_channel(monkeypatch, chunk, rep,
                                                    hk, dtype):
    """``O``, the leaving state and the gradients of all six operands
    and of the entering state, the kernels (state transposed, a
    channel's decay on its own lane) against the ``lax.scan``: in
    float32 equal to rounding, in bfloat16 ``O`` bit for bit and the
    gradients to the operands' rounding, as the scalar rule's
    (``test_gated_delta_scan.py``)."""
    args = _segment_by_channel(chunk, rep, dtype, hk)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[-1].shape)

    def outputs(carry):
        def loss(*a):
            leaving, o = carry(*a, dtype)
            return ((o.astype(jnp.float32) * weight).sum()
                    + (leaving * leaving).sum())
        # the program's text is read from the trace that runs
        traced = jax.jit(lambda *a: carry(*a, dtype) + jax.grad(
            loss, argnums=tuple(range(7)))(*a)).trace(*args)
        return str(traced.jaxpr), traced.lower().compile()(*args)

    _, want = outputs(gated_delta._scan_xla)
    _force_pallas(monkeypatch)
    text, got = outputs(gated_delta._scan_pallas_by_channel)
    exact = dtype == jnp.float32
    names = ("leaving", "o", "d_state", "d_last", "d_w", "d_k_onto",
             "d_q_into", "d_attn", "d_u")
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape, name
        a, b = np.float32(a), np.float32(b)
        scale = float(np.abs(b).max())
        if name == "o" and not exact:
            np.testing.assert_array_equal(
                a, np.float32(jnp.asarray(b).astype(dtype)), err_msg=name)
            continue
        if name == "d_attn":
            a, b = np.tril(a), np.tril(b)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=(2e-5 if exact else 2e-2) * scale,
            err_msg=name)
    # the kernels' names say which rule they carry
    assert "kda_scan_fwd" in text and "kda_scan_bwd" in text


def test_the_rule_by_the_scan_s_kernels_is_the_recurrence(monkeypatch):
    """``gated_delta_rule`` with a decay a channel where no block of
    the operands' kernels fits (XLA's operands, the scan's kernels,
    interpreted: the choice held to ``xla`` here) over two segments of
    float32 operands at 128-wide heads, against the per-token loop: the
    state crosses a segment's boundary turned and turned back."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (1, 1, 256, 128)
    q = unit(jax.random.normal(keys[0], shape)) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    g = -0.1 * jnp.exp(jax.random.normal(keys[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    want = gated_delta.gated_delta_recurrence(q, k, v, g, beta)
    _force_pallas(monkeypatch)
    monkeypatch.setattr(gated_delta, "prepare_impl", lambda *a, **kw: "xla")
    gated_delta._log_once.cache_clear()
    rule = lambda *a: gated_delta.gated_delta_rule(*a, chunk=64, segment=2)
    traced = jax.jit(rule).trace(q, k, v, g, beta)
    text = str(traced.jaxpr)
    assert "kda_scan_fwd" in text and "_prepare" not in text
    got = traced.lower().compile()(q, k, v, g, beta)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("lower", ["decay_dtype", "state_dtype"])
def test_a_decay_or_a_state_in_bfloat16_is_seen(x64, lower):
    """float32 operands with the decay cumulated (or the state carried)
    in bfloat16, against the float64 recurrence: an order of magnitude
    off what the float32 rule is, at a decay that lets a state live
    through its chunks (the cell's regime)."""
    q, k, v, g, beta = _operands(256, "drawn", seed=5)
    g = 0.05 * g
    want = gated_delta.gated_delta_recurrence(q, k, v, g, beta)
    low = lambda x: x.astype(jnp.float32)
    rule = lambda **kw: jax.jit(lambda *a: gated_delta.gated_delta_rule(
        *a, chunk=64, segment=2, **kw))(*map(low, (q, k, v, g, beta)))
    error = lambda o: float(jnp.abs(o - want).max())
    stated, lowered = error(rule()), error(rule(**{lower: jnp.bfloat16}))
    assert stated < 1e-4 and lowered > 10 * stated, (stated, lowered)


def test_a_decay_of_another_shape_is_refused():
    q, k, v, g, beta = _operands(32, "drawn", dtype=jnp.float32)
    with pytest.raises(ValueError, match="a decay a token"):
        gated_delta.gated_delta_rule(q, k, v, g[..., :4], beta)
