"""The chunked selective state-space scan (``ops/ssd.py``, Mamba-2's
SSD) against the per-token recurrence in float64 on the CPU: forward,
the gradients of x, dt, the decay, B, C, D and of the entering state;
log decays of -50 a token on some heads and 0 on others, where a
factorised ``(C e^G)(B e^-G)^T`` overflows; chunks of 64 and 256;
several segments against one; 1, 2 and 8 groups; a state carried over
two calls against one call; a padded length; the chooser and the log's
line. Small on purpose: no sequence is longer than 512 tokens."""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import ssd
from tests.gdn_common import x64  # noqa: F401 (fixture)

HEADS, DIM, STATE = 8, 4, 8
TOLERANCE = 1e-12
NAMES = ("y", "state", "dx", "ddt", "da", "db", "dc", "dskip", "dstate")


def _operands(seq, regime="drawn", groups=1, seed=0, dtype=jnp.float64):
    """x (1, S, H, P), dt and a (1, S, H), b and c (1, S, groups, N),
    skip (H,), an entering state (1, H, P, N). ``regime``: ``drawn`` (a
    = -dt exp(normal)), ``hard`` (-50 a token on the even heads, 0 on
    the odd ones) or ``mixed`` (drawn, with a run of -50 on the even
    heads in the middle of the sequence)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(keys[0], (1, seq, HEADS, DIM), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, seq, HEADS), dtype))
    a = -dt * jnp.exp(jax.random.normal(keys[2], (HEADS,), dtype))
    even = jnp.arange(HEADS) % 2 == 0
    if regime == "hard":
        a = jnp.broadcast_to(jnp.where(even, -50.0, 0.0), a.shape).astype(
            dtype)
    elif regime == "mixed":
        run = (jnp.arange(seq) >= seq // 3) & (jnp.arange(seq) < seq // 2)
        a = jnp.where(run[:, None] & even, -50.0, a)
    b = jax.random.normal(keys[3], (1, seq, groups, STATE), dtype)
    c = jax.random.normal(keys[4], (1, seq, groups, STATE), dtype)
    skip = jax.random.normal(keys[5], (HEADS,), dtype)
    state = 0.3 * jax.random.normal(
        keys[6], (1, HEADS, DIM, STATE), dtype)
    return x, dt, a, b, c, skip, state


def _oracle(*a):
    return ssd.ssd_recurrence(*a[:6], state=a[6])


@functools.lru_cache(maxsize=None)
def _value_and_grads(f):
    """One program a function: cases that hand the same function object
    operands of the same shapes (a shape's regimes are values) trace
    and compile it once."""
    weighted = lambda weights, *a: sum(
        (out * w).sum() for out, w in zip(f(*a), weights))
    return jax.jit(lambda weights, *a: f(*a) + jax.grad(
        weighted, argnums=tuple(range(1, 8)))(weights, *a))


def _both(fn, args):
    """(y, leaving state, gradients of all seven operands) of ``fn`` and
    of the recurrence under one seeded weighting of both outputs."""
    shapes = jax.eval_shape(_oracle, *args)
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    weights = tuple(
        jax.random.normal(key, shape.shape, jnp.float64)
        for key, shape in zip(keys, shapes))
    return (_value_and_grads(fn)(weights, *args),
            _value_and_grads(_oracle)(weights, *args))


def _close(got, want, names=NAMES):
    for name, a, b in zip(names, got, want):
        assert bool(jnp.isfinite(a).all()), name
        scale = max(1.0, float(jnp.abs(b).max()))
        np.testing.assert_allclose(
            a, b, rtol=0, atol=TOLERANCE * scale, err_msg=name)


@functools.lru_cache(maxsize=None)
def _scan(chunk, segment, **kw):
    return lambda *a: ssd.ssd_scan(
        *a[:6], chunk=chunk, state=a[6], segment=segment,
        return_state=True, **kw)


@pytest.mark.parametrize("regime,chunk,segment,seq", [
    ("drawn", 64, 2, 320), ("hard", 64, 2, 320), ("mixed", 64, 2, 320),
    ("mixed", 256, 1, 512), ("hard", 16, 2, 80), ("mixed", 16, 2, 80),
    ("drawn", 64, 8, 100), ("hard", 64, 8, 100),
], ids=lambda v: str(v))
def test_the_chunked_scan_is_the_recurrence(x64, regime, chunk, segment,
                                            seq):
    args = _operands(seq, regime)
    got, want = _both(_scan(chunk, segment), args)
    _close(got, want)
    if regime == "hard":
        # the decay did decay: the even heads forget at once, and the
        # gradient reaches the decay of the odd ones
        assert float(jnp.abs(got[4]).max()) > 0


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_groups_of_heads_share_b_and_c(x64, groups):
    args = _operands(160, "mixed", groups=groups, seed=groups)
    got, want = _both(_scan(32, 2), args)
    _close(got, want)


@pytest.mark.parametrize("regime", ["drawn", "hard"])
def test_chunks_and_segments_do_not_change_the_result(x64, regime):
    args = _operands(512, regime, seed=2)
    runs = [
        jax.jit(_scan(chunk, segment))(*args)
        for chunk, segment in ((64, 8), (256, 2), (64, 2), (256, 1))]
    for other in runs[1:]:
        _close(other, runs[0], ("y", "state"))


@pytest.mark.parametrize("cut", [64, 100])
def test_a_state_carried_over_two_calls_is_one_call(x64, cut):
    x, dt, a, b, c, skip, state = _operands(192, "drawn", groups=2, seed=5)
    scan = jax.jit(_scan(32, 2))
    whole, leaving = scan(x, dt, a, b, c, skip, state)
    head = lambda t: t[:, :cut]
    tail = lambda t: t[:, cut:]
    first, between = scan(*map(head, (x, dt, a, b, c)), skip, state)
    second, last = scan(*map(tail, (x, dt, a, b, c)), skip, between)
    _close((jnp.concatenate([first, second], axis=1), last),
           (whole, leaving), ("y", "state"))


def test_without_a_state_the_sequence_starts_from_zero(x64):
    x, dt, a, b, c, skip, state = _operands(96, seed=6)
    y = jax.jit(lambda *t: ssd.ssd_scan(*t, chunk=32))(x, dt, a, b, c, skip)
    want, _ = jax.jit(ssd.ssd_recurrence)(x, dt, a, b, c, skip)
    _close((y,), (want,), ("y",))
    assert y.shape == x.shape and y.dtype == x.dtype


def test_bfloat16_operands_keep_a_float32_state_and_decay():
    """The stated precision: operands rounded to bfloat16, the decay and
    the state float32. A bfloat16 decay reads further off where a chunk's
    cumulated decay is a few units, a bfloat16 state where the memory is
    long (the state outlives its chunk)."""
    x, dt, a, b, c, skip, state = _operands(512, seed=7, dtype=jnp.float32)
    low = lambda t: t.astype(jnp.bfloat16)

    oracle = jax.jit(lambda scale: ssd.ssd_recurrence(
        x, dt, scale * a, b, c, skip, state=state)[0])

    @functools.lru_cache(maxsize=None)
    def scan(**kw):
        return jax.jit(lambda scale: ssd.ssd_scan(
            low(x), dt, scale * a, low(b), low(c), skip, chunk=64,
            state=state, segment=2, return_state=True, **kw))

    def err(scale, **kw):
        want = oracle(scale)
        y, leaving = scan(**kw)(scale)
        assert y.dtype == jnp.bfloat16 and leaving.dtype == jnp.float32
        return float(jnp.sqrt(jnp.mean(
            (y.astype(jnp.float32) - want) ** 2) / jnp.mean(want ** 2)))

    stated = {scale: err(scale) for scale in (0.1, 0.01)}
    assert stated[0.1] < 0.01 and stated[0.01] < 0.01
    assert err(0.1, decay_dtype=jnp.bfloat16) > 1.5 * stated[0.1]
    assert err(0.01, state_dtype=jnp.bfloat16) > 1.05 * stated[0.01]


def test_what_the_scan_refuses():
    x, dt, a, b, c, skip, _ = _operands(32, groups=1, dtype=jnp.float32)
    with pytest.raises(ValueError, match="divide over the groups"):
        ssd.ssd_scan(x, dt, a, jnp.tile(b, (1, 1, 3, 1)),
                     jnp.tile(c, (1, 1, 3, 1)), skip)
    with pytest.raises(ValueError, match="one number a head and token"):
        ssd.ssd_scan(x, dt[..., :1], a, b, c, skip)


def test_the_chooser_says_xla_and_the_line_says_so(caplog):
    assert ssd.scan_impl(jnp.bfloat16, 64, 128, 256) == "xla"
    x, dt, a, b, c, skip, _ = _operands(96, seed=8, dtype=jnp.float32)
    ssd._log_once.cache_clear()
    with caplog.at_level(logging.INFO, logger="elasticdl_tpu.ops.ssd"):
        jax.eval_shape(
            lambda *t: ssd.ssd_scan(*t, chunk=16, segment=2),
            x, dt, a, b, c, skip)
    assert (
        "ssd scan heads=8x4 state=8 groups=1 chunk=16 impl=xla "
        "segments=3 (tokens=96)") in caplog.text
    # and at eight groups (Nemotron-H's count: a head a group here)
    x, dt, a, b, c, skip, _ = _operands(
        96, groups=8, seed=8, dtype=jnp.float32)
    with caplog.at_level(logging.INFO, logger="elasticdl_tpu.ops.ssd"):
        jax.eval_shape(
            lambda *t: ssd.ssd_scan(*t, chunk=16, segment=2),
            x, dt, a, b, c, skip)
    assert (
        "ssd scan heads=8x4 state=8 groups=8 chunk=16 impl=xla "
        "segments=3 (tokens=96)") in caplog.text
