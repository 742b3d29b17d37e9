"""The gated delta rule in chunked form (Gated DeltaNet, arXiv:2412.06464;
the linear-attention layers of Qwen3-Next).

Per value head, with a state ``S`` (key width x value width, zero at the
sequence's start), for each token ``t``::

    S = exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S = S + k_t u^T;
    o_t = S^T q_t

(``g_t <= 0`` the log decay, ``beta_t`` in (0, 1) the write strength).
``gated_delta_recurrence`` below is that loop, one token a step: the
oracle of the tests, never the timed path (32,768 sequential steps).

``gated_delta_rule`` computes the same thing a chunk of ``chunk`` tokens
at a time (the WY form of the delta rule, arXiv:2406.06484, with the
decay folded in). Inside a chunk, with ``G`` the decay cumulated from
the chunk's first token, ``D[i, j] = exp(G_i - G_j)`` for ``i >= j`` and
``A = strictly_lower(diag(beta) K K^T . D)``::

    T  = (I + A)^-1
    U  = T (beta V)              W = T (beta K . exp(G))
    V' = U - W S                 O = (Q . exp(G)) S + lower(Q K^T . D) V'
    S <- exp(G_last) S + (K . exp(G_last - G))^T V'

Everything but ``V'`` and ``S`` is independent of the state, so it is
batched matmuls over all chunks at once; a ``lax.scan`` over the chunks
carries ``S`` and runs two small matmuls a step; ``O`` is batched again
from the states the scan hands back. Every decay factor is an ``exp`` of
a difference that is ``<= 0``, so nothing overflows however negative
``g`` is. Checked against the per-token loop in float64: equal to 1e-15.

Precision: the cumulated decay, ``T`` and ``S`` are float32; the
matmuls take their operands in the compute dtype (``q``'s) and
accumulate in float32, the state too when it is an operand (as the
published Triton kernels do). The inverse is the product form, exact
because ``A`` is nilpotent (strictly lower, ``A^chunk = 0``)::

    (I + A)^-1 = (I - A)(I + A^2)(I + A^4) ... (I + A^(chunk/2))

in float32 at matmul precision "highest" (a default-precision float32
matmul on the TPU rounds its operands to bfloat16): log2(chunk) - 1
squarings and log2(chunk) products of chunk x chunk matrices, 0.3% of
the layer's FLOPs, where a triangular solve would be ``chunk``
dependent steps.

Memory: autodiff through the scan keeps one state a chunk (in the
compute dtype, as its matmul operand) and the chunk's ``W``, ``V'``,
decayed keys and C x C matrices (float32 ones, which the TPU pads from
64 to 128 lanes): about 4 GB a layer at 32,768 tokens, 32 heads of 128 x
128 and chunk 64, too much beside 10 GB of optimizer state. So a
sequence runs in segments of ``segment`` chunks, each under
``jax.checkpoint``, with the state carried from one to the next: the
backward rebuilds one segment's forward at a time and holds a
``1 / segments`` part of that (a recompute by groups of chunks; its cost
is one more forward of the rule, 0.5% of the cell's FLOPs). The inverse
has a VJP of its own from ``T`` alone. The output carries
``checkpoint_name`` ``GDN_OUT_NAME`` so that a remat policy can name it
as it names flash's (today's policies do not: the backward rebuilds the
segments' residuals whether or not ``o`` was kept).
"""

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# checkpoint_name of the rule's output (models/transformer.py:
# remat_block's policies name it beside flash's)
GDN_OUT_NAME = "gdn_out"
DEFAULT_CHUNK = 64
# chunks a segment: 8192 tokens at chunk 64
DEFAULT_SEGMENT = 128
# the implementation the compile-time line names; a later Pallas kernel
# for the chunk-to-chunk state would say "pallas"
IMPL = "xla"


def _matmul(a, b, dtype):
    """Batched ``a @ b``, operands in ``dtype``, float32 out (float64
    for float64 operands: the tests' exact comparison)."""
    return jnp.matmul(
        a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.promote_types(dtype, jnp.float32))


def _inverse_product(a):
    size = a.shape[-1]
    eye = jnp.eye(size, dtype=a.dtype)
    inverse, power = eye - a, a
    span = 2
    while span < size:
        power = _exact(power, power)  # a^span
        inverse = _exact(inverse, eye + power)
        span *= 2
    return inverse


def _exact(x, y):
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., C, C),
    C a power of two, by the product form (module docstring). Its
    gradient is the inverse's own, ``da = -T^T dT T^T``, from ``T``
    alone: autodiff of the product would keep every square and every
    partial product, ten C x C float32 matrices a chunk and head where
    this keeps one."""
    size = a.shape[-1]
    if size & (size - 1):
        raise ValueError("the chunk must be a power of two, got %d" % size)
    return _inverse_product(a)


def _unit_lower_inverse_fwd(a):
    inverse = unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, d_inverse):
    t = jnp.swapaxes(inverse, -1, -2)
    return (-_exact(_exact(t, d_inverse), t),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _chunks(state, q, k, v, g, beta, state_dtype, decay_dtype):
    """The rule over whole chunks from the state ``state``: q, k (B, Hk,
    1, N, C, Dk), v (B, Hk, R, N, C, Dv), g, beta (B, Hk, R, N, C)
    float32 -> (the state after them, o (B, Hk, R, N, C, Dv) float32)."""
    dtype, chunk = q.dtype, q.shape[-2]
    # G, from the chunk's first token
    cum = jnp.cumsum(g.astype(decay_dtype), axis=-1).astype(g.dtype)
    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    # exp of a masked difference: above the diagonal the difference is
    # positive and may overflow, and inf x 0 would poison the gradient
    decay = jnp.exp(jnp.where(
        row >= col, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    into = jnp.exp(cum)  # what reaches token i of the entering state
    last = cum[..., -1:]
    onto = jnp.exp(last - cum)  # what is left of token i at the end

    swap = lambda x: jnp.swapaxes(x, -1, -2)
    kk = _matmul(k, swap(k), dtype)  # (B, Hk, 1, N, C, C)
    a = jnp.where(row > col, kk * beta[..., :, None] * decay, 0.0)
    t = unit_lower_inverse(a)
    u = _matmul(t, beta[..., None] * v, dtype)
    # matmul operands only from here on: kept in the compute dtype
    w = _matmul(t, (beta * into)[..., None] * k, dtype).astype(dtype)
    k_onto = (onto[..., None] * k).astype(dtype)  # (B, Hk, R, N, C, Dk)
    q_into = into[..., None] * q
    attn = jnp.where(row >= col, _matmul(q, swap(k), dtype) * decay, 0.0)

    def step(state, xs):
        u_n, w_n, k_n, end = xs
        new_v = u_n - _matmul(w_n, state, dtype)
        held = state
        state = (
            jnp.exp(end)[..., None] * state
            + _matmul(swap(k_n), new_v, dtype)
        ).astype(state_dtype)
        return state, (new_v.astype(dtype), held.astype(dtype))

    chunks_first = lambda x: jnp.moveaxis(x, 3, 0)
    state, (new_v, states) = jax.lax.scan(
        step, state, tuple(map(chunks_first, (u, w, k_onto, last))))
    new_v = jnp.moveaxis(new_v, 0, 3)
    states = jnp.moveaxis(states, 0, 3)  # (B, Hk, R, N, Dk, Dv)
    return state, (
        _matmul(q_into, states, dtype) + _matmul(attn, new_v, dtype))


def gated_delta_rule(q, k, v, g, beta, chunk=DEFAULT_CHUNK,
                     segment=DEFAULT_SEGMENT, state_dtype=None,
                     decay_dtype=None):
    """q, k: (B, Hk, S, Dk), already normalised and scaled; v: (B, Hv,
    S, Dv) with ``Hv`` a multiple of ``Hk`` (value head ``h`` reads key
    head ``h // (Hv / Hk)``; q and k are never repeated in memory); g,
    beta: (B, Hv, S) float32. Returns o (B, Hv, S, Dv) in ``v``'s dtype.

    A sequence longer than ``segment`` chunks runs a segment at a time,
    each under ``jax.checkpoint``, the state carried between them: the
    backward then holds one segment's chunk matrices and states, not
    the sequence's (module docstring). A length that ``chunk`` (or, past
    one segment, the segment) does not divide is padded with tokens that
    write nothing and decay nothing (``beta = 0``, ``g = 0``) and cut
    again. ``state_dtype``, ``decay_dtype``: what the scan carries
    ``S`` in and what the decay is cumulated in (None: float32);
    anything else is for the tests and the benchmark's precision
    experiment (``scripts/gdn_precision.py``)."""
    batch, hk, seq, dk = q.shape
    hv, dv = v.shape[1], v.shape[3]
    if hv % hk:
        raise ValueError(
            "%d value heads do not divide over %d key heads" % (hv, hk))
    rep = hv // hk
    wide = jnp.promote_types(q.dtype, jnp.float32)
    state_dtype = state_dtype or wide
    decay_dtype = decay_dtype or wide
    span = chunk if seq <= chunk * segment else chunk * segment
    pad = -seq % span
    if pad:
        widen = lambda x: jnp.pad(
            x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    segments = max(1, (seq + pad) // (chunk * segment))
    num = (seq + pad) // (segments * chunk)  # chunks a segment
    # segments first; key-like (B, Hk, 1, N, C, Dk), value-like (B, Hk,
    # R, N, C, ...)
    split = lambda x, heads, *rest: jnp.moveaxis(
        x.reshape((batch,) + heads + (segments, num, chunk) + rest),
        1 + len(heads), 0)
    xs = (
        split(q, (hk, 1), dk), split(k, (hk, 1), dk),
        split(v, (hk, rep), dv),
        split(g.astype(wide), (hk, rep)), split(beta.astype(wide), (hk, rep)),
    )
    run = lambda state, xs: _chunks(state, *xs, state_dtype, decay_dtype)
    state0 = jnp.zeros((batch, hk, rep, dk, dv), state_dtype)
    if segments == 1:
        _, o = run(state0, tuple(x[0] for x in xs))
    else:
        _, o = jax.lax.scan(jax.checkpoint(run), state0, xs)
        o = jnp.moveaxis(o, 0, 3)  # (B, Hk, R, segments, N, C, Dv)
    o = o.reshape(batch, hv, seq + pad, dv)[:, :, :seq]
    return checkpoint_name(o.astype(v.dtype), GDN_OUT_NAME)


def gated_delta_recurrence(q, k, v, g, beta):
    """The rule one token a step, in the inputs' dtype: the definition
    the chunked form is tested against. Shapes as
    ``gated_delta_rule``."""
    rep = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(x, rep, axis=1) for x in (q, k))

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (B, H, D) and (B, H)
        state = jnp.exp(g_t)[..., None, None] * state
        u = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    tokens_first = lambda x: jnp.moveaxis(x, 2, 0)
    state0 = jnp.zeros(v.shape[:2] + (q.shape[-1], v.shape[-1]), v.dtype)
    _, o = jax.lax.scan(
        step, state0, tuple(map(tokens_first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2)
