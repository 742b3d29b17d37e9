"""Sequence/context parallelism: ring attention and all-to-all (Ulysses).

The reference has no long-context support at all (SURVEY.md §5); the only
axis it ever shards is the embedding-id axis across PS pods. These ops
are the new TPU-first capability: attention over a sequence sharded
across the ``sp`` mesh axis, communicating over ICI.

Two schedules, both differentiable (autodiff through scan/ppermute —
``ppermute``/``all_to_all`` have transpose rules, so the backward pass is
the reverse ring):

- ``ring_attention``: KV blocks rotate around the sp ring via
  ``ppermute`` while each device folds them into a flash-style online
  softmax. Memory O(S_local), comm overlaps compute under XLA latency
  hiding. Blockwise/RingAttention schedule (Liu et al.) — re-derived,
  not ported.
- ``ulysses_attention``: ``all_to_all`` re-shards seq <-> heads so each
  device holds the full sequence for H/sp heads, runs ordinary (flash)
  attention locally, and all-to-alls back. Cheaper comm for moderate S,
  requires heads % sp == 0.

Both are called *inside* jit on global arrays; they open a shard_map
manual region over the mesh.
"""

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.parallel.mesh import DATA_AXES

NEG_INF = -1e30


def _default_spec():
    # (batch, heads, seq, head_dim): batch over data axes, heads over tp,
    # seq over sp.
    return P(DATA_AXES, "tp", "sp", None)


def _block_update(carry, k_blk, v_blk, q, mask):
    """Fold one KV block into the running (m, l, acc) softmax state."""
    m_prev, l_prev, acc = carry
    s = (
        jnp.einsum(
            "bhqd,bhkd->bhqk", q, k_blk, preferred_element_type=jnp.float32
        )
    )
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l_prev * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd",
        p,
        v_blk.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _spec_axis_names(spec):
    names = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            names.extend(entry)
        else:
            names.append(entry)
    return tuple(names)


def _make_flash_ring(axis_name, sp_size, causal, sm_scale, spec_axes,
                     block_q, block_k, interpret):
    """Per-device ring fold whose block compute is the Pallas flash
    kernel (ops/flash_attention.py) instead of einsum math.

    Forward: each ring step runs the kernel on (q, k_blk) and merges
    the partial (o_t, lse_t) into the running output with the standard
    log-sum-exp combine. Backward (custom_vjp — the kernel's own vjp
    can't serve because the merge needs lse as a live output): re-rotate
    the KV ring, call the kernel's backward per block with the GLOBAL
    (o, lse, do) — exp(s - lse_global) IS the global softmax restricted
    to the block — accumulate dq locally, and let each block's (dk, dv)
    accumulators ride the ring home (sp hops = full circle).
    Schedule per Liu et al. RingAttention; implementation original.
    """
    from elasticdl_tpu.ops import flash_attention as F

    NEG = F.NEG_INF
    vary = lambda x: jax_compat.pvary(x, spec_axes)

    def lse_w(lse_from, lse_to):
        # (bh, 1, S) log-weights -> (bh, S, 1) multiplicative weights
        return jnp.exp(lse_from - lse_to).transpose(0, 2, 1)

    def kernel_fwd(q_m, k_blk, v_blk, src, my_idx):
        def zeros(_):
            return (
                jnp.zeros(q_m.shape, jnp.float32),
                jnp.full(
                    (q_m.shape[0], 1, q_m.shape[1]), NEG, jnp.float32
                ),
            )

        def run(_):
            def call(diag):
                def inner(_):
                    o_t, lse_t = F._fwd(
                        q_m, k_blk, v_blk, sm_scale, diag,
                        block_q, block_k, interpret,
                    )
                    return o_t.astype(jnp.float32), lse_t

                return inner

            if not causal:
                return call(False)(None)
            return jax.lax.cond(
                src == my_idx, call(True), call(False), None
            )

        if not causal:
            return run(None)
        return jax.lax.cond(src > my_idx, zeros, run, None)

    def kernel_bwd(q_m, k_blk, v_blk, o_m, lse, do_m, src, my_idx):
        def zeros(_):
            return (
                jnp.zeros(q_m.shape, jnp.float32),
                jnp.zeros(k_blk.shape, jnp.float32),
                jnp.zeros(v_blk.shape, jnp.float32),
            )

        def run(_):
            def call(diag):
                def inner(_):
                    dq, dk, dv = F._bwd(
                        q_m, k_blk, v_blk, o_m, lse, do_m, sm_scale,
                        diag, block_q, block_k, interpret,
                    )
                    return (
                        dq.astype(jnp.float32),
                        dk.astype(jnp.float32),
                        dv.astype(jnp.float32),
                    )

                return inner

            if not causal:
                return call(False)(None)
            return jax.lax.cond(
                src == my_idx, call(True), call(False), None
            )

        if not causal:
            return run(None)
        return jax.lax.cond(src > my_idx, zeros, run, None)

    perm = [(j, (j + 1) % sp_size) for j in range(sp_size)]

    @jax.custom_vjp
    def fold(q_m, k_m, v_m):
        o, _ = _fold_fwd(q_m, k_m, v_m)
        return o

    def _fold_fwd(q_m, k_m, v_m):
        # only the causal mask needs the device index; the non-causal
        # fold ignores src/my_idx entirely, and leaving a dead
        # axis_index in the program lowers to a PartitionId op the CPU
        # SPMD partitioner rejects
        my_idx = (
            jax.lax.axis_index(axis_name) if causal else jnp.uint32(0)
        )
        bh, seq, _ = q_m.shape

        def step(carry, t):
            o, lse, k_blk, v_blk = carry
            src = (my_idx - t) % sp_size
            o_t, lse_t = kernel_fwd(q_m, k_blk, v_blk, src, my_idx)
            lse_new = jnp.logaddexp(lse, lse_t)
            o = o * lse_w(lse, lse_new) + o_t * lse_w(lse_t, lse_new)
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            return (o, lse_new, k_blk, v_blk), None

        init = (
            vary(jnp.zeros(q_m.shape, jnp.float32)),
            vary(jnp.full((bh, 1, seq), NEG, jnp.float32)),
            k_m,
            v_m,
        )
        (o, lse, _, _), _ = jax.lax.scan(
            step, init, jnp.arange(sp_size)
        )
        o = o.astype(q_m.dtype)
        return o, (q_m, k_m, v_m, o, lse)

    def _fold_bwd(res, do_m):
        q_m, k_m, v_m, o_m, lse = res
        my_idx = (
            jax.lax.axis_index(axis_name) if causal else jnp.uint32(0)
        )

        def step(carry, t):
            dq, k_blk, v_blk, dk_acc, dv_acc = carry
            src = (my_idx - t) % sp_size
            dq_t, dk_t, dv_t = kernel_bwd(
                q_m, k_blk, v_blk, o_m, lse, do_m, src, my_idx
            )
            dq = dq + dq_t
            dk_acc = dk_acc + dk_t
            dv_acc = dv_acc + dv_t
            # the (dk, dv) accumulators ride with their blocks: after
            # sp hops both are back on the block's owner
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
            dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
            return (dq, k_blk, v_blk, dk_acc, dv_acc), None

        init = (
            vary(jnp.zeros(q_m.shape, jnp.float32)),
            k_m,
            v_m,
            vary(jnp.zeros(k_m.shape, jnp.float32)),
            vary(jnp.zeros(v_m.shape, jnp.float32)),
        )
        (dq, _, _, dk, dv), _ = jax.lax.scan(
            step, init, jnp.arange(sp_size)
        )
        return (
            dq.astype(q_m.dtype),
            dk.astype(k_m.dtype),
            dv.astype(v_m.dtype),
        )

    fold.defvjp(_fold_fwd, _fold_bwd)
    return fold


def ring_attention(
    q,
    k,
    v,
    mesh,
    axis_name="sp",
    causal=False,
    sm_scale=None,
    spec=None,
    remat=True,
    block_impl="auto",
    block_q=None,
    block_k=None,
    interpret=False,
):
    """Attention with q/k/v sequence-sharded over ``axis_name``.

    Shapes are the global (batch, heads, seq, head_dim); sharding of the
    operands must match ``spec`` (default: batch over dp/fsdp, heads over
    tp, seq over sp).

    ``block_impl`` picks the per-block compute inside the ring fold:
    "einsum" (XLA math, any backend), "flash" (the Pallas kernel —
    per-device work becomes true flash attention), or "auto" (flash on
    TPU when the local sequence fits the kernel's block constraints).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    spec = spec if spec is not None else _default_spec()
    sp_size = mesh.shape[axis_name]
    if sp_size == 1:
        from elasticdl_tpu.ops.attention import dot_product_attention

        # honor block_impl even in the ring-of-one degenerate case: a
        # user who pinned "einsum" (e.g. around a kernel bug) must not
        # silently get the Pallas path back via impl="auto"
        impl = {"flash": "pallas", "einsum": "xla"}.get(
            block_impl, "auto"
        )
        return dot_product_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, impl=impl,
            block_q=block_q, block_k=block_k, interpret=interpret,
            mesh=mesh, spec=spec,
        )

    spec_axes = _spec_axis_names(spec)
    seq_loc_global = q.shape[2] // sp_size
    resolved = block_impl
    if resolved == "auto":
        from elasticdl_tpu.ops import flash_attention as _F

        blk = _F._auto_block(seq_loc_global, 512)
        ok = (
            jax.default_backend() == "tpu"
            and seq_loc_global >= 128
            and seq_loc_global % min(blk, seq_loc_global) == 0
        )
        resolved = "flash" if ok else "einsum"
    if resolved not in ("flash", "einsum"):
        raise ValueError("unknown ring block_impl %r" % (block_impl,))
    if resolved == "flash":
        from elasticdl_tpu.ops import flash_attention as _F

        blk_q = min(
            block_q or _F._auto_block(seq_loc_global, 512),
            seq_loc_global,
        )
        blk_k = min(
            block_k or _F._auto_block(seq_loc_global, 1024),
            seq_loc_global,
        )
        if seq_loc_global % blk_q or seq_loc_global % blk_k:
            # the kernel grid would silently skip the tail rows
            raise ValueError(
                "flash ring fold needs the local sequence (%d = global "
                "%d / sp %d) divisible by the blocks (%d, %d)"
                % (seq_loc_global, q.shape[2], sp_size, blk_q, blk_k)
            )
        fold = _make_flash_ring(
            axis_name, sp_size, causal, sm_scale, spec_axes,
            blk_q, blk_k, interpret,
        )

        def flash_local_fn(q_loc, k_loc, v_loc):
            b, h, s, d = q_loc.shape
            merge = lambda t: t.reshape(b * h, s, d)
            o = fold(merge(q_loc), merge(k_loc), merge(v_loc))
            return o.reshape(b, h, s, d)

        # check_vma=False: under the checker jax 0.9.0 refuses the
        # kernel inside this fold in interpret mode ("dynamic_slice
        # requires varying manual axes to match"), although the kernel's
        # outputs declare their vma; the specs here mirror the (long
        # VMA-checked) einsum path below
        return jax_compat.shard_map(
            flash_local_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)

    def local_fn(q_loc, k_loc, v_loc):
        my_idx = jax.lax.axis_index(axis_name)
        seq_loc = q_loc.shape[2]
        q32 = q_loc.astype(jnp.float32) * sm_scale

        def step(carry, t):
            m, l, acc, k_blk, v_blk = carry
            # After t hops the block on this device originated at shard
            # (my_idx - t) mod sp.
            src = (my_idx - t) % sp_size

            def masked_update(operands):
                m, l, acc, k_blk, v_blk = operands
                if causal:
                    q_pos = my_idx * seq_loc + jnp.arange(seq_loc)
                    k_pos = src * seq_loc + jnp.arange(seq_loc)
                    mask = q_pos[:, None] >= k_pos[None, :]
                    mask = mask[None, None]
                else:
                    mask = None
                return _block_update((m, l, acc), k_blk, v_blk, q32, mask)

            if causal:
                # Blocks strictly in the future contribute nothing: skip
                # the matmuls entirely (branch selected at runtime).
                m, l, acc = jax.lax.cond(
                    src > my_idx,
                    lambda operands: operands[:3],
                    masked_update,
                    (m, l, acc, k_blk, v_blk),
                )
            else:
                m, l, acc = masked_update((m, l, acc, k_blk, v_blk))
            # Rotate KV one hop around the ring (device j -> j+1).
            perm = [(j, (j + 1) % sp_size) for j in range(sp_size)]
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            return (m, l, acc, k_blk, v_blk), None

        step_fn = jax.checkpoint(step) if remat else step
        batch, heads = q_loc.shape[0], q_loc.shape[1]
        # Literal-zero inits are "unvarying" in shard_map's VMA typing
        # while the scan outputs vary per device; pvary reconciles them.
        # Vary only over the axes the in/out spec mentions: axes absent
        # from the spec (e.g. pp/ep) must stay unvarying or the out-spec
        # check rejects the result.
        spec_axes = []
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                spec_axes.extend(entry)
            else:
                spec_axes.append(entry)
        vary = lambda x: jax_compat.pvary(
            x, tuple(spec_axes)
        )
        init = (
            vary(jnp.full((batch, heads, seq_loc), NEG_INF, jnp.float32)),
            vary(jnp.zeros((batch, heads, seq_loc), jnp.float32)),
            vary(
                jnp.zeros(
                    (batch, heads, seq_loc, q_loc.shape[3]), jnp.float32
                )
            ),
            k_loc,
            v_loc,
        )
        (m, l, acc, _, _), _ = jax.lax.scan(
            step_fn, init, jnp.arange(sp_size)
        )
        safe_l = jnp.where(l > 0.0, l, 1.0)
        return (acc / safe_l[..., None]).astype(q_loc.dtype)

    return jax_compat.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)


def ulysses_attention(
    q,
    k,
    v,
    mesh,
    axis_name="sp",
    causal=False,
    sm_scale=None,
    spec=None,
    attention_fn=None,
):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses schedule).

    Re-shards (heads sharded <- seq sharded), runs full-sequence local
    attention per head group, re-shards back. ``attention_fn(q, k, v,
    causal, sm_scale)`` defaults to the flash/XLA dispatcher.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    spec = spec if spec is not None else _default_spec()
    sp_size = mesh.shape[axis_name]
    if attention_fn is None:
        from elasticdl_tpu.ops.attention import dot_product_attention

        attention_fn = dot_product_attention
    if sp_size == 1:
        return attention_fn(q, k, v, causal=causal, sm_scale=sm_scale)
    # The all_to_all splits the *per-device* head count (global heads
    # already divided by whatever axes spec shards dim 1 over).
    head_axes = tuple(spec)[1] if len(tuple(spec)) > 1 else None
    if head_axes is None:
        head_shard = 1
    elif isinstance(head_axes, (tuple, list)):
        head_shard = math.prod(mesh.shape[a] for a in head_axes)
    else:
        head_shard = mesh.shape[head_axes]
    local_heads = q.shape[1] // head_shard
    if local_heads % sp_size:
        raise ValueError(
            "ulysses needs per-device heads (%d global / %d sharded = %d)"
            " divisible by sp (%d)"
            % (q.shape[1], head_shard, local_heads, sp_size)
        )

    def local_fn(q_loc, k_loc, v_loc):
        # (B, H_loc*sp, S/sp, D) -> (B, H_loc, S, D): scatter heads,
        # gather sequence.
        def seq_to_heads(x):
            return jax.lax.all_to_all(
                x, axis_name, split_axis=1, concat_axis=2, tiled=True
            )

        def heads_to_seq(x):
            return jax.lax.all_to_all(
                x, axis_name, split_axis=2, concat_axis=1, tiled=True
            )

        out = attention_fn(
            seq_to_heads(q_loc),
            seq_to_heads(k_loc),
            seq_to_heads(v_loc),
            causal=causal,
            sm_scale=sm_scale,
        )
        return heads_to_seq(out)

    return jax_compat.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)
