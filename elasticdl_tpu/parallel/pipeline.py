"""Pipeline parallelism over the ``pp`` mesh axis.

No reference counterpart: the reference's only model-parallel axis is the
embedding-id axis across PS pods (SURVEY.md §2.12, worker/ps_client.py
id-mod routing); layer pipelining is a new TPU-first capability, designed
the XLA way rather than as a port of any NCCL send/recv schedule.

Two schedules:

- ``schedule="gpipe"``: the round-1 design — one differentiable
  shard_map + scan + ppermute forward, backward via XLA autodiff of the
  scan. Simple, but autodiff saves the scan carry every tick, and the
  carry holds the whole per-device output buffer: O((M+S)·M) microbatch
  activations per device.

- ``schedule="1f1b"`` (default): explicitly scheduled forward AND
  backward (``jax.custom_vjp``). The forward saves exactly one
  activation per (chunk, microbatch) — the stage input — and the
  backward is its own reverse-pipeline scan that recomputes each
  stage under ``jax.vjp`` and accumulates parameter cotangents:
  O(V·M) activations per device, the 1F1B memory discipline. With
  ``num_chunks=V > 1`` the stage stack is split into V *interleaved
  virtual chunks* per device (Megatron-LM's interleaved schedule):
  chunk ``c`` lives on device ``c mod S``, all hops — including the
  wrap from device S-1 back to 0 — are the same cyclic ppermute, and
  the warmup/drain bubble divides by V (see :func:`schedule_info`).

  Honesty note on the name: under XLA the whole step is one program and
  ``custom_vjp`` runs the full forward before the backward, so the
  classic one-forward-one-backward *temporal* interleave cannot be
  expressed; in lockstep SPMD it would also *grow* the bubble (every
  tick costs a full F+B on all devices, masked or not). What survives
  of 1F1B on TPU is exactly what this implements: the scheduled
  backward, its linear activation memory, and the interleaved-chunk
  bubble reduction.

Composability: the schedule is per-data-shard, so pp composes freely
with data parallelism (batch stays sharded over dp/fsdp throughout).
Tensor parallelism composes *within* a stage: pass ``param_specs``
whose leaves shard stage-parameter dims over ``tp`` and use manual
collectives (``jax.lax.psum(..., "tp")``) inside ``stage_fn`` — the
shard_map manualizes every mesh axis, so the stage body addresses
``tp`` directly while ppermute routes activations along ``pp`` only.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.parallel.mesh import DATA_AXES


def stack_stage_params(stage_params_list):
    """Stack per-stage parameter pytrees on a new leading stage axis."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params_list
    )


def unstack_stage_params(stacked, num_stages):
    """Inverse of :func:`stack_stage_params` (host-side, for export)."""
    return [
        jax.tree_util.tree_map(lambda leaf: leaf[i], stacked)
        for i in range(num_stages)
    ]


def pipeline_spec(leaf=None):
    """PartitionSpec for stacked stage params: stage axis over ``pp``."""
    return P("pp")


def schedule_info(num_stages, num_microbatches, num_chunks=1,
                  fwd_cost=1.0, bwd_cost=2.0):
    """Analytic schedule accounting (the 'measured bubble' the tests
    assert against actual scan lengths).

    GPipe (V=1 forced): forward scan of M+S-1 ticks at stage cost f,
    backward M+S-1 ticks at f+b (remat tick) -> bubble (S-1)/(M+S-1).

    1f1b with V chunks: C = S*V chunks of cost f/V; forward M+C-1
    ticks, backward M+C-1 ticks at (f+b)/V -> useful fraction
    M*V/(M+S*V-1); bubble (S*V-1 - (V-1)*M)/(M+S*V-1)... computed
    directly below as 1 - useful/total.
    """
    S, M, V = num_stages, num_microbatches, num_chunks
    ticks = M + S * V - 1  # per direction
    total = ticks * (fwd_cost + (fwd_cost + bwd_cost)) / V
    useful = M * (2 * fwd_cost + bwd_cost)
    return {
        "ticks_per_direction": ticks,
        "bubble_fraction": 1.0 - useful / total,
        "activations_per_device": V * M,
    }


def pipeline_apply(
    stage_fn,
    stacked_params,
    x,
    num_microbatches,
    mesh,
    axis="pp",
    batch_spec=None,
    remat=True,
    schedule="1f1b",
    num_chunks=1,
    param_specs=None,
    params_layout="chunk",
):
    """Run ``x`` through a stack of pipeline stages.

    Args:
      stage_fn: ``(stage_params, activations) -> activations`` — one
        stage's computation on a (microbatch, ...) activation block. Must
        preserve the activation shape (homogeneous stages). Runs inside
        the shard_map manual region: it may use manual collectives over
        other mesh axes (e.g. ``jax.lax.psum(h, "tp")``).
      stacked_params: pytree whose leaves carry a leading chunk axis of
        size ``mesh.shape[axis] * num_chunks``, laid out ``P(axis)`` on
        that leading dim (finer per-leaf layouts via ``param_specs``).
      x: global batch ``(batch, ...)``, batch dim sharded over dp/fsdp
        and replicated over ``axis``.
      num_microbatches: pipeline depth M; each data shard's rows are
        split into M microbatches (local batch must divide evenly).
      batch_spec: PartitionSpec of ``x`` (default: dim 0 over dp/fsdp).
      schedule: "1f1b" (explicit scheduled backward, linear memory,
        supports interleaving) or "gpipe" (autodiff backward).
      remat: gpipe only (checkpoint each tick). The 1f1b schedule
        ALWAYS recomputes each stage from its saved input in the
        backward; the flag is ignored there.
      num_chunks: interleaved virtual chunks per device (V). V > 1
        requires ``num_microbatches <= num_stages`` (the conflict-free
        window of the interleaved schedule) and schedule="1f1b".
      params_layout: how the stacked chunk axis is ordered. "chunk"
        (default): chunk c at row c — the topology-portable layout a
        checkpoint wants — but devices need rows device-major, so V > 1
        pays a cross-shard permutation of the whole stage stack per
        step (fwd params, bwd params, bwd param-cotangents: ~3x the
        stage-stack bytes over ICI every step). "device": the caller
        stores the stack device-major at rest (row d*V + v holds chunk
        v*S + d — ``device_major_order``); the permutes vanish and
        parameter cotangents return device-major to match. Checkpoints
        of device-major state are pinned to (S, V) — convert with
        ``chunk major <-> device major`` helpers at
        save/restore-for-a-different-topology boundaries
        (models/pipeline_transformer.py wires this).
      param_specs: optional pytree of PartitionSpecs for
        ``stacked_params`` (default ``P(axis)`` on the leading dim);
        use to shard stage-parameter dims over ``tp`` for
        tensor-parallel stages.

    Returns the stacked stages' output with the same shape/sharding as
    ``x`` would have after all chunks' sequential application.
    """
    if params_layout not in ("chunk", "device"):
        raise ValueError(
            "params_layout must be 'chunk' or 'device', got %r"
            % (params_layout,)
        )
    num_stages = mesh.shape[axis]
    stage_axis_sizes = {
        leaf.shape[0] for leaf in jax.tree_util.tree_leaves(stacked_params)
    }
    if len(stage_axis_sizes) != 1:
        raise ValueError(
            "Inconsistent stage-axis sizes in stacked params: %s"
            % sorted(stage_axis_sizes)
        )
    (stacked_size,) = stage_axis_sizes
    if num_stages == 1 and num_chunks == 1:
        # Degenerate pipeline: sequential application of every stacked
        # stage, no collectives.
        def body(carry, stage_params):
            return stage_fn(stage_params, carry), None

        out, _ = jax.lax.scan(body, x, stacked_params)
        return out
    num_chunks = int(num_chunks)
    if stacked_size != num_stages * num_chunks:
        raise ValueError(
            "Stacked stage axis (%d) must equal %s extent * num_chunks "
            "(%d * %d)" % (stacked_size, axis, num_stages, num_chunks)
        )
    if num_chunks > 1:
        if schedule != "1f1b":
            raise ValueError("num_chunks > 1 requires schedule='1f1b'")
        if num_microbatches > num_stages:
            raise ValueError(
                "interleaved schedule needs num_microbatches (%d) <= "
                "num_stages (%d) — the conflict-free window; raise pp, "
                "lower M, or process more microbatches per update via "
                "the trainer's grad_accum_steps (each accumulation "
                "slice runs its own M<=S pipeline pass with exact "
                "large-batch semantics)"
                % (num_microbatches, num_stages)
            )
    spec = batch_spec if batch_spec is not None else P(DATA_AXES)
    if param_specs is None:
        param_specs = jax.tree_util.tree_map(
            lambda _: pipeline_spec(), stacked_params
        )
    if schedule == "gpipe":
        if params_layout != "chunk":
            raise ValueError(
                "params_layout='device' requires schedule='1f1b' "
                "(gpipe has no interleaving, so there is nothing to "
                "save)"
            )
        return _gpipe_apply(
            stage_fn, stacked_params, x, num_microbatches, mesh, axis,
            spec, param_specs, remat,
        )
    if schedule != "1f1b":
        raise ValueError("unknown pipeline schedule %r" % schedule)
    return _1f1b_apply(
        stage_fn, stacked_params, x, num_microbatches, mesh, axis,
        spec, param_specs, num_chunks, params_layout,
    )


# ---------------------------------------------------------------------------
# GPipe: differentiable forward, backward by scan autodiff (round-1 design)
# ---------------------------------------------------------------------------

def _gpipe_apply(stage_fn, stacked_params, x, M, mesh, axis, spec,
                 param_specs, remat):
    num_stages = mesh.shape[axis]

    def local_fn(params_loc, x_loc):
        # Local stage params: shard_map leaves a unit stage axis.
        params = jax.tree_util.tree_map(
            lambda leaf: jax.lax.squeeze(leaf, (0,)), params_loc
        )
        idx = jax.lax.axis_index(axis)
        batch_loc = x_loc.shape[0]
        if batch_loc % M != 0:
            raise ValueError(
                "Local batch %d not divisible by %d microbatches"
                % (batch_loc, M)
            )
        x_mb = x_loc.reshape((M, batch_loc // M) + x_loc.shape[1:])

        # Activation buffers derived from x_loc already vary over the
        # batch axes; each stage additionally computes different values,
        # so add ``pp`` to the varying set (shard_map VMA typing).
        vary = lambda v: jax_compat.pvary(v, (axis,))
        # Forward one hop toward the next stage; stage 0 receives zeros
        # (it reads fresh microbatches instead).
        perm = [(j, j + 1) for j in range(num_stages - 1)]

        def tick(carry, t):
            recv, outputs = carry
            inp = jnp.where(
                idx == 0,
                jax.lax.dynamic_index_in_dim(
                    x_mb, jnp.minimum(t, M - 1), 0, keepdims=False
                ),
                recv,
            )
            out = stage_fn(params, inp)
            # The microbatch leaving the last stage at tick t entered the
            # pipeline at tick t - (S - 1).
            m = t - (num_stages - 1)
            write = jnp.logical_and(idx == num_stages - 1, m >= 0)
            slot = jnp.clip(m, 0, M - 1)
            current = jax.lax.dynamic_index_in_dim(
                outputs, slot, 0, keepdims=False
            )
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, out, current), slot, 0
            )
            recv = jax.lax.ppermute(out, axis, perm)
            return (recv, outputs), None

        tick_fn = jax.checkpoint(tick) if remat else tick
        init = (
            vary(jnp.zeros_like(x_mb[0])),
            vary(jnp.zeros_like(x_mb)),
        )
        (_, outputs), _ = jax.lax.scan(
            tick_fn, init, jnp.arange(M + num_stages - 1)
        )
        # Only the last stage holds real outputs (others are zeros);
        # psum over pp replicates the result onto every stage.
        outputs = jax.lax.psum(outputs, axis)
        return outputs.reshape((batch_loc,) + x_loc.shape[1:])

    return jax_compat.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, spec),
        out_specs=spec,
    )(stacked_params, x)


# ---------------------------------------------------------------------------
# 1f1b: explicitly scheduled forward + backward via custom_vjp
# ---------------------------------------------------------------------------


def _spec_axes(spec):
    """Mesh axis names appearing in a PartitionSpec (flattened)."""
    names = []
    for entry in tuple(spec):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            names.extend(entry)
        else:
            names.append(entry)
    return tuple(names)

def device_major_order(S, V):
    """Chunk-axis permutation putting row ``d*V + v`` = chunk
    ``v*S + d`` — the order P("pp") slicing needs so device ``d`` gets
    its interleaved chunks {d, d+S, ..., d+(V-1)S} as local rows."""
    import numpy as _np

    return _np.arange(S * V).reshape(V, S).T.reshape(-1)


def chunk_major_order(S, V):
    """Inverse of :func:`device_major_order`."""
    import numpy as _np

    return _np.arange(S * V).reshape(S, V).T.reshape(-1)


def _device_major(stacked, S, V):
    """Reorder the chunk axis so P("pp") slicing hands device ``d`` its
    interleaved chunks as local rows [V] (see device_major_order).
    A cross-shard gather of the whole stage stack when traced on a
    pp-sharded array — the per-step cost params_layout="device"
    removes."""
    if V == 1:
        return stacked
    order = device_major_order(S, V)
    return jax.tree_util.tree_map(
        lambda leaf: jnp.take(leaf, order, axis=0), stacked
    )


def _chunk_major(stacked, S, V):
    """Inverse of :func:`_device_major` (for parameter cotangents)."""
    if V == 1:
        return stacked
    order = chunk_major_order(S, V)
    return jax.tree_util.tree_map(
        lambda leaf: jnp.take(leaf, order, axis=0), stacked
    )


def _1f1b_apply(stage_fn, stacked_params, x, M, mesh, axis, spec,
                param_specs, V, params_layout="chunk"):
    """Explicit forward/backward pipeline schedule.

    Chunk c (0..S*V-1) lives on device ``c mod S`` as its local chunk
    ``v = c // S``; a microbatch traverses chunks in order, every hop —
    including the S-1 -> 0 wrap between chunk vS-1 and vS — is the same
    cyclic +1 ppermute. Microbatch m is processed by chunk c at forward
    tick ``m + c``; with M <= S (enforced for V > 1) no device ever
    needs two chunks in one tick.

    Forward saves each (chunk, microbatch) input activation; backward
    is the mirrored reverse pipeline (cyclic -1), recomputing each
    chunk under ``jax.vjp`` from the saved input and accumulating
    parameter cotangents — so autodiff never sees the scans and per-tick
    carry snapshots (GPipe's memory blow-up) never materialize.
    """
    S = mesh.shape[axis]
    C = S * V
    T = M + C - 1  # ticks per direction

    def fwd_local(params_loc, x_loc):
        params = params_loc  # leading local chunk axis [V, ...]
        d = jax.lax.axis_index(axis)
        batch_loc = x_loc.shape[0]
        if batch_loc % M != 0:
            raise ValueError(
                "Local batch %d not divisible by %d microbatches"
                % (batch_loc, M)
            )
        x_mb = x_loc.reshape((M, batch_loc // M) + x_loc.shape[1:])
        vary = lambda b: jax_compat.pvary(
            b, (axis,) + _spec_axes(spec)
        )
        perm_fwd = [(j, (j + 1) % S) for j in range(S)]

        def pick_chunk(v):
            return jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_index_in_dim(
                    leaf, v, 0, keepdims=False
                ),
                params,
            )

        def tick(carry, t):
            recv, saved, outputs = carry
            # device d, tick t: local chunk v with m = t - d - v*S in
            # range; at most one valid v (M <= S when V > 1)
            v = jnp.clip((t - d) // S, 0, V - 1)
            m = t - d - v * S
            active = jnp.logical_and(m >= 0, m < M)
            m_idx = jnp.clip(m, 0, M - 1)
            is_first_chunk = jnp.logical_and(d == 0, v == 0)
            inp = jnp.where(
                is_first_chunk,
                jax.lax.dynamic_index_in_dim(
                    x_mb, m_idx, 0, keepdims=False
                ),
                recv,
            )
            # stash the chunk input (the backward's recompute point)
            cur = jax.lax.dynamic_index_in_dim(
                saved, v * M + m_idx, 0, keepdims=False
            )
            saved = jax.lax.dynamic_update_index_in_dim(
                saved, jnp.where(active, inp, cur), v * M + m_idx, 0
            )
            out = stage_fn(pick_chunk(v), inp)
            # last chunk C-1 = local chunk V-1 on device S-1
            is_last = jnp.logical_and(d == S - 1, v == V - 1)
            write = jnp.logical_and(is_last, active)
            cur_out = jax.lax.dynamic_index_in_dim(
                outputs, m_idx, 0, keepdims=False
            )
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, out, cur_out), m_idx, 0
            )
            recv = jax.lax.ppermute(out, axis, perm_fwd)
            return (recv, saved, outputs), None

        mb_shape = x_mb.shape[1:]
        init = (
            vary(jnp.zeros(mb_shape, x_loc.dtype)),
            vary(jnp.zeros((V * M,) + mb_shape, x_loc.dtype)),
            vary(jnp.zeros((M,) + mb_shape, x_loc.dtype)),
        )
        (_, saved, outputs), _ = jax.lax.scan(tick, init, jnp.arange(T))
        outputs = jax.lax.psum(outputs, axis)
        out = outputs.reshape((batch_loc,) + x_loc.shape[1:])
        return out, saved

    # saved: local [V*M slots, mb, ...] -> slot dim sharded over pp, the
    # microbatch dim carries x's batch sharding, feature dims follow
    saved_spec = P(*((axis,) + tuple(spec)))

    def bwd_local(params_loc, saved, g_loc):
        params = params_loc
        d = jax.lax.axis_index(axis)
        batch_loc = g_loc.shape[0]
        g_mb = g_loc.reshape((M, batch_loc // M) + g_loc.shape[1:])
        vary = lambda b: jax_compat.pvary(
            b, (axis,) + _spec_axes(spec)
        )
        perm_bwd = [(j, (j - 1) % S) for j in range(S)]
        # A stage that shards params over a further axis (tp in a
        # Megatron-style stage) sees only its slice of the in-stage
        # matmuls; VMA typing makes the vjp below psum that partial
        # input cotangent itself (the transpose of the implicit
        # vary-cast), so no explicit sum follows it.

        def pick_chunk(v):
            # pcast to varying over the data axes BEFORE the vjp: with
            # invarying params, VMA typing makes the vjp transpose psum
            # parameter cotangents over dp on every tick (the transpose
            # of the implicit pvary); varying params keep the cotangent
            # a per-shard partial, summed once outside the shard_map.
            return jax.tree_util.tree_map(
                lambda leaf: jax_compat.pvary(
                    jax.lax.dynamic_index_in_dim(
                        leaf, v, 0, keepdims=False
                    ),
                    _spec_axes(spec),
                ),
                params,
            )

        def tick(carry, u):
            recv, dparams, dx_mb = carry
            # reverse chunk index c' = (S-1-d) + v'*S handles B(m) at
            # tick u = m + c'; local chunk v = V-1-v'
            vp = jnp.clip((u - (S - 1 - d)) // S, 0, V - 1)
            m = u - (S - 1 - d) - vp * S
            active = jnp.logical_and(m >= 0, m < M)
            m_idx = jnp.clip(m, 0, M - 1)
            v = V - 1 - vp
            is_last_chunk = jnp.logical_and(d == S - 1, v == V - 1)
            g_in = jnp.where(
                is_last_chunk,
                jax.lax.dynamic_index_in_dim(
                    g_mb, m_idx, 0, keepdims=False
                ),
                recv,
            )
            inp = jax.lax.dynamic_index_in_dim(
                saved, v * M + m_idx, 0, keepdims=False
            )
            chunk_params = pick_chunk(v)
            _, vjp = jax.vjp(stage_fn, chunk_params, inp)
            dp, dinp = vjp(g_in)
            gate = jnp.where(active, 1.0, 0.0).astype(g_loc.dtype)
            dparams = jax.tree_util.tree_map(
                lambda acc, g: jax.lax.dynamic_update_index_in_dim(
                    acc,
                    jax.lax.dynamic_index_in_dim(
                        acc, v, 0, keepdims=False
                    )
                    + g * gate.astype(g.dtype),
                    v,
                    0,
                ),
                dparams,
                dp,
            )
            # chunk 0 (d == 0, v == 0) emits the input cotangent
            is_first_chunk = jnp.logical_and(d == 0, v == 0)
            write = jnp.logical_and(is_first_chunk, active)
            cur = jax.lax.dynamic_index_in_dim(
                dx_mb, m_idx, 0, keepdims=False
            )
            dx_mb = jax.lax.dynamic_update_index_in_dim(
                dx_mb, jnp.where(write, dinp, cur), m_idx, 0
            )
            recv = jax.lax.ppermute(dinp, axis, perm_bwd)
            return (recv, dparams, dx_mb), None

        mb_shape = g_mb.shape[1:]
        init = (
            vary(jnp.zeros(mb_shape, g_loc.dtype)),
            # params already vary over pp (and any tp dims); the
            # accumulated cotangents additionally vary over the batch
            # axes they flow in from
            jax.tree_util.tree_map(
                lambda leaf: jax_compat.pvary(
                    jnp.zeros_like(leaf), _spec_axes(spec)
                ),
                params,
            ),
            vary(jnp.zeros((M,) + mb_shape, g_loc.dtype)),
        )
        (_, dparams, dx_mb), _ = jax.lax.scan(tick, init, jnp.arange(T))
        # Each data shard accumulated cotangents for its batch slice;
        # the parameter gradient is their sum. Summing here with an
        # in-region psum then asking the out_spec boundary for a
        # replicated output double-counts under VMA checking (measured
        # exactly dp-fold on jax 0.8), so instead expose the per-shard
        # partials on an explicit leading data axis and let the caller
        # reduce OUTSIDE the manual region — XLA lowers that reduce to
        # the same psum over dp.
        dparams = jax.tree_util.tree_map(lambda leaf: leaf[None], dparams)
        dx = jax.lax.psum(
            dx_mb.reshape((batch_loc,) + g_loc.shape[1:]), axis
        )
        return dparams, dx

    # params_layout="device": the caller's stack is already device-
    # major at rest, so the three per-step cross-shard permutations
    # (fwd params, bwd params, bwd cotangents) are identity.
    to_device = (
        (lambda p: p) if params_layout == "device"
        else (lambda p: _device_major(p, S, V))
    )
    to_rest = (
        (lambda p: p) if params_layout == "device"
        else (lambda p: _chunk_major(p, S, V))
    )

    def _sharded_fwd(params, x):
        return jax_compat.shard_map(
            fwd_local,
            mesh=mesh,
            in_specs=(param_specs, spec),
            out_specs=(spec, saved_spec),
        )(to_device(params), x)

    @jax.custom_vjp
    def run(params, x):
        out, _ = _sharded_fwd(params, x)
        return out

    def run_fwd(params, x):
        out, saved = _sharded_fwd(params, x)
        return out, (params, saved)

    def run_bwd(res, g):
        params, saved = res
        partial_specs = jax.tree_util.tree_map(
            lambda p: P(*((DATA_AXES,) + tuple(p))), param_specs
        )
        dparams, dx = jax_compat.shard_map(
            bwd_local,
            mesh=mesh,
            in_specs=(param_specs, saved_spec, spec),
            out_specs=(partial_specs, spec),
        )(to_device(params), saved, g)
        dparams = jax.tree_util.tree_map(
            lambda leaf: leaf.sum(axis=0), dparams
        )
        return to_rest(dparams), dx

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, x)
