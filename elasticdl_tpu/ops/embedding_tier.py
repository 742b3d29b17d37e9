"""Fused device-tier embedding kernels: gather-merge and scatter-apply.

The device tier (train/device_tier.py) keeps the Zipfian hot set of each
host-PS embedding table resident in accelerator memory as a
fixed-capacity slot table ``[capacity + pad, dim]`` (the padding's first
row is a scratch slot that absorbs writes addressed "nowhere"). Three
fused ops make the tier free of host round trips on the hit path:

- ``fused_insert_gather`` — one dispatch per table per step: write this
  step's staged promotions into their slots (resetting their optimizer
  slot state), read the eviction victims' current values out (the host
  writes them back to the PS), and materialize the step's full row
  buffer by merging device-resident hits with the PS-pulled miss rows.
- ``fused_scatter_apply`` — the sparse optimizer step applied directly
  to the resident slots from the step's row gradients: no gradient for
  a hit row ever crosses back to host RAM. Mirrors the PS store's
  update math (ps/embedding_store.py) for sgd / momentum / nesterov /
  adagrad / adam so a row trains the same whichever tier holds it.
- ``gather_rows`` — plain slot gather (flush/writeback reads).

Two implementations share every call site: a pure-jnp one built on
XLA gather/scatter (``.at[].set``), and a Pallas TPU kernel pair (one
grid step per row, slot indices scalar-prefetched so the block index
map does the gather/scatter addressing). Both produce identical
results, asserted by tests/test_device_tier.py. Kernel choice:
``EDL_TIER_KERNEL`` = ``auto`` | ``jnp`` | ``pallas``, and ``auto``
means jnp on every backend. The Pallas pair is an explicit opt-in: on
the v5e it compiles and matches jnp exactly but was 1.6x to 5x slower
in isolation at DeepFM's shapes, it has no shard_map of its own, so
jax refuses it on a multi-device mesh ("Mosaic kernels cannot be
automatically partitioned"), and it cannot run on the CPU (PERF.md,
PR 21; ``scripts/probe_kernels.py`` is the probe). Whether it is made
faster or deleted is ROADMAP Speed work. Nothing stands between the
chosen kernel and the compiler: a kernel Mosaic refuses fails the step.

Block layout of the Pallas pair: a ``(1, dim)`` row block of a
``[rows, dim]`` table violates Mosaic's rule that a block's last two
dims be multiples of (8, 128) or equal the array's, so every kernel
addresses the 3-D view ``[rows, 1, dim]`` with ``(1, 1, dim)`` blocks.
In that form all three compile on a v5e (libtpu 0.0.34) at DeepFM's
shapes — dim 8 and dim 1, capacity 65,536 + pad.

Uniqueness contract: ``slots`` entries are unique per call except the
scratch sentinel, which may repeat — every op writes the scratch row
with set-semantics only, so duplicate scratch writes race benignly into
a row nothing ever reads.
"""

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.env_utils import env_str

KERNEL_ENV = "EDL_TIER_KERNEL"

# tests flip this to run the Pallas kernels in interpreter mode on CPU
# (same code path as TPU minus the Mosaic lowering)
INTERPRET = False

# optimizer -> number of [rows, dim] slot-state buffers (mirror of
# ps/embedding_store.OPT_SLOT_COUNTS for the tier-supported subset)
TIER_OPT_SLOTS = {
    "sgd": 0, "momentum": 1, "nesterov": 1, "adagrad": 1, "adam": 2,
}


def resolve_kernel(kind=None):
    """-> "jnp" | "pallas". ``auto`` is jnp everywhere; pallas runs only
    when asked for by name (see the module docstring)."""
    kind = (kind or env_str(KERNEL_ENV, "auto")).strip().lower()
    if kind not in ("auto", "pallas", "jnp"):
        raise ValueError(
            "%s must be auto|pallas|jnp (got %r)" % (KERNEL_ENV, kind)
        )
    return "jnp" if kind == "auto" else kind


def init_table_state(capacity, dim, opt_type, dtype=jnp.float32):
    """Fresh tier state for one table: weights + optimizer slot state +
    per-slot step counts (adam bias correction), all zeros. ``capacity``
    INCLUDES the scratch padding row(s)."""
    if opt_type not in TIER_OPT_SLOTS:
        raise ValueError(
            "device tier supports %s sparse optimizers (got %r)"
            % (sorted(TIER_OPT_SLOTS), opt_type)
        )
    state = {"rows": jnp.zeros((capacity, dim), dtype)}
    for k in range(TIER_OPT_SLOTS[opt_type]):
        state["slot%d" % k] = jnp.zeros((capacity, dim), dtype)
    state["steps"] = jnp.zeros((capacity,), jnp.int32)
    return state


# ---------------------------------------------------------------------
# pure-jnp implementations (XLA gather/scatter; what ``auto`` runs)


def _jnp_insert_gather(state, ins_slots, ins_rows, evict_slots, slots,
                       miss_rows):
    """-> (new_state, combined_rows, evicted_rows).

    Order matters: victims are read BEFORE staged inserts land (an
    insert may reuse a victim's slot this very step), and the combined
    buffer is gathered AFTER (a promotion is a hit from its first
    step). Padding convention: ``ins_slots``/``evict_slots`` pad with
    the scratch slot, ``slots`` pads misses with -1."""
    evicted = jnp.take(state["rows"], evict_slots, axis=0)
    new_state = dict(state)
    new_state["rows"] = state["rows"].at[ins_slots].set(ins_rows)
    for key, value in state.items():
        if key.startswith("slot"):
            new_state[key] = value.at[ins_slots].set(0.0)
    new_state["steps"] = state["steps"].at[ins_slots].set(0)
    hit = slots >= 0
    safe = jnp.where(hit, slots, 0)
    gathered = jnp.take(new_state["rows"], safe, axis=0)
    combined = jnp.where(hit[:, None], gathered, miss_rows)
    return new_state, combined, evicted


def _jnp_scatter_apply(state, slots, grads, opt_type, lr, momentum,
                       beta1, beta2, epsilon):
    """Sparse optimizer step on the resident slots; misses (slot -1)
    are routed to the scratch row. Update math mirrors
    ps/embedding_store.NumpyEmbeddingStore (fp32 bias corrections)."""
    scratch = state["rows"].shape[0] - 1
    target = jnp.where(slots >= 0, slots, scratch).astype(jnp.int32)
    w = jnp.take(state["rows"], target, axis=0)
    step = jnp.take(state["steps"], target) + 1
    new_state = dict(state)
    if opt_type == "sgd":
        new_w = w - lr * grads
    elif opt_type in ("momentum", "nesterov"):
        m = jnp.take(state["slot0"], target, axis=0)
        m = momentum * m + grads
        if opt_type == "nesterov":
            new_w = w - lr * (grads + momentum * m)
        else:
            new_w = w - lr * m
        new_state["slot0"] = state["slot0"].at[target].set(m)
    elif opt_type == "adagrad":
        s = jnp.take(state["slot0"], target, axis=0)
        s = s + grads * grads
        new_w = w - lr * grads / (jnp.sqrt(s) + epsilon)
        new_state["slot0"] = state["slot0"].at[target].set(s)
    elif opt_type == "adam":
        m = jnp.take(state["slot0"], target, axis=0)
        v = jnp.take(state["slot1"], target, axis=0)
        m = beta1 * m + (1.0 - beta1) * grads
        v = beta2 * v + (1.0 - beta2) * grads * grads
        stepf = step.astype(jnp.float32)[:, None]
        mhat = m / (1.0 - jnp.power(beta1, stepf))
        vhat = v / (1.0 - jnp.power(beta2, stepf))
        new_w = w - lr * mhat / (jnp.sqrt(vhat) + epsilon)
        new_state["slot0"] = state["slot0"].at[target].set(m)
        new_state["slot1"] = state["slot1"].at[target].set(v)
    else:
        raise ValueError("unsupported tier optimizer %r" % opt_type)
    new_state["rows"] = state["rows"].at[target].set(new_w)
    new_state["steps"] = state["steps"].at[target].set(step)
    return new_state


# ---------------------------------------------------------------------
# Pallas TPU kernels: one grid step per row, slot addressing done by
# the BlockSpec index maps over scalar-prefetched slot arrays.


def _rows3(x):
    """[n, dim] -> [n, 1, dim]: the view whose (1, 1, dim) row blocks
    satisfy the TPU block rule (see module docstring)."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def _row_block(dim, index):
    """One row of a _rows3 view; ``index(i, prefetched)`` -> row."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec(
        (1, 1, dim), lambda i, ref: (index(i, ref), 0, 0)
    )


def _pallas_gather(table, slots, miss_rows):
    """combined[i] = slots[i] >= 0 ? table[slots[i]] : miss_rows[i]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = miss_rows.shape

    def kernel(slots_ref, table_blk, miss_blk, out_ref):
        i = pl.program_id(0)
        hit = slots_ref[i] >= 0
        out_ref[...] = jnp.where(hit, table_blk[...], miss_blk[...])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            # the gather: block row = the slot (clamped to 0 on miss;
            # the select above discards the garbage row)
            _row_block(dim, lambda i, s: jnp.maximum(s[i], 0)),
            _row_block(dim, lambda i, s: i),
        ],
        out_specs=_row_block(dim, lambda i, s: i),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, dim), table.dtype),
        interpret=INTERPRET,
    )(slots, _rows3(table), _rows3(miss_rows)).reshape(n, dim)


def _pallas_set_rows(table, slots, rows):
    """table.at[slots].set(rows) (staged promotion insert); ``slots``
    pad with the scratch row, whose garbage nothing reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = rows.shape

    def kernel(slots_ref, table_blk, rows_blk, out_blk):
        del slots_ref, table_blk
        out_blk[...] = rows_blk[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            # the aliased table rides along so unvisited rows keep
            # their values (in-place update via the alias below)
            _row_block(dim, lambda i, s: s[i]),
            _row_block(dim, lambda i, s: i),
        ],
        out_specs=_row_block(dim, lambda i, s: s[i]),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (table.shape[0], 1, dim), table.dtype
        ),
        input_output_aliases={1: 0},
        interpret=INTERPRET,
    )(slots, _rows3(table), _rows3(rows)).reshape(table.shape)


def _pallas_insert_gather(state, ins_slots, ins_rows, evict_slots, slots,
                          miss_rows):
    evicted = _pallas_gather(
        state["rows"], evict_slots,
        jnp.zeros((evict_slots.shape[0],) + state["rows"].shape[1:],
                  state["rows"].dtype),
    )
    new_state = dict(state)
    new_state["rows"] = _pallas_set_rows(
        state["rows"], ins_slots, ins_rows
    )
    zeros = jnp.zeros_like(ins_rows)
    for key, value in state.items():
        if key.startswith("slot"):
            new_state[key] = _pallas_set_rows(value, ins_slots, zeros)
    # steps is a 1-d int32 vector; the scalar reset stays on XLA scatter
    # (a [n] set is not worth a kernel launch)
    new_state["steps"] = state["steps"].at[ins_slots].set(0)
    combined = _pallas_gather(new_state["rows"], slots, miss_rows)
    return new_state, combined, evicted


def _pallas_scatter_apply(state, slots, grads, opt_type, lr, momentum,
                          beta1, beta2, epsilon):
    """One grid step per gradient row: the BlockSpec index maps route
    each row's read-modify-write straight at its resident slot (misses
    at the scratch row). Aliased in/out so the update is in place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = grads.shape
    scratch = state["rows"].shape[0] - 1
    target = jnp.where(slots >= 0, slots, scratch).astype(jnp.int32)
    step = state["steps"].at[target].add(1)
    stepf = jnp.take(step, target).astype(jnp.float32)
    n_slots = sum(1 for k in state if k.startswith("slot"))

    row_spec = lambda: _row_block(dim, lambda i, tgt: i)
    slot_spec = lambda: _row_block(dim, lambda i, tgt: tgt[i])

    def kernel(tgt_ref, *refs):
        del tgt_ref
        grad_blk = refs[0]
        step_blk = refs[1]
        in_w = refs[2]
        in_slots = refs[3:3 + n_slots]
        out_w = refs[3 + n_slots]
        out_slots = refs[4 + n_slots:4 + 2 * n_slots]
        g = grad_blk[...]
        w = in_w[...]
        if opt_type == "sgd":
            out_w[...] = w - lr * g
        elif opt_type in ("momentum", "nesterov"):
            m = momentum * in_slots[0][...] + g
            if opt_type == "nesterov":
                out_w[...] = w - lr * (g + momentum * m)
            else:
                out_w[...] = w - lr * m
            out_slots[0][...] = m
        elif opt_type == "adagrad":
            s = in_slots[0][...] + g * g
            out_w[...] = w - lr * g / (jnp.sqrt(s) + epsilon)
            out_slots[0][...] = s
        else:  # adam
            t = step_blk[...]
            m = beta1 * in_slots[0][...] + (1.0 - beta1) * g
            v = beta2 * in_slots[1][...] + (1.0 - beta2) * g * g
            mhat = m / (1.0 - jnp.power(beta1, t))
            vhat = v / (1.0 - jnp.power(beta2, t))
            out_w[...] = w - lr * mhat / (jnp.sqrt(vhat) + epsilon)
            out_slots[0][...] = m
            out_slots[1][...] = v

    slot_keys = sorted(k for k in state if k.startswith("slot"))
    # step counts ride as a full [n, dim] row buffer so every operand
    # shares the one row-block shape
    inputs = [
        _rows3(grads),
        _rows3(jnp.broadcast_to(stepf[:, None], (n, dim))),
        _rows3(state["rows"]),
    ]
    inputs += [_rows3(state[k]) for k in slot_keys]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            row_spec(),                       # grads
            row_spec(),                       # step counts
            slot_spec(),                      # weights (read)
        ] + [slot_spec() for _ in slot_keys],
        out_specs=[slot_spec()] + [slot_spec() for _ in slot_keys],
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in inputs[2:]
        ],
        # weights/slot buffers update in place (alias input -> output);
        # input index offsets: [slots(prefetch), grads, step, rows, ...]
        input_output_aliases=dict(
            [(3, 0)] + [(4 + j, 1 + j) for j in range(n_slots)]
        ),
        interpret=INTERPRET,
    )(target, *inputs)
    outs = [o.reshape(state["rows"].shape) for o in outs]
    new_state = dict(state)
    new_state["rows"] = outs[0]
    for j, key in enumerate(slot_keys):
        new_state[key] = outs[1 + j]
    new_state["steps"] = step
    return new_state


# ---------------------------------------------------------------------
# public fused ops


def fused_insert_gather(state, ins_slots, ins_rows, evict_slots, slots,
                        miss_rows, kernel="jnp"):
    """Stage promotions in, read eviction victims out, and materialize
    the step's combined row buffer — one fused op (see module
    docstring for padding conventions)."""
    impl = (
        _pallas_insert_gather if kernel == "pallas"
        else _jnp_insert_gather
    )
    return impl(state, ins_slots, ins_rows, evict_slots, slots, miss_rows)


def fused_scatter_apply(state, slots, grads, opt_type="sgd", lr=0.01,
                        momentum=0.9, beta1=0.9, beta2=0.999,
                        epsilon=1e-8, kernel="jnp"):
    """Apply one step's row gradients to the resident slots in device
    memory (misses fall into the scratch row)."""
    impl = (
        _pallas_scatter_apply if kernel == "pallas"
        else _jnp_scatter_apply
    )
    return impl(
        state, slots, grads, opt_type, lr, momentum, beta1, beta2,
        epsilon,
    )


def gather_rows(state, slots, kernel="jnp"):
    """Read resident rows at ``slots`` (flush / eviction writeback)."""
    if kernel == "pallas":
        return _pallas_gather(
            state["rows"], slots,
            jnp.zeros(
                (slots.shape[0],) + state["rows"].shape[1:],
                state["rows"].dtype,
            ),
        )
    return jnp.take(state["rows"], jnp.maximum(slots, 0), axis=0)
