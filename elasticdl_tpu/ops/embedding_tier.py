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

All three are plain jnp on XLA's gather and scatter (``jnp.take``,
``.at[].set``), on every backend and on a mesh: Pallas kernels with one
grid step a row ran 1.6x to 5x slower than these on the v5e (PERF.md,
Section 7), so there are none.

Uniqueness contract: ``slots`` entries are unique per call except the
scratch sentinel, which may repeat — every op writes the scratch row
with set-semantics only, so duplicate scratch writes race benignly into
a row nothing ever reads.
"""

import jax.numpy as jnp

# optimizer -> number of [rows, dim] slot-state buffers (mirror of
# ps/embedding_store.OPT_SLOT_COUNTS for the tier-supported subset)
TIER_OPT_SLOTS = {
    "sgd": 0, "momentum": 1, "nesterov": 1, "adagrad": 1, "adam": 2,
}


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


def fused_insert_gather(state, ins_slots, ins_rows, evict_slots, slots,
                        miss_rows):
    """Stage promotions in, read eviction victims out, and materialize
    the step's combined row buffer — one fused op.
    -> (new_state, combined_rows, evicted_rows).

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


def fused_scatter_apply(state, slots, grads, opt_type="sgd", lr=0.01,
                        momentum=0.9, beta1=0.9, beta2=0.999,
                        epsilon=1e-8):
    """Apply one step's row gradients to the resident slots in device
    memory: the sparse optimizer step; misses (slot -1) are routed to
    the scratch row. Update math mirrors
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


def gather_rows(state, slots):
    """Read resident rows at ``slots`` (flush / eviction writeback)."""
    return jnp.take(state["rows"], jnp.maximum(slots, 0), axis=0)
