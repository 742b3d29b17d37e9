"""The held expert path's row buffer, stage by stage alone on one TPU
chip: what a stage costs at a buffer that is partly spare, in PR 35's
form (every stage runs the whole buffer) and in this tree's (the rows
that carry a pair).

    python scripts/held_rows.py            # on one TPU chip, ~5 min

At ``sdar30b-bd-s8k``'s layer (a buffer of 49,152 rows of 2048
bfloat16, 16 held experts of width 768, 16,384 tokens x top-8) with
16,384 / 28,000 / 36,000 / 49,152 held pairs, and at
``qwen3next80b-s32k``'s (65,536 rows, 32 experts of 512, 32,768 tokens
x top-10) with 26,000, it times (ms a call over 20 calls):

- the three calls of the gate / up projection (the backend's ``gmm``,
  ``gmm`` with a transposed rhs, ``tgmm``) under group sizes whose last
  group takes the spare rows (``whole``) and under the true ones
  (``held``);
- the dispatch's gather and its transpose, the combine's float32
  scatter-add and its backward (one gather of ``dy`` for the rows' and
  the gates' gradients), as ``take`` / ``.at[].add`` over the whole
  buffer and as ``ops/moe.py`` runs them: the gather in chunks up to
  the last that holds a pair, the two scatter-adds over the shortest
  prefix of the buffer that holds the pairs (eighths for the combine,
  quarters for the dispatch's transpose), the combine's backward over
  the whole buffer from one bfloat16 gather (a first version ran all
  four in chunks: a scatter-add costs 0.6 ms a call before its first
  row, and twelve of them took 16 ms for the one call's 5.8, PERF.md
  PR 36);
- the layer (dispatch, SwiGLU experts through ``grouped_matmul``,
  combine), value and gradients, both ways.

Then it checks the tree's layer against PR 35's form on the chip, where
the rows past the last held tile hold whatever the memory held: value
and gradients finite and equal to bfloat16's rounding. Writes
``chiprun_out/held_rows.json``.
"""

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from elasticdl_tpu.ops import moe as moe_ops  # noqa: E402

CALLS = 20
DIM = 2048

# cell -> (tokens, top_k, held experts, expert width, buffer rows,
# the held pairs to try)
LAYERS = {
    "sdar30b-bd-s8k": (16384, 8, 16, 768, 49152,
                       (16384, 28000, 36000, 49152)),
    "qwen3next80b-s32k": (32768, 10, 32, 512, 65536, (26000, 65536)),
}


def ms_per_call(fn, *args):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / CALLS * 1e3


def routed(tokens, k, count, held, seed=0):
    """(tokens, k) experts of ``8 x count`` of which exactly ``held``
    pairs, at seeded places, fall on the first ``count``, ragged."""
    rng = np.random.RandomState(seed)
    flat = rng.randint(count, 8 * count, tokens * k)
    share = rng.dirichlet(np.full(count, 3.0))
    flat[rng.permutation(tokens * k)[:held]] = rng.choice(
        count, held, p=share)
    return jnp.asarray(flat.reshape(tokens, k), jnp.int32)


def whole_sizes(sizes, rows):
    """PR 35's group sizes: the spare rows counted to the last group."""
    return sizes.at[-1].add(rows - sizes.sum())


# PR 35's permutes: one ``take`` / ``.at[].add`` over the whole buffer


def dispatch_whole(x, pairs, valid, k):
    return jnp.take(x, pairs // k, axis=0)


def combine_whole(rows, gates, pairs, valid):
    tokens, k = gates.shape
    gate_of = jnp.where(valid, jnp.take(gates.reshape(-1), pairs), 0.0)
    y = jnp.zeros((tokens, rows.shape[-1]), jnp.float32).at[pairs // k].add(
        rows.astype(jnp.float32) * gate_of[:, None])
    return y.astype(rows.dtype)


def layer(x, gates, weights, pairs, valid, sizes, dispatch, combine):
    rows = dispatch(x, pairs, valid, gates.shape[1])
    gate, up = (moe_ops.grouped_matmul(rows, w, sizes) for w in weights[:2])
    out = moe_ops.grouped_matmul(jax.nn.silu(gate) * up, weights[2], sizes)
    return combine(out, gates, pairs, valid)


def operands(tokens, k, count, width, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    make = lambda key, *shape: (
        jax.random.normal(key, shape, jnp.float32) * 0.05
    ).astype(jnp.bfloat16)
    x = make(keys[0], tokens, DIM) * 20
    gates = jax.nn.softmax(
        jax.random.normal(keys[1], (tokens, k), jnp.float32))
    weights = (make(keys[2], count, DIM, width),
               make(keys[3], count, DIM, width),
               make(keys[4], count, width, DIM))
    return x, gates, weights, make(keys[5], tokens, DIM) * 20


def time_matmuls(rows, width, sizes, record, **facts):
    """The gate / up projection's three calls, the spare rows in the
    last group and in none."""
    backend = moe_ops._gmm_backend()
    count = sizes.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    make = lambda key, *shape: (
        jax.random.normal(key, shape, jnp.float32) * 0.05
    ).astype(jnp.bfloat16)
    x, w, dy = (make(keys[0], rows, DIM), make(keys[1], count, DIM, width),
                make(keys[2], rows, width))
    tiles = moe_ops.projection_tiles(rows, DIM, width, x.dtype)
    calls = {
        "gmm": lambda x, w, dy, s: backend.gmm(
            x, w, s, x.dtype, tiles["fwd"]),
        "gmm_transposed": lambda x, w, dy, s: backend.gmm(
            dy, w, s, x.dtype, tiles["d_rows"], transpose_rhs=True),
        "tgmm": lambda x, w, dy, s: backend.tgmm(
            x.swapaxes(0, 1), dy, s, w.dtype, tiles["d_weights"],
            num_actual_groups=count),
    }
    for call, fn in calls.items():
        fn = jax.jit(fn)
        record(stage=call,
               whole_ms=ms_per_call(fn, x, w, dy, whole_sizes(sizes, rows)),
               held_ms=ms_per_call(fn, x, w, dy, sizes), **facts)


def permutes(dispatch, combine, k):
    """The four permutes of a layer as jitted functions of (x, gates,
    rows, dy, pairs, valid)."""
    def dispatch_t(x, gates, rows, dy, pairs, valid):
        _, vjp = jax.vjp(lambda x: dispatch(x, pairs, valid, k), x)
        return vjp(rows)

    def combine_t(x, gates, rows, dy, pairs, valid):
        _, vjp = jax.vjp(
            lambda rows, gates: combine(rows, gates, pairs, valid), rows,
            gates)
        return vjp(dy)

    return {
        "dispatch": jax.jit(lambda x, gates, rows, dy, pairs, valid: dispatch(
            x, pairs, valid, k)),
        "dispatch_transposed": jax.jit(dispatch_t),
        "combine": jax.jit(lambda x, gates, rows, dy, pairs, valid: combine(
            rows, gates, pairs, valid)),
        "combine_backward": jax.jit(combine_t),
    }


def held_permutes(k):
    """The same four as ``ops/moe.py`` runs them, the two backward
    ones called as the custom VJPs call them (through ``jax.vjp`` the
    timed program would hold the forward's loop too: XLA removes an
    unused gather, not an unused ``while``)."""
    return {
        "dispatch": jax.jit(
            lambda x, gates, rows, dy, pairs, valid: moe_ops.dispatch_held(
                x, pairs, valid, k)),
        "dispatch_transposed": jax.jit(
            lambda x, gates, rows, dy, pairs, valid:
            moe_ops._dispatch_held_bwd(k, (pairs, valid, x.shape[0]), rows)[0]),
        "combine": jax.jit(
            lambda x, gates, rows, dy, pairs, valid: moe_ops.combine_held(
                rows, gates, pairs, valid)),
        "combine_backward": jax.jit(
            lambda x, gates, rows, dy, pairs, valid:
            moe_ops._combine_held_bwd((rows, gates, pairs, valid), dy)[:2]),
    }


def time_case(cell, held, record):
    tokens, k, count, width, buffer_rows, _ = LAYERS[cell]
    experts = routed(tokens, k, count, held)
    pairs, valid, sizes, _, _, dropped = moe_ops.sort_held(
        experts, 8 * count, 0, count, buffer_rows)
    assert int(dropped) == 0 and int(sizes.sum()) == held
    facts = dict(cell=cell, buffer=buffer_rows, held=held)
    time_matmuls(buffer_rows, width, sizes, record, **facts)

    x, gates, weights, dy = operands(tokens, k, count, width)
    rows = dispatch_whole(x, pairs, valid, k)
    args = (x, gates, rows, dy, pairs, valid)
    whole = permutes(dispatch_whole, combine_whole, k)
    held_way = held_permutes(k)
    for stage, fn in whole.items():
        record(stage=stage, whole_ms=ms_per_call(fn, *args),
               held_ms=ms_per_call(held_way[stage], *args), **facts)

    def grads(dispatch, combine):
        def loss(x, gates, weights, sizes, dy, pairs, valid):
            y = layer(x, gates, weights, pairs, valid, sizes, dispatch,
                      combine)
            return (y.astype(jnp.float32) * dy.astype(jnp.float32)).sum(), y
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))

    was = grads(dispatch_whole, combine_whole)
    now = grads(moe_ops.dispatch_held, moe_ops.combine_held)
    spare = whole_sizes(sizes, buffer_rows)
    rest = (dy, pairs, valid)
    row = dict(
        stage="layer",
        whole_ms=ms_per_call(was, x, gates, weights, spare, *rest),
        held_ms=ms_per_call(now, x, gates, weights, sizes, *rest), **facts)
    # the check: PR 35's form is the reference
    (_, want_y), want = was(x, gates, weights, spare, *rest)
    (_, y), got = now(x, gates, weights, sizes, *rest)
    names = ("y", "dx", "d_gates", "d_w_gate", "d_w_up", "d_w_down")
    for name, a, b in zip(names, (want_y, *want[:2], *want[2]),
                          (y, *got[:2], *got[2])):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        row["finite_" + name] = bool(jnp.isfinite(b).all())
        row["error_" + name] = float(
            jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))
    record(**row)


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("held_rows: needs a TPU, found %s" % device.platform)
    report = {"device": device.device_kind, "calls": CALLS, "cases": []}

    def record(**row):
        print(json.dumps(row), flush=True)
        report["cases"].append(row)

    for cell, facts in LAYERS.items():
        for held in facts[-1]:
            time_case(cell, held, record)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "held_rows.json"), "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
