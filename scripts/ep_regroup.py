"""The regrouping under ``ep`` alone on one TPU chip: what a
``permute_rows`` costs at a receive buffer that is partly spare, as one
``take`` over the buffer whole (the form before PR 46) and as
``ops/moe.py`` runs it (a loop of gathers over chunks of
``held_chunk_rows`` that stops at the last chunk that carries a pair),
and what the plan of the two index vectors costs before any row moves.

    python scripts/ep_regroup.py            # on one TPU chip, ~2 min

At ``mellum2-ep4-s8k``'s shape (a buffer of 131,072 rows of 2304
bfloat16, 4 senders x 16 held experts, the (sender, expert) sizes a
Zipf(1.2) draw) with 65,536 / 81,920 / 90,112 / 131,072 rows that carry
a pair, it times (ms a call over 20 calls):

- ``take``: ``jnp.take(rows, by_expert)`` over the whole buffer;
- ``loop``: ``permute_rows(rows, by_expert, by_sender, carried)``, and
  ``loop_back`` the same through the inverse (the transpose's gather);
- ``zeros``: the buffer's zero fill alone (what the loop pays before
  its first chunk);
- ``plan``: ``regroup_plan`` alone, the two index vectors from the
  (sender, expert) sizes, as ``ops/moe.py`` builds them (the position
  plus a sum of steps at the 64 runs' ends: compares, no lookup) and,
  kept here for the comparison, as it built them before PR 48
  (``lookup_plan``: the run a position lies in, then four gathers in
  tables of 64 entries, one lookup a position each), each ``--calls``
  plans in ONE program (``plans_in_a_loop``: the host takes longer to
  launch a program here than the plan runs);
- ``runs``, for the record only (ships nothing): the regrouping as
  copies of its 64 contiguous (sender, expert) runs, in blocks of
  ``--block`` rows, in place of a row gather: whether rows that lie
  together are cheaper to move together (PERF.md Section 7).

It checks the loop against the ``take`` on the chip: equal on every row
below the last chunk's end, zero past it; and the plan against
``lookup_plan``: both vectors, the group sizes and the count equal.
``--rows 2048 --width 128 --chunk 256 --block 64 --calls 2`` rehearses
it on the CPU. Writes ``chiprun_out/ep_regroup.json``.
"""

import argparse
import functools
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

RANKS, HELD = 4, 16


def ms_per_call(fn, calls, *args):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls * 1e3


def zipf_received(carried, seed):
    """(senders, held experts) sizes that sum to ``carried``: a
    multinomial over Zipf(1.2) weights at seeded places."""
    rng = np.random.RandomState(seed)
    weights = 1.0 / np.arange(1, RANKS * HELD + 1) ** 1.2
    weights = rng.permutation(weights / weights.sum())
    return jnp.asarray(
        rng.multinomial(carried, weights).reshape(RANKS, HELD), jnp.int32)


@functools.partial(jax.jit, static_argnums=(1,))
def lookup_plan(received, buffer_rows):
    """``regroup_plan`` as it stood before PR 48: the run of each
    position by a compare against the runs' ends, then the run's two
    starts looked up in (ranks, held) tables, a gather a position."""
    ranks, held = received.shape
    by_sender_sizes = received.reshape(-1)
    by_expert_sizes = received.T.reshape(-1)
    sender_starts = (
        jnp.cumsum(by_sender_sizes) - by_sender_sizes).reshape(ranks, held)
    expert_starts = (
        jnp.cumsum(by_expert_sizes) - by_expert_sizes).reshape(held, ranks)
    at = jnp.arange(buffer_rows, dtype=jnp.int32)
    carried = by_sender_sizes.sum()
    carries = at < carried

    def segment(sizes):
        ends = jnp.cumsum(sizes)
        return jnp.minimum(
            jnp.sum(at[:, None] >= ends[None], axis=1, dtype=jnp.int32),
            sizes.shape[0] - 1)

    seg = segment(by_expert_sizes)
    expert, sender = seg // ranks, seg % ranks
    by_expert = jnp.where(
        carries,
        sender_starts[sender, expert] + at - expert_starts[expert, sender],
        at)
    seg = segment(by_sender_sizes)
    sender, expert = seg // held, seg % held
    by_sender = jnp.where(
        carries,
        expert_starts[expert, sender] + at - sender_starts[sender, expert],
        at)
    return (by_expert.astype(jnp.int32), by_sender.astype(jnp.int32),
            received.sum(axis=0).astype(jnp.int32), carried.astype(jnp.int32))


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def plans_in_a_loop(plan, received, buffer_rows, calls):
    """``calls`` + 1 plans in one program, each from the last one's
    table turned by an expert, through a value of its vectors that the
    compiler cannot fold."""
    def turn(_, received):
        by_expert, by_sender, _, _ = plan(received, buffer_rows)
        nothing = jnp.minimum(by_expert.max() + by_sender.max(), 0)
        return jnp.roll(received, 1, axis=1) + nothing

    return plan(
        jax.lax.fori_loop(0, calls, turn, received), buffer_rows)


@functools.partial(jax.jit, static_argnums=(2,))
def copy_runs(rows, received, block):
    """The rows grouped by expert as copies of the (sender, expert)
    runs, expert by expert, ``block`` rows a copy: a run's last block
    overshoots into the next run's place, which the next run then
    writes (the last run's into the spare rows)."""
    ranks, held = received.shape
    by_sender = received.reshape(-1)
    by_expert = received.T.reshape(-1)
    sources = (jnp.cumsum(by_sender) - by_sender).reshape(ranks, held).T
    sources = sources.reshape(-1)  # in the runs' order by expert
    targets = jnp.cumsum(by_expert) - by_expert
    blocks = -(-by_expert // block)
    ends = jnp.cumsum(blocks)
    last = rows.shape[0] - block

    def copy(i, buffer):
        run = jnp.sum(i >= ends, dtype=jnp.int32)
        within = (i - (ends[run] - blocks[run])) * block
        piece = jax.lax.dynamic_slice_in_dim(
            rows, jnp.minimum(sources[run] + within, last), block)
        return jax.lax.dynamic_update_slice_in_dim(
            buffer, piece, jnp.minimum(targets[run] + within, last), 0)

    return jax.lax.fori_loop(0, ends[-1], copy, jnp.zeros_like(rows))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=131072)
    parser.add_argument("--width", type=int, default=2304)
    parser.add_argument("--chunk", type=int, default=None,
                        help="rows a chunk (default: ops/moe.py's own)")
    parser.add_argument("--block", type=int, default=512)
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    if args.chunk:
        moe_ops.HELD_CHUNK_ROWS = args.chunk
    rows_n = args.rows
    chunk = moe_ops.held_chunk_rows(rows_n)
    device = jax.devices()[0]
    record = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "rows": rows_n, "width": args.width, "chunk": chunk,
        "block": args.block, "calls": args.calls, "cases": []}
    rows = (jax.random.normal(
        jax.random.PRNGKey(args.seed), (rows_n, args.width), jnp.float32)
    ).astype(jnp.bfloat16)
    take = jax.jit(lambda rows, index: jnp.take(rows, index, axis=0))
    plan = jax.jit(moe_ops.regroup_plan, static_argnums=(1,))
    loop = jax.jit(moe_ops.permute_rows)
    zeros = jax.jit(lambda rows: jnp.zeros_like(rows))
    record["zeros_ms"] = ms_per_call(zeros, args.calls, rows)
    print("zero fill of the buffer: %.3f ms" % record["zeros_ms"])
    for carried in (rows_n // 2, rows_n * 5 // 8, rows_n * 11 // 16, rows_n):
        received = zipf_received(carried, args.seed + carried)
        by_expert, by_sender, _, count = plan(received, rows_n)
        end = int(moe_ops.received_rows_run(count, rows_n))
        case = {
            "carried": carried, "rows_run": end,
            "plan_ms": ms_per_call(
                plans_in_a_loop, 1, plan, received, rows_n, args.calls
            ) / (args.calls + 1),
            "plan_lookup_ms": ms_per_call(
                plans_in_a_loop, 1, lookup_plan, received, rows_n,
                args.calls) / (args.calls + 1),
            "take_ms": ms_per_call(take, args.calls, rows, by_expert),
            "loop_ms": ms_per_call(
                loop, args.calls, rows, by_expert, by_sender, count),
            "loop_back_ms": ms_per_call(
                loop, args.calls, rows, by_sender, by_expert, count),
            "runs_ms": ms_per_call(
                copy_runs, args.calls, rows, received, args.block),
        }
        want = np.asarray(take(rows, by_expert).astype(jnp.float32))
        got = np.asarray(
            loop(rows, by_expert, by_sender, count).astype(jnp.float32))
        case["loop_equal"] = bool(
            (got[:end] == want[:end]).all() and not got[end:].any())
        case["plan_equal"] = all(
            bool((ours == theirs).all()) for ours, theirs in zip(
                plan(received, rows_n), lookup_plan(received, rows_n)))
        runs = np.asarray(
            copy_runs(rows, received, args.block).astype(jnp.float32))
        case["runs_equal"] = bool((runs[:carried] == want[:carried]).all())
        record["cases"].append(case)
        print(json.dumps(case))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "ep_regroup.json"), "w") as f:
        json.dump(record, f, indent=1)
    ok = all(c["loop_equal"] and c["plan_equal"] for c in record["cases"])
    print(json.dumps({"ok": ok, "device": record["device"]}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
