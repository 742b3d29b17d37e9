"""The expert layer's exchange in a run's artefacts: the collectives
under the ``moe/exchange`` scope that ``ops/moe.py:exchange_rows`` puts
around each all-to-all over ``ep`` (the dispatch's and the combine's,
forward and backward alike: an operation's ``op_name`` carries the
scope under ``transpose(`` too), and the ``moe_routing`` events'
counters of it. Part of the yardstick
(``tests/benchmark_harness/test_mellum2_metrics.py`` checks it on
hand-made operations and recorded events).

An exchange collective is an operation of a device's ``XLA Ops`` line
whose ``op_name`` lies under the scope and whose instruction says
all-to-all (``ragged-all-to-all`` too), all-gather, all-reduce,
reduce-scatter or collective-permute, a ``-start`` or a ``-done`` of an
asynchronous one included. Its time is the operation's own on the
core, start to end, and NOT an asynchronous pair's open span
(``collective_time_share`` reads that: 99.91% on ``pythia1b-fsdp4-s2k``,
where a collective is in flight nearly always and holds the core
rarely). The buffer an exchange writes into is made under the scope
too (a fill); it is compute, not exchange.

The trace is reduced once a traced run in a CPU child process
(``python benchmark/lib/ep_trace.py <xplane> <out dir>``, started by
``reduced`` from the first metric that asks) and leaves
``ep_reduced.json`` beside the other reductions. A program without the
scope (the parent of PR 45, every other configuration) leaves
``"scoped": false``, and every reader returns None.
"""

import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import loop_ledger, procs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

SCOPE = "moe/exchange"
REDUCE_TIMEOUT = 300
# the logged steps a counter's median is over, after the cell's warm-up
# (``metrics/expert_load_max_over_mean.py``'s range and reason)
EVENTS = 9


def is_exchange(name, op_name):
    """Whether one operation is a collective of the exchange, from its
    HLO text and its ``op_name``."""
    # by the opcode, as ``lib/trace_reduce.py`` tells a collective: an
    # instruction's name is jax's (``%ragged_all_to_all.9``) and its
    # result's layout has brackets of its own
    return SCOPE in op_name and bool(
        tr.COLLECTIVE.search(tr.opcode(name)))


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    exchange, compute, count = [], [], 0
    for name, start, end, op_name in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start or tr.opcode(name) in tr.CONTAINER:
            continue
        if is_exchange(name, op_name):
            exchange.append((start, end))
            count += 1
        elif not tr.COLLECTIVE.search(tr.opcode(name)):
            compute.append((start, end))
    held = tr.union(exchange)
    return {
        "steps": len(runs) - 1,
        "window_s": (hi - lo) / 1e9,
        "exchange_s": tr.total(held) / 1e9,
        "exchange_exposed_s": tr.total(
            tr.subtract(held, tr.union(compute))) / 1e9,
        "exchange_ops": count,
        "scoped": bool(count),
    }


def reduce(devices):
    """``devices``: {id: (ops, modules)} as ``loop_ledger.load_xspace``
    returns them."""
    out = {}
    for device_id, (ops, modules) in sorted(devices.items()):
        device = reduce_device(ops, modules)
        if device:
            out[str(device_id)] = device
    return {"devices": out}


def scoped_devices(reduced):
    return [d for d in (reduced or {}).get("devices", {}).values()
            if d.get("scoped") and d["window_s"]]


def share(reduced, key):
    """``key`` (``exchange_s`` or ``exchange_exposed_s``) over the
    traced window, worst device, in percent; None for a program without
    the scope."""
    shares = [d[key] / d["window_s"] for d in scoped_devices(reduced)]
    return 100.0 * max(shares) if shares else None


def seconds_a_step(reduced):
    """The exchange's time on the core a step, on the device where it
    is longest; None for a program without the scope."""
    times = [d["exchange_s"] / d["steps"] for d in scoped_devices(reduced)
             if d["steps"]]
    return max(times) if times else None


def routing_events(run):
    """The ``moe_routing`` events of the logged steps after the cell's
    warm-up that carry the exchange's counters (a fixed range of steps,
    not the window's wall time)."""
    first = run["cell"]["warmup_steps"]
    last = first + EVENTS * run["cell"]["log_every"]
    return [
        e for e in loop_ledger.worker_events(run)
        if e.get("event") == "moe_routing" and "sent_pairs" in e
        and first < e.get("step", 0) <= last
    ]


def counter_median(run, value):
    """The median over ``routing_events`` of ``value(event)``; None
    where there is no such event."""
    values = [value(e) for e in routing_events(run)]
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def reduced(run):
    """``ep_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``ep_reduce.log``)."""
    if "ep_reduced" in run:
        return run["ep_reduced"]
    run["ep_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "ep_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "ep_reduce.log"), "wb") as log:
            try:
                subprocess.run(
                    [sys.executable, os.path.abspath(__file__), xplane,
                     out],
                    env=procs.child_env(ROOT, "cpu"), stdout=log,
                    stderr=subprocess.STDOUT, timeout=REDUCE_TIMEOUT,
                    check=False,
                )
            except (OSError, subprocess.TimeoutExpired):
                pass
    if os.path.exists(path):
        with open(path) as f:
            run["ep_reduced"] = json.load(f)
    return run["ep_reduced"]


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "ep_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "ep_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
