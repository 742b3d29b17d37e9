"""A looped stack in a device trace.

Device seconds of ``models/moe_transformer.py:MoeTransformerLM._looped``
and of its loss (``ops/looped_exit.py``) by where an operation's
``op_name`` puts it, forward, recompute and backward alike (the name
carries the scopes under ``transpose(`` and inside a rematerialised
region too):

- ``exit``: anything under ``exit/head`` (the head's matmul a chunk of
  positions at a time, its log-sum-exp, the expected loss and their
  backward, which forms a chunk's logits again) or ``exit/gate`` (the
  gate, the exit distribution and its facts);
- ``outside_blocks``: anything under ``looped/pass``,
  ``looped/exit_norm`` or under the method's own scope ``._looped`` that lies
  under no ``block_<i>`` scope and under no ``exit/`` scope. On the
  first trace of the scanned passes (PR 55, 2,397 ms busy a step) that
  was: the stacked saved set written a pass by
  ``._looped/while/body/dynamic_update_slice`` (15.7 ms) and read back
  by ``.../while/body/squeeze`` in the backward (12.0), the loops' own
  ``._looped/while`` events (5.4), the end-of-pass norm forward,
  recompute and backward (``looped/exit_norm``, 1.3), the exits'
  ``squeeze`` and ``add_any`` (1.4) and the carry's ``add_any`` under
  ``looped/pass`` (0.02). The sums of the passes' weight gradients are
  NOT here: inside the backward loop XLA adds a pass's term to the
  carried sum in the fusion of the block's matmul that produced it,
  under that block's scope.

Part of the yardstick (``tests/benchmark_harness/test_ouro_metrics.py``
checks it on hand-made operations). Runs once a traced run in a CPU
child process (``python benchmark/lib/looped_trace.py <xplane> <out
dir>``, started by ``reduced`` from the first metric that asks) and
leaves ``looped_reduced.json`` beside the other reductions. A program
with nothing under the scopes (the parent of PR 55, every other
configuration) reads 0 seconds there and the readers return None.
"""

import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import loop_ledger, procs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

EXIT, OUTSIDE = "exit", "outside_blocks"
KINDS = [EXIT, OUTSIDE]
EXIT_RE = re.compile(r"(?:^|[/(])exit/(?:head|gate)(?=[/)]|$)")
LOOPED_RE = re.compile(
    r"(?:^|[/(])looped/(?:pass|exit_norm)(?=[/)]|$)"
    r"|\._looped(?=[/)]|$)")
BLOCK_RE = re.compile(r"(?:^|[/(])block_\d+(?=[/)]|$)")
REDUCE_TIMEOUT = 300


def classify(op_name):
    """The kind one operation counts under, from its ``op_name``: at
    most one."""
    if EXIT_RE.search(op_name):
        return [EXIT]
    if LOOPED_RE.search(op_name) and not BLOCK_RE.search(op_name):
        return [OUTSIDE]
    return []


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    seconds = dict.fromkeys(KINDS, 0.0)
    busy = []
    for name, start, end, op_name in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start or tr.opcode(name) in tr.CONTAINER:
            continue
        busy.append((start, end))
        for kind in classify(op_name):
            seconds[kind] += (end - start) / 1e9
    return {
        "steps": len(runs) - 1,
        "busy_s": tr.total(tr.union(busy)) / 1e9,
        "seconds": seconds,
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


def time_share(reduced, kind):
    """Device time of ``kind`` over busy time, worst device, in
    percent; None for a program with nothing under it."""
    shares = [
        d["seconds"][kind] / d["busy_s"]
        for d in (reduced or {}).get("devices", {}).values()
        if d["busy_s"]
    ]
    return 100.0 * max(shares) if shares and max(shares) > 0 else None


def reduced(run):
    """``looped_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``looped_reduce.log``)."""
    if "looped_reduced" in run:
        return run["looped_reduced"]
    run["looped_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "looped_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "looped_reduce.log"), "wb") as log:
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
            run["looped_reduced"] = json.load(f)
    return run["looped_reduced"]


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "looped_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "looped_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
