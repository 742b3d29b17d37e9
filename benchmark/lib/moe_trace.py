"""The expert layer in a device trace: device seconds under the four
``moe/`` scopes of ``models/moe_transformer.py:MoeMlp`` (``router``,
``dispatch``, ``experts``, ``combine``; forward and backward alike, an
operation's ``op_name`` carries the scope under ``transpose(`` too) and
of the grouped matmuls alone. Part of the yardstick
(``tests/benchmark_harness/test_moe_metrics.py`` checks it on
hand-made operations).

``lib/loop_ledger.py`` reads ``op_name`` from the ``.xplane.pb`` and
knows three scopes of the step; this file brings the expert layer's
pattern and calls that library's parser. One thing the scopes cannot
say: XLA compiles ``jax.lax.ragged_dot`` to Mosaic kernels of its own
and names them ``ragged-dot-...`` with no scope left in ``op_name``
(compiled for a v5e in PR 25). The program's only ragged dots are the
experts' grouped matmuls, so an operation of that name is charged to
``experts``; a kernel of the repo's own under ``moe/experts`` (a
``tpu_custom_call`` there) is a grouped matmul as well.

Runs once a traced run in a CPU child process (``python
benchmark/lib/moe_trace.py <xplane> <out dir>``, started by ``reduced``
from the first metric that asks) and leaves ``moe_reduced.json`` beside
the other reductions. A program without the scopes leaves
``"scoped": false`` and every reader returns None.
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

SCOPES = ("router", "dispatch", "experts", "combine")
SCOPE_RE = re.compile(r"(?:^|[/(])moe/(%s)(?=[/)]|$)" % "|".join(SCOPES))
RAGGED_DOT = "ragged-dot"
REDUCE_TIMEOUT = 300


def classify(name, op_name):
    """(scope or None, whether the operation is a grouped matmul) from
    an operation's HLO text and its ``op_name``."""
    if RAGGED_DOT in op_name or name.lstrip("%").startswith(RAGGED_DOT):
        return "experts", True
    m = SCOPE_RE.search(op_name)
    if not m:
        return None, False
    scope = m.group(1)
    return scope, scope == "experts" and tr.MOSAIC_KERNEL in name


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    scopes = dict.fromkeys(SCOPES, 0.0)
    matmul, busy = 0.0, []
    for name, start, end, op_name in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start or tr.opcode(name) in tr.CONTAINER:
            continue
        busy.append((start, end))
        scope, grouped = classify(name, op_name)
        if scope:
            scopes[scope] += (end - start) / 1e9
        if grouped:
            matmul += (end - start) / 1e9
    return {
        "steps": len(runs) - 1,
        "busy_s": tr.total(tr.union(busy)) / 1e9,
        "scopes_s": scopes,
        "expert_matmul_s": matmul,
        "scoped": any(scopes.values()),
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
    return [
        d for d in (reduced or {}).get("devices", {}).values()
        if d.get("scoped") and d["busy_s"]
    ]


def time_share(reduced, scopes=SCOPES):
    """Device time under ``scopes`` over busy time, worst device, in
    percent; None for a program without the scopes."""
    shares = [
        sum(d["scopes_s"][s] for s in scopes) / d["busy_s"]
        for d in scoped_devices(reduced)
    ]
    return 100.0 * max(shares) if shares else None


def reduced(run):
    """``moe_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``moe_reduce.log``)."""
    if "moe_reduced" in run:
        return run["moe_reduced"]
    run["moe_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "moe_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "moe_reduce.log"), "wb") as log:
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
            run["moe_reduced"] = json.load(f)
    return run["moe_reduced"]


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "moe_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "moe_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
