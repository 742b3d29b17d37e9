"""The gated short convolution and the leading dense MLPs in a device
trace: device seconds under the three ``short_conv/`` scopes of
``models/transformer.py:ShortConv`` (``in_proj``: the projection d -> 3
d; ``gate``: B X, the taps' shifted multiply-adds and C c, with their
backward, which forms both again; ``out_proj``: the projection d -> d)
and under ``dense_mlp`` (a dense block's MLP, whatever the model's
mixers are: ``models/transformer.py:Block``), forward and backward
alike (an operation's ``op_name`` carries the scope under ``transpose(``
and inside a rematerialised block too). A Mosaic kernel named
``short_conv...`` is charged to ``short_conv/gate`` wherever it was
called: the same reader serves a later kernel for the gates. Part of
the yardstick (``tests/benchmark_harness/test_lfm2_metrics.py`` checks
it on hand-made operations).

Runs once a traced run in a CPU child process (``python
benchmark/lib/conv_trace.py <xplane> <out dir>``, started by ``reduced``
from the first metric that asks) and leaves ``conv_reduced.json``
beside the other reductions. A program with nothing under a scope (the
parent of PR 49 has none of the four, every other configuration no
``short_conv/``) reads 0 seconds there and that scope's readers return
None.
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

CONV_SCOPES = ("in_proj", "gate", "out_proj")
CONV_KINDS = ["short_conv/" + s for s in CONV_SCOPES]
GATE, DENSE_MLP = "short_conv/gate", "dense_mlp"
CONV_RE = re.compile(
    r"(?:^|[/(])short_conv/(%s)(?=[/)]|$)" % "|".join(CONV_SCOPES))
DENSE_RE = re.compile(r"(?:^|[/(])dense_mlp(?=[/)]|$)")
CONV_KERNEL = "short_conv"
REDUCE_TIMEOUT = 300


def classify(name, op_name):
    """The kinds one operation counts under, from its HLO text and its
    ``op_name``: at most one ``short_conv/<scope>``, or ``dense_mlp``."""
    if tr.MOSAIC_KERNEL in name and CONV_KERNEL in tr.kernel_name(
            name).lower():
        return [GATE]
    m = CONV_RE.search(op_name)
    if m:
        return ["short_conv/" + m.group(1)]
    return [DENSE_MLP] if DENSE_RE.search(op_name) else []


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    seconds = dict.fromkeys(CONV_KINDS + [DENSE_MLP], 0.0)
    busy = []
    for name, start, end, op_name in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start or tr.opcode(name) in tr.CONTAINER:
            continue
        busy.append((start, end))
        for kind in classify(name, op_name):
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


def busy_devices(reduced):
    return [
        d for d in (reduced or {}).get("devices", {}).values()
        if d["busy_s"]
    ]


def time_share(reduced, kinds):
    """Device time of ``kinds`` over busy time, worst device, in
    percent; None for a program with nothing under ``kinds``."""
    shares = [
        sum(d["seconds"][k] for k in kinds) / d["busy_s"]
        for d in busy_devices(reduced)
    ]
    return 100.0 * max(shares) if shares and max(shares) > 0 else None


def reduced(run):
    """``conv_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``conv_reduce.log``)."""
    if "conv_reduced" in run:
        return run["conv_reduced"]
    run["conv_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "conv_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "conv_reduce.log"), "wb") as log:
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
            run["conv_reduced"] = json.load(f)
    return run["conv_reduced"]


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "conv_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "conv_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
