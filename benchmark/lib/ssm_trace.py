"""Mamba-2 state-space mixers in a device trace: device seconds under
the six ``mamba/`` scopes of ``models/transformer.py:Mamba2Mixer``
(``in_proj``, ``conv``, ``gates``, ``scan``, ``out_norm``,
``out_proj``), forward and backward alike (an operation's ``op_name``
carries the scope under ``transpose(`` and inside the scan's ``while``
loops too; a ``while`` itself is a container and its body's operations
are counted one by one). A Mosaic kernel whose name starts ``ssd`` is
charged to ``mamba/scan`` wherever it was called: the same reader
serves a later Pallas kernel for the scan. Part of the yardstick
(``tests/benchmark_harness/test_granite_metrics.py`` checks it on
hand-made operations).

Runs once a traced run in a CPU child process (``python
benchmark/lib/ssm_trace.py <xplane> <out dir>``, started by ``reduced``
from the first metric that asks) and leaves ``ssm_reduced.json`` beside
the other reductions. A program without the ``mamba/`` scopes (the
parent of PR 60, every other configuration) leaves ``"scoped": false``
and every reader returns None.
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

MAMBA_SCOPES = ("in_proj", "conv", "gates", "scan", "out_norm", "out_proj")
MAMBA_RE = re.compile(
    r"(?:^|[/(])mamba/(%s)(?=[/)]|$)" % "|".join(MAMBA_SCOPES))
SSD_KERNEL = "ssd"
REDUCE_TIMEOUT = 300
MAMBA_KINDS = ["mamba/" + s for s in MAMBA_SCOPES]
SCAN = ["mamba/scan"]
# the bytes-bound lines a fused kernel would take
BYTES_KINDS = ["mamba/conv", "mamba/gates", "mamba/out_norm"]


def classify(name, op_name):
    """``mamba/<scope>`` or None for one operation, from its HLO text
    and its ``op_name``."""
    if tr.MOSAIC_KERNEL in name and tr.kernel_name(
            name).lower().startswith(SSD_KERNEL):
        return "mamba/scan"
    m = MAMBA_RE.search(op_name)
    return "mamba/" + m.group(1) if m else None


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    seconds = dict.fromkeys(MAMBA_KINDS, 0.0)
    busy = []
    for name, start, end, op_name in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start or tr.opcode(name) in tr.CONTAINER:
            continue
        busy.append((start, end))
        kind = classify(name, op_name)
        if kind:
            seconds[kind] += (end - start) / 1e9
    return {
        "steps": len(runs) - 1,
        "busy_s": tr.total(tr.union(busy)) / 1e9,
        "seconds": seconds,
        "scoped": any(seconds[k] for k in MAMBA_KINDS),
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


def time_share(reduced, kinds):
    """Device time of ``kinds`` over busy time, worst device, in
    percent; None for a program without the ``mamba/`` scopes."""
    shares = [
        sum(d["seconds"][k] for k in kinds) / d["busy_s"]
        for d in scoped_devices(reduced)
    ]
    return 100.0 * max(shares) if shares else None


def reduced(run):
    """``ssm_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``ssm_reduce.log``)."""
    if "ssm_reduced" in run:
        return run["ssm_reduced"]
    run["ssm_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "ssm_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "ssm_reduce.log"), "wb") as log:
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
            run["ssm_reduced"] = json.load(f)
    return run["ssm_reduced"]


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "ssm_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "ssm_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
