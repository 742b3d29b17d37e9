"""Kimi Delta Attention and latent attention without positions in a
device trace: device seconds under the six ``kda/`` scopes of
``models/transformer.py:KimiDeltaAttention`` (``in_proj``, ``conv``,
``gates``, ``scan``, ``out_norm``, ``out_proj``), under the ``mla/``
scopes of ``LatentAttention`` and of the flash kernels it calls,
forward and backward alike (an operation's ``op_name`` carries the
scope under ``transpose(`` and inside the rule's ``while`` loops too; a
``while`` itself is a container and its body's operations are counted
one by one). A Mosaic kernel named ``kda...`` is charged to
``kda/scan`` wherever it was called: the same reader serves a later
Pallas kernel for the vector rule. Part of the yardstick
(``tests/benchmark_harness/test_kimi_metrics.py`` checks it on
hand-made operations).

Runs once a traced run in a CPU child process (``python
benchmark/lib/kda_trace.py <xplane> <out dir>``, started by ``reduced``
from the first metric that asks) and leaves ``kda_reduced.json`` beside
the other reductions. A program without the ``kda/`` scopes (the parent
of PR 58, every other configuration) leaves ``"scoped": false`` and
every reader returns None.
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

from benchmark.lib import loop_ledger, mla_trace, procs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

KDA_SCOPES = ("in_proj", "conv", "gates", "scan", "out_norm", "out_proj")
KDA_RE = re.compile(r"(?:^|[/(])kda/(%s)(?=[/)]|$)" % "|".join(KDA_SCOPES))
KDA_KERNEL = "kda"
REDUCE_TIMEOUT = 300
KDA_KINDS = ["kda/" + s for s in KDA_SCOPES]
MLA_KINDS = ["mla/" + s for s in mla_trace.MLA_SCOPES] + [mla_trace.FLASH]


def classify(name, op_name):
    """``kda/<scope>``, ``mla/<scope>``, ``flash`` or None for one
    operation, from its HLO text and its ``op_name``."""
    if tr.MOSAIC_KERNEL in name and KDA_KERNEL in tr.kernel_name(
            name).lower():
        return "kda/scan"
    m = KDA_RE.search(op_name)
    if m:
        return "kda/" + m.group(1)
    kind = mla_trace.classify(name, op_name)
    return kind if kind in MLA_KINDS else None


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    seconds = dict.fromkeys(KDA_KINDS + MLA_KINDS, 0.0)
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
        "scoped": any(seconds[k] for k in KDA_KINDS),
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
    percent; None for a program without the ``kda/`` scopes."""
    shares = [
        sum(d["seconds"][k] for k in kinds) / d["busy_s"]
        for d in scoped_devices(reduced)
    ]
    return 100.0 * max(shares) if shares else None


def reduced(run):
    """``kda_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``kda_reduce.log``)."""
    if "kda_reduced" in run:
        return run["kda_reduced"]
    run["kda_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "kda_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "kda_reduce.log"), "wb") as log:
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
            run["kda_reduced"] = json.load(f)
    return run["kda_reduced"]


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "kda_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "kda_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
