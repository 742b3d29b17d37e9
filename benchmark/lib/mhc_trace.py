"""The hyper-connected residual path and the multi-token-prediction
module in a device trace: device seconds under the three ``mhc/`` scopes
of ``models/transformer.py:HyperConnection`` (``coef``: the norm's
statistic, the 24-wide matmul, the sigmoids, the Sinkhorn iterations;
``pre``: ``u = H_pre X``; ``post``: ``X' = H_res X + H_post^T y``) and
under the three ``mtp/`` scopes of ``models/moe_transformer.py:
MoeTransformerLM`` (``proj``, ``block``, ``head``), forward and backward
alike (an operation's ``op_name`` carries the scope under ``transpose(``
and inside the Sinkhorn's ``while`` too; the ``while`` itself is a
container and its body's operations are counted one by one). The
module's block has hyper-connections of its own: an operation under
``mtp/block/.../mhc/pre`` counts under both. A Mosaic kernel named
``mhc...`` is charged to ``mhc/post`` wherever it was called: the same
reader serves a later kernel for the mixes. Part of the yardstick
(``tests/benchmark_harness/test_xing_metrics.py`` checks it on hand-made
operations).

Runs once a traced run in a CPU child process (``python
benchmark/lib/mhc_trace.py <xplane> <out dir>``, started by ``reduced``
from the first metric that asks) and leaves ``mhc_reduced.json`` beside
the other reductions. A program without the ``mhc/`` scopes (the parent
of PR 37, every other configuration) leaves ``"scoped": false`` and
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

from benchmark.lib import loop_ledger, procs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

MHC_SCOPES = ("coef", "pre", "post")
MTP_SCOPES = ("proj", "block", "head")
MHC_KINDS = ["mhc/" + s for s in MHC_SCOPES]
MTP_KINDS = ["mtp/" + s for s in MTP_SCOPES]
MHC_RE = re.compile(r"(?:^|[/(])mhc/(%s)(?=[/)]|$)" % "|".join(MHC_SCOPES))
MTP_RE = re.compile(r"(?:^|[/(])mtp/(%s)(?=[/)]|$)" % "|".join(MTP_SCOPES))
MHC_KERNEL = "mhc"
REDUCE_TIMEOUT = 300


def classify(name, op_name):
    """The kinds one operation counts under, from its HLO text and its
    ``op_name``: at most one ``mhc/<scope>`` and one ``mtp/<scope>``."""
    kinds = []
    if tr.MOSAIC_KERNEL in name and MHC_KERNEL in tr.kernel_name(
            name).lower():
        kinds.append("mhc/post")
    else:
        m = MHC_RE.search(op_name)
        if m:
            kinds.append("mhc/" + m.group(1))
    m = MTP_RE.search(op_name)
    if m:
        kinds.append("mtp/" + m.group(1))
    return kinds


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    seconds = dict.fromkeys(MHC_KINDS + MTP_KINDS, 0.0)
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
        "scoped": any(seconds[k] for k in MHC_KINDS),
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
    percent; None for a program without the ``mhc/`` scopes or with
    nothing under ``kinds``."""
    shares = [
        sum(d["seconds"][k] for k in kinds) / d["busy_s"]
        for d in scoped_devices(reduced)
    ]
    return 100.0 * max(shares) if shares and max(shares) > 0 else None


def reduced(run):
    """``mhc_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``mhc_reduce.log``)."""
    if "mhc_reduced" in run:
        return run["mhc_reduced"]
    run["mhc_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "mhc_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "mhc_reduce.log"), "wb") as log:
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
            run["mhc_reduced"] = json.load(f)
    return run["mhc_reduced"]


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "mhc_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "mhc_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
