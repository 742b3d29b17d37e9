"""Latent attention and the shared experts in a device trace: device
seconds under the five ``mla/`` scopes of ``models/transformer.py:
LatentAttention`` (``q_proj``, ``kv_down``, ``kv_up``, ``assemble``,
``out_proj``), under ``moe/shared`` of ``models/moe_transformer.py:
MoeMlp``, and of the flash kernels (forward and backward alike: an
operation's ``op_name`` carries the scope under ``transpose(`` too).
Part of the yardstick (``tests/benchmark_harness/
test_moonlight_metrics.py`` checks it on hand-made operations and on a
trace recorded on the chip).

Runs once a traced run in a CPU child process (``python
benchmark/lib/mla_trace.py <xplane> <out dir>``, started by ``reduced``
from the first metric that asks) and leaves ``mla_reduced.json`` beside
the other reductions. The same pass writes ``moe_reduced.json`` through
``lib/moe_trace.py:reduce`` where it is not there yet: the four
``moe_*`` metrics do not list this cell (their lists are the next
``benchmark`` issue's to extend), and Section 5's split of the expert
layer is read from that file by hand. A program without the scopes (the
parent of PR 29, every other configuration) leaves ``"scoped": false``
and every reader returns None.
"""

import gzip
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import loop_ledger, moe_trace, procs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

MLA_SCOPES = ("q_proj", "kv_down", "kv_up", "assemble", "out_proj")
MLA_RE = re.compile(r"(?:^|[/(])mla/(%s)(?=[/)]|$)" % "|".join(MLA_SCOPES))
SHARED_RE = re.compile(r"(?:^|[/(])moe/shared(?=[/)]|$)")
FLASH = "flash"
REDUCE_TIMEOUT = 300


def classify(name, op_name):
    """``mla/<scope>``, ``moe/shared``, ``flash`` or None for one
    operation, from its HLO text and its ``op_name``. A Mosaic kernel
    named ``flash...`` is a flash kernel wherever it was called."""
    if tr.MOSAIC_KERNEL in name and FLASH in (name + op_name).lower():
        return FLASH
    m = MLA_RE.search(op_name)
    if m:
        return "mla/" + m.group(1)
    if SHARED_RE.search(op_name):
        return "moe/shared"
    return None


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    seconds = dict.fromkeys(
        ["mla/" + s for s in MLA_SCOPES] + ["moe/shared", FLASH], 0.0)
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
        "scoped": any(
            secs for kind, secs in seconds.items() if kind != FLASH),
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


def time_share(reduced, kinds):
    """Device time of ``kinds`` over busy time, worst device, in
    percent; None for a program without the scopes."""
    shares = [
        sum(d["seconds"][k] for k in kinds) / d["busy_s"]
        for d in (reduced or {}).get("devices", {}).values()
        if d.get("scoped") and d["busy_s"]
    ]
    return 100.0 * max(shares) if shares else None


def reduced(run):
    """``mla_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``mla_reduce.log``)."""
    if "mla_reduced" in run:
        return run["mla_reduced"]
    run["mla_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "mla_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "mla_reduce.log"), "wb") as log:
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
            run["mla_reduced"] = json.load(f)
    return run["mla_reduced"]


def record(devices, path, periods=1):
    """The first ``periods`` whole step periods of the lowest device as
    a gzipped JSON of plain lists (``ops``: name, start, end, op_name;
    ``modules``), small enough to keep beside the tests: an operation's
    HLO text is cut to its head, with the Mosaic marker kept where it
    was there (``python benchmark/lib/mla_trace.py --record <xplane>
    <out.json.gz>``)."""
    ops, modules = devices[min(devices)]
    _, runs = tr.step_program(modules)
    lo, hi = runs[0][0], runs[periods][1]

    def cut(name):
        kernel = " " + tr.MOSAIC_KERNEL if tr.MOSAIC_KERNEL in name else ""
        return name[:160] + kernel

    body = {
        "ops": [[cut(n), s - lo, e - lo, op] for n, s, e, op in ops
                if lo <= s and e <= hi],
        "modules": [[n, s - lo, e - lo] for n, s, e in modules
                    if lo <= s and e <= hi],
    }
    with gzip.open(path, "wt") as f:
        json.dump(body, f)


def write(out_dir, name, value):
    tmp = os.path.join(out_dir, name + ".tmp")
    with open(tmp, "w") as f:
        json.dump(value, f, indent=1)
    os.replace(tmp, os.path.join(out_dir, name))


def main(argv):
    if argv[0] == "--record":
        record(loop_ledger.load_xspace(argv[1])[0], argv[2])
        return 0
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    if not os.path.exists(os.path.join(out_dir, "moe_reduced.json")):
        write(out_dir, "moe_reduced.json", moe_trace.reduce(devices))
    write(out_dir, "mla_reduced.json", reduce(devices))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
