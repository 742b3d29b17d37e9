"""A learned sparse-attention indexer in a device trace and in the
worker's log.

Device seconds under the five ``dsa/`` scopes of
``models/transformer.py:Attention`` with an indexer
(``ops/sparse_attention.py``): ``indexer_proj`` (the indexer's three
projections, its key's norm and the rotation), ``scores`` (the pairs'
scores formed for the kept set's mask), ``select`` (the k-th largest of
each query's scores: no FLOPs needed), ``attend`` (the softmax over the
kept keys, forward and backward) and ``indexer_loss`` (the indexer's KL
term and its gradient), forward, recompute and backward alike (an
operation's ``op_name`` carries the scope under ``transpose(`` and
inside a rematerialised block too). A Mosaic kernel is charged by its
NAME wherever it was called (``KERNEL_KINDS``: ``dsa_select``,
``dsa_mask``, ``dsa_indexer_loss``, ``flash_sparse...``), so the same
reader serves a later program that moves a call. Part of the yardstick
(``tests/benchmark_harness/test_keye_metrics.py`` checks it on
hand-made operations).

Runs once a traced run in a CPU child process (``python
benchmark/lib/dsa_trace.py <xplane> <out dir>``, started by ``reduced``
from the first metric that asks) and leaves ``dsa_reduced.json`` beside
the other reductions. A program with nothing under the scopes (the
parent of PR 51, every other configuration) reads 0 seconds there and
the scopes' readers return None.

``attention_line`` reads what the worker's log says of the call under
the selection (``mask=selected(2048) pairs run= masked= skipped=
blocks= (backward ...) kept=``), ``fill`` the kept entries over the
entries of the tiles that run.
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

SCOPES = ("indexer_proj", "scores", "select", "attend", "indexer_loss")
KINDS = ["dsa/" + s for s in SCOPES]
SCORES, SELECT = "dsa/scores", "dsa/select"
# what the indexer costs: everything but the attention it selects for
INDEXER_KINDS = [k for k in KINDS if k != "dsa/attend"]
SCOPE_RE = re.compile(
    r"(?:^|[/(])dsa/(%s)(?=[/)]|$)" % "|".join(SCOPES))
# a kernel's name (lower case, as ``trace_reduce.kernel_name`` gives the
# instruction's) -> its kind; the first word that matches
KERNEL_KINDS = (
    ("dsa_select", SELECT),
    ("dsa_mask", SCORES),
    ("dsa_indexer_loss", "dsa/indexer_loss"),
    ("flash_sparse", "dsa/attend"),
)
REDUCE_TIMEOUT = 300
PAIRS = r"run=(\d+) masked=(\d+) skipped=(\d+) blocks=(\d+)x(\d+)"
LINE_RE = re.compile(
    r"q=\((\d+), (\d+), (\d+), (\d+)\)[^\n]*mask=selected\((\d+)\) pairs "
    + PAIRS + r" \(backward " + PAIRS + r"\) kept=(\d+)")


def classify(name, op_name):
    """The kind one operation counts under, from its HLO text and its
    ``op_name``: at most one ``dsa/<scope>``."""
    if tr.MOSAIC_KERNEL in name:
        kernel = tr.kernel_name(name).lower()
        for word, kind in KERNEL_KINDS:
            if word in kernel:
                return [kind]
    m = SCOPE_RE.search(op_name)
    return ["dsa/" + m.group(1)] if m else []


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
    """``dsa_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``dsa_reduce.log``)."""
    if "dsa_reduced" in run:
        return run["dsa_reduced"]
    run["dsa_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "dsa_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "dsa_reduce.log"), "wb") as log:
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
            run["dsa_reduced"] = json.load(f)
    return run["dsa_reduced"]


def attention_line(text):
    """What the worker's log says of the attention under the selection:
    ``{"seq", "topk", "kept", "forward": (run, masked, skipped, block_q,
    block_k), "backward": the same}`` from the newest such line, None
    where there is none (another mask, the parent)."""
    found = LINE_RE.findall(text)
    if not found:
        return None
    numbers = [int(n) for n in found[-1]]
    return {"seq": numbers[2], "topk": numbers[4],
            "forward": tuple(numbers[5:10]),
            "backward": tuple(numbers[10:15]), "kept": numbers[15]}


def fill(line):
    """Kept score entries over the entries of the tiles the kernels
    compute, in percent: ``sum_t min(topk, t + 1)`` a head (counted
    here from the line's ``seq`` and ``topk``, not read from it) over
    pairs that run x tile area, the forward's two score-sized products
    and the backward's five each over their own tiles."""
    full = min(line["seq"], line["topk"])
    kept = full * (full + 1) / 2.0 + float(line["seq"] - full) * line["topk"]
    computed = sum(
        products * run * block_q * block_k
        for products, (run, _, _, block_q, block_k) in (
            (2, line["forward"]), (5, line["backward"])))
    return 100.0 * 7 * kept / computed


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "dsa_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "dsa_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
