"""Block diffusion's own work in a run's artefacts: device seconds
under the two scopes of ``ops/block_diffusion.py`` (``bd/noise``: the
draw of the noise levels and of the masked positions; ``bd/assemble``:
the two copies side by side, their positions, and the cut of the noisy
half before the head), forward and backward alike (an operation's
``op_name`` carries the scope under ``transpose(`` too), and the
attention line's pair counts and tiles under the block-structured mask
(``ops/attention.py``: ``mask=block_diffusion(8192, 4) pairs run=80
masked=24 skipped=176 blocks=1024x1024``). Part of the yardstick
(``tests/benchmark_harness/test_sdar_metrics.py`` checks it on
hand-made operations and recorded lines).

The trace is reduced once a traced run in a CPU child process
(``python benchmark/lib/bd_trace.py <xplane> <out dir>``, started by
``reduced`` from the first metric that asks) and leaves
``bd_reduced.json`` beside the other reductions. A program without the
scopes or the line (the parent of PR 35, every other configuration)
leaves ``"scoped": false`` and no line, and every reader returns None.
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

SCOPES = ("noise", "assemble")
KINDS = ["bd/" + s for s in SCOPES]
SCOPE_RE = re.compile(r"(?:^|[/(])bd/(%s)(?=[/)]|$)" % "|".join(SCOPES))
REDUCE_TIMEOUT = 300
PAIRS = r"run=(\d+) masked=(\d+) skipped=(\d+) blocks=(\d+)x(\d+)"
# the step's own line: the model's float32 init traces one too
LINE_RE = re.compile(
    r"attention impl=auto resolved to pallas \(backend=tpu, "
    r"q=\(([\d, ]+)\) bfloat16.*?mask=block_diffusion\((\d+), (\d+)\) "
    r"pairs %s(?: \(backward %s\))?" % (PAIRS, PAIRS))


def classify(op_name):
    """``bd/noise``, ``bd/assemble`` or None for one operation."""
    m = SCOPE_RE.search(op_name)
    return "bd/" + m.group(1) if m else None


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
        kind = classify(op_name)
        if kind:
            seconds[kind] += (end - start) / 1e9
    return {
        "steps": len(runs) - 1,
        "busy_s": tr.total(tr.union(busy)) / 1e9,
        "seconds": seconds,
        "scoped": any(seconds.values()),
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


def time_share(reduced, kinds=KINDS):
    """Device time of ``kinds`` over busy time, worst device, in
    percent; None for a program without the ``bd/`` scopes."""
    shares = [
        sum(d["seconds"][k] for k in kinds) / d["busy_s"]
        for d in (reduced or {}).get("devices", {}).values()
        if d.get("scoped") and d["busy_s"]
    ]
    return 100.0 * max(shares) if shares else None


def reduced(run):
    """``bd_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``bd_reduce.log``)."""
    if "bd_reduced" in run:
        return run["bd_reduced"]
    run["bd_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "bd_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "bd_reduce.log"), "wb") as log:
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
            run["bd_reduced"] = json.load(f)
    return run["bd_reduced"]


def attention_line(text):
    """What the worker's log says of the flash call under the mask:
    ``{"half_len", "block", "forward": (run, masked, skipped, block_q,
    block_k), "backward": the same}`` from the newest such line, None
    where there is none (another mask, the XLA path, the parent)."""
    found = LINE_RE.findall(text)
    if not found:
        return None
    numbers = found[-1]
    forward = tuple(int(n) for n in numbers[3:8])
    backward = tuple(int(n) for n in numbers[8:13]) if numbers[8] else forward
    return {"half_len": int(numbers[1]), "block": int(numbers[2]),
            "forward": forward, "backward": backward}


def fill(line):
    """Needed score entries over the entries of the tiles the kernels
    compute, in percent: L^2 + L B over pairs that run x tile area, the
    forward's two score-sized products and the backward's five each
    over their own tiles."""
    needed = line["half_len"] ** 2 + line["half_len"] * line["block"]
    computed = sum(
        products * run * block_q * block_k
        for products, (run, _, _, block_q, block_k) in (
            (2, line["forward"]), (5, line["backward"])))
    return 100.0 * 7 * needed / computed


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "bd_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "bd_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
