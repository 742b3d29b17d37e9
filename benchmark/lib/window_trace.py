"""Softmax attention by KIND of layer in a run's artefacts: device
seconds under the named scopes a model that mixes kinds puts around its
mixers (``models/transformer.py:Attention.kind_scope``:
``attn_full/...`` and ``attn_window/...`` with ``qkv``, ``rotary``,
``flash``, ``gate`` and ``out_proj`` beneath), forward and backward
alike (an operation's ``op_name`` carries the scope under
``transpose(`` too), the band's flash kernels told from the causal ones
by their names (``ops/flash_attention.py:_kernel_name``:
``flash_band_fwd``, ``flash_band_bwd``, ...; a Mosaic kernel named so
counts under ``attn_window/flash`` wherever it was called, any other
named ``flash...`` under ``attn_full/flash``), and the attention line's
pair counts and tiles under the band (``ops/attention.py``:
``mask=window(512) pairs run=63 masked=63 skipped=961
blocks=1024x1024``). Part of the yardstick
(``tests/benchmark_harness/test_laguna_metrics.py`` checks it on
hand-made operations and recorded lines).

The trace is reduced once a traced run in a CPU child process
(``python benchmark/lib/window_trace.py <xplane> <out dir>``, started
by ``reduced`` from the first metric that asks) and leaves
``window_reduced.json`` beside the other reductions. A program without
the scopes or the line (the parent of PR 42, every other configuration)
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

KIND_NAMES = ("full", "window")
PARTS = ("qkv", "rotary", "flash", "gate", "out_proj")
KINDS = ["attn_%s/%s" % (kind, part)
         for kind in KIND_NAMES for part in PARTS]
WINDOW_KINDS = [k for k in KINDS if k.startswith("attn_window/")]
BAND_KERNELS = "attn_window/flash"
SCOPE_RE = re.compile(
    r"(?:^|[/(])attn_(%s)/(%s)(?=[/)]|$)"
    % ("|".join(KIND_NAMES), "|".join(PARTS)))
REDUCE_TIMEOUT = 300
PAIRS = r"run=(\d+) masked=(\d+) skipped=(\d+) blocks=(\d+)x(\d+)"
# the step's own line: the model's float32 init traces one too
LINE_RE = re.compile(
    r"attention impl=auto resolved to pallas \(backend=tpu, "
    r"q=\(([\d, ]+)\) bfloat16.*?mask=window\((\d+)\) "
    r"pairs %s(?: \(backward %s\))?" % (PAIRS, PAIRS))


def classify(name, op_name):
    """``attn_<kind>/<part>`` or None for one operation, from its HLO
    text and its ``op_name``."""
    if tr.MOSAIC_KERNEL in name:
        head = name.split(" = ")[0].lower()
        if "flash_band" in head:
            return BAND_KERNELS
        if "flash" in head:
            return "attn_full/flash"
    m = SCOPE_RE.search(op_name)
    return "attn_%s/%s" % m.groups() if m else None


def reduce_device(ops, modules):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    seconds = dict.fromkeys(KINDS, 0.0)
    band_kernels = 0.0
    busy = []
    for name, start, end, op_name in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start or tr.opcode(name) in tr.CONTAINER:
            continue
        busy.append((start, end))
        kind = classify(name, op_name)
        if kind:
            seconds[kind] += (end - start) / 1e9
            if kind == BAND_KERNELS and tr.MOSAIC_KERNEL in name:
                band_kernels += (end - start) / 1e9
    return {
        "steps": len(runs) - 1,
        "busy_s": tr.total(tr.union(busy)) / 1e9,
        "seconds": seconds,
        "band_kernels_s": band_kernels,
        # a program of another family has flash kernels and no scope:
        # only a scope puts time under a part that is no kernel's
        "scoped": any(secs for kind, secs in seconds.items()
                      if not kind.endswith("/flash")),
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
            if d.get("scoped") and d["busy_s"]]


def time_share(reduced, kinds=WINDOW_KINDS):
    """Device time of ``kinds`` over busy time, worst device, in
    percent; None for a program without the scopes."""
    shares = [
        sum(d["seconds"][k] for k in kinds) / d["busy_s"]
        for d in scoped_devices(reduced)]
    return 100.0 * max(shares) if shares else None


def reduced(run):
    """``window_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``window_reduce.log``)."""
    if "window_reduced" in run:
        return run["window_reduced"]
    run["window_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "window_reduced.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "window_reduce.log"), "wb") as log:
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
            run["window_reduced"] = json.load(f)
    return run["window_reduced"]


def attention_line(text):
    """What the worker's log says of the flash call under the band:
    ``{"seq", "window", "forward": (run, masked, skipped, block_q,
    block_k), "backward": the same}`` from the newest such line, None
    where there is none (another mask, the XLA path, the parent)."""
    found = LINE_RE.findall(text)
    if not found:
        return None
    numbers = found[-1]
    forward = tuple(int(n) for n in numbers[2:7])
    backward = tuple(int(n) for n in numbers[7:12]) if numbers[7] else forward
    seq = int(numbers[0].split(",")[2])
    return {"seq": seq, "window": int(numbers[1]),
            "forward": forward, "backward": backward}


def fill(line):
    """Kept score entries over the entries of the tiles the kernels
    compute, in percent: S W - W (W - 1) / 2 over pairs that run x tile
    area, the forward's two score-sized products and the backward's
    five each over their own tiles."""
    window = min(line["window"], line["seq"])
    kept = line["seq"] * window - window * (window - 1) / 2.0
    computed = sum(
        products * run * block_q * block_k
        for products, (run, _, _, block_q, block_k) in (
            (2, line["forward"]), (5, line["backward"])))
    return 100.0 * 7 * kept / computed


def main(argv):
    xplane, out_dir = argv
    devices, _ = loop_ledger.load_xspace(xplane)
    tmp = os.path.join(out_dir, "window_reduced.json.tmp")
    with open(tmp, "w") as f:
        json.dump(reduce(devices), f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "window_reduced.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
