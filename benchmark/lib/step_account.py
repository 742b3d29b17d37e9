"""One account of a traced step's device time by the program's own
scopes (ISSUE 62): every non-container operation of the whole step
periods charged to ``elasticdl_tpu/observability/scopes.py:family`` of
its ``op_name`` (and, for a Mosaic kernel whose ``op_name`` lost its
scope, of its kernel's name), joined by instruction name with the
fusions the journal's ``scope_mix`` names as holding more than one
family's work (``observability/device.py:scope_mix``, the train step's
``xla_compile`` event). Part of the yardstick
(``tests/benchmark_harness/test_step_account.py`` checks it on
hand-made operations).

Runs once a traced run in a CPU child process (``python
benchmark/lib/step_account.py <xplane> <out dir>``, started by
``reduced`` from the first metric that asks) and leaves
``step_account.json`` beside ``trace_reduced.json``. Outside the
benchmark the same command reads any ``jax.profiler`` trace of a
worker; ``<out dir>/events/worker-*.ndjson`` gives ``scope_mix`` where
it is there.

For each device: ``steps``, ``period_ms``, ``busy_ms`` (the union of
the operations' intervals, a step) and ``op_ms`` (their sum: the
difference is ``overlap_ms``, operations that ran beside others),
``rows`` ``{family, scope, direction, ms, calls, kernels: {name: ms}}``
a step, ``unnamed`` and ``mixed`` with their twelve longest operations
and ``top_ops``, the twenty longest operations with family, scope and
direction. An operation without an ``op_name`` (a copy the compiler put
in) takes its nearest named operand's, up to ``NAME_HOPS`` operations
back, and ``inherited_ms`` says how much was named so. ``speaker`` is the device
with the most busy time: it speaks for a mesh, because the shares of
one device add up and a worst-of-each would not.

A program without the registry (the parent of PR 62) leaves no file
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

try:
    from elasticdl_tpu.observability import scopes  # noqa: E402
except ImportError:  # a program without the registry
    scopes = None

REDUCE_TIMEOUT = 300
TOP_OPS, TOP_LISTED = 20, 12
# how far back an operation without an ``op_name`` looks for its
# operand's (``observability/device.py:_NAME_HOPS``)
NAME_HOPS = 4
FULL_NAME = re.compile(r"^%?([\w.\-]+) = ")
OPERAND = re.compile(r"%([\w.\-]+)")
SHAPE = re.compile(r" = (\(.*?\)|\S+) [a-z]")
BLOCK = re.compile(r"block_\d+")


def instruction(name):
    """``fusion.1863`` from an operation's whole HLO text."""
    m = FULL_NAME.match(name)
    return m.group(1) if m else name.split(" ")[0].lstrip("%")


def inherited_op_name(name, texts, op_names):
    """The ``op_name`` of the nearest operand that has one, for an
    operation without its own: the first of its operands with a name,
    else the same of the first operand that ran, ``NAME_HOPS`` back."""
    for _ in range(NAME_HOPS):
        operands = OPERAND.findall(name.partition(" = ")[2])
        for operand in operands:
            if op_names.get(operand):
                return op_names[operand]
        name = next(
            (texts[operand] for operand in operands if operand in texts),
            None)
        if name is None:
            return ""
    return ""


def tail(op_name, length=96):
    """The end of an ``op_name`` with the blocks' indices folded."""
    return BLOCK.sub("block_N", op_name.rstrip(":"))[-length:]


def shape(name):
    """An operation's result shape, from its whole HLO text."""
    m = SHAPE.search(name)
    return m.group(1)[:80] if m else ""


def reduce_device(ops, modules, mix=None):
    """One device: ``ops`` (name, start, end, op_name) of its ``XLA
    Ops`` line, ``modules`` of its ``XLA Modules`` line, ``mix``
    {instruction: ``scope_mix`` row} or None; the window is
    ``lib/trace_reduce.py``'s (whole step periods)."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    steps = len(runs) - 1
    texts, op_names = {}, {}
    for name, _, _, op_name in ops:
        key = instruction(name)
        texts.setdefault(key, name)
        if op_name:
            op_names.setdefault(key, op_name)
    rows, by_op, busy, families = {}, {}, [], {}
    inherited = 0.0
    for name, start, end, op_name in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start or tr.opcode(name) in tr.CONTAINER:
            continue
        busy.append((start, end))
        ms = (end - start) / 1e6 / steps
        key = instruction(name)
        if not op_name:
            op_name = inherited_op_name(name, texts, op_names)
            inherited += ms if op_name else 0.0
        kernel = tr.kernel_name(name)
        charged = families.get((op_name, kernel))
        if charged is None:
            charged = families[op_name, kernel] = scopes.family(
                op_name, kernel)
        row = rows.setdefault(charged, {"ms": 0.0, "calls": 0, "kernels": {}})
        row["ms"] += ms
        row["calls"] += 1
        if kernel:
            row["kernels"][kernel] = row["kernels"].get(kernel, 0.0) + ms
        op = by_op.setdefault(key, {
            "op": key, "opcode": tr.opcode(name), "charged": charged,
            "op_name": tail(op_name), "shape": shape(name), "ms": 0.0})
        op["ms"] += ms
    listed = sorted(by_op.values(), key=lambda op: -op["ms"])

    def entry(op, **more):
        family, scope, direction = op["charged"]
        return dict(
            {k: op[k] for k in ("op", "opcode", "shape", "op_name", "ms")},
            family=family, scope=scope, direction=direction, **more)

    unnamed = [op for op in listed if op["charged"][0] == scopes.UNNAMED]
    mixed = None
    if mix is not None:
        found = [op for op in listed if op["op"] in mix]
        mixed = {
            "ms": sum(op["ms"] for op in found),
            "ops": [
                entry(op, root=mix[op["op"]]["root"], others=sorted(
                    set(mix[op["op"]]["bytes"]) - {mix[op["op"]]["root"]}))
                for op in found[:TOP_LISTED]
            ],
        }
    op_ms = sum(row["ms"] for row in rows.values())
    busy_ms = tr.total(tr.union(busy)) / 1e6 / steps
    return {
        "steps": steps,
        "period_ms": (hi - lo) / 1e6 / steps,
        "busy_ms": busy_ms,
        "op_ms": op_ms,
        "overlap_ms": op_ms - busy_ms,
        "inherited_ms": inherited,
        "rows": [
            {"family": family, "scope": scope, "direction": direction,
             "ms": row["ms"], "calls": row["calls"] / steps,
             "kernels": row["kernels"]}
            for (family, scope, direction), row in sorted(
                rows.items(), key=lambda kv: -kv[1]["ms"])
        ],
        "unnamed": {
            "ms": sum(op["ms"] for op in unnamed),
            "ops": [entry(op) for op in unnamed[:TOP_LISTED]],
        },
        "mixed": mixed,
        "top_ops": [entry(op) for op in listed[:TOP_OPS]],
    }


def reduce(devices, mix=None):
    """``devices``: {id: (ops, modules)} as ``loop_ledger.load_xspace``
    returns them; ``mix``: the ``scope_mix`` of the train step's
    ``xla_compile`` event, or None. What ``step_account.json`` holds."""
    by_op = None if mix is None else {
        row["op"]: row for row in mix.get("rows", ())}
    out = {}
    for device_id, (ops, modules) in sorted(devices.items()):
        device = reduce_device(ops, modules, by_op)
        if device:
            out[str(device_id)] = device
    speaker = max(out, key=lambda k: out[k]["busy_ms"], default=None)
    return {
        "speaker": speaker,
        "scope_mix": None if mix is None else {
            key: mix.get(key) for key in ("fusions", "mixed", "dropped")},
        "devices": out,
    }


def journal_mix(out_dir):
    """``scope_mix`` of the train step's first ``xla_compile`` event
    that carries one, from ``<out dir>/events/worker-*.ndjson``; None
    without it."""
    for event in loop_ledger.worker_events({"out": out_dir}):
        if (event.get("event") == "xla_compile"
                and str(event.get("fn", "")).endswith("train_step")
                and event.get("scope_mix")):
            return event["scope_mix"]
    return None


# ---------------------------------------------------------------------
# the readers


def speaker(account):
    """The device that speaks for the run, or None."""
    account = account or {}
    device = (account.get("devices") or {}).get(account.get("speaker"))
    return device if device and device["busy_ms"] else None


def share(account, keep):
    """Of the speaking device's busy time, the percentage in the rows
    ``keep(row)`` holds; None without an account."""
    device = speaker(account)
    if device is None:
        return None
    return 100.0 * sum(
        row["ms"] for row in device["rows"] if keep(row)
    ) / device["busy_ms"]


def family_share(account, family):
    return share(account, lambda row: row["family"] == family)


def named_share(account):
    """100 less ``unnamed``: what the registry names of the step."""
    unnamed = family_share(account, scopes.UNNAMED) if scopes else None
    return None if unnamed is None else 100.0 - unnamed


def mixed_share(account):
    """Time in the operations ``scope_mix`` lists over busy time; None
    where the journal carried no ``scope_mix``."""
    device = speaker(account)
    if device is None or device.get("mixed") is None:
        return None
    return 100.0 * device["mixed"]["ms"] / device["busy_ms"]


def reduced(run):
    """``step_account.json`` of this run, made on first use by a CPU
    child process; None when there is no trace, the program has no
    registry or the child failed (its output is in
    ``step_account.log``)."""
    if "step_account" in run:
        return run["step_account"]
    run["step_account"] = None
    if scopes is None:
        return None
    out = run["out"]
    path = os.path.join(out, "step_account.json")
    xplane = loop_ledger.newest_xplane(out)
    if xplane and not os.path.exists(path):
        with open(os.path.join(out, "step_account.log"), "wb") as log:
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
            run["step_account"] = json.load(f)
    return run["step_account"]


def main(argv):
    xplane, out_dir = argv
    if scopes is None:
        print("step_account: this program has no scope registry "
              "(elasticdl_tpu/observability/scopes.py)")
        return 0
    devices, _ = loop_ledger.load_xspace(xplane)
    account = reduce(devices, journal_mix(out_dir))
    if not account["devices"]:
        print("step_account: no step periods in %s" % xplane)
        return 0
    tmp = os.path.join(out_dir, "step_account.json.tmp")
    with open(tmp, "w") as f:
        json.dump(account, f, indent=1)
    os.replace(tmp, os.path.join(out_dir, "step_account.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
