"""Readers of the worker's phase ledger (ISSUE 23): what the program
says about its own loop, in the journal and in the profiler's trace.
Part of the yardstick (``tests/benchmark_harness/test_loop_ledger.py``
checks it on a hand-made journal and hand-made spans).

Two sources, one file:

- the worker's journal, ``events/worker-*.ndjson``. ``loop_phases``
  (one every ``log_every`` steps: the loop thread's nanoseconds by
  phase), ``slow_step`` and ``worker_startup``. An event's ``ts``
  (epoch seconds) places it in the window; its durations are
  nanoseconds of ``perf_counter_ns``. Only events inside the window
  count, and in a traced run only those of steps after the step at
  which the probe stopped the profiler (``trace.done``), as
  ``stall_share`` does: stopping the profiler stalls the loop itself.
  Pure Python; the harness's parent reads it and never imports jax.
- the ``.xplane.pb[.gz]``. The loop thread's annotations
  (``edl/step`` and the ``edl/<phase>`` inside it) are host events on
  the clock of the device's operations, and every operation's
  ``op_name`` carries the scopes of ``make_train_step`` (``forward``,
  ``loss``, ``optimizer``, and JAX's own ``transpose(`` on the
  backward). ``jax.profiler.ProfileData`` does not show the
  ``op_name`` (a stat of the event's metadata), so ``load_xspace``
  parses the file with ``google.protobuf`` against the few fields of
  ``xplane.proto`` it needs. That runs once a run in a CPU child
  process (``python benchmark/lib/loop_ledger.py <xplane> <out dir>``,
  started by ``reduced`` below from the first metric that asks) and
  leaves ``loop_reduced.json`` beside ``trace_reduced.json``, with
  ``loop_gaps.json`` (the gaps between step programs split by phase)
  and ``scopes.json`` (device seconds by scope) beside the report.

A program without the ledger (the parent of PR 23) leaves none of
this: every reader then returns None and raises nothing.
"""

import glob
import gzip
import json
import os
import re
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import procs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

STEP = "edl/step"
PHASE_PREFIX = "edl/"
SCOPES = ("forward", "loss", "optimizer")
SCOPE_RE = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(SCOPES))
REDUCE_TIMEOUT = 300


# ---------------------------------------------------------------------
# the journal


def worker_events(run):
    """The worker's journal as a list of events, in file order (read
    once a run)."""
    if "worker_journal" not in run:
        events = []
        pattern = os.path.join(run["out"], "events", "worker-*.ndjson")
        for path in sorted(glob.glob(pattern)):
            with open(path, errors="replace") as f:
                for line in f:
                    try:
                        events.append(json.loads(line))
                    except ValueError:
                        pass  # a line torn by the kill at the end
        run["worker_journal"] = events
    return run["worker_journal"]


def _after_trace(run):
    """The step after which events count in a traced run (0 in an
    untraced one); None when the trace never ended."""
    if not run["trace"]:
        return 0
    try:
        with open(os.path.join(run["out"], "trace.done")) as f:
            return int(f.read())
    except (OSError, ValueError):
        return None


def in_window(run, kind, step_key):
    """The journal's events of ``kind`` inside the measured window,
    past the traced steps; None when that cannot be told."""
    after = _after_trace(run)
    if after is None:
        return None
    t0, t1 = run["window"]
    return [
        e for e in worker_events(run)
        if e.get("event") == kind and t0 <= e.get("ts", 0) <= t1
        and e.get(step_key, 0) > after
    ]


def per_step_median_ms(run, nanoseconds):
    """Median over the window's ``loop_phases`` of ``nanoseconds(event)``
    a step, in milliseconds; None without such events."""
    events = in_window(run, "loop_phases", "first_step")
    values = [
        nanoseconds(e) / e["steps"] / 1e6 for e in events or []
        if e.get("steps")
    ]
    return statistics.median(values) if values else None


def startup_seconds(run, phase):
    """Seconds of one phase of the worker's (first) ``worker_startup``."""
    for event in worker_events(run):
        if event.get("event") == "worker_startup":
            ns = event.get("phases", {}).get(phase)
            return None if ns is None else ns / 1e9
    return None


# ---------------------------------------------------------------------
# the trace: pure functions over plain lists


def loop_thread(host_lines):
    """(steps, phases) of the loop thread: of ``host_lines`` (a list of
    lists of (name, start, end)), the one line with the most
    ``edl/step`` events. ``steps`` are those events, ``phases`` the
    line's other ``edl/`` events. An annotation-shaped event on any
    other thread counts for nothing: a parked producer names no gap.
    (None, None) when no line has a step."""
    best = max(
        host_lines, default=[],
        key=lambda events: sum(1 for e in events if e[0] == STEP),
    )
    steps = sorted(
        ((s, e) for name, s, e in best if name == STEP))
    if not steps:
        return None, None
    phases = [
        (name[len(PHASE_PREFIX):], s, e) for name, s, e in best
        if name.startswith(PHASE_PREFIX) and name != STEP and e > s
    ]
    return steps, phases


def program_gaps(runs, busy):
    """The device's idle intervals between consecutive executions of
    the step program: from one's end to the next one's start, less
    whatever other operation ran there."""
    between = [
        (runs[i][1], runs[i + 1][0]) for i in range(len(runs) - 1)
        if runs[i + 1][0] > runs[i][1]
    ]
    return tr.subtract(tr.union(between), busy)


def attribute_gaps(gaps, steps, phases):
    """Nanoseconds of ``gaps`` (merged idle intervals) under each phase
    of the loop thread; ``other`` is inside a step but under no phase,
    ``outside_step`` under no step at all. (The phases of a steady
    loop do not nest; the two of start-up that do are not in a traced
    window.)"""
    split = {}
    for name, start, end in phases:
        span = [(start, end)]
        under = tr.total(tr.subtract(span, tr.subtract(span, gaps)))
        if under:
            split[name] = split.get(name, 0.0) + under
    named = tr.union([(s, e) for _, s, e in phases])
    unnamed = tr.subtract(gaps, named)
    in_step = tr.union(steps)
    outside = tr.total(tr.subtract(unnamed, in_step))
    split["other"] = tr.total(unnamed) - outside
    split["outside_step"] = outside
    return split


def scope_of(op_name):
    """forward, backward, loss, optimizer or unscoped, from an
    operation's ``op_name``; a fusion carries its root instruction's."""
    if "transpose(" in op_name:
        return "backward"
    m = SCOPE_RE.search(op_name)
    return m.group(1) if m else "unscoped"


def reduce_device(ops, modules, steps, phases):
    """One device's share of the reduction. ``ops``: (name, start, end,
    op_name) of its ``XLA Ops`` line; ``modules``: (name, start, end)
    of its ``XLA Modules`` line."""
    _, runs = tr.step_program(modules)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    core = [
        (max(s, lo), min(e, hi), op_name) for name, s, e, op_name in ops
        if min(e, hi) > max(s, lo) and tr.opcode(name) not in tr.CONTAINER
    ]
    busy = tr.union([(s, e) for s, e, _ in core])
    scopes = dict.fromkeys(SCOPES + ("backward", "unscoped"), 0.0)
    for s, e, op_name in core:
        scopes[scope_of(op_name)] += (e - s) / 1e9
    out = {
        "busy_s": tr.total(busy) / 1e9,
        "scopes_s": scopes,
        # a program without the scopes (PR 23's parent) has no
        # optimizer share to report, not one of zero
        "scoped": any(scopes[name] for name in SCOPES),
    }
    if steps:
        gaps = program_gaps(runs, busy)
        out["gap_ns"] = tr.total(gaps)
        out["gap_split_ns"] = attribute_gaps(gaps, steps, phases)
    return out


def reduce(devices, host_lines):
    """``devices``: {id: (ops, modules)}; ``host_lines``: the host
    planes' lines. Returns what ``loop_reduced.json`` holds."""
    steps, phases = loop_thread(host_lines)
    reduced = {}
    for device_id, (ops, modules) in sorted(devices.items()):
        device = reduce_device(ops, modules, steps, phases or [])
        if device:
            reduced[str(device_id)] = device
    return {
        "annotated": steps is not None,
        # what an iteration takes while the profiler listens
        "step_wall_ms": [(e - s) / 1e6 for s, e in steps or []],
        "devices": reduced,
    }


def gap_attributed_share(reduced):
    """Of the idle time between step programs on the device that has
    most of it, the percentage under a named phase."""
    devices = [
        d for d in (reduced or {}).get("devices", {}).values()
        if d.get("gap_ns")
    ]
    if not devices:
        return None
    worst = max(devices, key=lambda d: d["gap_ns"])
    named = sum(
        ns for name, ns in worst["gap_split_ns"].items()
        if name not in ("other", "outside_step")
    )
    return 100.0 * named / worst["gap_ns"]


def optimizer_time_share(reduced):
    """Device time under the ``optimizer`` scope over busy time, worst
    device, in percent."""
    shares = [
        d["scopes_s"]["optimizer"] / d["busy_s"]
        for d in (reduced or {}).get("devices", {}).values()
        if d.get("scoped") and d["busy_s"]
    ]
    return 100.0 * max(shares) if shares else None


# ---------------------------------------------------------------------
# the trace: the file (CPU child process only)

_FIELDS = {
    # message: [(name, number, type, label, message type)]
    "XSpace": [("planes", 1, "message", "repeated", "XPlane")],
    "XPlane": [
        ("name", 2, "string", "optional", None),
        ("lines", 3, "message", "repeated", "XLine"),
        ("event_metadata", 4, "message", "repeated", "EventMetadataEntry"),
        ("stat_metadata", 5, "message", "repeated", "StatMetadataEntry"),
    ],
    "EventMetadataEntry": [
        ("key", 1, "int64", "optional", None),
        ("value", 2, "message", "optional", "XEventMetadata"),
    ],
    "StatMetadataEntry": [
        ("key", 1, "int64", "optional", None),
        ("value", 2, "message", "optional", "XStatMetadata"),
    ],
    "XLine": [
        ("name", 2, "string", "optional", None),
        ("timestamp_ns", 3, "int64", "optional", None),
        ("events", 4, "message", "repeated", "XEvent"),
    ],
    "XEvent": [
        ("metadata_id", 1, "int64", "optional", None),
        ("offset_ps", 2, "int64", "optional", None),
        ("duration_ps", 3, "int64", "optional", None),
    ],
    "XEventMetadata": [
        ("id", 1, "int64", "optional", None),
        ("name", 2, "string", "optional", None),
        ("stats", 5, "message", "repeated", "XStat"),
    ],
    "XStatMetadata": [
        ("id", 1, "int64", "optional", None),
        ("name", 2, "string", "optional", None),
    ],
    "XStat": [
        ("metadata_id", 1, "int64", "optional", None),
        ("str_value", 5, "string", "optional", None),
        ("ref_value", 7, "uint64", "optional", None),
    ],
}


def _xspace_class():
    """A message class for the fields of ``xplane.proto``
    (tsl/profiler/protobuf) that the reduction reads; unknown fields
    are skipped by the parser. Map fields are read as what they are on
    the wire: repeated (key, value) entries."""
    from google.protobuf import (
        descriptor_pb2,
        descriptor_pool,
        message_factory,
    )

    proto = descriptor_pb2.FileDescriptorProto(
        name="edlbench_xplane.proto", package="edlbench.xplane",
        syntax="proto3",
    )
    field = descriptor_pb2.FieldDescriptorProto
    for message, fields in _FIELDS.items():
        desc = proto.message_type.add(name=message)
        for name, number, kind, label, target in fields:
            added = desc.field.add(
                name=name, number=number,
                type=getattr(field, "TYPE_" + kind.upper()),
                label=getattr(field, "LABEL_" + label.upper()),
            )
            if target:
                added.type_name = ".edlbench.xplane." + target
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("edlbench.xplane.XSpace"))


def load_xspace(path):
    """(devices, host_lines) for ``reduce`` from an ``.xplane.pb`` or
    its gzipped copy."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    devices, host_lines = {}, []
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        op_name_ids = {
            key for key, name in stat_names.items() if name == "tf_op"}
        names, op_names = {}, {}
        for entry in plane.event_metadata:
            names[entry.key] = entry.value.name
            for stat in entry.value.stats:
                if stat.metadata_id in op_name_ids:
                    # a string, or a reference to an interned one
                    op_names[entry.key] = stat.str_value or stat_names.get(
                        stat.ref_value, "")

        def events(line, with_op_name=False):
            base = line.timestamp_ns
            for event in line.events:
                start = base + event.offset_ps / 1e3
                item = (names.get(event.metadata_id, ""), start,
                        start + event.duration_ps / 1e3)
                yield item + (
                    (op_names.get(event.metadata_id, ""),)
                    if with_op_name else ())

        device = tr.DEVICE_PLANE.match(plane.name)
        if device:
            lines = {line.name: line for line in plane.lines}
            if tr.OPS_LINE in lines:
                devices[int(device.group(1))] = (
                    list(events(lines[tr.OPS_LINE], True)),
                    list(events(lines[tr.MODULES_LINE]))
                    if tr.MODULES_LINE in lines else [],
                )
        elif plane.name.startswith("/host:"):
            host_lines += [list(events(line)) for line in plane.lines]
    return devices, host_lines


def newest_xplane(out_dir):
    found = []
    for base, _, names in os.walk(os.path.join(out_dir, "trace")):
        found += [
            os.path.join(base, n) for n in names
            if n.endswith((".xplane.pb", ".xplane.pb.gz"))
        ]
    return sorted(found)[-1] if found else None


def reduced(run):
    """``loop_reduced.json`` of this run, made on first use by a CPU
    child process; None when there is no trace or the child failed
    (its output is in ``loop_reduce.log``)."""
    if "loop_reduced" in run:
        return run["loop_reduced"]
    run["loop_reduced"] = None
    out = run["out"]
    path = os.path.join(out, "loop_reduced.json")
    xplane = newest_xplane(out)
    if xplane and not os.path.exists(path):
        env = procs.child_env(ROOT, "cpu")
        with open(os.path.join(out, "loop_reduce.log"), "wb") as log:
            try:
                subprocess.run(
                    [sys.executable, os.path.abspath(__file__), xplane,
                     out],
                    env=env, stdout=log, stderr=subprocess.STDOUT,
                    timeout=REDUCE_TIMEOUT, check=False,
                )
            except (OSError, subprocess.TimeoutExpired):
                pass
    if os.path.exists(path):
        with open(path) as f:
            run["loop_reduced"] = json.load(f)
    return run["loop_reduced"]


def main(argv):
    xplane, out_dir = argv
    result = reduce(*load_xspace(xplane))
    devices = result["devices"]
    for name, body in (
        ("loop_gaps.json", {
            k: {"gap_ns": d.get("gap_ns"),
                "split_ns": d.get("gap_split_ns")}
            for k, d in devices.items()}),
        ("scopes.json", {
            k: dict(d["scopes_s"], busy_s=d["busy_s"])
            for k, d in devices.items()}),
        ("loop_reduced.json", result),
    ):
        tmp = os.path.join(out_dir, name + ".tmp")
        with open(tmp, "w") as f:
            json.dump(body, f, indent=1)
        os.replace(tmp, os.path.join(out_dir, name))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
