"""From the profiler's ``.xplane.pb`` to the numbers the per-layer
metrics read. Part of the yardstick: every PR computes the same
numbers the same way (``tests/benchmark_harness/test_trace_reduce.py``
checks it on a recorded trace).

``load`` turns the file into plain lists (``jax.profiler.ProfileData``
needs jax, which this process may import: it runs on the CPU, after the
worker). ``reduce`` is pure Python over those lists:

- a device is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line
  holds one event per executed HLO operation, its ``XLA Modules`` line
  one per executed program;
- the step program is the module with the most device time. The traced
  window runs from the start of its first execution to the start of its
  last, so it holds whole step periods, gaps included;
- busy time is the union of the operation intervals inside the window;
  a gap is an interval of the window in which no operation runs;
- an event's name is the operation's whole HLO text; ``label`` cuts it
  to instruction, opcode and first shape (``%fusion.24 fusion
  f32[2048,50304]``);
- a kernel is a custom call to ``tpu_custom_call`` (a Pallas / Mosaic
  kernel). The program gives its kernels no name yet, so a kernel is
  summed under the instruction's name without its number (the flax
  scope it was called in, ``attn`` for flash attention) and its output
  dtypes: ``attn/bf16,f32`` is the flash forward (o, lse), ``attn/bf16``
  dq, ``attn/bf16,bf16`` dkv;
- a collective is an operation whose opcode says all-gather,
  reduce-scatter, all-reduce, all-to-all or collective-permute. On the
  ``XLA Ops`` line it holds the core: a synchronous collective, or the
  ``-done`` of an asynchronous one, for which the core waits. That is
  the EXPOSED time, and every device's plane has it. The time a
  collective is IN FLIGHT also counts an asynchronous one from start to
  done, which only the ``Async XLA Ops`` line shows, and the profiler
  writes that line for some devices only (on the v5e host of PR 22: for
  device 0 of four). So ``collective_s`` is read only from devices
  whose plane carries collectives on that line (``collective_async``),
  or from all when none does (a program without asynchronous
  collectives): one definition in one trace, never a mix
  (``collective_devices``).

Usage: ``python benchmark/lib/trace_reduce.py <xplane.pb[.gz]> <out.json>``;
a raw trace is left gzipped in place.
"""

import gzip
import json
import os
import re
import shutil
import statistics
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute"
)
# operations that only contain others: their interval is their
# children's, so they count neither as compute nor as busy on their own
CONTAINER = ("while", "conditional", "call")
INSTRUCTION = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = ")
OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
SHAPE = re.compile(r"\b([a-z]+\d*)(\[[\d,]*\])")
SOURCE_LINE = re.compile(r"^\$[^\s:<]+:\d+ ")
BLOCKED = re.compile(r"\b(acquire|wait|sleep|select|poll|get)$")
MOSAIC_KERNEL = 'custom_call_target="tpu_custom_call"'
TOP_OPS, TOP_GAPS = 10, 5
# a host event names a gap if it covers at least this much of it
GAP_COVER = 0.5


def load(path):
    """{plane name: {line name: [(name, start_ns, end_ns)]}}; lines of
    one name in one plane (the host's python threads) are joined."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for event in line.events:
                events.append((
                    event.name, float(event.start_ns),
                    float(event.start_ns + event.duration_ns),
                ))
    return planes


def union(intervals):
    """Sorted, merged copy of (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(intervals, holes):
    """The parts of merged ``intervals`` not covered by merged
    ``holes``."""
    out, j = [], 0
    for start, end in intervals:
        cursor = start
        while j < len(holes) and holes[j][1] <= cursor:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cursor:
                out.append((cursor, holes[k][0]))
            cursor = max(cursor, holes[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def opcode(name):
    m = OPCODE.search(name)
    return m.group(1) if m else name.split(".")[0].lstrip("%")


def label(name):
    """``%fusion.24 fusion f32[2048,50304]`` from the whole HLO text."""
    head = name.split(" = ")[0]
    if head == name:
        return name[:80]
    shape = SHAPE.search(name)
    return "%s %s %s" % (
        head, opcode(name), "".join(shape.groups()) if shape else "")


def kernel_name(name):
    """What a Mosaic kernel is summed under, or None for other ops."""
    if MOSAIC_KERNEL not in name:
        return None
    m = INSTRUCTION.match(name)
    result = name.split(" custom-call(")[0]
    dtypes = [dtype for dtype, _ in SHAPE.findall(result.split(" = ")[-1])]
    return "%s/%s" % (m.group(1) if m else "kernel", ",".join(dtypes))


def step_program(modules):
    """(name, executions sorted by start) of the module with the most
    device time."""
    by_name = {}
    for name, start, end in modules:
        # executions of one program differ only in the run id suffix
        by_name.setdefault(re.sub(r"\(\d+\)$", "", name), []).append(
            (start, end))
    if not by_name:
        return None, []
    name = max(by_name, key=lambda n: total(by_name[n]))
    return name, sorted(by_name[name])


def reduce_device(lines):
    ops = lines.get(OPS_LINE, [])
    program, runs = step_program(lines.get(MODULES_LINE, []))
    if not ops:
        return None
    if len(runs) >= 2:
        lo, hi, steps = runs[0][0], runs[-1][0], len(runs) - 1
        gaps = [
            (runs[i + 1][0] - runs[i][1]) / 1e6 for i in range(steps)
        ]
    elif runs:
        (lo, hi), steps, gaps = runs[0], 1, []
    else:
        lo = min(s for _, s, _ in ops)
        hi = max(e for _, _, e in ops)
        steps, gaps = 0, []

    def inside(events):
        return [
            (name, max(s, lo), min(e, hi)) for name, s, e in events
            if min(e, hi) > max(s, lo) and opcode(name) not in CONTAINER
        ]

    core = inside(ops)
    busy = union([(s, e) for _, s, e in core])
    op_seconds, kernels, collective, compute = {}, {}, [], []
    for name, s, e in core:
        short = label(name)
        op_seconds[short] = op_seconds.get(short, 0.0) + (e - s) / 1e9
        kernel = kernel_name(name)
        if kernel:
            kernels[kernel] = kernels.get(kernel, 0.0) + (e - s) / 1e9
        if COLLECTIVE.search(opcode(name)):
            collective.append((s, e))
        else:
            compute.append((s, e))
    in_flight = [
        (s, e) for name, s, e in inside(lines.get(ASYNC_LINE, []))
        if COLLECTIVE.search(opcode(name))
    ]
    collective = union(collective + in_flight)
    exposed = subtract(collective, union(compute))
    return {
        "program": program, "steps": steps,
        "window": (lo, hi),
        "window_s": (hi - lo) / 1e9,
        "busy_s": total(busy) / 1e9,
        "step_gap_median_ms": statistics.median(gaps) if gaps else None,
        "step_gaps_ms": gaps,
        "idle": subtract([(lo, hi)], busy),
        "ops": op_seconds, "kernels": kernels,
        "collective_s": total(collective) / 1e9,
        "collective_exposed_s": total(exposed) / 1e9,
        "collective_async": bool(in_flight),
    }


def collective_devices(devices):
    """The devices whose ``collective_s`` means time in flight: those
    whose plane shows asynchronous collectives from start to done when
    any does, else all of them (see the module's notes)."""
    seen = [d for d in devices if d.get("collective_async")]
    return seen or list(devices)


def name_gap(gap, host_events):
    """What the host was doing in a device gap: the shortest host event
    that covers at least ``GAP_COVER`` of it, led by the shortest such
    event that names a source line (``$file.py:line function``) when
    the shortest is a bare builtin; ``unattributed`` when none does."""
    start, end = gap
    covering = sorted(
        (e - s, name) for name, s, e in host_events
        if min(e, end) - max(s, start) >= GAP_COVER * (end - start)
    )
    if not covering:
        return "unattributed"
    # a thread parked in a lock or a sleep covers every gap and explains
    # none: it names the gap only if nothing else does
    working = [c for c in covering if not BLOCKED.search(c[1])] or covering
    leaf = working[0][1]
    for _, name in working:
        if SOURCE_LINE.match(name):
            return name if name == leaf else "%s > %s" % (name, leaf)
    return leaf


def reduce(planes):
    """The reduced trace, or None when no device plane has operations
    (a CPU run): the readers then return nothing."""
    devices = []
    for plane_name in sorted(planes):
        m = DEVICE_PLANE.match(plane_name)
        if not m:
            continue
        device = reduce_device(planes[plane_name])
        if device:
            device["id"] = int(m.group(1))
            devices.append(device)
    if not devices:
        return None
    host_events = [
        event
        for plane_name, lines in planes.items()
        if plane_name.startswith("/host:")
        for events in lines.values()
        for event in events if event[2] > event[1]
    ]
    ops = {}
    for device in devices:
        for name, secs in device["ops"].items():
            ops[name] = ops.get(name, 0.0) + secs / len(devices)
    # the longest gaps of the fullest-traced device, named by the host
    first = devices[0]
    gaps = sorted(first["idle"], key=lambda g: g[0] - g[1])[:TOP_GAPS]
    idle_gaps = [
        [name_gap(gap, host_events), (gap[1] - gap[0]) / 1e9]
        for gap in gaps
    ]
    for device in devices:
        del device["idle"], device["window"]
    return {
        "window_s": sum(d["window_s"] for d in devices) / len(devices),
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "steps": min(d["steps"] for d in devices),
        "devices": devices,
        "breakdown": {
            "device_ops": [
                [name, secs] for name, secs in sorted(
                    ops.items(), key=lambda kv: -kv[1])[:TOP_OPS]
            ],
            "idle_gaps": idle_gaps,
        },
    }


def main(argv):
    xplane, out_path = argv
    reduced = reduce(load(xplane))
    if reduced is None:
        print("trace_reduce: no device operations in %s" % xplane)
        return 0
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(reduced, f)
    os.replace(tmp, out_path)
    if not xplane.endswith(".gz"):
        # what a run leaves has to stay small: the raw trace is kept,
        # six times smaller (``load`` reads it either way)
        with open(xplane, "rb") as raw, gzip.open(xplane + ".gz", "wb") as z:
            shutil.copyfileobj(raw, z)
        os.remove(xplane)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
