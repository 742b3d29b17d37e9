#!/usr/bin/env python
"""Merge per-role EDL trace files into one Perfetto-loadable timeline.

Each role (master / worker-N / ps-N / serve-N) buffers Chrome trace
events to ``$EDL_TRACE_DIR/<role>-<pid>.trace.json``
(elasticdl_tpu/observability/trace.py). Timestamps are already
wall-clock microseconds, so merging is concatenation — plus flow
events that make the cross-role hops visible arrows in Perfetto
(https://ui.perfetto.dev) or chrome://tracing.

Flows thread by the PROPAGATED trace context first (ISSUE 9): spans
carrying ``trace_id``/``span_id``/``parent_id`` args — one worker step
or one serve predict request spanning worker → PS / client → serve →
PS — are grouped exactly, parent to child, no heuristics. Spans
WITHOUT a trace context (older trace files, standalone spans) fall
back to the PR-2 ``task_id`` correlation so old captures keep merging.

Usage:
    python scripts/merge_trace.py TRACE_DIR [-o merged.trace.json]
"""

import argparse
import json
import os
import re
import sys


def _parse_events(text):
    """Events from either trace shape: the object form
    {"traceEvents": [...]} (e.g. a re-merged file) or the JSON Array
    Format the role writers append — "[" + one event per line with
    trailing commas, closing "]" optional per the trace-event spec (a
    torn final line from a crashed process is skipped)."""
    try:
        data = json.loads(text)
    except ValueError:
        data = None
    if isinstance(data, dict):
        return data.get("traceEvents", [])
    if isinstance(data, list):
        return data
    events = []
    body = text.lstrip()
    if body.startswith("["):
        body = body[1:]
    for line in body.splitlines():
        line = line.strip().rstrip(",")
        if not line or line == "]":
            continue
        try:
            events.append(json.loads(line))
        except ValueError:
            continue  # torn tail write from a crashed role
    return events


def load_role_files(trace_dir):
    """[(filename, [events])] for every *.trace.json in the dir."""
    names = sorted(
        n for n in os.listdir(trace_dir)
        if n.endswith(".trace.json") and not n.startswith("merged")
    )
    loaded = []
    for name in names:
        path = os.path.join(trace_dir, name)
        try:
            with open(path, "r", encoding="utf-8") as f:
                events = _parse_events(f.read())
        except OSError as e:
            print("skipping %s: %s" % (path, e), file=sys.stderr)
            continue
        loaded.append((name, events))
    return loaded


# shared helpers for the consumers sitting on top of a capture
# (trace_summary.py, critical_path.py) — one definition, one behavior


def load_events(path):
    """Events from a trace DIR (merged in-memory) or a merged file."""
    if os.path.isdir(path):
        merged, _names = merge(path)
        return merged["traceEvents"]
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict):
        return data.get("traceEvents", [])
    return data


def role_by_pid(events):
    """pid -> role name, from the process_name metadata events."""
    return {
        e["pid"]: (e.get("args") or {}).get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }


def normalize_role(role):
    # "worker-3" -> "worker", "ps-0" -> "ps", "serve-1" -> "serve"
    return re.sub(r"-\d+$", "", str(role))


def percentile(values, q):
    """Nearest-rank percentile; None on an empty list."""
    if not values:
        return None
    ordered = sorted(values)
    index = min(len(ordered) - 1, int(round(q * (len(ordered) - 1))))
    return ordered[index]


# the worker ledger's phases (ISSUE 23) are children of the step's
# root on the root's own thread: the timeline already draws them nested
# in it, and an arrow through each would bury the arrows between
# processes that the flows are for
LEDGER_PHASE_PREFIX = "edl/"


def context_flow_events(events):
    """Flow (s/t/f) events threading every span of one TRACE (same
    propagated ``trace_id``) across processes, in timestamp order —
    the exact grouping the span context carried over gRPC metadata."""
    by_trace = {}
    for event in events:
        if event.get("ph") != "X":
            continue
        if event.get("name", "").startswith(LEDGER_PHASE_PREFIX):
            continue
        trace_id = (event.get("args") or {}).get("trace_id")
        if not trace_id:
            continue
        by_trace.setdefault(trace_id, []).append(event)
    flows = []
    for trace_id, spans in sorted(by_trace.items()):
        if len(spans) < 2:
            continue
        spans.sort(key=lambda e: e["ts"])
        for i, event in enumerate(spans):
            phase = "s" if i == 0 else ("f" if i == len(spans) - 1 else "t")
            flow = {
                "name": "trace",
                "cat": "trace",
                "ph": phase,
                "id": trace_id[:16],
                "ts": event["ts"],
                "pid": event["pid"],
                "tid": event["tid"],
            }
            if phase == "f":
                flow["bp"] = "e"  # bind to the enclosing slice
            flows.append(flow)
    return flows


def task_flow_events(events):
    """Flow (s/t/f) events connecting same-task_id spans across
    processes, in timestamp order. Task groups whose EVERY span also
    carries a trace context are skipped (context_flow_events already
    threads them exactly); mixed groups still thread fully — the
    master's ``dispatch`` span has a task_id but no trace context (the
    worker's get_task poll runs outside any root span), and dropping
    the worker's context-carrying train/push spans from its group
    would orphan the dispatch arrow the PR-2 timeline promises."""
    by_task = {}
    for event in events:
        if event.get("ph") != "X":
            continue
        args = event.get("args") or {}
        task_id = args.get("task_id")
        if task_id in (None, ""):
            continue
        by_task.setdefault(task_id, []).append(event)
    by_task = {
        task_id: spans
        for task_id, spans in by_task.items()
        if any(
            not (e.get("args") or {}).get("trace_id") for e in spans
        )
    }
    flows = []
    for task_id, spans in sorted(by_task.items(), key=lambda kv: str(kv[0])):
        if len(spans) < 2:
            continue
        spans.sort(key=lambda e: e["ts"])
        for i, event in enumerate(spans):
            phase = "s" if i == 0 else ("f" if i == len(spans) - 1 else "t")
            flow = {
                "name": "task",
                "cat": "task",
                "ph": phase,
                "id": str(task_id),
                "ts": event["ts"],
                "pid": event["pid"],
                "tid": event["tid"],
            }
            if phase == "f":
                flow["bp"] = "e"  # bind to the enclosing slice
            flows.append(flow)
    return flows


def merge(trace_dir):
    role_files = load_role_files(trace_dir)
    if not role_files:
        raise SystemExit("no *.trace.json files in %s" % trace_dir)
    events = []
    for _name, role_events in role_files:
        events.extend(role_events)
    events.extend(context_flow_events(events))
    events.extend(task_flow_events(events))
    # stable display: metadata first, then time order
    events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0)))
    return {"traceEvents": events}, [name for name, _ in role_files]


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("trace_dir", help="EDL_TRACE_DIR of the run")
    parser.add_argument(
        "-o", "--output", default="",
        help="output path (default: TRACE_DIR/merged.trace.json)",
    )
    args = parser.parse_args(argv)
    merged, names = merge(args.trace_dir)
    out = args.output or os.path.join(args.trace_dir, "merged.trace.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(merged, f)
    print(
        "merged %d role file(s) (%s) -> %s [%d events]"
        % (len(names), ", ".join(names), out, len(merged["traceEvents"]))
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
