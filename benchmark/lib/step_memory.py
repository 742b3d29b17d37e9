"""Readers of the program's own HBM account (ISSUE 47), in the
worker's journal: the train step's ``xla_compile`` event (``memory``:
the compiler's count of the program in bytes a device; ``peak_live``:
what the scheduled program holds at its fullest point, by the
program's scopes: ``observability/device.py``) and the
``device_memory`` events (the allocator, read at ``state_init``,
``first_step`` and ``teardown``). Pure Python; the harness's parent
reads it and never imports jax. A program without them (the parent of
PR 47) leaves none of this: every reader then returns None and raises
nothing."""

import json
import os

from benchmark.lib import loop_ledger

# outside this band the walk over the schedule and the compiler's own
# peak disagree: the groups then say nothing of where the peak lies
CALIBRATED = (0.85, 1.15)
NOT_NAMED = ("other", "unnamed")


def step_event(run):
    """The train step's first ``xla_compile`` event that carries the
    compiler's count (found as ``collective_gb_per_step`` finds its
    own); None without one."""
    for event in loop_ledger.worker_events(run):
        if (event.get("event") == "xla_compile"
                and str(event.get("fn", "")).endswith("train_step")
                and event.get("memory")):
            return event
    return None


def step_gb(run, key):
    """``memory[key]`` of the train step in GB; None without it."""
    event = step_event(run)
    return None if event is None else event["memory"][key] / 1e9


def device_memory(run):
    """The worker's ``device_memory`` events, in the journal's order."""
    return [
        event for event in loop_ledger.worker_events(run)
        if event.get("event") == "device_memory"
    ]


def worker_peak_bytes(run):
    """The fullest device's peak (``peak_in_use + peak_reserved``) of
    the ``device_memory`` event at ``teardown``, else of the last one
    journaled; None without one or where the backend has no allocator
    (a CPU rehearsal: a sum of live arrays is no device's peak)."""
    found = device_memory(run)
    if not found:
        return None
    event = next(
        (e for e in found if e.get("at") == "teardown"), found[-1])
    peaks = [
        d["peak_in_use"] + d["peak_reserved"]
        for d in event.get("devices") or ()
    ]
    return max(peaks) if peaks else None


def named_share(peak_live):
    """Of ``walk_peak``, the percentage in groups the program's scopes
    name; None for a walk that is not calibrated against the
    compiler's peak."""
    ratio = (peak_live or {}).get("walk_over_compiler")
    if ratio is None or not CALIBRATED[0] <= ratio <= CALIBRATED[1]:
        return None
    if not peak_live.get("walk_peak"):
        return None
    named = sum(
        group["bytes"] for group in peak_live["groups"]
        if group["scope"] not in NOT_NAMED)
    return 100.0 * named / peak_live["walk_peak"]


def write_step_memory(run):
    """Leaves ``step_memory.json`` beside ``loop_gaps.json``: the train
    step's ``memory`` and ``peak_live`` and the ``device_memory``
    readings. Returns the step's event (None without one, and then
    writes nothing)."""
    event = step_event(run)
    if event is None:
        return None
    body = {
        "fn": event.get("fn"),
        "cost_fetch_seconds": event.get("cost_fetch_seconds"),
        "memory": event["memory"],
        "peak_live": event.get("peak_live"),
        "device_memory": [
            {key: e.get(key) for key in (
                "at", "ts", "source", "devices", "fullest",
                "bytes_in_use", "peak_bytes", "limit_bytes")}
            for e in device_memory(run)
        ],
    }
    path = os.path.join(run["out"], "step_memory.json")
    with open(path + ".tmp", "w") as f:
        json.dump(body, f, indent=1)
    os.replace(path + ".tmp", path)
    return event
