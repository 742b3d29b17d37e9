"""Readers of what the program's processes say: the worker's log and
the master's journal. Copied from ``chip_smoke.py`` (PR 21) and
extended with the timestamps of the compile ledger."""

import glob
import json
import os
import re
import time

TS_RE = re.compile(r"^(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3}) ")
STEP_RE = re.compile(r"step (\d+) loss (\S+)")
DEVICES_RE = re.compile(
    r"devices: platform=(\S+) device_kind=(.+?) "
    r"local_devices=(\d+) global_devices=(\d+)"
)
ATTENTION_RE = re.compile(r"attention impl=auto resolved to (\w+)")
COMPILE_RE = re.compile(
    r"xla (re)?compile #(\d+) of (\S+?):? (?:call |\()([\d.]+)s"
)


def log_seconds(line):
    """Epoch seconds of a log line's millisecond timestamp (local
    time, as the harness's own ``time.time()`` marks are)."""
    m = TS_RE.match(line)
    if not m:
        return None
    return time.mktime(
        time.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")
    ) + int(m.group(2)) / 1e3


def parse_worker_log(text):
    """``steps``: (number, epoch seconds, loss) of every ``step N loss``
    line; the ``devices:`` line; what attention resolved to; and the
    compile ledger: (function, epoch seconds, call seconds, number)."""
    facts = {"steps": [], "compiles": [], "attention": []}
    for line in text.splitlines():
        m = STEP_RE.search(line)
        if m:
            facts["steps"].append(
                (int(m.group(1)), log_seconds(line), float(m.group(2)))
            )
            continue
        m = DEVICES_RE.search(line)
        if m:
            facts["platform"] = m.group(1)
            facts["device_kind"] = m.group(2)
            facts["local_devices"] = int(m.group(3))
            facts["device_count"] = int(m.group(4))
            continue
        m = ATTENTION_RE.search(line)
        if m:
            if m.group(1) not in facts["attention"]:
                facts["attention"].append(m.group(1))
            continue
        m = COMPILE_RE.search(line)
        if m:
            facts["compiles"].append({
                "fn": m.group(3), "at": log_seconds(line),
                "call_s": float(m.group(4)), "n": int(m.group(2)),
            })
    return facts


def read_journal(events_dir):
    """The master's journal as a list of events, in file order."""
    events = []
    for path in sorted(
        glob.glob(os.path.join(events_dir, "master-*.ndjson"))
    ):
        with open(path, errors="replace") as f:
            for line in f:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    return events


def count_tasks(events, until):
    """(attempted, failed): tasks dispatched up to ``until`` (epoch
    seconds; the stop signal), and those of them that were reported
    not ok or requeued at any time."""
    dispatched, bad = set(), set()
    for event in events:
        kind, task = event.get("event"), event.get("task")
        if kind == "task_dispatch":
            if float(event["ts"]) <= until:
                dispatched.add(task)
        elif kind == "task_requeue" or (
            kind == "task_report" and not event.get("ok")
        ):
            bad.add(task)
    return len(dispatched), len(dispatched & bad)
