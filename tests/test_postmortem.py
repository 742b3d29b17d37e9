"""Postmortem tooling (ISSUE 3): journal/dump merge + ordering +
correlation threading in scripts/postmortem.py, and first-ever coverage
for scripts/trace_summary.py (the per-HLO-category breakdown of a
``jax.profiler`` capture)."""

import gzip
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
import postmortem  # noqa: E402
import trace_summary  # noqa: E402


# ---------------------------------------------------------------------------
# helpers


def write_journal(events_dir, name, records):
    path = os.path.join(str(events_dir), name)
    with open(path, "w", encoding="utf-8") as f:
        for record in records:
            f.write(json.dumps(record) + "\n")
    return path


def ev(ts, role, event, seq=None, **fields):
    record = {"ts": ts, "role": role, "pid": 1, "event": event}
    if seq is not None:
        record["seq"] = seq
    record.update(fields)
    return record


# ---------------------------------------------------------------------------
# postmortem: parsing, merge, ordering


def test_torn_tail_line_is_skipped_not_fatal(tmp_path):
    path = write_journal(
        tmp_path, "worker-1-10.events.ndjson",
        [ev(1.0, "worker-1", "role_start")],
    )
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"ts": 2.0, "role": "worker-1", "eve')  # SIGKILL tear
    events = postmortem.load_journals(str(tmp_path))
    assert len(events) == 1
    assert events[0]["event"] == "role_start"
    assert events[0]["source"] == "worker-1-10.events.ndjson"


def test_timeline_is_time_ordered_across_roles(tmp_path):
    write_journal(
        tmp_path, "master-1.events.ndjson",
        [ev(5.0, "master", "task_report", task=1),
         ev(1.0, "master", "task_dispatch", task=1, worker=0)],
    )
    write_journal(
        tmp_path, "worker-0-2.events.ndjson",
        [ev(3.0, "worker-0", "checkpoint_saved", version=4)],
    )
    report = postmortem.postmortem(str(tmp_path))
    kinds = [e["event"] for e in report["timeline"]]
    assert kinds == ["task_dispatch", "checkpoint_saved", "task_report"]


def test_dump_events_dedupe_against_journal_by_seq(tmp_path):
    """A crash dump re-records the journaled tail; the merged timeline
    must hold one copy of each (role, pid, seq)."""
    journaled = [
        ev(1.0, "worker-3", "role_start", seq=1, worker=3),
        ev(2.0, "worker-3", "task_dispatch", seq=2, task=7, worker=3),
    ]
    write_journal(tmp_path, "worker-3-9.events.ndjson", journaled)
    dump = {
        "role": "worker-3", "pid": 1, "reason": "sigterm",
        "dumped_at": 2.5,
        # the dump holds the same two events PLUS one that never made
        # the journal (emitted after the last flush... write-through
        # normally prevents this, but a dump must still contribute it)
        "events": journaled + [
            ev(2.4, "worker-3", "crash_dump", seq=3, worker=3)
        ],
    }
    with open(
        os.path.join(str(tmp_path), "worker-3-9.dump.json"), "w"
    ) as f:
        json.dump(dump, f)
    report = postmortem.postmortem(str(tmp_path))
    assert len(report["timeline"]) == 3
    assert [e["seq"] for e in report["timeline"]] == [1, 2, 3]
    assert report["dumps"][0]["reason"] == "sigterm"


def test_summary_threads_by_correlation_ids(tmp_path):
    """The acceptance story: worker-3 relaunched, its requeued task,
    the master's alert — one threaded summary."""
    write_journal(
        tmp_path, "master-1.events.ndjson",
        [
            ev(1.0, "master", "worker_register", worker=3, epoch=101),
            ev(2.0, "master", "task_dispatch", task=41, worker=3),
            ev(9.0, "master", "worker_register", worker=3, epoch=102,
               relaunch=True),
            ev(9.1, "master", "task_requeue", task=41, worker=3,
               retries=0, counted=False),
            ev(12.0, "master", "alert_raised", alert="dead_air",
               target="3"),
            ev(15.0, "master", "worker_presumed_dead", worker=3),
        ],
    )
    report = postmortem.postmortem(str(tmp_path))
    worker3 = report["summary"]["workers"]["3"]
    assert worker3["registrations"] == [101, 102]
    assert worker3["requeued_tasks"] == [41]
    assert worker3["alerts"] == ["dead_air"]
    assert worker3["presumed_dead"] == 1
    text = postmortem.render_text(
        report["timeline"], report["summary"], report["dumps"],
        report["alert_counters"],
    )
    assert "worker_register" in text and "dead_air" in text


def test_metrics_snapshot_alert_counters_fold_in(tmp_path):
    write_journal(
        tmp_path, "master-1.events.ndjson",
        [ev(1.0, "master", "role_start")],
    )
    with open(
        os.path.join(str(tmp_path), "master.metrics.txt"), "w"
    ) as f:
        f.write(
            "# TYPE edl_master_alerts_total counter\n"
            'edl_master_alerts_total{alert="dead_air"} 2\n'
            "edl_up 1\n"
        )
    report = postmortem.postmortem(str(tmp_path))
    assert report["alert_counters"] == {
        'edl_master_alerts_total{alert="dead_air"}': 2.0
    }


def test_cli_writes_json_and_exits_by_content(tmp_path):
    write_journal(
        tmp_path, "master-1.events.ndjson",
        [ev(1.0, "master", "role_start")],
    )
    out = str(tmp_path / "incident.json")
    assert postmortem.main([str(tmp_path), "-o", out]) == 0
    with open(out) as f:
        report = json.load(f)
    assert report["timeline"][0]["event"] == "role_start"
    empty = tmp_path / "empty"
    empty.mkdir()
    assert postmortem.main([str(empty)]) == 1


# ---------------------------------------------------------------------------
# trace_summary (previously zero coverage)


def _write_profiler_trace(trace_dir, stamp, events):
    profile_dir = os.path.join(
        str(trace_dir), "plugins", "profile", stamp
    )
    os.makedirs(profile_dir, exist_ok=True)
    path = os.path.join(profile_dir, "host.trace.json.gz")
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump({"traceEvents": events}, f)
    return path


_TPU_META = {
    "ph": "M", "name": "process_name", "pid": 7,
    "args": {"name": "/device:TPU:0"},
}


def _hlo(name, dur, category, bytes_accessed=0, flops=0):
    return {
        "ph": "X", "pid": 7, "tid": 1, "ts": 0, "dur": dur,
        "name": name,
        "args": {
            "hlo_category": category,
            "bytes_accessed": str(bytes_accessed),
            "flops": str(flops),
        },
    }


def test_latest_trace_path_picks_newest_stamp(tmp_path):
    _write_profiler_trace(tmp_path, "2020_01_01", [_TPU_META])
    newest = _write_profiler_trace(tmp_path, "2024_12_31", [_TPU_META])
    assert trace_summary.latest_trace_path(str(tmp_path)) == newest


def test_summarize_trace_breaks_down_by_hlo_category(tmp_path, capsys):
    events = [
        _TPU_META,
        # a host process that must be ignored
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "python"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 999,
         "name": "host_op", "args": {"hlo_category": "host"}},
        # while-wrapped ops are excluded (double counting)
        _hlo("while_loop_body", 500, "loop"),
        _hlo("fusion.1", 3000, "convolution",
             bytes_accessed=3_000_000, flops=9_000_000),
        _hlo("fusion.2", 1000, "all-reduce", bytes_accessed=1_000_000),
    ]
    path = _write_profiler_trace(tmp_path, "2024_01_01", events)
    returned = trace_summary.summarize_trace(str(tmp_path), steps=2)
    assert returned == path
    out = capsys.readouterr().out
    assert "convolution" in out and "all-reduce" in out
    assert "host" not in out.split("trace at:")[0].splitlines()[0]
    # device total = 3000+1000 us -> 4.0 ms over 2 steps
    assert "device time: 4.0 ms / 2 steps" in out
    # convolution is 75% of device time
    assert " 75.0%" in out


def test_summarize_trace_while_prefixed_ops_excluded(tmp_path, capsys):
    events = [
        _TPU_META,
        _hlo("while", 10_000, "loop"),
        _hlo("dot.3", 1000, "matmul"),
    ]
    _write_profiler_trace(tmp_path, "2024_02_02", events)
    trace_summary.summarize_trace(str(tmp_path), steps=1)
    out = capsys.readouterr().out
    # the while wrapper's 10ms must not inflate the total
    assert "device time: 1.0 ms / 1 steps" in out


def test_capture_trace_drives_profiler_and_summarizes(tmp_path):
    """capture_trace must start/stop the JAX profiler around run_once
    and summarize what landed. Exercised on CPU: the trace still
    contains XLA ops with hlo_category args."""
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))

    def run_once():
        float(step(x))  # fence so device work lands inside the trace

    try:
        trace_summary.capture_trace(run_once, str(tmp_path), steps=1)
    except IndexError:
        # some CPU builds emit no device track at all — the capture
        # protocol itself (start/stop/summarize path) still ran; the
        # category math is covered by the synthetic-trace tests above
        pytest.skip("jax CPU profiler emitted no categorized trace")
