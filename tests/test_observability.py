"""Observability subsystem (ISSUE 2 + the ISSUE 3 flight recorder):
registry semantics, Prometheus exposition, health/readiness endpoints,
RPC interceptors on a live in-process master<->worker channel, the
trace-merge round trip, the structured event journal, and the master's
fleet telemetry + anomaly detectors behind /statusz and /alerts."""

import json
import sys
import urllib.request

import numpy as np
import pytest

from elasticdl_tpu.observability import events
from elasticdl_tpu.observability import metrics as obs_metrics
from elasticdl_tpu.observability import trace
from elasticdl_tpu.observability.http_server import ObservabilityServer
from elasticdl_tpu.observability.metrics import Registry


def _get(url):
    try:
        response = urllib.request.urlopen(url, timeout=5)
        return response.status, response.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ---------------------------------------------------------------------------
# registry semantics


def test_counter_labels_accumulate_independently():
    reg = Registry(enabled=True)
    c = reg.counter("reqs_total", "requests", ("method", "code"))
    c.labels(method="get_task", code="OK").inc()
    c.labels(method="get_task", code="OK").inc(2)
    c.labels(method="get_task", code="UNAVAILABLE").inc()
    assert c.get("get_task", "OK") == 3
    assert c.get("get_task", "UNAVAILABLE") == 1
    with pytest.raises(ValueError):
        c.labels(method="only-one-label")


def test_counter_rejects_decrement():
    reg = Registry(enabled=True)
    c = reg.counter("ups_total", "u")
    with pytest.raises((TypeError, ValueError)):
        c.dec()


def test_gauge_set_function_reads_live_state():
    reg = Registry(enabled=True)
    state = {"depth": 0}
    g = reg.gauge("queue_depth", "d")
    g.set_function(lambda: state["depth"])
    state["depth"] = 7
    assert "queue_depth 7" in reg.render()


def test_histogram_buckets_are_cumulative():
    reg = Registry(enabled=True)
    h = reg.histogram("lat", "latency", ("m",), buckets=(0.1, 1.0, 10.0))
    child = h.labels(m="push")
    for value in (0.05, 0.5, 0.5, 5.0, 50.0):
        child.observe(value)
    text = reg.render()
    assert 'lat_bucket{m="push",le="0.1"} 1' in text
    assert 'lat_bucket{m="push",le="1"} 3' in text
    assert 'lat_bucket{m="push",le="10"} 4' in text
    assert 'lat_bucket{m="push",le="+Inf"} 5' in text
    assert 'lat_count{m="push"} 5' in text
    assert h.get_count("push") == 5


def test_registry_get_or_create_is_idempotent():
    reg = Registry(enabled=True)
    a = reg.counter("same", "x", ("l",))
    b = reg.counter("same", "x", ("l",))
    assert a is b
    with pytest.raises(ValueError):
        reg.counter("same", "x", ("other",))


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False)
    c = reg.counter("nope_total", "n", ("l",))
    c.labels(l="x").inc()
    c.inc(5)
    g = reg.gauge("g", "g")
    g.set(3)
    h = reg.histogram("h", "h")
    h.observe(1.0)
    assert c is obs_metrics.NOOP and g is obs_metrics.NOOP
    assert reg.render() == ""


def test_metrics_disabled_without_knobs(monkeypatch):
    monkeypatch.delenv("EDL_METRICS", raising=False)
    monkeypatch.delenv("EDL_METRICS_PORT", raising=False)
    assert not obs_metrics.metrics_enabled()
    monkeypatch.setenv("EDL_METRICS_PORT", "9090")
    assert obs_metrics.metrics_enabled()
    monkeypatch.setenv("EDL_METRICS", "0")  # explicit off wins
    assert not obs_metrics.metrics_enabled()


def test_exposition_format_golden():
    reg = Registry(enabled=True)
    c = reg.counter("edl_reqs_total", "Requests served", ("code",))
    c.labels(code="OK").inc(2)
    g = reg.gauge("edl_depth", "Queue depth")
    g.set(3)
    h = reg.histogram("edl_lat_seconds", "Latency", buckets=(0.5,))
    h.observe(0.25)
    assert reg.render() == (
        "# HELP edl_depth Queue depth\n"
        "# TYPE edl_depth gauge\n"
        "edl_depth 3\n"
        "# HELP edl_lat_seconds Latency\n"
        "# TYPE edl_lat_seconds histogram\n"
        'edl_lat_seconds_bucket{le="0.5"} 1\n'
        'edl_lat_seconds_bucket{le="+Inf"} 1\n'
        "edl_lat_seconds_sum 0.25\n"
        "edl_lat_seconds_count 1\n"
        "# HELP edl_reqs_total Requests served\n"
        "# TYPE edl_reqs_total counter\n"
        'edl_reqs_total{code="OK"} 2\n'
    )


def test_render_survives_failing_and_nonfinite_callback_gauges():
    """A broken callback gauge must not take /metrics down: its value
    renders as NaN (and explicit non-finite sets render, not raise)."""
    reg = Registry(enabled=True)
    reg.gauge("broken", "b").set_function(lambda: 1 / 0)
    reg.gauge("neg_inf", "n").set(float("-inf"))
    text = reg.render()
    assert "broken NaN" in text
    assert "neg_inf -Inf" in text


def test_label_values_are_escaped():
    reg = Registry(enabled=True)
    c = reg.counter("esc_total", "e", ("path",))
    c.labels(path='a"b\\c\nd').inc()
    assert 'esc_total{path="a\\"b\\\\c\\nd"} 1' in reg.render()


# ---------------------------------------------------------------------------
# health endpoints


def test_healthz_readyz_role_transitions():
    reg = Registry(enabled=True)
    server = ObservabilityServer("ps-0", 0, registry=reg).start()
    try:
        ready = {"model": False}
        server.add_readiness_check("model_initialized",
                                   lambda: ready["model"])
        base = "http://localhost:%d" % server.port
        assert _get(base + "/healthz")[0] == 200
        status, body = _get(base + "/readyz")
        assert status == 503 and "model_initialized" in body
        ready["model"] = True  # the role milestone flips
        assert _get(base + "/readyz")[0] == 200
        status, body = _get(base + "/metrics")
        assert status == 200
        assert 'edl_up{role="ps-0"} 1' in body
        assert _get(base + "/nope")[0] == 404
    finally:
        server.stop()


def test_raising_readiness_check_is_unready():
    reg = Registry(enabled=True)
    server = ObservabilityServer("w", 0, registry=reg)
    server.add_readiness_check("boom", lambda: 1 / 0)
    ok, failing = server.readiness()
    assert not ok and failing == ["boom"]


# ---------------------------------------------------------------------------
# http daemon error paths (ISSUE 14): previously only exercised
# incidentally through role smokes


def test_unknown_routes_answer_404_and_server_survives():
    reg = Registry(enabled=True)
    server = ObservabilityServer("w", 0, registry=reg).start()
    try:
        base = "http://localhost:%d" % server.port
        for path in ("/nope", "/metricsz", "/profilez/extra", "/"):
            assert _get(base + path)[0] == 404, path
        # 404s never take the daemon down
        assert _get(base + "/healthz")[0] == 200
    finally:
        server.stop()


def test_busy_port_degrades_to_no_server(caplog):
    """maybe_start on an occupied port returns None instead of raising:
    telemetry is best-effort, a port collision must not kill the job."""
    import socket

    from elasticdl_tpu.observability import http_server

    holder = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    holder.bind(("0.0.0.0", 0))
    holder.listen(1)
    busy_port = holder.getsockname()[1]
    try:
        assert http_server.maybe_start("w", cli_port=busy_port) is None
    finally:
        holder.close()


def test_raising_json_handler_answers_500_and_daemon_survives():
    reg = Registry(enabled=True)
    server = ObservabilityServer("master", 0, registry=reg).start()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("snapshot source broke")
        return {"ok": calls["n"]}

    server.add_json_handler("/statusz", flaky)
    try:
        base = "http://localhost:%d" % server.port
        status, body = _get(base + "/statusz")
        assert status == 500 and "snapshot source broke" in body
        # the handler thread died with the request, not the daemon:
        # probes still answer and the next handler call succeeds
        assert _get(base + "/healthz")[0] == 200
        status, body = _get(base + "/statusz")
        assert status == 200 and json.loads(body) == {"ok": 2}
        assert _get(base + "/metrics")[0] == 200
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# RPC interceptors on a live in-process master<->worker channel


@pytest.fixture
def live_metrics(monkeypatch):
    """Flip the process-global registry to enabled for the duration of
    the test, restoring the disabled default afterwards."""
    from elasticdl_tpu.observability import grpc_metrics

    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    monkeypatch.setattr(grpc_metrics, "_client_cache", (None, None))
    yield obs_metrics.default_registry()
    obs_metrics.reset_default_registry()


def test_interceptors_count_live_master_rpcs(live_metrics):
    from elasticdl_tpu.common.grpc_utils import build_server, find_free_port
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.proto.services import add_master_servicer_to_server
    from elasticdl_tpu.worker.master_client import MasterClient

    dispatcher = TaskDispatcher({"s": (0, 64)}, records_per_task=32)
    server = build_server()
    add_master_servicer_to_server(MasterServicer(dispatcher), server)
    port = find_free_port()
    server.add_insecure_port("localhost:%d" % port)
    server.start()
    try:
        mc = MasterClient("localhost:%d" % port, worker_id=0)
        assert mc.reset_worker() == mc.incarnation > 0
        task = mc.get_task()
        assert task.task_id != 0
        mc.report_task_result(task.task_id)

        text = live_metrics.render()
        for series in (
            'edl_grpc_server_handled_total{service="Master",'
            'method="get_task",code="OK"} 1',
            'edl_grpc_client_handled_total{service="Master",'
            'method="get_task",code="OK"} 1',
            'edl_grpc_server_latency_seconds_count{service="Master",'
            'method="get_task"} 1',
            'edl_grpc_client_latency_seconds_count{service="Master",'
            'method="get_task"} 1',
        ):
            assert series in text, series
        # every Master AND Pserver method's latency histogram is
        # pre-registered (zero-count series are part of the contract)
        from elasticdl_tpu.proto import services

        for method in list(services._MASTER_METHODS) + list(
            services._PSERVER_METHODS
        ):
            assert (
                'edl_grpc_client_latency_seconds_count' in text
                and 'method="%s"' % method in text
            ), method
    finally:
        server.stop(0)


def test_client_interceptor_counts_deadline_exceeded(live_metrics):
    """DEADLINE_EXCEEDED is a visible counter, not just a log line:
    point a client at a port nobody answers quickly enough."""
    import grpc

    from elasticdl_tpu.observability.grpc_metrics import (
        instrument_channel,
    )
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
    from elasticdl_tpu.proto.services import MasterStub

    channel = instrument_channel(
        grpc.insecure_channel("localhost:1")  # nothing listens
    )
    stub = MasterStub(channel)
    with pytest.raises(grpc.RpcError):
        stub.get_task(pb.GetTaskRequest(worker_id=0), timeout=0.2)
    counter = live_metrics.get("edl_grpc_client_handled_total")
    assert (
        counter.get("Master", "get_task", "UNAVAILABLE")
        + counter.get("Master", "get_task", "DEADLINE_EXCEEDED")
    ) >= 1


def test_uninstrumented_channel_when_disabled(monkeypatch):
    import grpc

    from elasticdl_tpu.observability.grpc_metrics import (
        instrument_channel, server_interceptors,
    )

    monkeypatch.delenv("EDL_METRICS", raising=False)
    monkeypatch.delenv("EDL_METRICS_PORT", raising=False)
    channel = grpc.insecure_channel("localhost:1")
    assert instrument_channel(channel) is channel
    assert server_interceptors() == ()


# ---------------------------------------------------------------------------
# cross-role trace + merge round trip


def test_trace_merge_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv(trace.TRACE_DIR_ENV, str(tmp_path))
    # emulate the three roles of a run in one process (real roles are
    # separate processes; distinct pids keep their tracks apart)
    master = trace.TraceWriter("master", str(tmp_path), pid=1001)
    worker = trace.TraceWriter("worker-0", str(tmp_path), pid=1002)

    monkeypatch.setattr(trace, "_writer", master)
    trace.complete("dispatch", __import__("time").time() - 0.01,
                   task_id=7, worker_id=0)
    master.flush()

    monkeypatch.setattr(trace, "_writer", worker)
    with trace.task_context(7):
        with trace.span("train_batch", version=1):
            with trace.span("ps_push", version=1):
                pass
    worker.flush()
    monkeypatch.setattr(trace, "_writer", None)

    sys.path.insert(0, "scripts")
    try:
        import merge_trace
    finally:
        sys.path.pop(0)
    merged, names = merge_trace.merge(str(tmp_path))
    assert len(names) == 2
    events = merged["traceEvents"]
    # Perfetto-loadable: valid JSON with the traceEvents array shape
    json.loads(json.dumps(merged))
    spans = [e for e in events if e.get("ph") == "X"]
    task7 = [e for e in spans if e["args"].get("task_id") == 7]
    assert {e["name"] for e in task7} == {
        "dispatch", "train_batch", "ps_push"
    }
    # dispatch (master pid) and train/push (worker pid) line up on one
    # timeline, correlated by task_id through flow events
    assert {e["pid"] for e in task7} == {1001, 1002}
    flows = [e for e in events if e.get("ph") in ("s", "t", "f")]
    assert [f["ph"] for f in flows] == ["s", "t", "f"]
    assert all(f["id"] == "7" for f in flows)
    # the span thread-local context propagated into the nested ps_push
    push = next(e for e in spans if e["name"] == "ps_push")
    assert push["args"]["task_id"] == 7


def test_span_is_inert_without_trace_dir(monkeypatch):
    monkeypatch.setattr(trace, "_writer", None)
    with trace.span("nothing", task_id=1):
        pass
    trace.instant("nope")
    trace.complete("nope", 0.0)
    assert not trace.enabled()


# ---------------------------------------------------------------------------
# structured event journal (ISSUE 3 flight recorder)


@pytest.fixture
def journal_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(tmp_path))
    monkeypatch.setenv(events.JOB_NAME_ENV, "test-job")
    yield tmp_path
    events._reset_for_tests()


def _read_journal(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def test_journal_is_write_through_ndjson(journal_dir):
    """Every emit is on disk before the call returns — the SIGKILL
    guarantee: no flush() needed to observe the lines."""
    journal = events.configure("worker-0")
    events.emit("role_start", worker=0, epoch=7)
    events.emit("task_dispatch", task=41, worker=0)
    records = _read_journal(journal.path)
    assert [r["event"] for r in records] == ["role_start",
                                             "task_dispatch"]
    first = records[0]
    assert first["role"] == "worker-0" and first["job"] == "test-job"
    assert first["seq"] == 1 and first["ts"] > 0
    assert records[1]["task"] == 41


def test_emit_unknown_event_type_raises(journal_dir):
    events.configure("worker-0")
    with pytest.raises(ValueError):
        events.emit("not_a_real_event")


def test_emit_survives_reentrant_write(journal_dir):
    """The SIGTERM drain hook emits while the interrupted thread may be
    inside this journal's own file.write(); Python raises RuntimeError
    ('reentrant call') on the nested write. emit() must swallow it —
    losing one line beats crashing the drain, and the record is still
    in the ring for the crash dump."""
    journal = events.configure("worker-0")
    events.emit("role_start", worker=0)  # opens the file

    class ReentrantFile:
        def write(self, line):
            raise RuntimeError("reentrant call inside TextIOWrapper")

        def flush(self):
            raise RuntimeError("reentrant call inside TextIOWrapper")

        def close(self):
            pass

    journal._file = ReentrantFile()
    events.emit("worker_draining", worker=0, reason="sigterm")
    assert journal._ring[-1]["event"] == "worker_draining"


def test_journal_inert_without_events_dir(monkeypatch, tmp_path):
    monkeypatch.delenv(events.EVENTS_DIR_ENV, raising=False)
    assert events.configure("worker-0") is None
    assert not events.enabled()
    events.emit("role_start")  # no-op, no crash, nothing written
    events.flush()
    assert events.dump("whatever") is None
    assert not list(tmp_path.iterdir())


def test_ring_dump_is_bounded_and_first_reason_wins(journal_dir):
    journal = events.configure("ps-0")
    for i in range(events._RING_SIZE + 50):
        events.emit("round_fill", version=i, fill=1, worker=0)
    path = events.dump("sigterm")
    assert path == journal.dump_path
    # a later crash path must not overwrite the original cause
    assert events.dump("uncaught:RuntimeError") is None
    with open(path, encoding="utf-8") as f:
        payload = json.load(f)
    assert payload["reason"] == "sigterm"
    assert payload["role"] == "ps-0"
    assert len(payload["events"]) == events._RING_SIZE
    # the ring holds the LAST K events
    assert payload["events"][-1]["version"] == events._RING_SIZE + 49


def test_excepthook_dumps_ring(journal_dir, monkeypatch):
    journal = events.configure("worker-2")
    events.emit("role_start", worker=2)
    monkeypatch.setattr(events, "_hooks_installed", False)
    calls = []
    monkeypatch.setattr(sys, "excepthook",
                        lambda *a: calls.append(a))
    events.install_crash_hooks()
    try:
        raise RuntimeError("boom")
    except RuntimeError:
        sys.excepthook(*sys.exc_info())
    assert calls, "original excepthook must still run"
    with open(journal.dump_path, encoding="utf-8") as f:
        assert json.load(f)["reason"] == "uncaught:RuntimeError"


# ---------------------------------------------------------------------------
# fleet telemetry + anomaly detectors (master/fleet.py)


def _blob(role="", **kw):
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    return pb.TelemetryBlob(role=role, **kw)


def _fleet(**kw):
    from elasticdl_tpu.master.fleet import FleetMonitor

    defaults = dict(
        straggler_factor=3.0, dead_air_secs=60.0,
        stuck_round_secs=60.0, version_lag_max=100,
    )
    defaults.update(kw)
    return FleetMonitor(**defaults)


def test_straggler_fires_only_against_a_fleet():
    fleet = _fleet()
    fleet.observe(0, _blob(step_time_ewma=0.1))
    fleet.observe(1, _blob(step_time_ewma=0.9))
    assert fleet.evaluate() == []  # two workers: no median to trust
    fleet.observe(2, _blob(step_time_ewma=0.1))
    firing = fleet.evaluate()
    assert [a["alert"] for a in firing] == ["straggler"]
    assert firing[0]["worker_id"] == 1
    # the straggler recovers -> the alert clears
    fleet.observe(1, _blob(step_time_ewma=0.12))
    assert fleet.evaluate() == []


def test_dead_air_fires_after_window_and_clears_on_forget():
    fleet = _fleet(dead_air_secs=0.05)
    fleet.observe(0, _blob(role="worker-0"))
    import time

    time.sleep(0.1)
    firing = fleet.evaluate()
    assert [a["alert"] for a in firing] == ["dead_air"]
    assert firing[0]["role"] == "worker-0"
    fleet.forget(0)
    assert fleet.evaluate() == []


def test_eviction_forces_dead_air_tombstone(monkeypatch):
    """A fast-task job's 3x-average task timeout can evict a dead
    worker BEFORE the dead-air window elapses (observed live: avg task
    0.25 s -> eviction at 0.75 s vs a 3 s window). The eviction must
    force the transition — counter + journal + a tombstone on /alerts
    — never silently erase the story."""
    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    try:
        fleet = _fleet(dead_air_secs=60.0)  # window far in the future
        fleet.observe(1, _blob(role="worker-1"))
        assert fleet.evaluate() == []
        fleet.mark_dead(1)  # task monitor eviction beat the window
        firing = fleet.evaluate()
        assert [a["alert"] for a in firing] == ["dead_air"]
        assert firing[0]["evicted"] is True
        assert firing[0]["role"] == "worker-1"
        counter = obs_metrics.default_registry().get(
            "edl_master_alerts_total"
        )
        assert counter.get("dead_air") == 1
        # the tombstone persists while the worker stays gone...
        assert fleet.evaluate(), "tombstone must not self-clear"
        # ...and clears when a relaunch re-registers the worker_id
        fleet.observe(1, _blob(role="worker-1"))
        assert fleet.evaluate() == []
    finally:
        obs_metrics.reset_default_registry()


def test_stuck_round_fires_when_fill_stalls():
    fleet = _fleet(stuck_round_secs=0.05)
    fleet.observe(-1, _blob(role="ps-0", round_buffer_fill=2,
                            model_version=5))
    import time

    time.sleep(0.1)
    assert [a["alert"] for a in fleet.evaluate()] == ["stuck_round"]
    # the round completes (fill empties, version advances): clears
    fleet.observe(-1, _blob(role="ps-0", round_buffer_fill=0,
                            model_version=6))
    assert fleet.evaluate() == []


def test_version_lag_runaway_fires():
    fleet = _fleet(version_lag_max=10)
    fleet.observe(-1, _blob(role="ps-0", version_lag=50))
    assert [a["alert"] for a in fleet.evaluate()] == ["version_lag"]


def test_alert_transitions_bump_counter_once(monkeypatch):
    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    try:
        fleet = _fleet(version_lag_max=10)
        fleet.observe(-1, _blob(role="ps-0", version_lag=50))
        fleet.evaluate()
        fleet.evaluate()  # still firing: edge-triggered, no re-count
        counter = obs_metrics.default_registry().get(
            "edl_master_alerts_total"
        )
        assert counter.get("version_lag") == 1
        text = obs_metrics.default_registry().render()
        assert "edl_master_alerts_firing 1" in text
    finally:
        obs_metrics.reset_default_registry()


def test_alert_clear_and_reraise_cycle_counts_and_journals(
    monkeypatch, tmp_path
):
    """Satellite (ISSUE 15): raise→clear→re-raise cycles. Only raise
    paths were asserted before; this pins the full cycle — the counter
    bumps once per RAISE transition (twice across the cycle), never on
    clear, and BOTH edges land in the journal."""
    monkeypatch.setenv("EDL_METRICS", "1")
    monkeypatch.setenv("EDL_EVENTS_DIR", str(tmp_path))
    obs_metrics.reset_default_registry()
    events._reset_for_tests()
    events.configure("master")
    try:
        fleet = _fleet(version_lag_max=10)
        # raise
        fleet.observe(-1, _blob(role="ps-0", version_lag=50))
        assert [a["alert"] for a in fleet.evaluate()] == ["version_lag"]
        # clear (lag recovers)
        fleet.observe(-1, _blob(role="ps-0", version_lag=0))
        assert fleet.evaluate() == []
        # re-raise
        fleet.observe(-1, _blob(role="ps-0", version_lag=80))
        assert [a["alert"] for a in fleet.evaluate()] == ["version_lag"]
        counter = obs_metrics.default_registry().get(
            "edl_master_alerts_total"
        )
        assert counter.get("version_lag") == 2  # one per raise, none per clear
        lines = []
        for path in tmp_path.glob("*.events.ndjson"):
            with open(path, encoding="utf-8") as f:
                lines += [json.loads(l) for l in f if l.strip()]
        edges = [
            (e["event"], e["alert"]) for e in lines
            if e["event"] in ("alert_raised", "alert_cleared")
        ]
        assert edges == [
            ("alert_raised", "version_lag"),
            ("alert_cleared", "version_lag"),
            ("alert_raised", "version_lag"),
        ], edges
    finally:
        obs_metrics.reset_default_registry()
        events._reset_for_tests()


def test_straggler_clear_and_reraise_cycle(monkeypatch):
    """The straggler detector's clear edge (recovery) and re-raise
    both transition correctly — cycle coverage for a second detector
    family (fleet-relative, not threshold-absolute)."""
    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    try:
        fleet = _fleet(straggler_factor=2.0)
        for wid, ewma in ((0, 0.1), (1, 0.1), (2, 0.9)):
            fleet.observe(wid, _blob(step_time_ewma=ewma))
        assert [a["alert"] for a in fleet.evaluate()] == ["straggler"]
        fleet.observe(2, _blob(step_time_ewma=0.11))  # recovers
        assert fleet.evaluate() == []
        fleet.observe(2, _blob(step_time_ewma=0.95))  # degrades again
        assert [a["alert"] for a in fleet.evaluate()] == ["straggler"]
        counter = obs_metrics.default_registry().get(
            "edl_master_alerts_total"
        )
        assert counter.get("straggler") == 2
    finally:
        obs_metrics.reset_default_registry()


# ---------------------------------------------------------------------------
# training-health detectors (ISSUE 15)


def test_health_detectors_raise_and_clear(monkeypatch):
    """nonfinite_loss / loss_spike / grad_explosion: raise on recent
    counter movement (or a live streak), clear after the recency
    window, re-raise on the next movement."""
    import time

    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    try:
        fleet = _fleet(health_alert_secs=0.2)
        fleet.observe(0, _blob(
            role="worker-0", health_nonfinite_batches=1,
            health_nonfinite_streak=1,
        ))
        fleet.observe(1, _blob(
            role="worker-1", health_loss_spikes=1,
            health_grad_explosions=1,
        ))
        kinds = {a["alert"] for a in fleet.evaluate()}
        assert kinds == {
            "nonfinite_loss", "loss_spike", "grad_explosion"
        }, kinds
        # a LIVE streak keeps nonfinite_loss firing past the window
        time.sleep(0.3)
        fleet.observe(0, _blob(
            role="worker-0", health_nonfinite_batches=1,
            health_nonfinite_streak=1,
        ))
        kinds = {a["alert"] for a in fleet.evaluate()}
        assert kinds == {"nonfinite_loss"}, kinds
        # streak ends, counters stop moving: everything clears
        fleet.observe(0, _blob(
            role="worker-0", health_nonfinite_batches=1,
        ))
        time.sleep(0.3)
        assert fleet.evaluate() == []
        # re-raise on the next increment
        fleet.observe(1, _blob(
            role="worker-1", health_loss_spikes=2,
            health_grad_explosions=1,
        ))
        assert [a["alert"] for a in fleet.evaluate()] == ["loss_spike"]
        counter = obs_metrics.default_registry().get(
            "edl_master_alerts_total"
        )
        assert counter.get("loss_spike") == 2
        assert counter.get("nonfinite_loss") == 1
        assert counter.get("grad_explosion") == 1
    finally:
        obs_metrics.reset_default_registry()


def test_label_shift_detector_tags_the_window():
    import time

    fleet = _fleet(health_alert_secs=0.2, label_shift_delta=0.1,
                   id_novelty_max=0.8)
    for i in range(6):  # warm the label-rate EWMA
        fleet.observe_stream_window(128 * (i + 1), 0.5, 0.2)
    assert fleet.evaluate() == []
    fleet.observe_stream_window(896, 0.85, 0.2)  # label rate jumps
    firing = fleet.evaluate()
    assert [a["alert"] for a in firing] == ["label_shift"]
    assert firing[0]["watermark"] == 896  # drift attributable to a window
    assert firing[0]["reason"] == "label_rate"
    time.sleep(0.3)  # back in band: clears after the window
    assert fleet.evaluate() == []
    # novelty-rate ceiling is the other trigger
    fleet.observe_stream_window(1024, 0.5, 0.95)
    firing = fleet.evaluate()
    assert firing and firing[0]["reason"] == "id_novelty"


def test_statusz_health_section():
    fleet = _fleet()
    fleet.observe(0, _blob(
        role="worker-0", health_loss_ewma=0.69,
        health_nonfinite_batches=2, health_skipped_batches=1,
    ))
    fleet.observe(-1, _blob(
        role="ps-0", ps_row_norm_p50=0.07, ps_row_norm_p99=1.2,
        ps_dead_row_fraction=0.25, ps_exploding_rows=3,
    ))
    fleet.observe_stream_window(512, 0.4, 0.1)
    body = fleet.snapshot()
    json.dumps(body)  # JSON-ready
    health = body["health"]
    assert health["workers"]["worker-0"]["health_nonfinite_batches"] == 2
    assert health["workers"]["worker-0"]["health_skipped_batches"] == 1
    assert health["ps"]["ps-0"]["ps_exploding_rows"] == 3
    assert health["ps"]["ps-0"]["ps_dead_row_fraction"] == 0.25
    assert health["stream"]["windows"] == 1
    assert body["thresholds"]["health_alert_secs"] == 30.0


def test_snapshot_carries_fleet_and_extras():
    fleet = _fleet()
    fleet.observe(0, _blob(role="worker-0", step_time_ewma=0.25,
                           model_version=12))
    body = fleet.snapshot(extra={"tasks": {"pending": 3}})
    json.dumps(body)  # must be JSON-ready
    entry = body["fleet"]["worker-0"]
    assert entry["step_time_ewma"] == pytest.approx(0.25)
    assert entry["model_version"] == 12
    assert body["tasks"] == {"pending": 3}
    assert body["thresholds"]["straggler_factor"] == 3.0


def test_statusz_and_alerts_served_over_http():
    reg = Registry(enabled=True)
    server = ObservabilityServer("master", 0, registry=reg).start()
    try:
        fleet = _fleet(dead_air_secs=0.01)
        fleet.observe(3, _blob(role="worker-3"))
        server.add_json_handler("/statusz", fleet.snapshot)
        server.add_json_handler("/alerts", fleet.alerts)
        import time

        time.sleep(0.05)
        base = "http://localhost:%d" % server.port
        status, body = _get(base + "/statusz")
        assert status == 200
        snap = json.loads(body)
        assert "worker-3" in snap["fleet"]
        status, body = _get(base + "/alerts")
        assert status == 200
        alerts = json.loads(body)
        assert [a["alert"] for a in alerts] == ["dead_air"]
        # a broken handler degrades to 500, never kills the server
        server.add_json_handler("/boom", lambda: 1 / 0)
        assert _get(base + "/boom")[0] == 500
        assert _get(base + "/healthz")[0] == 200
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# telemetry piggyback: servicer ingestion + worker/PS production


def test_servicer_feeds_fleet_from_piggybacked_blobs():
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    fleet = _fleet()
    dispatcher = TaskDispatcher({"s": (0, 64)}, records_per_task=32)
    servicer = MasterServicer(dispatcher, fleet_monitor=fleet)
    request = pb.GetTaskRequest(
        worker_id=0,
        telemetry=pb.TelemetryBlob(role="worker-0",
                                   step_time_ewma=0.5),
    )
    servicer.get_task(request)
    # a blob-less RPC is still a liveness sighting
    servicer.report_task_result(
        pb.ReportTaskResultRequest(task_id=1, worker_id=5)
    )
    snap = fleet.snapshot()
    assert snap["fleet"]["worker-0"]["step_time_ewma"] == pytest.approx(
        0.5
    )
    assert "worker-5" in snap["fleet"]


def test_worker_telemetry_blob_reflects_training(tmp_path):
    from elasticdl_tpu.data.readers import RecordIODataReader
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.worker.worker import Worker
    from tests.test_utils import create_mnist_recordio

    class LoopbackClient:
        """In-process MasterClient twin with the telemetry surface."""

        def __init__(self, servicer):
            from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

            self._pb = pb
            self._servicer = servicer
            self.worker_id = 0
            self.incarnation = None
            self.telemetry_provider = None

        def _req(self, cls, **kw):
            request = cls(**kw)
            if self.telemetry_provider is not None:
                blob = self.telemetry_provider()
                if blob is not None:
                    request.telemetry.CopyFrom(blob)
            return request

        def get_task(self, task_type=None):
            request = self._req(
                self._pb.GetTaskRequest, worker_id=self.worker_id
            )
            if task_type is not None:
                request.task_type = task_type
            return self._servicer.get_task(request)

        def report_task_result(self, task_id, err_message="",
                               exec_counters=None):
            self._servicer.report_task_result(
                self._req(
                    self._pb.ReportTaskResultRequest,
                    task_id=task_id, err_message=err_message,
                    worker_id=self.worker_id,
                )
            )

        def report_version(self, version):
            pass

        def report_evaluation_metrics(self, *a, **kw):
            pass

        def get_comm_info(self):
            return self._pb.CommInfo(rank=0, world_size=1,
                                     mesh_epoch=0)

    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=96,
                          seed=0)
    reader = RecordIODataReader(data_dir=str(train_dir))
    fleet = _fleet()
    dispatcher = TaskDispatcher(
        training_shards=reader.create_shards(), records_per_task=32,
    )
    servicer = MasterServicer(dispatcher, fleet_monitor=fleet)
    worker = Worker(
        LoopbackClient(servicer),
        "tests.models.mnist_with_export",
        reader,
        minibatch_size=32,
        wait_sleep_secs=0.05,
    )
    worker.run()
    assert dispatcher.finished()
    snap = fleet.snapshot()
    entry = snap["fleet"]["worker-0"]
    # the piggybacked blobs carried real training telemetry
    assert entry["step_time_ewma"] > 0
    assert entry["examples_per_sec"] > 0
    assert entry["last_task_seconds"] > 0
    assert entry["model_version"] >= 3


def test_ps_telemetry_blob_reports_rates_and_fill():
    servicer = _sync_ps_servicer(grads_to_wait=2)
    first = servicer.telemetry_blob()
    assert first.role == "ps-0" and first.push_rate == 0.0
    # one buffered push: fill=1, rates computed over the window
    servicer.push_gradients(_push_request(version=0, worker_id=1))
    blob = servicer.telemetry_blob()
    assert blob.round_buffer_fill == 1
    assert blob.push_rate > 0
    assert blob.model_version == 0


def _sync_ps_servicer(grads_to_wait=2):
    from elasticdl_tpu.ps.embedding_store import create_store
    from elasticdl_tpu.ps.servicer import PserverServicer

    store = create_store(seed=0, prefer_native=False)
    store.set_optimizer("sgd", lr=0.1)
    store.create_table("emb", 4, init_scale=0.05)
    return PserverServicer(
        store, use_async=False, grads_to_wait=grads_to_wait,
    )


def _push_request(version, worker_id=None):
    from elasticdl_tpu.common.tensor_utils import ndarray_to_blob
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    request = pb.PushGradientsRequest()
    request.gradients.version = version
    slices = request.gradients.embedding_tables["emb"]
    ndarray_to_blob(
        np.ones((2, 4), np.float32), slices.concat_tensors
    )
    slices.ids.extend([0, 1])
    if worker_id is not None:
        request.worker_id = worker_id
    return request


def test_sync_round_lifecycle_is_journaled(tmp_path, monkeypatch):
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(tmp_path))
    journal = events.configure("ps-0")
    try:
        servicer = _sync_ps_servicer(grads_to_wait=2)
        servicer.push_gradients(_push_request(version=0, worker_id=0))
        servicer.push_gradients(_push_request(version=0, worker_id=1))
        # now store version is 1: a version-0 push is stale
        servicer.push_gradients(_push_request(version=0, worker_id=0))
        kinds = [r["event"] for r in _read_journal(journal.path)]
        assert kinds == [
            "round_open", "round_fill", "round_fill", "round_close",
            "stale_push_rejected",
        ]
    finally:
        events._reset_for_tests()


# ---------------------------------------------------------------------------
# role wiring: PS readiness milestone + master dispatcher gauges


def _ps_servicer():
    from elasticdl_tpu.ps.embedding_store import create_store
    from elasticdl_tpu.ps.servicer import PserverServicer

    store = create_store(seed=0, prefer_native=False)
    store.set_optimizer("sgd", lr=1.0)
    return PserverServicer(store, use_async=True)


def test_ps_model_initialized_transitions():
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    servicer = _ps_servicer()
    assert not servicer.model_initialized()
    infos = pb.Model()
    infos.embedding_table_infos.add(name="emb", dim=4, initializer="0.05")
    servicer.push_embedding_table_infos(infos)
    assert servicer.model_initialized()


def test_ps_dense_init_also_flips_ready():
    from elasticdl_tpu.common.tensor_utils import ndarray_to_blob
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    servicer = _ps_servicer()
    assert not servicer.model_initialized()
    request = pb.Model(version=0)
    ndarray_to_blob(np.ones((2, 2), np.float32),
                    request.dense_parameters["w"])
    servicer.push_model(request)
    assert servicer.model_initialized()


def test_dispatcher_stats_track_lifecycle():
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    dispatcher = TaskDispatcher({"s": (0, 64)}, records_per_task=32)
    stats = dispatcher.stats()
    assert stats["pending"] == {"training": 2}
    assert stats["queue_depth"] == {"training": 2, "evaluation": 0}

    task = dispatcher.get(worker_id=0)
    stats = dispatcher.stats()
    assert stats["pending"] == {"training": 1}
    assert stats["doing"] == {"training": 1}

    dispatcher.report(task.task_id, success=True, worker_id=0)
    stats = dispatcher.stats()
    assert stats["done"] == {"training": 1}
    assert stats["doing"] == {}


def test_timing_bridge_feeds_phase_metrics(monkeypatch):
    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    try:
        from elasticdl_tpu.common.timing_utils import Timing

        ledger = Timing()
        with ledger.step(1):
            with ledger.phase("dispatch"):
                pass
        # the step series is the whole loop iteration
        assert ledger.last_seconds["batch_process"] >= (
            ledger.last_seconds["dispatch"])
        text = obs_metrics.default_registry().render()
        assert (
            'edl_phase_seconds_count{phase="batch_process"} 1' in text
        )
        assert 'edl_phase_seconds_count{phase="dispatch"} 1' in text
        assert "edl_step_time_seconds" in text
    finally:
        obs_metrics.reset_default_registry()
