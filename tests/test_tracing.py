"""Cross-role distributed tracing (ISSUE 9).

Covers the acceptance criteria directly:

- a context propagated over REAL gRPC: the server handler's span is a
  child of the exact client-side RPC attempt (trace_id + parent_id
  linkage, not task-id heuristics);
- retry_call attempts are distinct child spans — a fault-injected
  UNAVAILABLE burst shows as failed attempt spans, with no duplicate
  span-ends;
- head sampling: ``EDL_TRACE_SAMPLE=0`` is provably inert (no context,
  no gRPC metadata, an uninstrumented channel), and an UNSAMPLED
  trace's ``sampled=0`` flag propagates so remote roles record
  nothing; tail-keep retains slow unsampled traces locally;
- histogram exemplars: the slowest recent sampled observation's
  trace_id rides /metrics only on the content-negotiated OpenMetrics
  (or env-gated) path — the default 0.0.4 exposition is byte-identical
  to the pre-exemplar format;
- a deepfm local-executor run yields ONE trace per step whose worker
  root span has PS-side child spans, and a serve predict through real
  gRPC reaches a real PS server inside the request's trace;
- scripts: merge_trace threads flows by trace context,
  critical_path.py attributes per-segment self time, trace_summary.py
  groups by trace_id.
"""

import json
import os
import sys
import tempfile
import time

import grpc
import numpy as np
import pytest

from elasticdl_tpu.common.grpc_utils import (
    build_channel,
    build_server,
    find_free_port,
    retry_call,
)
from elasticdl_tpu.common import overload
from elasticdl_tpu.observability import metrics as obs_metrics
from elasticdl_tpu.observability import trace
from elasticdl_tpu.observability.trace_propagation import (
    TraceContextClientInterceptor,
    intercept_trace_channel,
)
from elasticdl_tpu.testing import faults


def _scripts():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if path not in sys.path:
        sys.path.insert(0, path)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """EDL_TRACE_DIR armed + a configured writer; resets module state
    (writer, env caches, thread-locals) afterwards."""
    monkeypatch.setenv(trace.TRACE_DIR_ENV, str(tmp_path))
    monkeypatch.delenv(trace.SAMPLE_ENV, raising=False)
    monkeypatch.delenv(trace.TAIL_KEEP_ENV, raising=False)
    trace.configure("tracetest")
    yield tmp_path
    trace._reset_for_tests()


def _spans(trace_dir):
    _scripts()
    import merge_trace

    trace.flush()
    merged, _names = merge_trace.merge(str(trace_dir))
    return [e for e in merged["traceEvents"] if e.get("ph") == "X"]


# ---------------------------------------------------------------------------
# context format


def test_traceparent_round_trip():
    ctx = trace.SpanContext("ab" * 16, "cd" * 8, True)
    parsed = trace.parse_traceparent(ctx.to_traceparent())
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id
    assert parsed.sampled
    unsampled = trace.SpanContext("ab" * 16, "cd" * 8, False)
    assert unsampled.to_traceparent().endswith("-00")
    assert not trace.parse_traceparent(
        unsampled.to_traceparent()
    ).sampled


@pytest.mark.parametrize("garbage", [
    "", "banana", "00-zz-cd-01", "00-" + "a" * 31 + "-" + "c" * 16 + "-01",
    "00-%s-%s" % ("a" * 32, "c" * 16), None,
])
def test_traceparent_garbage_is_none(garbage):
    assert trace.parse_traceparent(garbage) is None


def test_extract_context_reads_metadata():
    ctx = trace.SpanContext("ab" * 16, "cd" * 8, True)
    metadata = (
        ("other", "x"), (trace.METADATA_KEY, ctx.to_traceparent()),
    )
    assert trace.extract_context(metadata).trace_id == ctx.trace_id
    assert trace.extract_context((("other", "x"),)) is None
    assert trace.extract_context(None) is None


# ---------------------------------------------------------------------------
# sampling: 0 is provably inert; fractional propagates sampled=0


def test_sample_zero_yields_no_context_and_no_events(
    traced, monkeypatch
):
    monkeypatch.setenv(trace.SAMPLE_ENV, "0")
    with trace.root_span("train_batch") as ctx:
        assert ctx is None
        assert trace.current_context() is None
        with trace.span("ps_pull"):  # legacy span still records
            pass
    spans = _spans(traced)
    assert [e["name"] for e in spans] == ["ps_pull"]
    assert "trace_id" not in spans[0]["args"]


def test_sample_zero_builds_uninstrumented_channel(
    traced, monkeypatch
):
    monkeypatch.setenv(trace.SAMPLE_ENV, "0")
    # deadline-budget propagation (ISSUE 19) rides build_channel too
    # and is on by default; with BOTH kill switches thrown the call
    # path is byte-identical to a bare build (the ISSUE 9 overhead
    # acceptance, extended to every propagation layer)
    monkeypatch.setenv(overload.DEADLINE_BUDGET_ENV, "0")
    channel = build_channel("localhost:1")
    assert "_interceptor" not in type(channel).__module__
    channel.close()


def test_trace_disabled_builds_uninstrumented_channel(monkeypatch):
    monkeypatch.delenv(trace.TRACE_DIR_ENV, raising=False)
    monkeypatch.setenv(overload.DEADLINE_BUDGET_ENV, "0")
    channel = build_channel("localhost:1")
    assert "_interceptor" not in type(channel).__module__
    channel.close()


def test_client_interceptor_injects_traceparent(traced):
    captured = {}

    def continuation(details, request):
        captured["metadata"] = details.metadata
        return "outcome"

    class Details:
        method = "/elasticdl_tpu.Master/get_task"
        timeout = 1.0
        metadata = None
        credentials = None
        wait_for_ready = None
        compression = None

    interceptor = TraceContextClientInterceptor()
    # outside any trace: metadata untouched
    assert interceptor.intercept_unary_unary(
        continuation, Details(), None
    ) == "outcome"
    assert captured["metadata"] is None
    with trace.root_span("step") as ctx:
        interceptor.intercept_unary_unary(continuation, Details(), None)
    sent = trace.extract_context(captured["metadata"])
    assert sent.trace_id == ctx.trace_id
    assert sent.sampled


def test_unsampled_context_propagates_flag_without_recording(
    traced, monkeypatch
):
    monkeypatch.setenv(trace.SAMPLE_ENV, "0.5")
    monkeypatch.setattr(trace, "_rng", _FixedRng(0.9))  # draw > rate
    captured = {}

    def continuation(details, request):
        captured["metadata"] = details.metadata
        return "outcome"

    class Details:
        method = "/m"
        timeout = None
        metadata = None
        credentials = None
        wait_for_ready = None
        compression = None

    interceptor = TraceContextClientInterceptor()
    with trace.root_span("step") as ctx:
        assert ctx is not None and not ctx.sampled
        with trace.span("ps_pull"):
            pass
        interceptor.intercept_unary_unary(continuation, Details(), None)
    sent = trace.extract_context(captured["metadata"])
    assert sent.trace_id == ctx.trace_id
    assert not sent.sampled  # the flag crosses the wire
    assert _spans(traced) == []  # ...and nothing recorded locally


class _FixedRng:
    def __init__(self, value):
        self._value = value

    def random(self):
        return self._value


def test_tail_keep_retains_slow_unsampled_trace(traced, monkeypatch):
    monkeypatch.setenv(trace.SAMPLE_ENV, "0.01")
    monkeypatch.setenv(trace.TAIL_KEEP_ENV, "20")
    monkeypatch.setattr(trace, "_rng", _FixedRng(0.9))
    # fast unsampled root: buffered spans are DROPPED
    with trace.root_span("train_batch") as fast:
        with trace.span("ps_pull"):
            pass
    # slow unsampled root: the buffer flushes, marked tail_kept
    with trace.root_span("train_batch") as slow:
        with trace.span("ps_pull"):
            time.sleep(0.05)
    spans = _spans(traced)
    trace_ids = {e["args"].get("trace_id") for e in spans}
    assert slow.trace_id in trace_ids
    assert fast.trace_id not in trace_ids
    root = next(e for e in spans if e["name"] == "train_batch")
    assert root["args"]["tail_kept"] is True
    child = next(e for e in spans if e["name"] == "ps_pull")
    assert child["args"]["parent_id"] == root["args"]["span_id"]


def test_tail_kept_trace_keeps_late_bound_spans(traced, monkeypatch):
    """A bound callable finishing AFTER its tail-kept root closed (the
    async-push shape) must still land in the trace file — and after a
    DROPPED root, late spans are discarded, not leaked into a dead
    buffer."""
    monkeypatch.setenv(trace.SAMPLE_ENV, "0.01")
    monkeypatch.setenv(trace.TAIL_KEEP_ENV, "20")
    monkeypatch.setattr(trace, "_rng", _FixedRng(0.9))

    def push():
        with trace.span("ps_push"):
            pass

    with trace.root_span("train_batch") as kept:
        late_push = trace.bind_context(push)
        time.sleep(0.05)
    late_push()  # the root already flushed its tail buffer
    with trace.root_span("train_batch") as dropped:
        dropped_push = trace.bind_context(push)
    dropped_push()
    spans = _spans(traced)
    late = [e for e in spans if e["name"] == "ps_push"]
    assert [e["args"]["trace_id"] for e in late] == [kept.trace_id]
    assert not any(
        e["args"].get("trace_id") == dropped.trace_id for e in spans
    )


def test_sampled_zero_metadata_suppresses_server_handler(traced):
    """The server side of sampled=0: a handler receiving an unsampled
    traceparent records neither its own span nor any span the handler
    body opens (child roles don't record)."""
    calls = []

    def handler(request, context):
        with trace.span("ps_apply_push"):
            calls.append(1)
        return "resp"

    wrapped = trace.traced_handler(handler, "Pserver", "push_gradients")

    class Ctx:
        def __init__(self, sampled):
            self._sampled = sampled

        def invocation_metadata(self):
            parent = trace.SpanContext("ef" * 16, "12" * 8, self._sampled)
            return ((trace.METADATA_KEY, parent.to_traceparent()),)

    assert wrapped("req", Ctx(sampled=False)) == "resp"
    assert _spans(traced) == []
    assert wrapped("req", Ctx(sampled=True)) == "resp"
    spans = _spans(traced)
    assert {e["name"] for e in spans} == {
        "Pserver/push_gradients", "ps_apply_push"
    }
    server = next(
        e for e in spans if e["name"] == "Pserver/push_gradients"
    )
    assert server["args"]["trace_id"] == "ef" * 16
    assert server["args"]["parent_id"] == "12" * 8
    apply = next(e for e in spans if e["name"] == "ps_apply_push")
    assert apply["args"]["parent_id"] == server["args"]["span_id"]
    assert calls == [1, 1]


def test_annotate_merges_into_open_span(traced):
    """Mid-block facts (the serve abort path's status code) land on
    the innermost open span even when the exception that ends the
    block carries no code of its own."""
    with pytest.raises(RuntimeError):
        with trace.root_span("serve_predict") as outer:
            ctx = outer
            trace.annotate(code="RESOURCE_EXHAUSTED", rows=4)
            raise RuntimeError("bare abort")
    spans = _spans(traced)
    root = next(e for e in spans if e["name"] == "serve_predict")
    assert root["args"]["trace_id"] == ctx.trace_id
    assert root["args"]["code"] == "RESOURCE_EXHAUSTED"
    assert root["args"]["rows"] == 4
    assert root["args"]["error"] == "RuntimeError"
    # inert outside any span
    trace.annotate(code="X")


def test_serve_shed_root_span_records_status_code(traced):
    """A shed predict's root span carries the abort's status code (the
    critical_path 'shed' classifier) even though grpc's context.abort
    raises a code-less exception."""
    import grpc as grpc_mod

    from elasticdl_tpu.serve import batcher as batcher_mod
    from elasticdl_tpu.serve.servicer import ServeServicer

    class Engine:
        loaded = True

        class batcher:
            max_batch = 32
            default_deadline_secs = 1.0

        @staticmethod
        def predict(features, rows, deadline_secs):
            raise batcher_mod.QueueFull("at depth")

    class Ctx:
        code = None

        def invocation_metadata(self):
            return ()

        def time_remaining(self):
            return 5.0

        def abort(self, code, detail):
            self.code = code
            raise Exception(detail)  # grpc's abort: bare, code-less

    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
    from elasticdl_tpu.common.tensor_utils import ndarray_to_blob

    request = pb.PredictRequest()
    ndarray_to_blob(np.ones((2, 4), np.float32),
                    request.features["ids"])
    servicer = ServeServicer(Engine())
    context = Ctx()
    with pytest.raises(Exception):
        servicer.predict(request, context)
    assert context.code == grpc_mod.StatusCode.RESOURCE_EXHAUSTED
    spans = _spans(traced)
    root = next(e for e in spans if e["name"] == "serve_predict")
    assert root["args"]["code"] == "RESOURCE_EXHAUSTED"
    _scripts()
    import critical_path

    report = critical_path.build_report(
        critical_path.load_events(str(traced))
    )
    assert "shed" in report["predict"]["segments"]


# ---------------------------------------------------------------------------
# propagation over real gRPC + retry_call attempt spans


def _master_server():
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.proto.services import add_master_servicer_to_server

    dispatcher = TaskDispatcher({"s": (0, 64)}, records_per_task=32)
    server = build_server()
    add_master_servicer_to_server(MasterServicer(dispatcher), server)
    port = find_free_port()
    server.add_insecure_port("localhost:%d" % port)
    server.start()
    return server, port


def test_context_propagates_through_real_grpc(traced):
    from elasticdl_tpu.worker.master_client import MasterClient

    server, port = _master_server()
    try:
        mc = MasterClient("localhost:%d" % port, worker_id=0)
        with trace.root_span("train_batch", role="worker") as ctx:
            task = mc.get_task()
        assert task is not None
    finally:
        server.stop(0)
    spans = _spans(traced)
    ours = [e for e in spans if e["args"].get("trace_id") == ctx.trace_id]
    by_name = {e["name"]: e for e in ours}
    # one trace spans the client root, the RPC attempt, and the SERVER
    # handler — linked by explicit parent ids through the metadata hop
    assert {"train_batch", "rpc_attempt", "Master/get_task"} <= set(
        by_name
    )
    root = by_name["train_batch"]
    attempt = by_name["rpc_attempt"]
    handler = by_name["Master/get_task"]
    assert "parent_id" not in root["args"]
    assert attempt["args"]["parent_id"] == root["args"]["span_id"]
    assert handler["args"]["parent_id"] == attempt["args"]["span_id"]
    assert handler["args"]["kind"] == "grpc_server"


def test_retry_attempts_are_distinct_failed_child_spans(
    traced, monkeypatch
):
    """A fault-injected UNAVAILABLE burst: each retry_call attempt is
    its own child span — the failed ones carry error/code args — and
    the enclosing span ends exactly once."""
    monkeypatch.setenv(
        faults.FAULT_SPEC_ENV, "tracer:get_task:unavailable:2"
    )
    faults._reset_for_tests()
    faults.set_role("tracer")
    server, port = _master_server()
    try:
        from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
        from elasticdl_tpu.proto.services import MasterStub

        stub = MasterStub(build_channel("localhost:%d" % port))
        with trace.root_span("train_batch") as ctx:
            retry_call(
                lambda: stub.get_task(
                    pb.GetTaskRequest(worker_id=0), timeout=5
                ),
                "get_task", budget_secs=30.0, base_delay=0.01,
            )
    finally:
        server.stop(0)
        faults._reset_for_tests()
        monkeypatch.delenv(faults.FAULT_SPEC_ENV, raising=False)
    spans = [
        e for e in _spans(traced)
        if e["args"].get("trace_id") == ctx.trace_id
    ]
    attempts = sorted(
        (e for e in spans if e["name"] == "rpc_attempt"),
        key=lambda e: e["args"]["attempt"],
    )
    assert [a["args"]["attempt"] for a in attempts] == [1, 2, 3]
    assert [a["args"].get("code") for a in attempts] == [
        "UNAVAILABLE", "UNAVAILABLE", None,
    ]
    # every attempt is a child of the SAME root, which ended once
    roots = [e for e in spans if e["name"] == "train_batch"]
    assert len(roots) == 1
    assert all(
        a["args"]["parent_id"] == roots[0]["args"]["span_id"]
        for a in attempts
    )
    # distinct span ids: no span was double-ended into two events
    span_ids = [e["args"]["span_id"] for e in spans]
    assert len(span_ids) == len(set(span_ids))


# ---------------------------------------------------------------------------
# histogram exemplars + exposition content negotiation


def test_histogram_exemplar_tracks_slowest_sampled_observation(traced):
    reg = obs_metrics.Registry(enabled=True)
    hist = reg.histogram("lat_seconds", "l", buckets=(0.1, 1.0))
    hist.observe(0.9)  # outside any trace: no exemplar
    assert "# {" not in reg.render(exemplars=True)
    with trace.root_span("step") as slow_ctx:
        hist.observe(0.5)
    with trace.root_span("step"):
        hist.observe(0.05)  # faster: must NOT displace the exemplar
    plain = reg.render()
    assert "# {" not in plain  # default 0.0.4 path: no exemplars
    text = reg.render(exemplars=True)
    assert '# {trace_id="%s"} 0.5' % slow_ctx.trace_id in text
    # the exemplar rides the first bucket containing its value
    line = next(l for l in text.splitlines() if "# {" in l)
    assert line.startswith('lat_seconds_bucket{le="1"}')


def test_exemplar_window_admits_fresh_trace(traced, monkeypatch):
    reg = obs_metrics.Registry(enabled=True)
    hist = reg.histogram("lat_seconds", "l", buckets=(10.0,))
    with trace.root_span("step"):
        hist.observe(5.0)
    monkeypatch.setattr(obs_metrics, "EXEMPLAR_WINDOW_SECS", 0.0)
    with trace.root_span("step") as fresh:
        hist.observe(0.5)  # faster but RECENT: replaces the stale one
    assert 'trace_id="%s"' % fresh.trace_id in reg.render(exemplars=True)


def test_metrics_endpoint_content_negotiation(traced, monkeypatch):
    import urllib.request

    from elasticdl_tpu.observability.http_server import (
        ObservabilityServer,
    )

    monkeypatch.delenv(obs_metrics.EXEMPLARS_ENV, raising=False)
    reg = obs_metrics.Registry(enabled=True)
    hist = reg.histogram("edl_lat_seconds", "l", buckets=(1.0,))
    with trace.root_span("step"):
        hist.observe(0.5)
    server = ObservabilityServer("w", 0, registry=reg).start()
    try:
        base = "http://localhost:%d/metrics" % server.port
        plain = urllib.request.urlopen(base, timeout=5)
        body = plain.read().decode()
        # default path: plain 0.0.4 — parseable by existing consumers
        # (no exemplar markers, no EOF terminator, 0.0.4 content type)
        assert "# {" not in body and "# EOF" not in body
        assert "version=0.0.4" in plain.headers["Content-Type"]
        for line in body.splitlines():
            assert line.startswith("#") or " # " not in line
        request = urllib.request.Request(
            base, headers={"Accept": "application/openmetrics-text"}
        )
        negotiated = urllib.request.urlopen(request, timeout=5)
        om_body = negotiated.read().decode()
        assert "# {trace_id=" in om_body
        assert om_body.endswith("# EOF\n")
        assert "openmetrics-text" in negotiated.headers["Content-Type"]
        # a STOCK Prometheus advertises openmetrics WITH a text/plain
        # fallback — it must keep getting the plain 0.0.4 body it
        # parsed yesterday, not this pragmatic exposition
        stock = urllib.request.Request(base, headers={
            "Accept": "application/openmetrics-text;version=1.0.0,"
            "text/plain;version=0.0.4;q=0.5,*/*;q=0.1"
        })
        stock_reply = urllib.request.urlopen(stock, timeout=5)
        stock_body = stock_reply.read().decode()
        assert "# {" not in stock_body and "# EOF" not in stock_body
        assert "version=0.0.4" in stock_reply.headers["Content-Type"]
        # env gate: exemplars on the plain path, still 0.0.4 framed
        monkeypatch.setenv(obs_metrics.EXEMPLARS_ENV, "1")
        gated = urllib.request.urlopen(base, timeout=5).read().decode()
        assert "# {trace_id=" in gated and "# EOF" not in gated
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# scripts: merge threading, critical path, trace summary


def _write_trace_file(trace_dir, role, pid, events):
    path = os.path.join(str(trace_dir), "%s-%d.trace.json" % (role, pid))
    meta = {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": role}}
    with open(path, "w", encoding="utf-8") as f:
        f.write("[\n")
        for event in [meta] + events:
            f.write(json.dumps(event) + ",\n")


def _span_event(name, ts, dur, pid, trace_id=None, span_id=None,
                parent_id=None, **args):
    if trace_id:
        args["trace_id"] = trace_id
        args["span_id"] = span_id
        if parent_id:
            args["parent_id"] = parent_id
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": pid,
            "tid": 1, "args": args}


def test_merge_threads_flows_by_trace_context(tmp_path):
    _scripts()
    import merge_trace

    tid = "aa" * 16
    _write_trace_file(tmp_path, "worker-0", 1, [
        _span_event("train_batch", 0, 100, 1, tid, "01" * 8,
                    role="worker"),
        _span_event("legacy_a", 500, 10, 1, task_id=9),
    ])
    _write_trace_file(tmp_path, "ps-0", 2, [
        _span_event("Pserver/push_gradients", 10, 20, 2, tid, "02" * 8,
                    parent_id="01" * 8),
        _span_event("legacy_b", 520, 10, 2, task_id=9),
    ])
    merged, _names = merge_trace.merge(str(tmp_path))
    flows = [e for e in merged["traceEvents"]
             if e.get("ph") in ("s", "t", "f")]
    trace_flows = [f for f in flows if f["cat"] == "trace"]
    task_flows = [f for f in flows if f["cat"] == "task"]
    # the context-carrying spans thread by trace_id...
    assert [f["ph"] for f in trace_flows] == ["s", "f"]
    assert all(f["id"] == tid[:16] for f in trace_flows)
    # ...and do NOT double-thread through the task heuristic, which
    # still serves the legacy spans
    assert [f["ph"] for f in task_flows] == ["s", "f"]
    assert {f["ts"] for f in task_flows} == {500, 520}


def test_merge_task_flows_survive_mixed_groups(tmp_path):
    """The master's dispatch span has a task_id but NO trace context
    (get_task runs outside the worker's root span); the worker's
    train span carries both. The task flow must still thread the two —
    only groups FULLY covered by context threading are skipped."""
    _scripts()
    import merge_trace

    tid = "ff" * 16
    _write_trace_file(tmp_path, "master", 5, [
        _span_event("dispatch", 0, 50, 5, task_id=7),
    ])
    _write_trace_file(tmp_path, "worker-0", 6, [
        _span_event("train_batch", 100, 900, 6, tid, "01" * 8,
                    task_id=7, role="worker"),
        _span_event("ps_push", 500, 100, 6, tid, "02" * 8,
                    parent_id="01" * 8, task_id=7),
    ])
    merged, _names = merge_trace.merge(str(tmp_path))
    task_flows = [e for e in merged["traceEvents"]
                  if e.get("ph") in ("s", "t", "f")
                  and e.get("cat") == "task"]
    # dispatch threads into the context-carrying worker spans
    assert [f["ph"] for f in task_flows] == ["s", "t", "f"]
    assert {f["ts"] for f in task_flows} == {0, 100, 500}


def test_critical_path_attribution_math(tmp_path):
    _scripts()
    import critical_path

    tid = "bb" * 16
    # root 10ms; pull child 2ms; push child 3ms containing a 2ms
    # server-side apply -> compute self = 5ms, push self = 1ms
    _write_trace_file(tmp_path, "worker-0", 1, [
        _span_event("train_batch", 0, 10000, 1, tid, "01" * 8,
                    role="worker"),
        _span_event("ps_pull_batch", 1000, 2000, 1, tid, "02" * 8,
                    parent_id="01" * 8),
        _span_event("ps_push", 5000, 3000, 1, tid, "03" * 8,
                    parent_id="01" * 8),
    ])
    _write_trace_file(tmp_path, "ps-0", 2, [
        _span_event("Pserver/push_gradients", 5500, 2000, 2, tid,
                    "04" * 8, parent_id="03" * 8),
    ])
    report = critical_path.build_report(
        critical_path.load_events(str(tmp_path))
    )
    assert report["traces"] == 1
    step = report["step"]
    assert step["count"] == 1
    assert step["roles"] == ["ps", "worker"]
    assert step["multi_role_traces"] == 1
    segments = step["segments"]
    assert segments["compute"]["p50_ms"] == pytest.approx(5.0)
    assert segments["pull"]["p50_ms"] == pytest.approx(2.0)
    assert segments["push"]["p50_ms"] == pytest.approx(1.0)
    assert segments["apply"]["p50_ms"] == pytest.approx(2.0)
    shares = sum(s["share"] for s in segments.values())
    assert shares == pytest.approx(1.0, abs=1e-3)


def test_critical_path_keeps_its_answers_with_phase_children(tmp_path):
    """ISSUE 23: the root now covers the whole loop iteration and has
    the ledger's phases as children. The same step as above, with 1 ms
    of input wait before it and 2 ms of bookkeeping after: compute,
    pull, push and apply read what they read without the phases, and
    the iteration's new time has segments of its own."""
    _scripts()
    import critical_path
    import merge_trace

    tid = "bd" * 16
    root = "01" * 8
    _write_trace_file(tmp_path, "worker-0", 1, [
        _span_event("train_batch", 0, 13000, 1, tid, root,
                    role="worker"),
        _span_event("edl/input_wait", 0, 1000, 1, tid, "05" * 8,
                    parent_id=root),
        # the step's own phases: unmapped, they inherit compute
        _span_event("edl/dispatch", 1000, 1000, 1, tid, "06" * 8,
                    parent_id=root),
        _span_event("ps_pull_batch", 2000, 2000, 1, tid, "02" * 8,
                    parent_id=root),
        _span_event("edl/device_wait", 4000, 2000, 1, tid, "07" * 8,
                    parent_id=root),
        _span_event("ps_push", 6000, 3000, 1, tid, "03" * 8,
                    parent_id=root),
        _span_event("edl/health", 9000, 2000, 1, tid, "08" * 8,
                    parent_id=root),
        _span_event("edl/report", 11000, 1500, 1, tid, "09" * 8,
                    parent_id=root),
        _span_event("edl/callbacks", 12500, 500, 1, tid, "0a" * 8,
                    parent_id=root),
    ])
    _write_trace_file(tmp_path, "ps-0", 2, [
        _span_event("Pserver/push_gradients", 6500, 2000, 2, tid,
                    "04" * 8, parent_id="03" * 8),
    ])
    report = critical_path.build_report(
        critical_path.load_events(str(tmp_path))
    )
    step = report["step"]
    assert step["count"] == 1 and step["roles"] == ["ps", "worker"]
    segments = step["segments"]
    assert segments["compute"]["p50_ms"] == pytest.approx(5.0)
    assert segments["pull"]["p50_ms"] == pytest.approx(2.0)
    assert segments["push"]["p50_ms"] == pytest.approx(1.0)
    assert segments["apply"]["p50_ms"] == pytest.approx(2.0)
    assert segments["input_wait"]["p50_ms"] == pytest.approx(1.0)
    assert segments["bookkeeping"]["p50_ms"] == pytest.approx(2.0)
    assert "other" not in segments
    # merge_trace threads the spans it threaded without the phases:
    # the root, the pull, the push and the PS's apply
    merged, _names = merge_trace.merge(str(tmp_path))
    flows = [e for e in merged["traceEvents"]
             if e.get("ph") in ("s", "t", "f") and e["cat"] == "trace"]
    assert [f["ph"] for f in flows] == ["s", "t", "t", "f"]
    assert [f["ts"] for f in flows] == [0, 2000, 6000, 6500]


def test_critical_path_classifies_shed_predicts(tmp_path):
    _scripts()
    import critical_path

    tid = "cc" * 16
    _write_trace_file(tmp_path, "serve-0", 3, [
        _span_event("serve_predict", 0, 2000, 3, tid, "01" * 8,
                    role="serve", error="DeadlineExpired",
                    code="DEADLINE_EXCEEDED"),
    ])
    report = critical_path.build_report(
        critical_path.load_events(str(tmp_path))
    )
    predict = report["predict"]
    assert predict["segments"]["shed"]["p50_ms"] == pytest.approx(2.0)
    assert report["slowest"][0]["shed"] is True


def test_trace_summary_groups_by_trace(tmp_path):
    _scripts()
    import trace_summary

    for i, tid in enumerate(("dd" * 16, "ee" * 16)):
        _write_trace_file(tmp_path, "worker-%d" % i, 10 + i, [
            _span_event("train_batch", 0, 1000 * (i + 1), 10 + i, tid,
                        "01" * 8, role="worker"),
            _span_event("ps_pull", 100, 200, 10 + i, tid, "02" * 8,
                        parent_id="01" * 8, role="ps"),
        ])
    summary = trace_summary.summarize_edl_traces(str(tmp_path))
    assert summary["traces"] == 2
    assert summary["names"]["train_batch"]["count"] == 2
    assert summary["names"]["ps_pull"]["p50_ms"] == pytest.approx(0.2)
    slowest = summary["slowest"]
    assert slowest[0]["duration_ms"] >= slowest[-1]["duration_ms"]
    assert slowest[0]["roles"] == ["ps", "worker"]
    assert slowest[0]["spans"] == 2
    trace_summary.print_edl_summary(summary)  # smoke the table


# ---------------------------------------------------------------------------
# acceptance: deepfm local-executor end to end


@pytest.fixture(scope="module")
def deepfm_traced_run():
    """One traced deepfm local-executor run shared by the e2e tests."""
    tmp = tempfile.mkdtemp(prefix="edl-tracing-")
    trace_dir = os.path.join(tmp, "traces")
    from tests.test_utils import create_ctr_recordio

    create_ctr_recordio(tmp + "/f0.rec", num_records=96, seed=0)
    previous = {
        key: os.environ.get(key)
        for key in (trace.TRACE_DIR_ENV, trace.SAMPLE_ENV)
    }
    os.environ[trace.TRACE_DIR_ENV] = trace_dir
    os.environ[trace.SAMPLE_ENV] = "1"
    try:
        from elasticdl_tpu.train.local_executor import LocalExecutor

        executor = LocalExecutor(
            "elasticdl_tpu.models.deepfm", training_data=tmp,
            minibatch_size=32, num_epochs=1,
        )
        executor.train()
        trace.flush()
    finally:
        for key, value in previous.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value
        trace._reset_for_tests()
    return executor, trace_dir


def test_deepfm_local_run_yields_one_trace_per_step_with_ps_children(
    deepfm_traced_run,
):
    _executor, trace_dir = deepfm_traced_run
    spans = _spans(trace_dir)
    by_trace = {}
    for event in spans:
        tid = event["args"].get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(event)
    roots = [e for e in spans if e["name"] == "train_batch"]
    # ONE trace per step: every root owns a distinct trace_id
    assert len(roots) == 3  # 96 records / 32
    assert len({r["args"]["trace_id"] for r in roots}) == len(roots)
    for trace_spans in by_trace.values():
        root = next(
            e for e in trace_spans if "parent_id" not in e["args"]
        )
        assert root["name"] == "train_batch"
        assert root["args"]["role"] == "worker"
        # PS-side children, linked via the propagated context
        ps_children = [
            e for e in trace_spans if e["args"].get("role") == "ps"
        ]
        assert ps_children, trace_spans
        span_ids = {
            e["args"]["span_id"] for e in trace_spans
        }
        assert all(
            e["args"]["parent_id"] in span_ids for e in ps_children
        )
        assert any(
            e["name"] == "ps_apply_push" for e in ps_children
        )


def test_critical_path_report_on_deepfm_run(deepfm_traced_run):
    _scripts()
    import critical_path

    _executor, trace_dir = deepfm_traced_run
    report = critical_path.build_report(
        critical_path.load_events(trace_dir)
    )
    step = report["step"]
    assert step["count"] == 3
    # the CI tier-1d gate: every step trace spans worker AND ps
    assert step["multi_role_traces"] == step["count"]
    assert {"worker", "ps"} <= set(step["roles"])
    assert {"compute", "pull", "apply"} <= set(step["segments"])
    for stats in step["segments"].values():
        assert stats["p99_ms"] >= stats["p50_ms"] >= 0.0


# ---------------------------------------------------------------------------
# acceptance: serve predict through real gRPC with a real PS


@pytest.mark.slow
def test_serve_predict_trace_reaches_real_ps(tmp_path, monkeypatch):
    """client -> serve batcher -> model -> EmbeddingClient -> PS, one
    trace: the serve root span's descendants include the REAL PS
    server's handler span, linked via propagated context across two
    gRPC hops (client->serve is real gRPC too; the serve root opens at
    admission)."""
    from elasticdl_tpu.models import deepfm
    from elasticdl_tpu.ps.embedding_store import create_store
    from elasticdl_tpu.ps.servicer import PserverServicer
    from elasticdl_tpu.proto.services import (
        add_pserver_servicer_to_server,
        add_serve_servicer_to_server,
    )
    from elasticdl_tpu.serve.client import ServeClient
    from elasticdl_tpu.serve.engine import ServingEngine
    from elasticdl_tpu.serve.servicer import ServeServicer
    from elasticdl_tpu.train.export import export_train_state
    from elasticdl_tpu.train.local_executor import LocalExecutor
    from elasticdl_tpu.worker.ps_client import PSClient
    from tests.test_utils import create_ctr_recordio

    monkeypatch.setenv(trace.TRACE_DIR_ENV, str(tmp_path / "traces"))
    monkeypatch.setenv(trace.SAMPLE_ENV, "1")
    trace.configure("servetest")

    data = tmp_path / "data"
    data.mkdir()
    create_ctr_recordio(str(data / "f0.rec"), num_records=64, seed=0)
    executor = LocalExecutor(
        "elasticdl_tpu.models.deepfm", training_data=str(data),
        minibatch_size=32, num_epochs=1,
    )
    executor.train()
    export_dir = str(tmp_path / "export")
    export_train_state(executor.state, export_dir)

    # a REAL PS server (build_server: traced handlers), seeded with the
    # locally trained rows
    store = create_store(seed=0, prefer_native=False)
    store.set_optimizer("adam", lr=0.001)
    ps_server = build_server()
    add_pserver_servicer_to_server(
        PserverServicer(store, use_async=True), ps_server
    )
    ps_port = find_free_port()
    ps_server.add_insecure_port("localhost:%d" % ps_port)
    ps_server.start()
    engine = None
    serve_server = None
    client = None
    try:
        ps_client = PSClient(["localhost:%d" % ps_port])
        specs = deepfm.sparse_embedding_specs(batch_size=32)
        ps_client.push_embedding_table_infos(
            [(s.name, s.dim, str(float(s.init_scale))) for s in specs]
        )
        local_store = executor.trainer.preparer._ps.store
        ps_client.push_embedding_rows({
            s.name: local_store.export_table(s.name) for s in specs
        })
        engine = ServingEngine(
            "elasticdl_tpu.models.deepfm", export_dir,
            ps_client=ps_client, max_batch=32, max_delay_ms=2.0,
            deadline_ms=60000.0,
        ).start(block=True)
        serve_server = build_server()
        add_serve_servicer_to_server(ServeServicer(engine), serve_server)
        serve_port = find_free_port()
        serve_server.add_insecure_port("localhost:%d" % serve_port)
        serve_server.start()
        client = ServeClient("localhost:%d" % serve_port)
        ids = np.random.RandomState(3).randint(
            0, 1000, size=(4, 10)
        ).astype(np.int64)
        outputs, _step, _stamp = client.predict(
            {"ids": ids}, deadline_secs=120
        )
        assert np.isfinite(outputs["output"]).all()
    finally:
        if client is not None:
            client.close()
        if serve_server is not None:
            serve_server.stop(0)
        if engine is not None:
            engine.drain(timeout=5)
        ps_server.stop(0)
        trace.flush()
        trace._reset_for_tests()
    spans = _spans(tmp_path / "traces")
    roots = [e for e in spans if e["name"] == "serve_predict"]
    assert len(roots) == 1
    root = roots[0]
    tid = root["args"]["trace_id"]
    ours = {
        e["args"]["span_id"]: e
        for e in spans
        if e["args"].get("trace_id") == tid
    }
    ps_handler = next(
        (e for e in ours.values()
         if e["name"].startswith("Pserver/pull")), None
    )
    assert ps_handler is not None, sorted(
        e["name"] for e in ours.values()
    )
    assert ps_handler["args"]["kind"] == "grpc_server"
    # walk parents from the PS handler back to the serve root: the
    # chain crosses the batcher thread hand-off AND the gRPC hop
    node = ps_handler
    hops = []
    while "parent_id" in node["args"]:
        hops.append(node["name"])
        node = ours[node["args"]["parent_id"]]
    assert node is root, hops
    assert "serve_batch_run" in (hops + [node["name"]])


# ---------------------------------------------------------------------------
# serve drain satellite: trace flush + trace_flushed event


def test_serve_drain_flushes_trace_and_journals_event(
    tmp_path, monkeypatch, deepfm_traced_run
):
    from elasticdl_tpu.observability import events
    from elasticdl_tpu.serve.main import ServeRole, parse_serve_args
    from elasticdl_tpu.train.export import export_train_state

    executor, _ = deepfm_traced_run
    export_dir = str(tmp_path / "export")
    export_train_state(executor.state, export_dir)
    monkeypatch.setenv(trace.TRACE_DIR_ENV, str(tmp_path / "traces"))
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(tmp_path / "events"))
    trace.configure("serve-0")
    journal = events.configure("serve-0")
    try:
        role = ServeRole(parse_serve_args([
            "--model_zoo", "elasticdl_tpu.models.deepfm",
            "--export_dir", export_dir,
        ]))
        with trace.span("serve_smoke"):
            pass
        role.drain(reason="test")
        with open(journal.path, encoding="utf-8") as f:
            names = [json.loads(line)["event"] for line in f
                     if line.strip()]
        assert "trace_flushed" in names
        assert names.index("trace_flushed") < names.index("serve_drained")
        # the flush is real: the span above is on disk
        spans = _spans(tmp_path / "traces")
        assert any(e["name"] == "serve_smoke" for e in spans)
        role.drain(reason="test")  # idempotent: no second event
        with open(journal.path, encoding="utf-8") as f:
            again = [json.loads(line)["event"] for line in f
                     if line.strip()]
        assert again.count("trace_flushed") == 1
    finally:
        events._reset_for_tests()
        trace._reset_for_tests()


# ---------------------------------------------------------------------------
# buffered span-id entropy (ISSUE 15 satellite)


def test_entropy_pool_id_shapes_and_uniqueness():
    """Pooled ids keep the W3C wire shape (16-hex span / 32-hex trace)
    and never repeat across refills (10k ids spans ~20 refills of the
    4 KiB buffer at 8 bytes/id... it spans at least 19 boundaries)."""
    from elasticdl_tpu.observability.trace import (
        _new_span_id,
        _new_trace_id,
    )

    span_ids = {_new_span_id() for _ in range(10_000)}
    assert len(span_ids) == 10_000
    assert all(len(s) == 16 for s in span_ids)
    trace_ids = {_new_trace_id() for _ in range(1_000)}
    assert len(trace_ids) == 1_000
    assert all(len(t) == 32 for t in trace_ids)
    int(next(iter(span_ids)), 16)  # hex


def test_entropy_pool_refills_and_resets():
    from elasticdl_tpu.observability.trace import _EntropyPool

    pool = _EntropyPool(size=32)  # tiny: force refills every 4 takes
    taken = [pool.take(8) for _ in range(20)]
    assert all(len(t) == 8 for t in taken)
    assert len(set(taken)) == 20  # refills never re-deal bytes
    # fork-safety hook: reset() empties the buffer so a child draws
    # fresh entropy instead of replaying the parent's remainder
    pool.reset()
    assert pool._buf == b"" and pool._pos == 0
    assert len(pool.take(8)) == 8  # next take refills cleanly


def test_entropy_pool_concurrent_takes_are_distinct():
    import threading

    from elasticdl_tpu.observability.trace import _new_span_id

    out = [None] * 8

    def draw(i):
        out[i] = [_new_span_id() for _ in range(2_000)]

    threads = [
        threading.Thread(target=draw, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    merged = [s for chunk in out for s in chunk]
    assert len(set(merged)) == len(merged)
