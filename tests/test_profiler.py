"""Continuous profiling (ISSUE 14): the per-role stack sampler, its
span correlation, the /profilez endpoint on every role's HTTP daemon,
the bounded-ring memory contract, and the report tooling
(scripts/profile_report.py, critical_path.py --frames,
bench_trend.py)."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from elasticdl_tpu.observability import metrics as obs_metrics
from elasticdl_tpu.observability import profiler, trace
from elasticdl_tpu.observability.http_server import ObservabilityServer
from elasticdl_tpu.observability.profiler import (
    StackSampler,
    _Agg,
    collapsed,
    segment_of_span,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "scripts"))
import bench_trend  # noqa: E402
import critical_path  # noqa: E402
import profile_report  # noqa: E402


def _get(url):
    try:
        response = urllib.request.urlopen(url, timeout=5)
        return response.status, response.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.fixture
def clean_profiler(monkeypatch):
    """No EDL_PROF_HZ inherited, and no sampler left running after."""
    monkeypatch.delenv(profiler.HZ_ENV, raising=False)
    yield
    profiler._reset_for_tests()
    trace._reset_for_tests()


def _burn_thread(stop, span_names=(), trace_dir=None):
    """A busy thread with a recognizable hot frame; optionally wraps
    the work in (nested) trace spans. numpy work releases the GIL, so
    the sampler reliably lands samples here."""

    def burn_hot_loop(a):
        return np.linalg.svd(a)[0]

    def run():
        a = np.random.rand(150, 150)
        while not stop.is_set():
            if span_names:
                with trace.root_span(span_names[0], role="worker"):
                    if len(span_names) > 1:
                        with trace.span(span_names[1], role="ps"):
                            burn_hot_loop(a)
                    else:
                        burn_hot_loop(a)
            else:
                burn_hot_loop(a)

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    return thread


def _wait_for_samples(sampler, minimum=5, timeout=5.0, frame=None):
    """The sampler's snapshot once it holds ``minimum`` samples and,
    with ``frame``, a stack through that frame: on a loaded machine the
    first five samples (25 ms at 200 Hz) can all precede the burn
    thread's first call."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        snap = sampler.snapshot()
        if snap["samples"] >= minimum and (frame is None or any(
                frame in f for e in snap["stacks"] for f in e["stack"])):
            return snap
        time.sleep(0.05)
    return sampler.snapshot()


# ---------------------------------------------------------------------------
# disabled = provably inert


def test_disabled_is_provably_inert(clean_profiler):
    assert profiler.configured_hz() == 0.0
    assert profiler.maybe_start("worker-0") is None
    assert profiler.sampler() is None and not profiler.enabled()
    assert not [
        t for t in threading.enumerate()
        if t.name.startswith("edl-prof") and t.is_alive()
    ]


def test_profilez_404_when_disabled(clean_profiler):
    server = ObservabilityServer("worker-0", 0).start()
    try:
        status, body = _get(
            "http://localhost:%d/profilez" % server.port
        )
        assert status == 404
        assert "disabled" in body and "EDL_PROF_HZ" in body
    finally:
        server.stop()


def test_bad_hz_values_disable(clean_profiler, monkeypatch):
    for bad in ("banana", "-3", "0"):
        monkeypatch.setenv(profiler.HZ_ENV, bad)
        assert profiler.configured_hz() == 0.0
        assert profiler.maybe_start("x") is None


# ---------------------------------------------------------------------------
# sampling


def test_sampler_collects_hot_frames(clean_profiler):
    sampler = StackSampler("worker-0", hz=200)
    sampler.start()
    stop = threading.Event()
    thread = _burn_thread(stop)
    try:
        snap = _wait_for_samples(sampler, frame="burn_hot_loop")
    finally:
        stop.set()
        thread.join()
        sampler.stop()
    assert snap["samples"] >= 5
    assert snap["role"] == "worker-0" and snap["hz"] == 200
    frames = [f for e in snap["stacks"] for f in e["stack"]]
    assert any("burn_hot_loop" in f for f in frames), frames


def test_sampler_never_samples_itself(clean_profiler):
    sampler = StackSampler("w", hz=400)
    sampler.start()
    time.sleep(0.4)  # mostly idle: only the sampler itself is busy
    sampler.stop()
    snap = sampler.snapshot()
    assert snap["stacks"]  # the sleeping caller was sampled
    # the sampler's own thread is the one whose stack runs through
    # _run; on a loaded machine the CALLER is caught inside start() or
    # stop(), frames of the same module, and that is a true sample
    for entry in snap["stacks"]:
        assert not any(
            "StackSampler._run" in frame for frame in entry["stack"]
        ), entry


def test_stop_joins_the_thread(clean_profiler):
    sampler = StackSampler("w", hz=100)
    sampler.start()
    assert sampler.running()
    sampler.stop()
    assert not sampler.running()
    assert not [
        t for t in threading.enumerate()
        if t.name == "edl-prof-w" and t.is_alive()
    ]


def test_samples_metric_and_overhead_gauge(clean_profiler, monkeypatch):
    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    try:
        sampler = StackSampler("worker-0", hz=200)
        sampler.start()
        stop = threading.Event()
        thread = _burn_thread(stop)
        try:
            _wait_for_samples(sampler)
        finally:
            stop.set()
            thread.join()
            sampler.stop()
        registry = obs_metrics.default_registry()
        assert registry.get("edl_prof_samples_total").get(
            "worker-0"
        ) >= 5
        text = registry.render()
        assert 'edl_prof_samples_total{role="worker-0"}' in text
        assert 'edl_prof_overhead_ratio{role="worker-0"}' in text
        ratio = sampler.overhead_ratio()
        assert 0.0 <= ratio < 0.5  # sampling, not tracing
    finally:
        obs_metrics.reset_default_registry()


# ---------------------------------------------------------------------------
# bounded memory under churn


def test_bucket_is_bounded_under_stack_churn(clean_profiler):
    agg = _Agg()
    for i in range(1000):
        agg.add((None, ("mod:fn_%d" % i,)), None, 16)
    assert len(agg.stacks) == 16
    assert agg.samples == 1000
    assert agg.overflow == 1000 - 16


def test_ring_rotates_and_stays_bounded(clean_profiler, monkeypatch):
    monkeypatch.setattr(profiler, "_BUCKET_SECS", 0.05)
    sampler = StackSampler("w", hz=250, ring_secs=0.2, max_stacks=8)
    assert sampler._ring.maxlen == 4
    sampler.start()
    stop = threading.Event()
    thread = _burn_thread(stop)
    try:
        time.sleep(1.0)  # many bucket lifetimes
        with sampler._lock:
            assert len(sampler._ring) <= 4
    finally:
        stop.set()
        thread.join()
        sampler.stop()
    snap = sampler.snapshot()
    # snapshot window reflects the bounded ring, not the full runtime
    assert snap["window_secs"] < 0.75


def test_collapsed_rendering_folds_segment_and_overflow(clean_profiler):
    snap = {
        "stacks": [
            {"stack": ["a:f", "b:g"], "count": 3, "segment": "apply",
             "trace_id": "t1"},
            {"stack": ["a:f"], "count": 2, "segment": None,
             "trace_id": None},
        ],
        "overflow": 5,
    }
    text = collapsed(snap)
    lines = text.splitlines()
    assert lines[0] == "[apply];a:f;b:g 3"
    assert lines[1] == "a:f 2"
    assert lines[2] == "(overflow) 5"


# ---------------------------------------------------------------------------
# span correlation


def test_segment_mapping_mirrors_critical_path():
    # every exact-name mapping the trace analyzer uses must agree with
    # the profiler's sample tagging (drift would put a span's samples
    # in a different bucket than its self time)
    for name, segment in critical_path._SEGMENT_BY_NAME.items():
        assert segment_of_span(name) == segment
    assert segment_of_span("train_batch") == "compute"
    assert segment_of_span("Pserver/push_gradients") == "apply"
    assert segment_of_span("Pserver/pull_embedding_batch") == "pull"
    assert segment_of_span("Master/get_task") == "queue_wait"
    assert segment_of_span("whatever_else") == "other"


def test_samples_inside_spans_are_tagged(clean_profiler, tmp_path,
                                         monkeypatch):
    monkeypatch.setenv(trace.TRACE_DIR_ENV, str(tmp_path))
    trace.configure("worker-0")
    sampler = StackSampler("worker-0", hz=250)
    sampler.start()
    stop = threading.Event()
    thread = _burn_thread(
        stop, span_names=("train_batch", "ps_apply_push")
    )
    try:
        deadline = time.time() + 8.0
        segments = set()
        while time.time() < deadline:
            snap = sampler.snapshot()
            segments = {e["segment"] for e in snap["stacks"]}
            if "apply" in segments:
                break
            time.sleep(0.1)
    finally:
        stop.set()
        thread.join()
        sampler.stop()
    # the inner span's samples carry its segment AND its trace id
    assert "apply" in segments, segments
    tagged = [
        e for e in snap["stacks"] if e["segment"] == "apply"
    ]
    assert any(e["trace_id"] for e in tagged)
    # publication is balanced: nothing left once all spans closed
    assert trace.profiled_spans() == {}


def test_unmapped_nested_span_inherits_enclosing_publication(
        clean_profiler, tmp_path, monkeypatch):
    """rpc_attempt / ps_apply_round style spans map to no segment, so
    they must NOT overwrite the publication: their samples inherit the
    nearest mapped ancestor's segment, exactly like critical_path.py
    inherits their self time."""
    monkeypatch.setenv(trace.TRACE_DIR_ENV, str(tmp_path))
    trace.configure("worker-0")
    sampler = StackSampler("worker-0", hz=5)
    sampler.start()
    ident = threading.get_ident()
    try:
        with trace.root_span("train_batch"):
            with trace.span("ps_push"):
                with trace.span("rpc_attempt", attempt=1):
                    assert trace.profiled_spans()[ident][1] == "ps_push"
            with trace.span("Pserver/push_gradients"):
                with trace.span("ps_apply_round"):
                    published = trace.profiled_spans()[ident]
                    assert published[1] == "Pserver/push_gradients"
                    assert segment_of_span(published[1]) == "apply"
            assert trace.profiled_spans()[ident][1] == "train_batch"
    finally:
        sampler.stop()
    assert trace.profiled_spans() == {}


def test_stopped_sampler_freezes_overhead_gauge(clean_profiler,
                                                monkeypatch):
    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    try:
        sampler = StackSampler("w", hz=100)
        sampler.start()
        time.sleep(0.1)
        sampler.stop()
        gauge = obs_metrics.default_registry().get(
            "edl_prof_overhead_ratio"
        )
        frozen = gauge.get("w")
        assert frozen == sampler.overhead_ratio()
        time.sleep(0.1)
        # the ratio does not silently decay after stop (the duty-cycle
        # clock stops with the sampler)
        assert gauge.get("w") == frozen
    finally:
        obs_metrics.reset_default_registry()


def test_unsampled_spans_are_not_published(clean_profiler, tmp_path,
                                           monkeypatch):
    monkeypatch.setenv(trace.TRACE_DIR_ENV, str(tmp_path))
    monkeypatch.setenv(trace.SAMPLE_ENV, "0")
    trace.configure("worker-0")
    trace._profiler_attach()
    try:
        with trace.root_span("train_batch"):
            assert trace.profiled_spans() == {}
    finally:
        trace._profiler_detach()


def test_publication_inert_without_profiler(clean_profiler, tmp_path,
                                            monkeypatch):
    monkeypatch.setenv(trace.TRACE_DIR_ENV, str(tmp_path))
    trace.configure("worker-0")
    with trace.root_span("train_batch"):
        with trace.span("ps_apply_push"):
            assert trace.profiled_spans() == {}


# ---------------------------------------------------------------------------
# /profilez on every role's daemon: window capture vs ring snapshot


@pytest.mark.parametrize("role", ["master", "ps-0", "worker-0",
                                  "serve-0"])
def test_profilez_capture_matches_ring_for_role(role, clean_profiler,
                                                monkeypatch):
    monkeypatch.setenv(profiler.HZ_ENV, "250")
    sampler = profiler.maybe_start(role)
    assert sampler is not None
    server = ObservabilityServer(role, 0).start()
    stop = threading.Event()
    thread = _burn_thread(stop)
    base = "http://localhost:%d" % server.port
    try:
        _wait_for_samples(sampler)
        status, body = _get(base + "/profilez?seconds=0.4")
        assert status == 200
        capture = json.loads(body)
        status, body = _get(base + "/profilez")
        assert status == 200
        ring = json.loads(body)
    finally:
        stop.set()
        thread.join()
        server.stop()
        profiler.stop()
    # parity: same role, same schema, and the same hot frame shows in
    # both the on-demand window and the rolling ring
    for snap in (capture, ring):
        assert snap["role"] == role
        assert snap["hz"] == 250
        assert {"samples", "window_secs", "stacks"} <= set(snap)
    hot = lambda s: any(  # noqa: E731
        "burn_hot_loop" in f
        for e in s["stacks"] for f in e["stack"]
    )
    assert hot(capture) and hot(ring)
    # the window capture saw only its window, the ring the whole run
    assert capture["samples"] <= ring["samples"]


def test_profilez_collapsed_format_and_bad_params(clean_profiler,
                                                  monkeypatch):
    monkeypatch.setenv(profiler.HZ_ENV, "250")
    profiler.maybe_start("worker-0")
    server = ObservabilityServer("worker-0", 0).start()
    stop = threading.Event()
    thread = _burn_thread(stop)
    base = "http://localhost:%d" % server.port
    try:
        _wait_for_samples(profiler.sampler())
        status, text = _get(
            base + "/profilez?format=collapsed"
        )
        assert status == 200
        line = text.splitlines()[0]
        stack, count = line.rsplit(" ", 1)
        assert ";" in stack and int(count) >= 1
        status, _ = _get(base + "/profilez?seconds=nope")
        assert status == 400
        status, _ = _get(base + "/profilez?format=xml")
        assert status == 400
    finally:
        stop.set()
        thread.join()
        server.stop()
        profiler.stop()


def test_capture_journals_profile_captured(clean_profiler, tmp_path,
                                           monkeypatch):
    from elasticdl_tpu.observability import events

    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(tmp_path))
    monkeypatch.setenv(profiler.HZ_ENV, "100")
    journal = events.configure("worker-0")
    try:
        sampler = profiler.maybe_start("worker-0")
        sampler.capture(0.05)
    finally:
        profiler.stop()
        events._reset_for_tests()
    with open(journal.path, encoding="utf-8") as f:
        kinds = [json.loads(line)["event"] for line in f if line.strip()]
    assert kinds == ["profiler_started", "profile_captured"]


# ---------------------------------------------------------------------------
# report tooling


def _capture(role, stacks):
    return {
        "role": role, "hz": 29.0,
        "samples": sum(s["count"] for s in stacks),
        "overflow": 0, "window_secs": 2.0, "stacks": stacks,
    }


def _entry(stack, count, segment=None, trace_id=None):
    return {"stack": stack, "count": count, "segment": segment,
            "trace_id": trace_id}


def test_profile_report_merges_roles(tmp_path):
    worker = _capture("worker-0", [
        _entry(["t:run", "w:train", "s:train_step"], 60, "compute",
               "abc"),
        _entry(["t:run", "w:train", "c:push"], 20, "push", "abc"),
    ])
    ps = _capture("ps-0", [
        _entry(["g:handler", "s:apply"], 30, "apply", "def"),
    ])
    for name, capture in (("worker-0", worker), ("ps-0", ps)):
        with open(tmp_path / ("%s.profile.json" % name), "w") as f:
            json.dump(capture, f)
    captures = profile_report.load_captures(
        profile_report.discover([str(tmp_path)])
    )
    assert len(captures) == 2
    merged = profile_report.merge_collapsed(captures)
    assert merged["worker-0;[compute];t:run;w:train;s:train_step"] == 60
    assert merged["ps-0;[apply];g:handler;s:apply"] == 30
    top = profile_report.per_role_top(captures, top=2)
    assert top["worker-0"]["samples"] == 80
    assert top["worker-0"]["top"][0]["frame"] == "s:train_step"
    assert top["ps-0"]["top"][0] == {
        "frame": "s:apply", "self": 30, "total": 30,
    }
    # the CLI end to end
    rc = profile_report.main([str(tmp_path)])
    assert rc == 0
    assert (tmp_path / "merged.collapsed.txt").exists()


def test_critical_path_frames_by_segment(tmp_path):
    capture = _capture("worker-0", [
        _entry(["w:train", "s:train_step"], 50, "compute", "abc"),
        _entry(["w:train", "c:push"], 10, "push", "abc"),
        _entry(["idle:poll"], 99),  # untagged: excluded
    ])
    path = tmp_path / "worker-0.profile.json"
    with open(path, "w") as f:
        json.dump(capture, f)
    frames = critical_path.frames_by_segment(
        critical_path.load_profiles(str(tmp_path)), top=2
    )
    assert set(frames) == {"compute", "push"}
    assert frames["compute"][0]["count"] == 50
    assert frames["compute"][0]["roles"] == ["worker-0"]


def test_bench_trend_flags_both_directions(tmp_path):
    for n, sps, p99 in ((1, 10.0, 5.0), (2, 20.0, 4.0)):
        with open(tmp_path / ("BENCH_r%02d.json" % n), "w") as f:
            json.dump({"parsed": {
                "metric": "headline", "value": 1.0,
                "extra": {"steps_per_sec": sps, "serve_p99_ms": p99},
            }}, f)
    journal = tmp_path / "journal.jsonl"
    with open(journal, "w") as f:
        f.write(json.dumps({"ts": "t1", "wire_micro": {
            "steps_per_sec": 12.0, "serve_p99_ms": 9.0,
        }}) + "\n")
        f.write("{torn line\n")
    sources = bench_trend.load_bench_rounds(str(tmp_path))
    sources += bench_trend.load_journal(str(journal))
    metrics, regressions = bench_trend.analyze(
        bench_trend.build_series(sources), threshold=0.2
    )
    flagged = {r["metric"] for r in regressions}
    # throughput fell 12 vs best 20; latency rose 9 vs best 4
    assert flagged == {"steps_per_sec", "serve_p99_ms"}
    assert metrics["steps_per_sec"]["direction"] == "higher"
    assert metrics["serve_p99_ms"]["direction"] == "lower"
    # headline never moved: tracked but quiet
    assert not metrics["headline"]["regressing"]


def test_bench_trend_direction_heuristic():
    assert bench_trend.lower_is_better("serving_p99_ms")
    assert bench_trend.lower_is_better("deepfm_profiler_overhead_ratio")
    assert bench_trend.lower_is_better("holdout_logloss")
    assert not bench_trend.lower_is_better("deepfm_ctr_steps_per_sec")
    assert not bench_trend.lower_is_better("transformer_mfu")
    assert not bench_trend.lower_is_better("tier_hit_rate")
