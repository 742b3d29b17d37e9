"""The compile split (ISSUE 33): what ``observability/device.py`` reads
from ``jax.monitoring``. Counts and structure on the CPU, never a
speed: a wrapped call's ``stages``, the process totals over all
programs, the persistent cache's hit and miss, the log line the
benchmark parses, and the off switch."""

import json
import logging
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring
from jax.experimental.compilation_cache import compilation_cache

from elasticdl_tpu.common import platform
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import events, trace

TRACE, LOWER, BACKEND = device_obs._STAGE_OF


@pytest.fixture(autouse=True)
def fresh_books():
    device_obs.reset_for_tests()
    yield
    device_obs.reset_for_tests()


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compilation cache of this test's own, taking every
    program however small (what ``benchmark/run.py`` gives a worker)."""
    names = {
        # tests/conftest.py switches the cache off for the suite
        "jax_enable_compilation_cache": True,
        # jax's option is the environment variable's name in lower
        # case (the literal stays in common/platform.py, which places
        # a process's cache; ``tests/test_chip_smoke.py`` holds it so)
        platform.COMPILE_CACHE_ENV.lower(): str(tmp_path / "jax_cache"),
        "jax_persistent_cache_min_compile_time_secs": 0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }
    before = {name: getattr(jax.config, name) for name in names}
    for name, value in names.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield tmp_path / "jax_cache"
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    jax.clear_caches()


@pytest.fixture
def journal(tmp_path, monkeypatch):
    monkeypatch.setenv("EDL_EVENTS_DIR", str(tmp_path / "events"))
    events.configure("worker-0")

    def read(kind):
        records = []
        for path in sorted((tmp_path / "events").glob("*.ndjson")):
            records += [json.loads(x) for x in path.read_text().splitlines()]
        return [r for r in records if r["event"] == kind]

    yield read
    events._reset_for_tests()


def _toy(x):
    return jnp.tanh(x @ x).sum()


def _compile_cold_then_warm(name="toy_step"):
    """One wrapper compiles into the empty cache; after
    ``jax.clear_caches()`` a fresh wrapper of the same function finds
    the program there."""
    x = jnp.ones((48, 48))
    cold = device_obs.instrumented_jit(_toy, name=name)
    cold(x)
    jax.clear_caches()
    warm = device_obs.instrumented_jit(_toy, name=name)
    warm(x)
    return cold, warm


@pytest.mark.parametrize("which,cache", [(0, "miss"), (1, "hit")])
def test_a_cold_compile_misses_and_a_warm_one_hits(cache_dir, which, cache):
    stages = _compile_cold_then_warm()[which].stages
    assert stages["cache"] == cache
    assert [s["stage"] for s in stages["spans"]] == [
        "trace", "lower", "backend"]
    if cache == "hit":
        assert stages["retrieval_s"] > 0 and "saved_s" in stages
        assert stages["retrieval_s"] <= stages["backend_s"] + 1e-4
    else:
        assert "retrieval_s" not in stages and "saved_s" not in stages
        assert any(cache_dir.iterdir())


@pytest.mark.parametrize("which", [0, 1])
def test_the_stages_lie_inside_the_call(cache_dir, which):
    before = time.time()
    wrapper = _compile_cold_then_warm()[which]
    stages, call = wrapper.stages, wrapper.last_compile_secs
    assert stages["first_run_s"] >= 0
    named = stages["trace_s"] + stages["lower_s"] + stages["backend_s"]
    assert named <= call + 1e-3
    assert named + stages["first_run_s"] == pytest.approx(call, abs=1e-3)
    # on the epoch clock, in order, none before the test began
    edges = [t for s in stages["spans"] for t in (s["start"], s["end"])]
    assert edges == sorted(edges) and before <= edges[0]
    assert edges[-1] <= time.time()


def test_the_process_totals_count_both_and_the_journal_names_the_miss(
        cache_dir, journal):
    wrappers = _compile_cold_then_warm()  # the books hold them weakly
    totals = device_obs.compile_totals()
    # jnp.ones is a program too: eager ops go through the same cache
    assert totals["hits"] >= 1 and totals["misses"] >= 1
    assert totals["requests"] == totals["hits"] + totals["misses"]
    misses = journal("xla_cache_miss")
    assert len(misses) == totals["misses"]
    assert "jit(_toy)" in {m["module"] for m in misses}
    assert all(m["backend_s"] >= 0 and m["phase"] is None for m in misses)
    (cold, warm) = journal("xla_compile")
    assert cold["stages"]["cache"] == "miss"
    assert warm["stages"]["cache"] == "hit"
    assert device_obs.compile_stats()["toy_step"]["stages"]["cache"] == "hit"
    assert len(wrappers) == 2


def test_without_a_cache_directory_the_cache_is_off():
    wrapper = device_obs.instrumented_jit(_toy, name="toy_step")
    wrapper(jnp.ones((40, 40)))
    assert wrapper.stages["cache"] == "off"
    totals = device_obs.compile_totals()
    assert totals["requests"] >= 1
    assert totals["hits"] == totals["misses"] == 0


def test_an_eager_op_between_two_calls_is_in_the_totals_and_in_no_wrapper():
    first = device_obs.instrumented_jit(_toy, name="first")
    second = device_obs.instrumented_jit(lambda x: x * 3 + 1, name="second")
    x = jnp.ones((24, 24))
    first(x)
    mark = device_obs.compile_totals()
    jnp.cumsum(jnp.arange(19.0).reshape(19, 1) ** 3)  # eager programs
    eager = device_obs.compile_totals()
    assert eager["requests"] > mark["requests"]
    assert eager["backend_s"] > mark["backend_s"]
    second(x)
    for wrapper in (first, second):
        assert [s["stage"] for s in wrapper.stages["spans"]] == [
            "trace", "lower", "backend"]
    wrapped = sum(
        w.stages["backend_s"] for w in (first, second))
    assert device_obs.compile_totals()["backend_s"] > wrapped


def test_a_jit_traced_inside_the_step_is_its_parents_time():
    """The totals grow by this thread's outermost spans and by nothing
    else: the call's own, as its ``stages`` list them, and what the cost
    fetch left on the thread's list after it. ``inner``'s two traces lie
    inside the outer one and add nothing. Spans against spans: no second
    of the machine's load enters (the argument's own eager compile is
    over before the totals are read)."""
    inner = jax.jit(lambda x: jnp.sin(x) * 2)
    wrapper = device_obs.instrumented_jit(
        lambda x: inner(x).sum() + inner(x + 1).mean(), name="outer")
    x = jnp.ones((17, 3))
    before = device_obs.compile_totals()
    wrapper(x)
    after = device_obs.compile_totals()
    stages = wrapper.stages
    # one outermost trace, though jax traced ``inner`` inside it
    assert [s["stage"] for s in stages["spans"]] == [
        "trace", "lower", "backend"]
    outermost = stages["spans"] + list(device_obs._stage_tls.spans)
    traced = sum(s["end"] - s["start"] for s in outermost
                 if s["stage"] == "trace")
    # the spans' ends are rounded to a microsecond
    assert after["trace_s"] - before["trace_s"] == pytest.approx(
        traced, abs=2e-5)


def _report(event, start, end):
    """What jax does around one stage, on the calling thread."""
    device_obs._on_stage_start(event, start, fun_name="f")
    device_obs._on_stage_span(event, start, end, fun_name="f")


@pytest.mark.parametrize("case", ["another_thread", "before_the_call",
                                  "after_the_call"])
def test_only_spans_that_start_inside_the_call_on_its_thread_are_charged(
        case):
    device_obs.install_listeners()
    t0, elapsed = 1000.0, 10.0
    _report(TRACE, 1001.0, 1002.0)
    _report(BACKEND, 1003.0, 1007.0)
    if case == "another_thread":
        # inside the call's seconds, but another thread's compile
        thread = threading.Thread(
            target=_report, args=(BACKEND, 1004.0, 1006.0))
        thread.start()
        thread.join(10)
    elif case == "before_the_call":
        _report(LOWER, 990.0, 999.5)  # an eager op before t0
    else:
        _report(LOWER, 1010.5, 1012.0)  # the cost fetch's relower
    stages = device_obs._call_stages(t0, elapsed)
    assert stages["trace_s"] == 1.0 and stages["backend_s"] == 4.0
    assert stages["lower_s"] == 0.0 and stages["first_run_s"] == 5.0
    assert len(stages["spans"]) == 2
    # the totals hold all three
    totals = device_obs.compile_totals()
    assert totals["trace_s"] + totals["lower_s"] + totals["backend_s"] > 5.0
    # and nothing is charged twice: the next call starts clean
    assert device_obs._call_stages(t0, elapsed)["spans"] == []


def test_the_log_line_reads_as_before(caplog):
    from benchmark.lib.logs import COMPILE_RE, parse_worker_log

    wrapper = device_obs.instrumented_jit(_toy, name="train_step")
    with caplog.at_level(logging.INFO):
        wrapper(jnp.ones((56, 56)))
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("xla compile #1 of")]
    match = COMPILE_RE.search(line)
    call = "%.2f" % wrapper.last_compile_secs
    assert match and match.group(3) == "train_step"
    assert match.group(4) == call
    assert line.startswith(
        "xla compile #1 of train_step: call %ss, cost fetch " % call)
    assert "; stages trace " in line and "(cache off) first run " in line
    assert line.index("cost fetch") < line.index("stages") < line.index(
        "collectives")
    (fact,) = parse_worker_log(
        "2026-09-28 00:00:00,000 INFO " + line)["compiles"]
    assert (fact["fn"], fact["n"], fact["call_s"]) == (
        "train_step", 1, float(call))


def test_a_step_that_hits_the_jit_cache_calls_no_listener():
    calls = []

    def count(event, *args, **kwargs):
        calls.append(event)

    monitoring.register_event_listener(count)
    monitoring.register_scalar_listener(count)
    monitoring.register_event_duration_secs_listener(count)
    monitoring.register_event_time_span_listener(count)
    try:
        wrapper = device_obs.instrumented_jit(_toy, name="toy_step")
        x = jnp.ones((32, 32))
        wrapper(x)
        assert calls and device_obs.compile_totals()["listener_calls"] > 0
        del calls[:]
        mine = device_obs.compile_totals()["listener_calls"]
        for _ in range(20):
            wrapper(x)
        assert wrapper.cache_hits == 20 and wrapper.compiles == 1
        assert calls == []
        assert device_obs.compile_totals()["listener_calls"] == mine
    finally:
        monitoring.unregister_event_listener(count)
        monitoring.unregister_scalar_listener(count)
        monitoring.unregister_event_duration_listener(count)
        monitoring.unregister_event_time_span_listener(count)


def test_switched_off_registers_no_listener(monkeypatch):
    monkeypatch.setenv(device_obs.DEVICE_OBS_ENV, "0")
    monkeypatch.setattr(device_obs, "_listeners_installed", False)
    before = [
        len(get()) for get in (
            monitoring.get_event_listeners,
            monitoring.get_scalar_listeners,
            monitoring.get_event_duration_listeners,
            monitoring.get_event_time_span_listeners)]
    wrapped = device_obs.instrumented_jit(_toy)
    device_obs.install_listeners()
    assert type(wrapped) is type(jax.jit(_toy))
    assert wrapped(jnp.ones((8, 8))).shape == ()
    assert device_obs._listeners_installed is False
    # nothing observed is not "nothing compiled"
    assert device_obs.compile_totals() is None
    assert before == [
        len(get()) for get in (
            monitoring.get_event_listeners,
            monitoring.get_scalar_listeners,
            monitoring.get_event_duration_listeners,
            monitoring.get_event_time_span_listeners)]
    assert device_obs.telemetry() == {}


class _Count:
    def __init__(self):
        self.value = 0

    def inc(self, amount=1):
        self.value += amount


def test_the_metrics_series(cache_dir, monkeypatch):
    """The lazy instruments resolve once a process, so the counting is
    checked on stand-ins and the names on the declarations."""
    import inspect

    hits, misses = _Count(), _Count()
    monkeypatch.setattr(device_obs, "_m_persistent_hits", hits)
    monkeypatch.setattr(device_obs, "_m_persistent_misses", misses)
    _compile_cold_then_warm()
    totals = device_obs.compile_totals()
    assert (hits.value, misses.value) == (totals["hits"], totals["misses"])
    assert hits.value >= 1 and misses.value >= 1
    source = inspect.getsource(device_obs)
    assert '"edl_xla_persistent_cache_hits_total"' in source
    assert '"edl_xla_persistent_cache_misses_total"' in source
    # the per-step counter went: it was calls less compiles
    assert "edl_xla_cache_hits_total" not in source
    assert not hasattr(device_obs, "_m_cache_hits")


def test_the_stages_are_on_the_compile_span_s_clock(
        tmp_path, monkeypatch, journal):
    """The ``compile`` span under ``EDL_TRACE_DIR`` is what it was,
    one span and no children; the journal's ``stages.spans`` carry
    the split, and lie inside it on the same epoch clock."""
    monkeypatch.setenv("EDL_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("EDL_TRACE_SAMPLE", "1")
    trace.configure("worker-0")
    try:
        wrapper = device_obs.instrumented_jit(_toy, name="toy_step")
        with trace.root_span("train_batch", role="worker"):
            wrapper(jnp.ones((20, 20)))
        trace.flush()
    finally:
        trace._reset_for_tests()
    spans = [
        e for path in tmp_path.glob("*.json*")
        for e in _trace_events(path) if e.get("ph") == "X"
    ]
    assert sorted(e["name"] for e in spans) == ["compile", "train_batch"]
    (compile_span,) = [e for e in spans if e["name"] == "compile"]
    assert set(compile_span["args"]) >= {"fn", "seconds", "recompile"}
    assert "cache" not in compile_span["args"]
    (event,) = journal("xla_compile")
    stages = event["stages"]["spans"]
    assert [s["stage"] for s in stages] == ["trace", "lower", "backend"]
    # the trace's ``ts`` and ``dur`` are microseconds of the same epoch
    start = compile_span["ts"] / 1e6
    end = start + compile_span["dur"] / 1e6
    for stage in stages:
        assert start - 1e-3 <= stage["start"] <= stage["end"] <= end + 1e-3


def _trace_events(path):
    text = path.read_text()
    try:
        loaded = json.loads(text)
    except ValueError:
        loaded = [json.loads(x.rstrip(",")) for x in text.splitlines()
                  if x.strip().startswith("{")]
    if isinstance(loaded, dict):
        loaded = loaded.get("traceEvents", [])
    return loaded
