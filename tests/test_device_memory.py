"""The program's own HBM account (ISSUE 47), the allocator's side:
``memory_snapshot`` keeps the local devices apart and its summary is
the FULLEST device's, what its loaded programs reserve counted in; the
``device_memory`` journal event leaves a worker process at three points
and never from a step; the compile ledger carries the compiler's count
of every wrapped program."""

import json
import logging

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from elasticdl_tpu.common import timing_utils  # noqa: E402
from elasticdl_tpu.common.timing_utils import Timing  # noqa: E402
from elasticdl_tpu.observability import device as device_obs  # noqa: E402
from elasticdl_tpu.observability import events  # noqa: E402

GB = 10 ** 9


@pytest.fixture(autouse=True)
def _isolate_device_obs(monkeypatch):
    monkeypatch.delenv(device_obs.DEVICE_OBS_ENV, raising=False)
    device_obs.reset_for_tests()
    yield
    device_obs.reset_for_tests()


@pytest.fixture
def journal(tmp_path, monkeypatch):
    monkeypatch.setenv("EDL_EVENTS_DIR", str(tmp_path))
    events.configure("worker-0")

    def read(kind=None):
        records = []
        for path in sorted(tmp_path.glob("worker-0-*.events.ndjson")):
            records += [
                json.loads(line)
                for line in path.read_text().splitlines()
            ]
        return [r for r in records if kind in (None, r["event"])]

    yield read
    events._reset_for_tests()


class FakeDevice:
    def __init__(self, index, stats):
        self.id = index
        self._stats = stats
        self.asked = 0

    def memory_stats(self):
        self.asked += 1
        return self._stats


def tpu_stats(in_use, reserved, peak_in_use, peak_reserved, **more):
    """The keys a v5e's allocator reports (benchmark/lib/probe.py reads
    the same ones on the chip)."""
    return dict(
        bytes_in_use=in_use, bytes_reserved=reserved,
        peak_bytes_in_use=peak_in_use, peak_bytes_reserved=peak_reserved,
        bytes_limit=int(16.9 * GB), **more)


@pytest.fixture
def four_chips(monkeypatch):
    """Four devices as ``mellum2-ep4-s8k`` leaves them: every rank holds
    the same program (8.9 GB reserved), rank 2 received the most rows."""
    devices = [
        FakeDevice(0, tpu_stats(6.0 * GB, 8.9 * GB, 6.1 * GB, 8.9 * GB)),
        FakeDevice(1, tpu_stats(6.2 * GB, 8.9 * GB, 6.3 * GB, 8.9 * GB)),
        FakeDevice(2, tpu_stats(6.1 * GB, 8.9 * GB, 6.9 * GB, 8.9 * GB,
                                largest_free_block_bytes=int(0.7 * GB))),
        FakeDevice(3, tpu_stats(6.0 * GB, 8.9 * GB, 6.0 * GB, 8.9 * GB)),
    ]
    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    return devices


def test_the_summary_is_the_fullest_device_s_and_not_a_sum(four_chips):
    snap = device_obs.memory_snapshot()
    assert snap["source"] == "allocator"
    assert [d["id"] for d in snap["devices"]] == [0, 1, 2, 3]
    assert snap["fullest"] == 2
    # one device's numbers: a sum would read 67.6 GB of limit
    assert snap["limit_bytes"] == int(16.9 * GB)
    assert snap["peak_bytes"] == int(6.9 * GB) + int(8.9 * GB)
    # in use now: buffers plus what the loaded programs reserve
    assert snap["bytes_in_use"] == int(6.1 * GB) + int(8.9 * GB)
    fullest = snap["devices"][2]
    assert fullest == {
        "id": 2, "in_use": int(6.1 * GB), "reserved": int(8.9 * GB),
        "peak_in_use": int(6.9 * GB), "peak_reserved": int(8.9 * GB),
        "limit": int(16.9 * GB), "largest_free_block": int(0.7 * GB)}
    assert "largest_free_block" not in snap["devices"][0]
    # every device asked once, and the allocator's own peak is the
    # answer: no host-side watermark on this path
    assert [d.asked for d in four_chips] == [1, 1, 1, 1]
    assert device_obs._hbm_peak == 0
    json.dumps(snap)


def test_telemetry_carries_what_runs_out(four_chips):
    tel = device_obs.telemetry()
    assert tel["hbm_bytes_in_use"] == int(6.1 * GB) + int(8.9 * GB)
    assert tel["hbm_peak_bytes"] == int(6.9 * GB) + int(8.9 * GB)
    assert tel["hbm_limit_bytes"] == int(16.9 * GB)
    # 15.0 of 16.9 GB: over the alert's default 0.9 only by the
    # reserve, which the old reading (6.1 of 16.9 a device) never saw
    assert tel["hbm_bytes_in_use"] / tel["hbm_limit_bytes"] < 0.9
    assert tel["hbm_peak_bytes"] / tel["hbm_limit_bytes"] > 0.9


def test_a_device_without_counters_is_left_out(monkeypatch):
    devices = [
        FakeDevice(0, None),
        FakeDevice(1, tpu_stats(2 * GB, 1 * GB, 3 * GB, 1 * GB)),
    ]
    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    snap = device_obs.memory_snapshot()
    assert [d["id"] for d in snap["devices"]] == [1]
    assert snap["fullest"] == 0 and snap["peak_bytes"] == 4 * GB


def test_the_cpu_fallback_keeps_its_meaning(monkeypatch):
    monkeypatch.setenv(device_obs.HBM_LIMIT_ENV, "1000000")
    keep = jnp.ones((128, 128))
    snap = device_obs.memory_snapshot()
    if snap["source"] != "live_arrays":
        pytest.skip("this backend has an allocator")
    assert snap["devices"] == [] and snap["fullest"] is None
    assert snap["bytes_in_use"] >= keep.nbytes
    assert snap["limit_bytes"] == 1000000
    # the watermark outlives the arrays
    peak = snap["peak_bytes"]
    assert peak >= snap["bytes_in_use"]
    del keep
    assert device_obs.memory_snapshot()["peak_bytes"] >= peak


def test_switched_off_it_reads_nothing(monkeypatch, four_chips, journal):
    monkeypatch.setenv(device_obs.DEVICE_OBS_ENV, "0")
    assert device_obs.memory_snapshot() == {}
    device_obs.journal_memory("teardown")
    assert journal("device_memory") == []
    assert [d.asked for d in four_chips] == [0, 0, 0, 0]
    raw = device_obs.instrumented_jit(lambda x: x + 1)
    assert type(raw) is type(jax.jit(lambda x: x + 1))


# ---------------------------------------------------------------------
# the journal event


def worker_process(ledger, steps=6):
    """A worker's life as its loop thread lives it."""
    ledger.begin_startup(ledger.start() - 5_000_000)
    previous = timing_utils.bind(ledger)
    try:
        from elasticdl_tpu.worker.trainer import Trainer

        class OneState(Trainer):
            def create_state(self, features):
                return {"w": jnp.zeros((4,))}

        trainer, state = OneState.__new__(OneState), None
        for number in range(1, steps + 1):
            with ledger.step(number) as step:
                step.has_batch(number)
                state = trainer.ensure_state(state, {"features": None})
                with ledger.phase("dispatch"):
                    pass
    finally:
        timing_utils.bind(previous)
    ledger.begin_teardown()
    ledger.begin_teardown()  # the drain and run()'s finally both call
    with ledger.phase("drain"):
        pass
    ledger.end_teardown()


def test_a_worker_journals_it_three_times(four_chips, journal):
    worker_process(Timing(interval=2))
    found = journal("device_memory")
    assert [e["at"] for e in found] == [
        "state_init", "first_step", "teardown"]
    for event in found:
        assert event["source"] == "allocator" and event["fullest"] == 2
        assert len(event["devices"]) == 4
        assert event["limit_bytes"] == int(16.9 * GB)
        assert {"ts", "role", "pid", "seq"} <= set(event)
    # on the journal's clock, in the records' order: after the state
    # was made, right after ``worker_startup``, before the teardown's
    # own record
    order = [
        (e["event"], e.get("at")) for e in journal()
        if e["event"] in ("device_memory", "worker_startup",
                          "worker_teardown")]
    assert order == [
        ("device_memory", "state_init"), ("worker_startup", None),
        ("device_memory", "first_step"), ("device_memory", "teardown"),
        ("worker_teardown", None)]
    # the allocator is asked at those three points and at no step
    assert [d.asked for d in four_chips] == [3, 3, 3, 3]


def test_the_field_sets_of_the_two_records_are_as_they_were(
        four_chips, journal):
    worker_process(Timing(interval=2))
    (startup,) = journal("worker_startup")
    (teardown,) = journal("worker_teardown")
    envelope = {"ts", "role", "pid", "seq", "job", "event"}
    assert set(startup) - envelope <= {
        "start_ts", "wall_ns", "phases", "compiles", "listener_calls"}
    assert set(teardown) - envelope == {"start_ts", "wall_ns", "phases"}
    assert "state_init" in startup["phases"]


def test_a_master_never_asks_for_devices(monkeypatch, journal):
    def opened():
        raise AssertionError("the master asked jax for its devices")

    monkeypatch.setattr(jax, "local_devices", opened)
    monkeypatch.setattr(jax, "live_arrays", opened)
    ledger = Timing()
    ledger.begin_startup(ledger.start(), "master_startup")
    ledger.end_record("serve", ledger.start())
    ledger.end_startup()
    ledger.begin_teardown("master_teardown")
    ledger.end_teardown()
    assert [e["event"] for e in journal()] == [
        "master_startup", "master_teardown"]


# ---------------------------------------------------------------------
# the compiler's count in the compile ledger


class FakeAnalysis:
    argument_size_in_bytes = 6_210_000_000
    output_size_in_bytes = 6_210_000_000
    alias_size_in_bytes = 6_210_000_000
    temp_size_in_bytes = 8_930_000_000
    generated_code_size_in_bytes = 40_000_000
    peak_memory_in_bytes = 15_180_000_000


class FakeCompiled:
    def __init__(self, analysis):
        self._analysis = analysis

    def memory_analysis(self):
        return self._analysis


def test_compiled_memory_takes_the_runtime_s_peak_where_it_gives_one():
    memory = device_obs.compiled_memory(FakeCompiled(FakeAnalysis()))
    assert memory == {
        "arguments": 6_210_000_000, "outputs": 6_210_000_000,
        "aliased": 6_210_000_000, "temporaries": 8_930_000_000,
        "code": 40_000_000, "peak": 15_180_000_000,
        "peak_from": "compiler"}
    assert device_obs.memory_text(memory, int(16.9 * GB)) == (
        "arguments 6.21 GB (aliased 6.21), temporaries 8.93 GB, outputs "
        "6.21 GB, code 0.04 GB, peak 15.18 GB of 16.90")
    assert device_obs.memory_text(memory).endswith("peak 15.18 GB")


def test_compiled_memory_sums_where_it_gives_none():
    class NoPeak(FakeAnalysis):
        peak_memory_in_bytes = 0

    memory = device_obs.compiled_memory(FakeCompiled(NoPeak()))
    assert memory["peak_from"] == "sum"
    assert memory["peak"] == 6_210_000_000 + 8_930_000_000
    assert device_obs.compiled_memory(FakeCompiled(None)) is None


def test_every_wrapped_program_s_compile_carries_the_count(journal, caplog):
    """On this backend: the event's new fields beside the old ones, and
    a log line of its own after the compile line, which stays as the
    benchmark's log parser reads it."""
    from benchmark.lib import logs

    step = device_obs.instrumented_jit(
        lambda x: (x @ x.T).sum(), name="train_step")
    with caplog.at_level(
            logging.INFO, logger="elasticdl_tpu.observability.device"):
        step(jnp.ones((64, 32)))
        step(jnp.ones((64, 32)))
    (event,) = journal("xla_compile")
    assert set(event) >= {
        "fn", "compiles", "seconds", "cost_fetch_seconds", "stages",
        "collectives", "kernels", "memory", "peak_live"}
    memory = event["memory"]
    assert set(memory) == {
        "arguments", "outputs", "aliased", "temporaries", "code", "peak",
        "peak_from"}
    assert memory["arguments"] == 64 * 32 * 4
    assert memory["peak"] >= memory["arguments"]
    lines = [r.getMessage() for r in caplog.records]
    compile_line, memory_line = lines[-2:]
    assert compile_line.startswith("xla compile #1 of train_step: call ")
    assert "memory" not in compile_line
    assert memory_line.startswith(
        "xla memory of train_step: arguments 0.00 GB (aliased 0.00), ")
    # neither reads as a compile twice, nor the new one as a step
    stamped = "\n".join(
        "2026-10-01 02:00:0%d,000 INFO x: %s" % (i, line)
        for i, line in enumerate((compile_line, memory_line)))
    assert len(logs.parse_worker_log(stamped)["compiles"]) == 1
    assert logs.parse_worker_log(stamped)["steps"] == []
