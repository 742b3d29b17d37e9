"""The phase ledger (common/timing_utils.py; reference
timing_utils.py:17-48): it always counts, the phases of a step add up
to its wall time, a slow step and every interval leave the process as
schema'd journal events, and nothing it logs reads as a step or a
compile to the benchmark's log parser."""

import json
import logging
import time

import pytest

from elasticdl_tpu.common import timing_utils
from elasticdl_tpu.common.timing_utils import Timing
from elasticdl_tpu.observability import events

STEP_PHASES = ("input_wait", "dispatch", "device_wait", "report")


@pytest.fixture
def journal(tmp_path, monkeypatch):
    """The events this process journals, as a function returning them."""
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


class Clock:
    """Stands in for the ``time`` module inside the ledger: the tests
    that judge a step slow must not depend on this machine's load."""

    def __init__(self):
        self.now_ns = 1_000_000_000

    def perf_counter_ns(self):
        return self.now_ns

    def sleep(self, seconds):
        self.now_ns += int(seconds * 1e9)

    time = staticmethod(time.time)


@pytest.fixture
def clock(monkeypatch):
    fake = Clock()
    monkeypatch.setattr(timing_utils, "time", fake)
    return fake


def run_steps(ledger, clock, first, count, slow=None):
    """``count`` steps of four phases of 0.5 ms; step ``slow`` =
    (number, phase) spends 60 ms more in that phase."""
    for number in range(first, first + count):
        with ledger.step(number) as step:
            step.has_batch(task_id=7)
            for name in STEP_PHASES:
                with ledger.phase(name):
                    clock.sleep(0.0005)
                    if slow == (number, name):
                        clock.sleep(0.06)


def test_counts_with_nothing_switched_on(monkeypatch):
    monkeypatch.delenv("EDL_METRICS", raising=False)
    ledger = Timing()
    with ledger.phase("phase"):
        pass
    assert ledger.summary()["phase"]["count"] == 1


def test_accumulates_per_phase():
    ledger = Timing()
    for _ in range(3):
        with ledger.timeit("a"):
            time.sleep(0.01)
    with ledger.timeit("b"):
        pass
    summary = ledger.summary()
    assert summary["a"]["count"] == 3
    assert summary["a"]["seconds"] >= 0.03
    assert summary["b"]["count"] == 1


def test_report_resets():
    ledger = Timing()
    with ledger.phase("x"):
        pass
    ledger.report("task done")
    assert ledger.summary() == {}


def test_sync_on_jax_result():
    import jax.numpy as jnp

    ledger = Timing()
    start = ledger.start()
    result = jnp.ones((8, 8)) @ jnp.ones((8, 8))
    ledger.end_record_sync("matmul", start, result)
    assert ledger.summary()["matmul"]["count"] == 1


@pytest.mark.parametrize("nested", [False, True])
def test_phases_of_a_step_add_up_to_its_wall_time(journal, nested):
    ledger = Timing(interval=1)
    with ledger.step(1) as step:
        step.has_batch()
        with ledger.phase("restore"):
            time.sleep(0.004)
            if nested:
                with ledger.phase("state_init"):
                    time.sleep(0.1)
        time.sleep(0.002)  # nobody's: other
        with ledger.phase("dispatch"):
            time.sleep(0.003)
    (event,) = journal("loop_phases")
    phases = event["phases"]
    assert sum(phases.values()) == event["wall_ns"]
    assert phases["other"] >= 2_000_000
    assert all(ns >= 0 for ns in phases.values())
    assert set(phases) == {"restore", "dispatch", "other"} | (
        {"state_init"} if nested else set())
    if nested:
        # a phase is charged its own time, not its children's: the
        # outer one slept 4 ms around an inner one of 100 ms
        assert phases["state_init"] >= 100_000_000
        assert 4_000_000 <= phases["restore"] < phases["state_init"]
    assert event["first_step"] == event["last_step"] == 1


def test_cancelled_step_leaves_no_record(journal):
    ledger = Timing(interval=1)
    with ledger.step(1) as step:
        with ledger.phase("input_wait"):
            pass
        step.cancel()
    ledger.report()
    assert journal("loop_phases") == []
    assert timing_utils.STEP_PHASE not in ledger.last_seconds


def test_slow_phase_yields_exactly_one_slow_step(journal, clock, caplog):
    ledger = Timing(interval=8)
    with caplog.at_level(logging.WARNING):
        run_steps(ledger, clock, 1, 16, slow=(12, "device_wait"))
    (event,) = journal("slow_step")
    assert event["step"] == 12 and event["task"] == 7
    assert max(event["phases"], key=event["phases"].get) == "device_wait"
    assert event["wall_ns"] > 1.5 * event["median_ns"]
    assert event["wall_ns"] - event["median_ns"] > 20_000_000
    assert sum(event["phases"].values()) == event["wall_ns"]
    assert {"invol_ctx_switches", "major_faults"} <= set(event)
    assert [r for r in caplog.records if "slow_step" in r.getMessage()]
    intervals = journal("loop_phases")
    assert [(e["first_step"], e["last_step"]) for e in intervals] == [
        (1, 8), (9, 16)]
    assert intervals[1]["slowest_step"] == 12
    assert intervals[1]["slowest_wall_ns"] == event["wall_ns"]


def test_no_slow_step_on_or_after_a_compile(journal, clock):
    compiles = [0]
    ledger = Timing(interval=100, compile_count=lambda: compiles[0])
    run_steps(ledger, clock, 1, 10)
    compiles[0] += 1  # the compile lands in step 11, which is slow
    run_steps(ledger, clock, 11, 1, slow=(11, "dispatch"))
    run_steps(ledger, clock, 12, 1, slow=(12, "device_wait"))  # and the next
    assert journal("slow_step") == []
    run_steps(ledger, clock, 13, 1, slow=(13, "device_wait"))
    assert [e["step"] for e in journal("slow_step")] == [13]


def test_a_loop_that_runs_ahead_is_judged_by_runs(journal, clock):
    """Three steps that only dispatch (1 ms) and a fourth that reads
    the device and pays for all four (400 ms): not slow, the ordinary
    shape of a loop that runs ahead. A run that takes 4 s is."""
    ledger = Timing(interval=4)

    def run_of_four(first, wait):
        for number in range(first, first + 4):
            with ledger.step(number):
                with ledger.phase("dispatch"):
                    clock.sleep(0.001)
                if number % 4 == 0:
                    with ledger.phase("device_wait"):
                        clock.sleep(wait)

    for k in range(12):
        run_of_four(1 + 4 * k, 0.4)
    assert journal("slow_step") == []
    run_of_four(49, 4.0)
    (event,) = journal("slow_step")
    assert event["step"] == 52 and event["steps"] == 4
    assert event["wall_ns"] == 4_004_000_000
    assert event["median_ns"] == 101_000_000  # a step of a 404 ms run
    assert event["phases"]["dispatch"] == 4_000_000


def run_late(ledger, clock, first, count, device_ms, between=None):
    """The worker loop's order on the fake clock: iteration N
    dispatches step N (0.5 ms), then waits for step N - 1, which the
    device finishes ``device_ms(N - 1)`` after it finished step N - 2
    or was handed it. ``between(N)`` runs after iteration N closed."""
    done = clock.now_ns  # the device is idle
    for number in range(first, first + count):
        with ledger.step(number) as step:
            step.has_batch(task_id=7)
            with ledger.phase("input_wait"):
                clock.sleep(0.0001)
            with ledger.phase("dispatch"):
                clock.sleep(0.0005)
            before = done
            done = max(done, clock.now_ns) + int(device_ms(number) * 1e6)
            if number > first:
                ledger.read_ahead()
                with ledger.phase("device_wait"):
                    clock.now_ns = max(clock.now_ns, before)
            with ledger.phase("report"):
                clock.sleep(0.0003)
        if between is not None:
            between(number)


def test_a_step_read_late_is_still_judged_alone(journal, clock):
    """Every step read one step late: an iteration's wall time is one
    device step, the one before its own, so the device's slow step 12
    is the one slow step, seen by iteration 13, a run of one."""
    ledger = Timing(interval=8)
    run_late(ledger, clock, 1, 16, lambda n: 160 if n == 12 else 100)
    (event,) = journal("slow_step")
    assert event["step"] == 13 and event["steps"] == 1
    assert max(event["phases"], key=event["phases"].get) == "device_wait"
    assert event["median_ns"] == 100_000_000
    assert event["wall_ns"] == 160_000_000
    intervals = journal("loop_phases")
    assert [e["slowest_step"] for e in intervals] == [2, 13]
    # the loop's own turn lies under the device's step: the wall time
    # of sixteen iterations is fifteen device steps and a host's turn
    assert sum(e["wall_ns"] for e in intervals) == (
        14 * 100_000_000 + 160_000_000 + 900_000)


def test_the_interval_counts_the_steps_read_ahead_and_the_drains(
        journal, clock):
    """``ahead_steps``: the reads that began with a later step out;
    ``drains``: those that had none behind them, by reason. A drain
    after the interval's last step closed leaves with an event of no
    steps when the stream's totals are reported."""
    ledger = Timing(interval=4)

    def between(number):
        if number == 6:
            # a checkpoint after step 6: read with nothing queued
            ledger.drained("checkpoint")
            with ledger.phase("device_wait"):
                clock.sleep(0.1)

    run_late(ledger, clock, 1, 6, lambda n: 100, between)
    run_late(ledger, clock, 7, 2, lambda n: 100)
    ledger.drained("end")
    ledger.report("training stream")
    intervals = journal("loop_phases")
    assert [(e["first_step"], e["last_step"], e["steps"], e["ahead_steps"],
             e["drains"]) for e in intervals] == [
        (1, 4, 4, 3, {}),
        # step 7 follows a drain: nothing of step 6 was left to read
        (5, 8, 4, 3, {"checkpoint": 1}),
        (8, 8, 0, 0, {"end": 1}),
    ]
    assert intervals[2]["wall_ns"] == 0
    assert sum(intervals[2]["phases"].values()) == 0
    # nothing is pending: a second report journals nothing
    ledger.report("again")
    assert len(journal("loop_phases")) == 3


def test_ledger_events_pass_the_schema(journal, clock):
    ledger = Timing(interval=4)
    ledger.begin_startup(clock.perf_counter_ns() - 5_000_000)
    with ledger.phase("backend_init"):
        pass
    run_steps(ledger, clock, 1, 14, slow=(13, "input_wait"))
    ledger.begin_teardown()
    with ledger.phase("drain"):
        pass
    ledger.end_teardown()
    kinds = [r["event"] for r in journal()]
    # ``device_memory``: the worker's two records journal the device's
    # memory at their edge (tests/test_device_memory.py)
    assert set(kinds) == {
        "worker_startup", "loop_phases", "slow_step", "worker_teardown",
        "device_memory"}
    assert set(kinds) <= events.EVENT_TYPES
    for record in journal():
        assert {"ts", "role", "pid", "seq", "event"} <= set(record)
        if record["event"] == "device_memory":
            continue
        assert isinstance(record["wall_ns"], int)
        assert all(isinstance(ns, int)
                   for ns in record["phases"].values())
    # unknown names are still refused: the vocabulary is closed
    with pytest.raises(ValueError):
        events.emit("loop_phase")


def test_startup_holds_the_first_iteration_under_its_own_names(
    journal, clock
):
    ledger = Timing(interval=2)
    start = clock.perf_counter_ns() - 50_000_000
    ledger.begin_startup(start)
    ledger.end_record("imports", start, end=start + 30_000_000)
    with ledger.phase("master_connect"):
        pass
    run_steps(ledger, clock, 1, 1)  # the first iteration: start-up's
    (startup,) = journal("worker_startup")
    phases = startup["phases"]
    assert phases["imports"] == 30_000_000
    assert {"first_task", "first_step", "master_connect", "device_wait",
            "other"} <= set(phases)
    assert "input_wait" not in phases and "dispatch" not in phases
    assert sum(phases.values()) == startup["wall_ns"] >= 50_000_000
    run_steps(ledger, clock, 2, 1)
    (interval,) = journal("loop_phases")
    assert interval["first_step"] == 2 and "input_wait" in interval["phases"]
    # teardown closes a start-up that never saw a step
    idle = Timing()
    idle.begin_startup(clock.perf_counter_ns())
    idle.begin_teardown()
    idle.end_teardown()
    assert len(journal("worker_startup")) == 2
    assert len(journal("worker_teardown")) == 1


def test_log_lines_add_no_step_and_no_compile(journal, clock, caplog):
    from benchmark.lib.logs import parse_worker_log

    ledger = Timing(interval=8)
    ledger.begin_startup(clock.perf_counter_ns())
    with caplog.at_level(logging.INFO):
        run_steps(ledger, clock, 1, 16, slow=(14, "dispatch"))
        ledger.report("training stream")
    said = "\n".join(
        "2026-09-27 00:00:00,000 INFO " + r.getMessage()
        for r in caplog.records
    )
    assert "slow_step" in said and "phase ledger" in said
    assert "worker start-up" in said
    facts = parse_worker_log(said)
    assert facts["steps"] == [] and facts["compiles"] == []


def test_current_is_the_threads_ledger():
    import threading

    mine = Timing()
    previous = timing_utils.bind(mine)
    try:
        assert timing_utils.current() is mine
        seen = []
        thread = threading.Thread(
            target=lambda: seen.append(timing_utils.current()))
        thread.start()
        thread.join(10)
        # another thread gets its own: a parked producer names nothing
        assert seen and seen[0] is not mine
    finally:
        timing_utils.bind(previous)


def test_process_age_is_read_from_the_operating_system():
    age = timing_utils.process_age_ns()
    assert age is not None and age > 0
    time.sleep(0.05)
    assert timing_utils.process_age_ns() - age >= 30_000_000


def test_sparse_trainer_phases_recorded():
    """SparseTrainer records sparse_pull / batch_process / sparse_push
    (the reference's get_model / batch / report_gradient phases)."""
    import flax.linen as nn
    import numpy as np

    from elasticdl_tpu.data.pipeline import MASK_KEY
    from elasticdl_tpu.ps.local_client import LocalPSClient
    from elasticdl_tpu.train.optimizers import create_optimizer
    from elasticdl_tpu.train.sparse import (
        SparseEmbeddingSpec,
        SparseTrainer,
        embedding_lookup,
    )

    class _Model(nn.Module):
        @nn.compact
        def __call__(self, features, training: bool = False):
            return nn.Dense(1)(
                embedding_lookup(features, "e", combiner="sum")
            )[:, 0]

    trainer = SparseTrainer(
        _Model(),
        lambda labels, logits: (logits - labels) ** 2,
        create_optimizer("SGD", learning_rate=0.1),
        [SparseEmbeddingSpec("e", 4, feature_key="ids")],
        LocalPSClient(opt_type="sgd", lr=0.1),
        compute_dtype="float32",
    )
    batch = {
        "features": {"ids": np.arange(8).reshape(8, 1)},
        "labels": np.ones(8, np.float32),
        MASK_KEY: np.ones(8, dtype=bool),
    }
    trainer.train_step(None, batch)
    summary = trainer.timing.summary()
    assert {"sparse_pull", "batch_process", "sparse_push"} <= set(summary)
