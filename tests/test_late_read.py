"""The loop reads a step late (ISSUE 57): step N + 1 is dispatched
before anything of step N is fetched; every step is read exactly once,
in order, by one transfer; a step's line carries its own loss and
facts; whatever reads or persists the state, or ends the stream,
finishes the step in flight first; the sentinels keep their word.

A real ``Worker`` drives a trainer whose device values record when
they are fetched, over batches handed to its loop directly."""

import json
import logging

import numpy as np
import pytest

from elasticdl_tpu.common import timing_utils
from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.data.readers import RecordIODataReader
from elasticdl_tpu.observability import events
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.train.callbacks import Callback
from elasticdl_tpu.train.health import HealthSentinelError, HealthTracker
from elasticdl_tpu.worker.trainer import JaxTrainer, PendingStep, Trainer
from elasticdl_tpu.worker.worker import MeshEpochChanged, Worker

BATCH = 8


class Leaf:
    """A device value of step ``step``: fetching it is written down."""

    def __init__(self, log, step, kind, value):
        self.log, self.step, self.kind, self.value = log, step, kind, value

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.step, self.kind))
        return np.asarray(self.value, dtype)

    def __float__(self):
        # a read outside the one transfer would show as this
        self.log.append(("float", self.step, self.kind))
        return float(self.value)


class Recording(Trainer):
    """The state is the number of steps run; step N's loss is N.5."""

    def __init__(self, log, health=None, nonfinite_at=()):
        self.log = log
        self.health = health
        self._nonfinite_at = set(nonfinite_at)

    def train_step(self, state, batch):
        step = (state or 0) + 1
        log = self.log
        log.append(("dispatch", step))
        bad = step in self._nonfinite_at
        self.facts = {"noise": {"mean_t": Leaf(log, step, "fact", step)}}
        if self.health is not None:
            self.health_scalars = (
                Leaf(log, step, "grad_norm", 1.0),
                Leaf(log, step, "nonfinite", bad),
            )
        return step, Leaf(
            log, step, "loss", float("nan") if bad else step + 0.5)


class _FakeMasterClient:
    worker_id = 0
    telemetry_provider = None

    def __init__(self, log):
        self._log = log

    def get_comm_info(self):
        return pb.CommInfo(rank=0, world_size=1, mesh_epoch=0)

    def report_version(self, version):
        self._log.append(("version", version))


class Noted(Callback):
    def __init__(self, log, stop_at=None):
        super().__init__()
        self._log, self._stop_at = log, stop_at

    def on_batch_end(self, step, loss):
        self._log.append(("callback", step, loss.step))
        if step == self._stop_at:
            self.worker.stop_training = True


class SavedStates:
    def __init__(self, log):
        self._log = log

    def save(self, version, state):
        self._log.append(("save", version, state))

    def close(self):
        pass


def batches_of(count):
    return [{
        "features": np.zeros((BATCH, 1), np.float32),
        "labels": np.full(BATCH, i, np.float32),
        MASK_KEY: np.ones(BATCH, np.float32),
    } for i in range(1, count + 1)]


@pytest.fixture
def journal(tmp_path, monkeypatch):
    monkeypatch.setenv("EDL_EVENTS_DIR", str(tmp_path))
    events.configure("worker-0")

    def read(kind):
        records = []
        for path in sorted(tmp_path.glob("worker-0-*.events.ndjson")):
            records += [json.loads(line)
                        for line in path.read_text().splitlines()]
        return [r for r in records if r["event"] == kind]

    yield read
    events._reset_for_tests()


@pytest.fixture
def lines():
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    logger = logging.getLogger("elasticdl_tpu.worker.worker")
    logger.addHandler(handler)
    yield seen
    logger.removeHandler(handler)


def make_worker(log, health=None, nonfinite_at=(), log_every=2,
                stop_at=None, checkpoint_steps=0):
    worker = Worker(
        _FakeMasterClient(log), "elasticdl_tpu.models.mnist",
        RecordIODataReader(data_dir="/nonexistent"),
        minibatch_size=BATCH, log_loss_steps=log_every,
        report_version_steps=4,
    )
    worker.trainer = Recording(log, health, nonfinite_at)
    worker._callbacks = [Noted(log, stop_at)]
    worker._callbacks[0].set_worker(worker)
    if checkpoint_steps:
        worker._checkpoint_mgr = SavedStates(log)
        worker._checkpoint_steps = checkpoint_steps
    real_report = worker.tds.report_record_done
    worker.tds.report_record_done = lambda count: (
        log.append(("report", count)), real_report(count))
    if health is not None:
        real_observe = health.observe
        health.observe = lambda loss, norm, bad: (
            log.append(("observe", loss, bool(bad))),
            real_observe(loss, norm, bad))[1]
    return worker


def run_loop(worker, batches):
    previous = timing_utils.bind(worker._timing)
    try:
        worker._train_batches_sequential(batches)
    finally:
        worker._timing.report("test")
        timing_utils.bind(previous)


def at(log, *entry):
    (index,) = [i for i, e in enumerate(log) if e == entry]
    return index


def steps_of(log, what):
    return [e[1] for e in log if e[0] == what]


# ---------------------------------------------------------------- order

def test_the_next_step_is_dispatched_before_the_last_one_is_read(
        journal):
    log = []
    tracker = HealthTracker(action="alert")
    worker = make_worker(log, health=tracker)
    run_loop(worker, batches_of(9))
    assert steps_of(log, "dispatch") == list(range(1, 10))
    for step in range(1, 9):
        assert at(log, "dispatch", step + 1) < at(log, "fetch", step, "loss")
        # and the read is done before the step after that goes out
        if step < 8:
            assert at(log, "fetch", step, "loss") < at(
                log, "dispatch", step + 2)
    # the last step has no successor: the end of the batches reads it
    assert at(log, "dispatch", 9) < at(log, "fetch", 9, "loss")
    assert worker._in_flight is None and worker._version == 9
    # nothing was read outside the one transfer
    assert not [e for e in log if e[0] == "float"]
    # a callback is handed the newest dispatched step's number and
    # loss, in the iteration that dispatched it
    assert [e[1:] for e in log if e[0] == "callback"] == [
        (n, n) for n in range(1, 10)]
    assert at(log, "dispatch", 5) < at(log, "callback", 5, 5) < at(
        log, "dispatch", 6)
    assert [e[1] for e in log if e[0] == "version"] == [4, 8]


def test_every_step_is_observed_exactly_once_and_in_order(journal):
    log = []
    tracker = HealthTracker(action="alert")
    worker = make_worker(log, health=tracker)
    run_loop(worker, batches_of(9))
    assert [e[1:] for e in log if e[0] == "observe"] == [
        (n + 0.5, False) for n in range(1, 10)]
    assert tracker.samples == 9
    # one transfer a step: loss and the two scalars together, the
    # facts with them on the steps that log (every second) alone
    reports = [i for i, e in enumerate(log) if e == ("report", BATCH)]
    assert len(reports) == 9
    for step in range(1, 10):
        fetched = [e[2] for e in log if e[:2] == ("fetch", step)]
        assert fetched == ["loss", "grad_norm", "nonfinite"] + (
            ["fact"] if step % 2 == 0 else [])
        # observed before its records are reported
        assert at(log, "observe", step + 0.5, False) < reports[step - 1]


def test_a_line_carries_its_own_step_s_loss_and_facts(journal, lines):
    log = []
    worker = make_worker(log, health=HealthTracker(action="alert"))
    run_loop(worker, batches_of(9))
    assert [l for l in lines if l.startswith("step ")] == [
        "step %d loss %.6f" % (n, n + 0.5) for n in (2, 4, 6, 8)]
    assert [(e["step"], e["mean_t"]) for e in journal("bd_noise")] == [
        (n, float(n)) for n in (2, 4, 6, 8)]


def test_without_health_scalars_only_a_logged_step_is_read(journal):
    """``SpmdTrainer``'s case: nothing is fetched between logged steps,
    and a logged step's loss and facts come a step late too."""
    log = []
    worker = make_worker(log, log_every=4)
    run_loop(worker, batches_of(9))
    assert [e[1:] for e in log if e[0] == "fetch"] == [
        (4, "loss"), (4, "fact"), (8, "loss"), (8, "fact")]
    assert at(log, "dispatch", 5) < at(log, "fetch", 4, "loss") < at(
        log, "dispatch", 6)
    assert len([e for e in log if e[0] == "report"]) == 9


# --------------------------------------------------------------- drains

def drains(journal):
    total = {}
    for event in journal("loop_phases"):
        for reason, count in event["drains"].items():
            total[reason] = total.get(reason, 0) + count
    return total


def test_the_end_of_the_batches_finishes_the_step_in_flight(journal):
    log = []
    worker = make_worker(log, health=HealthTracker(action="alert"))
    run_loop(worker, batches_of(5))
    assert steps_of(log, "observe") == [n + 0.5 for n in range(1, 6)]
    assert drains(journal) == {"end": 1}
    intervals = journal("loop_phases")
    assert sum(e["steps"] for e in intervals) == 5
    # every step but the stream's first was read with its successor out
    assert sum(e["ahead_steps"] for e in intervals) == 4


def test_a_parked_evaluation_is_the_drain_s_reason(journal):
    log = []
    worker = make_worker(log, health=HealthTracker(action="alert"))
    worker.tds.out_of_band_tasks.append(pb.Task(type=pb.EVALUATION))
    run_loop(worker, batches_of(3))
    assert steps_of(log, "observe") == [1.5, 2.5, 3.5]
    assert drains(journal) == {"eval": 1}


def test_a_checkpoint_holds_a_state_whose_step_was_read(journal):
    log = []
    worker = make_worker(
        log, health=HealthTracker(action="alert"), checkpoint_steps=3)
    run_loop(worker, batches_of(7))
    assert [e[1:] for e in log if e[0] == "save"] == [(3, 3), (6, 6)]
    for step in (3, 6):
        # dispatched, read at once (no step behind it), then saved,
        # and only then does the next step go out
        assert (at(log, "dispatch", step)
                < at(log, "observe", step + 0.5, False)
                < at(log, "save", step, step)
                < at(log, "dispatch", step + 1))
    assert steps_of(log, "observe") == [n + 0.5 for n in range(1, 8)]
    assert drains(journal) == {"checkpoint": 2, "end": 1}
    # the steps right after a save had nothing in flight to read late
    assert sum(e["ahead_steps"] for e in journal("loop_phases")) == 4


def test_a_stream_checkpoint_finishes_the_step_in_flight(journal):
    log = []
    worker = make_worker(
        log, health=HealthTracker(action="alert"), checkpoint_steps=1000)
    worker._stream_ckpt_every = 100
    worker._stream_ckpt_mark = 0

    class Crossing(Noted):
        def on_batch_end(self, step, loss):
            if step == 2:
                # the heartbeat's cached watermark crosses a boundary:
                # step 3's bookkeeping saves
                self.worker._seen_stream_watermark = 150

    worker._callbacks = [Crossing(log)]
    worker._callbacks[0].set_worker(worker)
    run_loop(worker, batches_of(4))
    assert (at(log, "observe", 3.5, False) < at(log, "save", 3, 3)
            < at(log, "dispatch", 4))
    assert drains(journal) == {"checkpoint": 1, "end": 1}


def test_a_moved_mesh_epoch_finishes_the_step_in_flight(journal):
    log = []
    worker = make_worker(log, health=HealthTracker(action="alert"))

    class Moves:
        def epoch_moved(self, seen):
            return worker._version == 3

    worker._multihost = Moves()
    with pytest.raises(MeshEpochChanged):
        run_loop(worker, batches_of(6))
    assert steps_of(log, "dispatch") == [1, 2, 3]
    assert steps_of(log, "observe") == [1.5, 2.5, 3.5]
    assert len([e for e in log if e[0] == "report"]) == 3
    assert worker._in_flight is None
    assert drains(journal) == {"mesh": 1}


def test_stop_training_finishes_the_step_in_flight(journal):
    log = []
    worker = make_worker(
        log, health=HealthTracker(action="alert"), stop_at=4)
    run_loop(worker, batches_of(9))
    assert steps_of(log, "dispatch") == [1, 2, 3, 4]
    assert steps_of(log, "observe") == [1.5, 2.5, 3.5, 4.5]
    assert worker._version == 4 and worker._in_flight is None
    assert drains(journal) == {"stop": 1}


def test_a_batch_that_is_not_there_yet_finishes_the_step_in_flight(
        journal):
    """The master may be waiting for the unread step's task before it
    hands out another: the loop never waits for a batch with a step
    unread."""
    log = []
    worker = make_worker(log, health=HealthTracker(action="alert"))

    class Late:
        def __init__(self, batches):
            self._batches = iter(batches)
            self.taken = 0

        def __iter__(self):
            return self

        def __next__(self):
            batch = next(self._batches)
            self.taken += 1
            return batch

        def ready(self):
            # the fourth batch is late
            return self.taken != 3

    run_loop(worker, Late(batches_of(5)))
    assert at(log, "fetch", 3, "loss") < at(log, "dispatch", 4)
    assert at(log, "dispatch", 5) < at(log, "fetch", 4, "loss")
    assert steps_of(log, "observe") == [n + 0.5 for n in range(1, 6)]
    assert drains(journal) == {"input": 1, "end": 1}


def test_an_exception_leaving_the_loop_finishes_the_step_in_flight(
        journal):
    log = []
    worker = make_worker(log, health=HealthTracker(action="alert"))

    def broken():
        yield from batches_of(3)
        raise OSError("the shard is gone")

    with pytest.raises(OSError):
        run_loop(worker, broken())
    assert steps_of(log, "observe") == [1.5, 2.5, 3.5]
    assert len([e for e in log if e[0] == "report"]) == 3
    assert worker._in_flight is None
    assert drains(journal) == {"error": 1}


# ------------------------------------------------------------ sentinels

def test_halt_raises_within_one_step_and_before_a_checkpoint(journal):
    log = []
    tracker = HealthTracker(action="halt")
    worker = make_worker(
        log, health=tracker, nonfinite_at=[4], checkpoint_steps=5)
    with pytest.raises(HealthSentinelError):
        run_loop(worker, batches_of(9))
    # step 5 went out before step 4 was read, and nothing after it
    assert steps_of(log, "dispatch") == [1, 2, 3, 4, 5]
    assert [e[2] for e in log if e[0] == "observe"] == [
        False, False, False, True]
    # step 5 started from the state that tripped: never read, never
    # saved, its records and step 4's never reported done
    assert not [e for e in log if e[0] == "save"]
    assert len([e for e in log if e[0] == "report"]) == 3
    assert worker._in_flight is None
    assert tracker.nonfinite_total == 1


def test_halt_on_a_checkpoint_s_step_raises_before_the_save(journal):
    log = []
    worker = make_worker(
        log, health=HealthTracker(action="halt"), nonfinite_at=[3],
        checkpoint_steps=3)
    with pytest.raises(HealthSentinelError):
        run_loop(worker, batches_of(9))
    assert steps_of(log, "dispatch") == [1, 2, 3]
    assert not [e for e in log if e[0] == "save"]


def test_halt_on_the_last_step_raises_at_the_end_of_the_batches(journal):
    log = []
    worker = make_worker(
        log, health=HealthTracker(action="halt"), nonfinite_at=[3])
    with pytest.raises(HealthSentinelError):
        run_loop(worker, batches_of(3))
    assert worker._in_flight is None


@pytest.mark.parametrize("action,skipped", [("skip", 2), ("alert", 0)])
def test_skip_counts_what_it_sees_and_alert_only_warns(
        journal, action, skipped):
    log = []
    tracker = HealthTracker(action=action)
    worker = make_worker(log, health=tracker, nonfinite_at=[3, 6])
    run_loop(worker, batches_of(8))
    assert [e[2] for e in log if e[0] == "observe"] == [
        n in (3, 6) for n in range(1, 9)]
    assert tracker.nonfinite_total == 2
    assert tracker.skipped_batches == skipped
    assert len([e for e in log if e[0] == "report"]) == 8


def test_with_health_off_the_step_has_no_extra_outputs(monkeypatch):
    """``EDL_HEALTH=0``: the jitted step returns the state and the
    loss, the trainer holds no scalars, and a step's record has none."""
    import flax.linen as nn

    from elasticdl_tpu.train.optimizers import create_optimizer

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, features, training: bool = False):
            return nn.Dense(1)(features)[:, 0]

    monkeypatch.setenv("EDL_HEALTH", "0")
    trainer = JaxTrainer(
        Tiny(), lambda labels, out: (out - labels) ** 2,
        create_optimizer("SGD", learning_rate=0.1),
        compute_dtype="float32",
    )
    assert trainer.health is None
    batch = batches_of(1)[0]
    state, loss = trainer.train_step(None, batch)
    assert len(trainer._train_step(state, batch)) == 2
    pending = trainer.pending_step(loss)
    assert pending == PendingStep(loss, None, None)
    value, facts = trainer.read_step(pending, with_facts=True)
    assert value == pytest.approx(float(loss)) and facts == {}


def test_the_jitted_trainer_s_scalars_come_in_one_transfer(monkeypatch):
    """A real ``JaxTrainer``: ``train_step`` fetches nothing, and
    ``read_step`` is one ``jax.device_get`` of one tree."""
    import flax.linen as nn
    import jax

    from elasticdl_tpu.train.optimizers import create_optimizer

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, features, training: bool = False):
            return nn.Dense(1)(features)[:, 0]

    tracker = HealthTracker(action="alert")
    trainer = JaxTrainer(
        Tiny(), lambda labels, out: (out - labels) ** 2,
        create_optimizer("SGD", learning_rate=0.1),
        compute_dtype="float32", health=tracker,
    )
    batch = batches_of(1)[0]
    state, _ = trainer.train_step(None, batch)  # compiles
    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda tree: gets.append(tree) or real_get(tree))
    state, loss = trainer.train_step(state, batch)
    assert gets == [] and tracker.samples == 0
    pending = trainer.pending_step(loss)
    assert all(isinstance(x, jax.Array) for x in pending.health)
    value, _ = trainer.read_step(pending)
    assert len(gets) == 1 and tracker.samples == 1
    assert tracker.loss_last == pytest.approx(value)
