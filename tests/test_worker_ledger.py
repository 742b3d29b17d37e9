"""The worker loop under the phase ledger (ISSUE 23): a real Worker
against a real in-process master journals every phase of every step,
observing it changes nothing in the loop, and a profiler session sees
the program's phases on the clock of the XLA operations."""

import glob
import json
import time

import numpy as np
import pytest

from elasticdl_tpu.common import timing_utils
from elasticdl_tpu.data.readers import RecordIODataReader
from elasticdl_tpu.observability import events
from elasticdl_tpu.observability import metrics as obs_metrics
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.worker import Worker
from tests.test_utils import create_mnist_recordio
from tests.test_worker_distributed import start_master

LOOP_PHASES = {
    "input_wait", "dispatch", "device_wait", "health", "checkpoint",
    "report", "mesh_check", "log", "callbacks", "other",
}


@pytest.fixture
def worker_journal(tmp_path, monkeypatch):
    events_dir = tmp_path / "events"
    monkeypatch.setenv("EDL_EVENTS_DIR", str(events_dir))
    events.configure("worker-0")

    def read(kind):
        records = []
        for path in sorted(events_dir.glob("worker-0-*.events.ndjson")):
            records += [
                json.loads(line)
                for line in path.read_text().splitlines()
            ]
        return [r for r in records if r["event"] == kind]

    yield read
    events._reset_for_tests()


def test_loop_journals_its_phases_and_metrics_change_nothing(
    tmp_path, monkeypatch, worker_journal
):
    """With metrics collected (what ``--metrics_port`` switches on) the
    loop still never calls ``block_until_ready``; every step's wall time
    is split over the loop's phases; the step series is served."""
    import jax

    monkeypatch.setenv("EDL_METRICS", "1")
    # the detector's floor in seconds, not its 20 ms: beside five other
    # workers the machine holds a 9 ms step up for 30 ms (33 involuntary
    # context switches in the one that showed), never for a second, and
    # a step that compiled again or waited on the master would be
    monkeypatch.setattr(timing_utils, "SLOW_MIN_NS", 1_000_000_000)
    obs_metrics.reset_default_registry()
    blocked = []
    real_block = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda tree: blocked.append(1) or real_block(tree),
    )
    train_dir = tmp_path / "train"
    valid_dir = tmp_path / "valid"
    train_dir.mkdir()
    valid_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=256, seed=0)
    create_mnist_recordio(str(valid_dir / "f0.rec"), num_records=32, seed=1)
    server, dispatcher, _evals, port = start_master(
        str(train_dir), str(valid_dir), str(tmp_path / "export"),
        eval_steps=0,
    )
    try:
        worker = Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "elasticdl_tpu.models.mnist",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            wait_sleep_secs=0.1,
            log_loss_steps=4,
        )
        worker.run()
        assert dispatcher.finished() and not dispatcher.job_failed()
        text = obs_metrics.default_registry().render()
    finally:
        server.stop(None)
        obs_metrics.reset_default_registry()
    assert blocked == []
    # 2 epochs x 256 records / 32 = 16 steps, journaled every 4
    intervals = worker_journal("loop_phases")
    assert sum(e["steps"] for e in intervals) == 16
    assert [e["last_step"] for e in intervals][:4] == [4, 8, 12, 16]
    for event in intervals:
        assert sum(event["phases"].values()) == event["wall_ns"]
        assert event["phases"]["other"] >= 0
    seen = set().union(*(e["phases"] for e in intervals))
    # no worker.main opened a start-up record here, so the first step
    # is the loop's, state init and compile with it; it carried a
    # compile, so it is not judged slow, and none of the sixteen is
    assert seen == LOOP_PHASES | {"state_init"}
    assert set(intervals[1]["phases"]) == LOOP_PHASES
    assert worker_journal("slow_step") == []
    assert 'edl_phase_seconds_count{phase="batch_process"} 16' in text
    assert 'edl_phase_seconds_count{phase="device_wait"}' in text
    assert "edl_step_time_seconds" in text
    # the worker put the thread's ledger back when its loop ended
    assert timing_utils.current() is not worker._timing


def test_profiler_session_holds_the_phases_on_the_ops_clock(tmp_path):
    """Five steps of a tiny model inside ``jax.profiler``: ``edl/step``
    with its ``step_num`` and the phases nested in it sit on one thread
    line, every XLA operation of step N starts after ``dispatch`` N
    started, and all of them are over when iteration N + 1's
    ``device_wait``, the late read of step N, returns. Nothing is set in
    the program to get this."""
    import flax.linen as nn
    import jax
    from jax.profiler import ProfileData

    from elasticdl_tpu.data.pipeline import MASK_KEY
    from elasticdl_tpu.train.optimizers import create_optimizer
    from elasticdl_tpu.worker.trainer import JaxTrainer

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, features, training: bool = False):
            return nn.Dense(1)(nn.tanh(nn.Dense(64)(features)))[:, 0]

    trainer = JaxTrainer(
        Tiny(), lambda labels, out: (out - labels) ** 2,
        create_optimizer("SGD", learning_rate=0.1),
        compute_dtype="float32",
    )
    batch = {
        "features": np.ones((16, 32), np.float32),
        "labels": np.ones(16, np.float32),
        MASK_KEY: np.ones(16, dtype=bool),
    }
    ledger = timing_utils.Timing()
    previous = timing_utils.bind(ledger)
    try:
        state, loss = trainer.train_step(None, batch)  # compiles
        jax.block_until_ready(loss)
        pending = trainer.pending_step(loss)
        jax.profiler.start_trace(str(tmp_path))
        for number in range(1, 6):
            with ledger.step(number) as step:
                with ledger.phase("input_wait"):
                    time.sleep(0.001)
                step.has_batch()
                state, loss = trainer.train_step(state, batch)
                # the loop's order: the step before is read with this
                # one already dispatched
                late, pending = pending, trainer.pending_step(loss)
                trainer.read_step(late)
        jax.block_until_ready(loss)
        jax.profiler.stop_trace()
    finally:
        timing_utils.bind(previous)
    (path,) = glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [
        (line.name, [
            (e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
            for e in line.events
        ])
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
    ]
    loop_lines = [
        evs for _, evs in lines if any(e[0] == "edl/step" for e in evs)]
    assert len(loop_lines) == 1
    loop = loop_lines[0]
    steps = sorted(
        (e for e in loop if e[0] == "edl/step"), key=lambda e: e[1])
    assert [e[3]["step_num"] for e in steps] == [1, 2, 3, 4, 5]
    phases = [e for e in loop if e[0].startswith("edl/") and e not in steps]
    assert {e[0] for e in phases} == {
        "edl/input_wait", "edl/dispatch", "edl/device_wait", "edl/health"}
    for phase in phases:
        # every phase lies inside exactly one step of the same line
        assert sum(
            1 for s in steps if s[1] <= phase[1] and phase[2] <= s[2]
        ) == 1, phase
    # the client's threads also mark where their pool opens and closes a
    # region (``ThreadpoolListener::``, no duration): a thread closes
    # step N's whenever the machine next runs it, inside step N + 1 on
    # a loaded one, and that is no operation of either step
    ops = [
        e for name, evs in lines if name.startswith("tf_XLAPjRtCpuClient")
        for e in evs
        if not e[0].startswith(("end: ", "ThreadpoolListener::"))
    ]
    # five runs of one program, one after the other (each takes the
    # state of the one before): a fifth of the operations each
    ops.sort(key=lambda op: op[1])
    per_step, rest = divmod(len(ops), len(steps))
    assert per_step and not rest, [op[0] for op in ops]

    def phase_of(step, name):
        (found,) = [
            p for p in phases
            if p[0] == name and step[1] <= p[1] <= step[2]]
        return found

    for i, step in enumerate(steps):
        mine = ops[i * per_step:(i + 1) * per_step]
        assert all(
            op[1] >= phase_of(step, "edl/dispatch")[1] for op in mine)
        if i + 1 < len(steps):
            # read a step late: step N's operations are over when the
            # ``device_wait`` of iteration N + 1 returns
            wait = phase_of(steps[i + 1], "edl/device_wait")
            assert all(op[2] <= wait[2] for op in mine)
