"""The records around the loop (ISSUE 33): ``worker_startup`` says what
jax compiled or loaded in each phase and when the process started,
SIGTERM's arrival is journaled once the task is done, and the master
has a start-up and a teardown record of its own. Counts and structure,
never a speed."""

import json
import threading
import time

import jax.numpy as jnp
import pytest

from elasticdl_tpu.common import timing_utils
from elasticdl_tpu.common.timing_utils import Timing
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import events
from tests.test_compile_stages import cache_dir  # noqa: F401 (a fixture)

COUNTS = ("requests", "hits", "misses")
SECONDS = ("trace_s", "lower_s", "backend_s")


@pytest.fixture
def journal(tmp_path, monkeypatch):
    events_dir = tmp_path / "events"
    monkeypatch.setenv("EDL_EVENTS_DIR", str(events_dir))
    events.configure("worker-0")
    device_obs.reset_for_tests()

    def read(kind, role="*"):
        records = []
        for path in sorted(events_dir.glob(role + "-*.events.ndjson")):
            records += [json.loads(x) for x in path.read_text().splitlines()]
        return [r for r in records if r["event"] == kind]

    yield read
    events._reset_for_tests()
    device_obs.set_phase_source(None)
    device_obs.reset_for_tests()


def _first_iteration(ledger, shape):
    """What a worker does up to its first step's return: eager
    programs while the state is made, then the wrapped step."""
    step_fn = device_obs.instrumented_jit(
        lambda x: jnp.tanh(x @ x.T).sum(), name="train_step")
    with ledger.phase("state_init"):
        x = jnp.linspace(0.0, 1.0, shape[0] * shape[1]).reshape(shape)
    with ledger.step(1) as step:
        with ledger.phase("input_wait"):
            pass
        step.has_batch(task_id=1)
        with ledger.phase("dispatch"):
            out = step_fn(x)
        with ledger.phase("device_wait"):
            out.block_until_ready()
    return step_fn


def test_startup_compiles_sum_to_the_process_totals_at_the_first_step(
        journal):
    device_obs.install_listeners()
    ledger = Timing()
    ledger.begin_startup(time.perf_counter_ns())
    step_fn = _first_iteration(ledger, (13, 7))
    totals = device_obs.compile_totals()
    (startup,) = journal("worker_startup")
    compiles = startup["compiles"]
    # named by start-up's own phases, and only those that compiled
    assert {"state_init", "first_step"} <= set(compiles)
    assert set(compiles) <= set(startup["phases"])
    assert "device_wait" not in compiles and "first_task" not in compiles
    for key in COUNTS:
        assert sum(c[key] for c in compiles.values()) == totals[key]
    for key in SECONDS:
        assert sum(c[key] for c in compiles.values()) == pytest.approx(
            totals[key], abs=1e-3)
    assert all(set(c) == set(COUNTS + SECONDS) for c in compiles.values())
    # the step's own compile is the first step's, the cost fetch's
    # relower with it
    stages = step_fn.stages
    assert compiles["first_step"]["requests"] >= 1
    assert compiles["first_step"]["backend_s"] >= stages["backend_s"] - 1e-3
    assert compiles["first_step"]["trace_s"] >= stages["trace_s"] - 1e-3
    assert compiles["state_init"]["requests"] >= 1
    # once start-up is over nothing is read at a phase's close
    assert device_obs._phase_source is None
    closed = json.dumps(ledger._compiles)
    with ledger.step(2) as step:
        step.has_batch(task_id=1)
        with ledger.phase("dispatch"):
            jnp.ones((3, 11)) * 2  # a program nobody charges to a phase
    assert json.dumps(ledger._compiles) == closed
    assert len(journal("worker_startup")) == 1


def test_a_miss_names_the_startup_phase_it_fell_in(journal, cache_dir):  # noqa: F811
    device_obs.install_listeners()
    ledger = Timing()
    ledger.begin_startup(time.perf_counter_ns())
    _first_iteration(ledger, (11, 5))
    jnp.ones((5, 3)) + 1  # after start-up: no phase to name
    misses = journal("xla_cache_miss")
    by_module = {m["module"]: m["phase"] for m in misses}
    assert by_module["jit(<lambda>)"] == "first_step"
    phases = [m["phase"] for m in misses]
    assert "state_init" in phases and phases[-1] is None
    (startup,) = journal("worker_startup")
    during = [m for m in misses if m["phase"] is not None]
    assert len(during) == sum(
        c["misses"] for c in startup["compiles"].values())


def test_the_startup_record_starts_with_the_process(journal):
    main_start = time.perf_counter_ns()
    ledger = timing_utils.start_ledger(main_start, main_start)
    with ledger.phase("master_connect"):
        pass
    ledger.begin_teardown()
    ledger.end_record("exit", ledger.start())
    ledger.end_teardown()
    (startup,) = journal("worker_startup")
    (teardown,) = journal("worker_teardown")
    born = time.time() - timing_utils.process_age_ns() / 1e9
    # back-dated to the operating system's word (10 ms ticks)
    assert startup["start_ts"] == pytest.approx(born, abs=0.1)
    assert startup["phases"]["imports"] > 0
    for record in (startup, teardown):
        assert sum(record["phases"].values()) == record["wall_ns"]
        end = record["start_ts"] + record["wall_ns"] / 1e9
        # the record ends where it was journaled, on the journal's clock
        assert record["start_ts"] <= end <= record["ts"] + 0.01
        assert record["ts"] - end < 0.5
    assert startup["start_ts"] + startup["wall_ns"] / 1e9 <= (
        teardown["start_ts"] + 0.01)
    assert startup["compiles"] == {}


@pytest.mark.parametrize("installed", [False, True])
def test_compiles_are_in_a_record_only_where_they_were_observed(
        journal, monkeypatch, installed):
    """A process without the listeners (the master, ``EDL_DEVICE_OBS=0``)
    leaves ``compiles`` and ``listener_calls`` out: an empty
    ``compiles`` says nothing compiled, an absent one that nobody
    looked, and the benchmark's readers then report nothing."""
    if installed:
        device_obs.install_listeners()
    else:
        monkeypatch.setattr(device_obs, "_listeners_installed", False)
    ledger = timing_utils.Timing()
    ledger.begin_startup(time.perf_counter_ns(), "master_startup")
    ledger.end_record("configure", ledger.start())
    ledger.end_startup()
    (record,) = journal("master_startup")
    assert ("compiles" in record) is installed
    assert ("listener_calls" in record) is installed
    assert sum(record["phases"].values()) == record["wall_ns"]


@pytest.mark.parametrize("kind", ["master_startup", "master_teardown"])
def test_the_masters_records(tmp_path, journal, kind):
    from elasticdl_tpu.common.grpc_utils import find_free_port
    from elasticdl_tpu.master.master import Master
    from tests.test_utils import create_mnist_recordio

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    create_mnist_recordio(str(data_dir / "f0.rec"), num_records=64, seed=0)
    main_start = time.perf_counter_ns()
    ledger = timing_utils.start_ledger(
        main_start, main_start, event="master_startup")
    ledger.end_record("configure", main_start)
    master = Master(
        "elasticdl_tpu.models.mnist", training_data=str(data_dir),
        records_per_task=32, port=find_free_port(), ledger=ledger,
    )
    try:
        master.prepare()
    finally:
        master.stop()
    ledger.end_record("exit", ledger.start())
    ledger.end_teardown()
    (record,) = journal(kind, role="master")
    assert sum(record["phases"].values()) == record["wall_ns"]
    assert record["phases"]["other"] >= 0
    if kind == "master_startup":
        assert {"imports", "configure", "zoo", "tasks", "serve",
                "other"} == set(record["phases"])
        # journaled once role_start is: the port was listening
        (role_start,) = journal("role_start", role="master")
        assert role_start["seq"] < record["seq"]
        assert record["start_ts"] + record["wall_ns"] / 1e9 <= (
            record["ts"] + 0.01)
    else:
        assert {"stop_observability", "stop_services", "stop_server",
                "exit", "other"} == set(record["phases"])
        (role_stop,) = journal("role_stop", role="master")
        assert role_stop["seq"] < record["seq"]
        assert record["start_ts"] <= role_stop["ts"]


def test_sigterm_to_exit_is_one_account(tmp_path, journal):
    """What the SIGTERM hook does to a worker mid-task, by hand: the
    handler only notes the time, the loop journals ``drain_requested``
    once the task is finished, ``worker_teardown`` follows."""
    from elasticdl_tpu.data.readers import RecordIODataReader
    from elasticdl_tpu.worker.drain import SigtermDrain
    from elasticdl_tpu.worker.master_client import MasterClient
    from elasticdl_tpu.worker.worker import Worker
    from tests.test_utils import create_mnist_recordio
    from tests.test_worker_distributed import start_master

    train_dir = tmp_path / "train"
    valid_dir = tmp_path / "valid"
    train_dir.mkdir()
    valid_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=512, seed=0)
    create_mnist_recordio(str(valid_dir / "f0.rec"), num_records=32, seed=1)
    server, dispatcher, _evals, port = start_master(
        str(train_dir), str(valid_dir), str(tmp_path / "export"),
        eval_steps=0,
    )
    main_start = time.perf_counter_ns()
    ledger = timing_utils.start_ledger(main_start, main_start)
    hook = SigtermDrain()
    try:
        worker = Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "elasticdl_tpu.models.mnist",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32, wait_sleep_secs=0.1, ledger=ledger,
        )
        hook.bind(worker)
        runner = threading.Thread(target=worker.run, daemon=True)
        runner.start()
        deadline = time.time() + 120
        while time.time() < deadline and worker._version < 3:
            time.sleep(0.01)
        sent = time.time()
        hook._on_term(15, None)
        runner.join(timeout=120)
        assert not runner.is_alive() and worker._drain_done
    finally:
        server.stop(None)
    ledger.end_record("exit", ledger.start())
    ledger.end_teardown()
    (requested,) = journal("drain_requested")
    (teardown,) = journal("worker_teardown")
    (startup,) = journal("worker_startup")
    assert requested["seq"] < teardown["seq"]
    assert requested["reason"] == "sigterm"
    assert requested["signal_ts"] == pytest.approx(sent, abs=0.5)
    # the task in flight was finished first: whole steps, and a record
    # that starts where the request's event was written
    assert 3 <= requested["step"] <= requested["finished_step"]
    assert requested["finished_step"] % 2 == 0  # 64 records / 32 a step
    assert requested["signal_ts"] <= requested["ts"] <= (
        teardown["start_ts"] + 0.01)
    assert {"drain", "teardown", "exit", "other"} <= set(teardown["phases"])
    assert sum(teardown["phases"].values()) == teardown["wall_ns"]
    # start-up closed at the first step, long before
    assert startup["seq"] < requested["seq"]
    assert "first_step" in startup["compiles"]
    assert not dispatcher.finished()
