"""Fault injection: a real worker process dies holding tasks; the
master's liveness detection recovers them and a surviving worker drains
the job. The reference had no fault-injection tests at all (SURVEY.md
§5 "fault injection: none; CI relies on natural preemption")."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from elasticdl_tpu.common.grpc_utils import build_server, find_free_port
from elasticdl_tpu.data.readers import RecordIODataReader
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.master.task_monitor import TaskMonitor
from elasticdl_tpu.proto.services import add_master_servicer_to_server
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.worker import Worker
from tests.test_utils import create_mnist_recordio

CRASHER = r"""
import os, sys
sys.path.insert(0, %(repo)r)
from elasticdl_tpu.worker.master_client import MasterClient
mc = MasterClient(%(addr)r, worker_id=1)
task = mc.get_task()
assert task.task_id != 0, "no task to hold"
os._exit(1)  # die mid-task, nothing reported
"""


def test_worker_crash_recovers_and_job_completes(tmp_path):
    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=256, seed=0)
    reader = RecordIODataReader(data_dir=str(train_dir))

    dispatcher = TaskDispatcher(
        training_shards=reader.create_shards(),
        records_per_task=64,
        num_epochs=1,
        seed=0,
    )
    servicer = MasterServicer(dispatcher, None)
    monitor = TaskMonitor(
        dispatcher, servicer, None, liveness_timeout_secs=4.0,
        scan_interval_secs=0.2,
    )
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    port = find_free_port()
    server.add_insecure_port("localhost:%d" % port)
    server.start()
    monitor.start()
    try:
        # chaos: a real OS process grabs a task and dies holding it
        script = CRASHER % {
            "repo": os.path.dirname(os.path.dirname(__file__)),
            "addr": "localhost:%d" % port,
        }
        proc = subprocess.run(
            [sys.executable, "-c", script], timeout=60,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 1
        assert dispatcher.doing_tasks(), "crasher held no task"

        # liveness detection must recover the orphaned task
        deadline = time.time() + 15
        while dispatcher.doing_tasks() and time.time() < deadline:
            time.sleep(0.2)
        assert not dispatcher.doing_tasks(), "task never recovered"

        # a surviving worker drains the whole job, crashed task included
        worker = Worker(
            MasterClient("localhost:%d" % port, worker_id=2),
            "tests.models.mnist_with_export",
            reader,
            minibatch_size=32,
            wait_sleep_secs=0.1,
        )
        worker.run()
        assert dispatcher.finished()
        assert not dispatcher.job_failed()
    finally:
        monitor.stop()
        server.stop(0)


VICTIM = r"""
import sys, time
sys.path.insert(0, %(repo)r)
from elasticdl_tpu.observability import events
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.worker.master_client import MasterClient

events.configure("worker-1")
events.install_crash_hooks()
mc = MasterClient(%(addr)r, worker_id=1)
mc.telemetry_provider = lambda: pb.TelemetryBlob(
    role="worker-1", step_time_ewma=0.1, model_version=1)
mc.reset_worker()
events.emit("role_start", worker=1, epoch=mc.incarnation or 0)
task = mc.get_task()
assert task.task_id != 0, "no task to hold"
print("READY", flush=True)
while True:  # heartbeat mid-round until killed
    mc.get_comm_info()
    time.sleep(0.2)
"""


@pytest.mark.slow
@pytest.mark.parametrize("kill_signal",
                         [signal.SIGTERM, signal.SIGKILL])
def test_worker_kill_fires_dead_air_and_leaves_flight_record(
    tmp_path, monkeypatch, kill_signal,
):
    """ISSUE 3 chaos acceptance: kill a real worker process mid-round;
    the master's fleet monitor must raise a dead-air alert within the
    detection window (counter incremented), the victim's flight record
    must be on disk (journal always; ring dump for the SIGTERM/eviction
    path — SIGKILL can't run hooks, write-through covers it), and
    scripts/postmortem.py must thread one timeline spanning the
    victim's record, the master's requeue, and the alert."""
    from elasticdl_tpu.master.fleet import FleetMonitor
    from elasticdl_tpu.observability import events
    from elasticdl_tpu.observability import metrics as obs_metrics
    from tests.test_utils import create_mnist_recordio

    events_dir = tmp_path / "events"
    events_dir.mkdir()
    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=128,
                          seed=0)
    reader = RecordIODataReader(data_dir=str(train_dir))

    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(events_dir))
    monkeypatch.setenv("EDL_METRICS", "1")
    obs_metrics.reset_default_registry()
    events.configure("master")
    dispatcher = TaskDispatcher(
        training_shards=reader.create_shards(), records_per_task=64,
        num_epochs=1, seed=0,
    )
    fleet = FleetMonitor(
        straggler_factor=3.0, dead_air_secs=1.5,
        stuck_round_secs=60.0, version_lag_max=1000,
    )
    servicer = MasterServicer(dispatcher, fleet_monitor=fleet)
    monitor = TaskMonitor(
        dispatcher, servicer, None, liveness_timeout_secs=4.0,
        scan_interval_secs=0.2, fleet_monitor=fleet,
    )
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    port = find_free_port()
    server.add_insecure_port("localhost:%d" % port)
    server.start()
    monitor.start()
    victim = None
    try:
        victim = subprocess.Popen(
            [sys.executable, "-c", VICTIM % {
                "repo": os.path.dirname(os.path.dirname(__file__)),
                "addr": "localhost:%d" % port,
            }],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 events.EVENTS_DIR_ENV: str(events_dir)},
            stdout=subprocess.PIPE, text=True,
        )
        assert victim.stdout.readline().strip() == "READY"
        assert dispatcher.doing_tasks(), "victim held no task"

        # chaos: kill the worker process mid-round
        victim.send_signal(kill_signal)
        victim.wait(timeout=30)
        killed_at = time.time()

        # the dead-air detector must fire within its window (the scan
        # thread evaluates every 0.2 s; window is 1.5 s of silence)
        deadline = killed_at + 10
        fired = None
        while time.time() < deadline:
            fired = [
                a for a in fleet.alerts()
                if a["alert"] == "dead_air" and a["worker_id"] == 1
            ]
            if fired:
                break
            time.sleep(0.1)
        assert fired, "dead-air alert never fired for the victim"
        assert time.time() - killed_at < 10, "detection too slow"
        counter = obs_metrics.default_registry().get(
            "edl_master_alerts_total"
        )
        assert counter.get("dead_air") >= 1

        # the victim's flight record survived it
        journals = [
            name for name in os.listdir(str(events_dir))
            if name.startswith("worker-1") and
            name.endswith(".events.ndjson")
        ]
        assert journals, "victim journal missing"
        with open(str(events_dir / journals[0])) as f:
            victim_events = [json.loads(line) for line in f]
        assert any(e["event"] == "role_start" for e in victim_events)
        dumps = [
            name for name in os.listdir(str(events_dir))
            if name.startswith("worker-1") and
            name.endswith(".dump.json")
        ]
        if kill_signal == signal.SIGTERM:
            # the crash hook dumped the ring on the way down
            assert dumps, "victim ring dump missing after SIGTERM"
            with open(str(events_dir / dumps[0])) as f:
                assert json.load(f)["reason"] == "sigterm"

        # liveness recovery requeues the orphaned task -> journaled
        deadline = time.time() + 15
        while dispatcher.doing_tasks() and time.time() < deadline:
            time.sleep(0.1)
        assert not dispatcher.doing_tasks(), "task never recovered"
    finally:
        monitor.stop()
        server.stop(0)
        if victim is not None and victim.poll() is None:
            victim.kill()
        events.flush()

    # postmortem threads one correlation-keyed timeline across the
    # victim's record, the master's requeue, and the alert
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "scripts"
    ))
    try:
        import postmortem
    finally:
        sys.path.pop(0)
    report = postmortem.postmortem(str(events_dir))
    events._reset_for_tests()
    kinds = {e["event"] for e in report["timeline"]}
    assert {"role_start", "worker_register", "task_dispatch",
            "alert_raised", "task_requeue",
            "worker_presumed_dead"} <= kinds, kinds
    timeline_ts = [e.get("ts", 0) for e in report["timeline"]]
    assert timeline_ts == sorted(timeline_ts)
    worker1 = report["summary"]["workers"]["1"]
    assert worker1["registrations"], "victim registration not threaded"
    assert worker1["requeued_tasks"], "requeue not threaded"
    assert "dead_air" in worker1["alerts"]
    if kill_signal == signal.SIGTERM:
        assert worker1["dump"] == "sigterm"


def test_ps_crash_restart_job_completes(tmp_path):
    """A parameter-server shard dies mid-training and is relaunched on
    the same address with checkpoint restore; the worker's PS client
    retries through the outage (ps_client.py PS_RETRY_BUDGET) and the
    job completes — no task-retry budget burned on the restart window.
    (Reference behavior: same-id PS relaunch behind a stable per-pod
    Service, instance_manager; worker main's channel connect retries.)"""
    import signal
    import socket

    from elasticdl_tpu.master.servicer import MasterServicer
    from tests.test_utils import create_ctr_recordio

    def free_port():
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def wait_port(port, timeout=90):
        deadline = time.time() + timeout
        while time.time() < deadline:
            s = socket.socket()
            try:
                s.connect(("127.0.0.1", port))
                return
            except OSError:
                time.sleep(0.3)
            finally:
                s.close()
        raise TimeoutError(port)

    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_ctr_recordio(str(train_dir / "f0.rec"), num_records=768, seed=0)
    reader = RecordIODataReader(data_dir=str(train_dir))
    dispatcher = TaskDispatcher(
        training_shards=reader.create_shards(),
        records_per_task=128,
        num_epochs=2,
        seed=0,
    )
    server = build_server()
    add_master_servicer_to_server(MasterServicer(dispatcher, None), server)
    master_port = find_free_port()
    server.add_insecure_port("localhost:%d" % master_port)
    server.start()

    ps_port = free_port()
    ckpt_dir = str(tmp_path / "ps_ckpt")

    def spawn_ps(restore):
        cmd = [
            sys.executable, "-m", "elasticdl_tpu.ps.server",
            "--ps_id", "0", "--num_ps_pods", "1",
            "--port", str(ps_port),
            "--opt_type", "adam", "--opt_args", "lr=0.01",
            "--checkpoint_dir", ckpt_dir,
            "--checkpoint_steps", "2",
        ]
        if restore:
            cmd += ["--checkpoint_dir_for_init", ckpt_dir]
        return subprocess.Popen(
            cmd,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )

    ps_proc = spawn_ps(restore=False)
    wait_port(ps_port)
    try:
        worker = Worker(
            MasterClient("localhost:%d" % master_port, worker_id=0),
            "elasticdl_tpu.models.deepfm",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=64,
            wait_sleep_secs=0.1,
            ps_addrs=["localhost:%d" % ps_port],
        )
        runner = threading.Thread(target=worker.run, daemon=True)
        runner.start()

        # let training make progress (PS checkpoints every 2 versions)
        deadline = time.time() + 120
        while time.time() < deadline and not (
            os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir)
        ):
            time.sleep(0.2)
        assert os.listdir(ckpt_dir), "PS never checkpointed"

        # chaos: SIGKILL the PS shard mid-job, relaunch with restore
        ps_proc.send_signal(signal.SIGKILL)
        ps_proc.wait(timeout=30)
        time.sleep(2)  # let the worker hit the outage window
        ps_proc = spawn_ps(restore=True)

        runner.join(timeout=180)
        assert not runner.is_alive(), "worker never finished after PS restart"
        assert dispatcher.finished(), "job did not complete"
        assert not dispatcher.job_failed(), (
            "PS restart window burned the task retry budget"
        )
    finally:
        server.stop(0)
        if ps_proc.poll() is None:
            ps_proc.kill()


def _wait_port(port, timeout=90):
    import socket

    deadline = time.time() + timeout
    while time.time() < deadline:
        s = socket.socket()
        try:
            s.connect(("127.0.0.1", port))
            return
        except OSError:
            time.sleep(0.3)
        finally:
            s.close()
    raise TimeoutError(port)


def test_master_sigkill_mid_epoch_replay_no_shard_lost_or_doubled(
    tmp_path, monkeypatch,
):
    """ISSUE 4 tentpole acceptance: SIGKILL a real master process
    mid-epoch; the relaunched master replays its state journal
    (EDL_STATE_DIR), resumes the dispatcher, and the job completes with
    every task reported done EXACTLY once across both master lifetimes.
    The worker survives the outage on its jittered get_task retry
    budget and re-registers when it sees the master_epoch move."""
    from elasticdl_tpu.master import state_store
    from elasticdl_tpu.observability import events

    state_dir = tmp_path / "state"
    events_dir = tmp_path / "events"
    train_dir = tmp_path / "train"
    for d in (state_dir, events_dir, train_dir):
        d.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=256,
                          seed=0)
    master_port = find_free_port()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        state_store.STATE_DIR_ENV: str(state_dir),
        events.EVENTS_DIR_ENV: str(events_dir),
    }
    env.pop("EDL_FAULT_SPEC", None)

    def spawn_master(tag):
        log = open(str(tmp_path / ("master-%s.log" % tag)), "w")
        return subprocess.Popen(
            [
                sys.executable, "-m", "elasticdl_tpu.master.main",
                "--model_zoo", "elasticdl_tpu.models.mnist",
                "--training_data", str(train_dir),
                "--records_per_task", "32",
                "--num_epochs", "2",
                "--port", str(master_port),
                "--task_timeout_secs", "60",
            ],
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
        )

    journal_path = state_dir / state_store.JOURNAL_NAME

    def journal_ops():
        if not journal_path.is_file():
            return []
        ops = []
        with open(str(journal_path)) as f:
            for line in f:
                try:
                    ops.append(json.loads(line))
                except ValueError:
                    pass  # torn tail (SIGKILL mid-write) is expected
        return ops

    def done_ops():
        return [op for op in journal_ops() if op["op"] == "done"]

    # one patience for the whole test, under tests/conftest.py's LIMIT:
    # whichever wait runs it out fails with its own message, not the
    # limit's
    patience = time.time() + 200

    def left():
        return max(1.0, patience - time.time())

    # what the worker's get_task retries wait for is the relaunched
    # master answering, however long python and jax take to import on a
    # loaded machine: through the outage the budget is the test's own
    # patience, no stopwatch on the relaunch. Once the worker has
    # reported to the new master it is trimmed (below): the in-process
    # worker outlives the finished master by its whole budget before
    # concluding job-over
    from elasticdl_tpu.worker import master_client as mc_module

    monkeypatch.setattr(mc_module, "MASTER_RETRY_BUDGET_SECS", left())

    master = spawn_master("first")
    runner = None
    killed = threading.Event()
    try:
        _wait_port(master_port, timeout=min(90, left()))
        mc = MasterClient("localhost:%d" % master_port, worker_id=0)
        mc.reset_worker()
        worker = Worker(
            mc,
            "elasticdl_tpu.models.mnist",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            wait_sleep_secs=0.1,
        )
        # mid-epoch by construction: 16 tasks over 2 epochs, and the
        # worker's sixth report waits for the kill. (It used to be "by
        # construction" only on a loaded machine: on a quiet one the
        # worker reported all 16 in the half second before the journal
        # showed three, and the relaunched master found the job
        # finished and left before the test looked for its port.)
        reports = iter(range(1, 17))
        report = mc.report_task_result

        def held_at_the_sixth(*args, **kwargs):
            if next(reports, 17) > 5:
                killed.wait(timeout=left())
            return report(*args, **kwargs)

        monkeypatch.setattr(mc, "report_task_result", held_at_the_sixth)
        runner = threading.Thread(target=worker.run, daemon=True)
        runner.start()

        # let the job make real progress, then kill the master cold
        # while tasks are still in flight
        deadline = time.time() + min(120, left())
        while time.time() < deadline:
            done = done_ops()
            if len(done) >= 3:
                break
            time.sleep(0.1)
        assert len(done) >= 3, "job made no progress before the kill"
        master.send_signal(signal.SIGKILL)
        master.wait(timeout=30)
        killed.set()
        killed_at = len(done_ops())
        # the worker is inside the outage window from here until the
        # relaunch has imported what a master imports: seconds

        master = spawn_master("relaunch")
        # a bind failure surfaces here, loudly
        _wait_port(master_port, timeout=left())
        # a task done beyond those the first master saw: the worker's
        # retries reached the new one and it re-registered. Calls from
        # here on meet a master that answers until the job is over
        deadline = time.time() + left()
        while (time.time() < deadline and master.poll() is None
               and len(done_ops()) <= killed_at):
            time.sleep(0.1)
        monkeypatch.setattr(mc_module, "MASTER_RETRY_BUDGET_SECS", 20.0)
        # the relaunched master replays the journal, serves the rest of
        # the job, and exits 0 when the dispatcher reports finished
        try:
            rc = master.wait(timeout=left())
        except subprocess.TimeoutExpired:
            master.kill()
            raise AssertionError(
                "relaunched master did not finish the job:\n%s"
                % open(str(tmp_path / "master-relaunch.log")).read()[-4000:]
            )
        assert rc == 0, (
            "relaunched master failed:\n%s"
            % open(str(tmp_path / "master-relaunch.log")).read()[-4000:]
        )
        # the worker exits after its retry budget concludes job-over
        runner.join(timeout=left() + 20)
        assert not runner.is_alive(), "worker never finished"
    finally:
        killed.set()
        if master.poll() is None:
            master.kill()
        if runner is not None and runner.is_alive():
            runner.join(timeout=5)

    # --- accounting: every task done exactly once, none lost ---
    ops = journal_ops()
    created = {
        task[0]
        for op in ops if op["op"] == "tasks_created"
        for task in op["tasks"]
    }
    done_ids = [op["task"] for op in ops if op["op"] == "done"]
    assert len(created) == 16, created  # 8 tasks/epoch x 2 epochs
    assert sorted(done_ids) == sorted(created), (
        "done ops do not match created tasks exactly once: %r vs %r"
        % (sorted(done_ids), sorted(created))
    )
    boots = [op for op in ops if op["op"] == "master_restarted"]
    assert len(boots) == 2  # original + relaunch

    # --- flight recorder: the restart threads through the postmortem ---
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "scripts"
    ))
    try:
        import postmortem
    finally:
        sys.path.pop(0)
    report = postmortem.postmortem(str(events_dir))
    kinds = {e["event"] for e in report["timeline"]}
    assert {"role_start", "master_restarted", "task_dispatch",
            "worker_register"} <= kinds, kinds
    timeline_ts = [e.get("ts", 0) for e in report["timeline"]]
    assert timeline_ts == sorted(timeline_ts)
    # the worker re-registered with the relaunched master: at least two
    # worker_register events for worker 0 (one per master lifetime)
    registers = [
        e for e in report["timeline"]
        if e["event"] == "worker_register" and e.get("worker") == 0
    ]
    assert len(registers) >= 2, registers


@pytest.mark.parametrize(
    "async_push,device_tier",
    [
        (False, False),
        # ISSUE 5 acceptance: the same SIGKILL/auto-restore/resync
        # protocol must hold with the double-buffered async push on —
        # an in-flight push resolves (retry budget) or surfaces at the
        # depth-1 join, never silently drops. Slow-marked: the fault
        # window alone is ~a minute; the fast lane keeps the sync
        # variant.
        pytest.param(True, False, marks=pytest.mark.slow),
        # ISSUE 6 acceptance: PS SIGKILL mid-job with the DEVICE TIER
        # enabled loses no tier-held updates — the restored-stamp
        # change triggers flush-then-invalidate (the tier's rows,
        # newer than the restored checkpoint, write back before the
        # map drops), and at job end every resident row's value
        # matches the PS store (writebacks all landed).
        pytest.param(True, True, marks=pytest.mark.slow),
    ],
)
def test_ps_sigkill_auto_restore_and_worker_resync(
    tmp_path, monkeypatch, async_push, device_tier
):
    """ISSUE 4 tentpole acceptance: SIGKILL the PS mid-round and
    relaunch it with NO restore flag — the PS auto-restores its newest
    complete checkpoint from its own --checkpoint_dir, stamps
    restored_version on responses, and the worker detects the version
    regression, resyncs (re-pushes table infos), rolls its version back
    to the PS's reality, and the job completes. Version accounting
    stays consistent: the worker's final version equals the PS store
    version (each accepted async push bumps it by one from the restored
    base), exactly as a no-fault run's accounting — no pushes vanished
    into a void."""
    import socket

    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.observability import events
    from tests.test_utils import create_ctr_recordio

    events_dir = tmp_path / "events"
    events_dir.mkdir()
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(events_dir))
    if async_push:
        # read by SparseTrainer at construction (inside Worker below)
        monkeypatch.setenv("EDL_ASYNC_PUSH", "1")
    if device_tier:
        monkeypatch.setenv("EDL_DEVICE_TIER", "1")
        # a PARTIAL hot set (256 rows over the ctr fixture's 1000-id
        # uniform vocab): misses keep flowing so the PS still sees
        # pushes (the kill-once trigger counts push_gradients — a
        # full-residency tier absorbs ALL traffic and the fault never
        # fires), and LFU churn keeps eviction writebacks live across
        # the kill window
        monkeypatch.setenv("EDL_DEVICE_TIER_ROWS", "256")
        monkeypatch.setenv("EDL_DEVICE_TIER_PROMOTE", "2")
        # match the PS server's optimizer config (adam lr=0.01)
        monkeypatch.setenv("EDL_DEVICE_TIER_OPT", "adam")
        monkeypatch.setenv("EDL_DEVICE_TIER_OPT_ARGS", "lr=0.01")
    events.configure("worker-0")

    train_dir = tmp_path / "train"
    train_dir.mkdir()
    # enough records that the job still holds real work when the kill
    # fires: the kill-decision poll below can lag many steps under
    # full-suite CPU contention, and a job that drains before the
    # SIGKILL leaves nothing to resync (the flight-recorder asserts at
    # the end would then fail on a technicality, not a recovery bug)
    create_ctr_recordio(str(train_dir / "f0.rec"), num_records=1152, seed=0)
    reader = RecordIODataReader(data_dir=str(train_dir))
    dispatcher = TaskDispatcher(
        training_shards=reader.create_shards(),
        records_per_task=128,
        num_epochs=2,
        seed=0,
    )
    server = build_server()
    add_master_servicer_to_server(MasterServicer(dispatcher, None), server)
    master_port = find_free_port()
    server.add_insecure_port("localhost:%d" % master_port)
    server.start()

    def free_port():
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    ps_port = free_port()
    ckpt_dir = str(tmp_path / "ps_ckpt")

    def spawn_ps(fault_spec=None):
        # note: NO --checkpoint_dir_for_init — restore must be automatic
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               events.EVENTS_DIR_ENV: str(events_dir)}
        env.pop("EDL_FAULT_SPEC", None)
        if fault_spec:
            env["EDL_FAULT_SPEC"] = fault_spec
        return subprocess.Popen(
            [
                sys.executable, "-m", "elasticdl_tpu.ps.server",
                "--ps_id", "0", "--num_ps_pods", "1",
                "--port", str(ps_port),
                "--opt_type", "adam", "--opt_args", "lr=0.01",
                "--checkpoint_dir", ckpt_dir,
                "--checkpoint_steps", "3",
            ],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )

    # Deterministic mid-job death (testing/faults.py): the PS SIGKILLs
    # ITSELF on its 12th push_gradients — checkpoints at versions 3/6/9
    # are complete by then and 24 of the job's 36 steps remain, so
    # there is always post-kill work left to resync. (An external
    # kill decided by polling the worker's version raced the worker
    # under full-suite CPU contention: by the time the polling thread
    # got scheduled the job had drained, and the flight-recorder
    # asserts below failed with nothing left to push — a 1-in-N flake
    # once the ISSUE-5 wire path sped the steps up.)
    ps_proc = spawn_ps("ps-0:push_gradients:kill-once:12")
    _wait_port(ps_port)
    try:
        worker = Worker(
            MasterClient("localhost:%d" % master_port, worker_id=0),
            "elasticdl_tpu.models.deepfm",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=64,
            wait_sleep_secs=0.1,
            ps_addrs=["localhost:%d" % ps_port],
        )
        runner = threading.Thread(target=worker.run, daemon=True)
        runner.start()

        from elasticdl_tpu.ps.checkpoint import SparseCheckpointSaver

        # the injected kill-once takes the PS down mid-job (SIGKILL:
        # rc is nonzero), after versions past complete checkpoints —
        # the relaunch restores an observably older version (the
        # version-REGRESSION detection path this test pins; a kill
        # landing exactly on a checkpoint would be the restored-stamp
        # path instead, which kill-once on push 12 ≠ 0 mod 3 avoids)
        rc = ps_proc.wait(timeout=120)
        assert rc != 0, "PS survived its kill-once fault"
        restored_floor = SparseCheckpointSaver.latest_version(ckpt_dir)
        assert restored_floor is not None, "PS never checkpointed"

        time.sleep(2)  # let the worker hit the outage window
        ps_proc = spawn_ps()
        # the relaunch must reach serving (restore done, ps_restored
        # journaled) before this test can tear it down — a fast job
        # ending right after the relaunch must not kill a booting PS
        _wait_port(ps_port)

        runner.join(timeout=180)
        assert not runner.is_alive(), "worker never finished after PS restart"
        assert dispatcher.finished(), "job did not complete"
        assert not dispatcher.job_failed(), (
            "PS restart window burned the task retry budget"
        )
        # rolled back then advanced: the final version is consistent
        # with the restored base, not the pre-kill high-water mark
        assert worker.trainer._version >= restored_floor
        if device_tier:
            # no lost updates across the SIGKILL: the trainer's
            # end-of-life close() flushed the tier, and every resident
            # row's device value must match what the (restarted) PS
            # now stores — the resync flush + eviction/periodic
            # writebacks all landed
            import numpy as np

            from elasticdl_tpu.worker.ps_client import PSClient

            tier = worker.trainer.device_tier
            assert tier is not None, "EDL_DEVICE_TIER did not engage"
            assert tier.epoch >= 1, (
                "PS relaunch never invalidated the tier"
            )
            probe = PSClient(["localhost:%d" % ps_port])
            for table in ("deepfm_emb", "deepfm_linear"):
                ids, rows = tier.table_rows(table)
                if not ids.size:
                    continue
                np.testing.assert_allclose(
                    probe.pull_embedding_vectors(table, ids), rows,
                    rtol=1e-5, atol=1e-6,
                )
    finally:
        server.stop(0)
        if ps_proc.poll() is None:
            ps_proc.kill()
        events.flush()
        events._reset_for_tests()

    # --- flight recorder: restore + resync are journaled ---
    from tests.test_utils import load_journal

    ps_events = load_journal(events_dir, "ps-0")
    restored = [e for e in ps_events if e["event"] == "ps_restored"]
    assert restored, "relaunched PS journaled no ps_restored event"
    assert restored[0]["version"] >= restored_floor
    worker_events = load_journal(events_dir, "worker-0")
    resynced = [e for e in worker_events if e["event"] == "worker_resynced"]
    assert resynced, "worker journaled no worker_resynced event"
    assert resynced[0]["restored"] == restored[0]["version"]


# ---------------------------------------------------------------------------
# ISSUE 7: graceful drain under preemption


@pytest.mark.slow
def test_worker_drain_under_async_push_and_device_tier_tier_ps_parity(
    tmp_path, monkeypatch,
):
    """ISSUE 7 acceptance (graceful path): drain a worker mid-job under
    EDL_ASYNC_PUSH + EDL_DEVICE_TIER — begin_drain is exactly what the
    SIGTERM hook calls. The drain must (a) finish the current task
    (done-exactly-once: zero task_requeue events end to end), (b) join
    the in-flight push and flush dirty tier rows so every resident
    row's device value matches the PS (tier<->PS parity), and (c)
    deregister so the removal stays alert-silent."""
    import numpy as np

    from elasticdl_tpu.master.autoscaler import DrainManager
    from elasticdl_tpu.master.fleet import FleetMonitor
    from elasticdl_tpu.observability import events
    from elasticdl_tpu.worker.ps_client import PSClient
    from tests.test_utils import create_ctr_recordio

    events_dir = tmp_path / "events"
    events_dir.mkdir()
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(events_dir))
    monkeypatch.setenv("EDL_ASYNC_PUSH", "1")
    monkeypatch.setenv("EDL_DEVICE_TIER", "1")
    monkeypatch.setenv("EDL_DEVICE_TIER_ROWS", "256")
    monkeypatch.setenv("EDL_DEVICE_TIER_PROMOTE", "2")
    monkeypatch.setenv("EDL_DEVICE_TIER_OPT", "adam")
    monkeypatch.setenv("EDL_DEVICE_TIER_OPT_ARGS", "lr=0.01")
    # the drain watchdog must not fire under full-suite CPU contention
    monkeypatch.setenv("EDL_DRAIN_DEADLINE_SECS", "300")
    events.configure("master")

    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_ctr_recordio(str(train_dir / "f0.rec"), num_records=1152,
                        seed=0)
    reader = RecordIODataReader(data_dir=str(train_dir))
    dispatcher = TaskDispatcher(
        training_shards=reader.create_shards(),
        records_per_task=128, num_epochs=2, seed=0,
    )
    fleet = FleetMonitor(dead_air_secs=60.0)
    servicer = MasterServicer(dispatcher, None, fleet_monitor=fleet)
    drain = DrainManager(dispatcher, servicer=servicer, fleet=fleet,
                         deadline_secs=240.0)
    servicer.drain_manager = drain
    monitor = TaskMonitor(
        dispatcher, servicer, liveness_timeout_secs=60.0,
        scan_interval_secs=0.5, fleet_monitor=fleet,
        drain_manager=drain,
    )
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    master_port = find_free_port()
    server.add_insecure_port("localhost:%d" % master_port)
    server.start()
    monitor.start()

    import socket

    def free_port():
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    ps_port = free_port()
    ps_proc = subprocess.Popen(
        [
            sys.executable, "-m", "elasticdl_tpu.ps.server",
            "--ps_id", "0", "--num_ps_pods", "1",
            "--port", str(ps_port),
            # async PS: EDL_ASYNC_PUSH's supported mode (a sync PS
            # rejects the second worker's post-drain pushes as stale)
            "--use_async", "1",
            "--opt_type", "adam", "--opt_args", "lr=0.01",
        ],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             events.EVENTS_DIR_ENV: str(events_dir)},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    _wait_port(ps_port)
    try:
        worker = Worker(
            MasterClient("localhost:%d" % master_port, worker_id=0),
            "elasticdl_tpu.models.deepfm",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=64, wait_sleep_secs=0.1,
            ps_addrs=["localhost:%d" % ps_port],
        )
        runner = threading.Thread(target=worker.run, daemon=True)
        runner.start()
        # drain once real progress exists: tasks done AND tier traffic
        deadline = time.time() + 120
        while time.time() < deadline and (
            dispatcher.stats()["done"].get("training", 0) < 2
        ):
            time.sleep(0.2)
        assert dispatcher.stats()["done"].get("training", 0) >= 2, (
            "worker made no progress"
        )
        drain.begin_drain(0, reason="scale_down")
        runner.join(timeout=180)
        assert not runner.is_alive(), "draining worker never exited"
        assert worker._drain_done

        # (b) tier<->PS parity: every resident row's device value must
        # equal what the PS stores — the drain's flush landed
        tier = worker.trainer.device_tier
        assert tier is not None, "EDL_DEVICE_TIER did not engage"
        probe = PSClient(["localhost:%d" % ps_port])
        compared = 0
        for table in ("deepfm_emb", "deepfm_linear"):
            ids, rows = tier.table_rows(table)
            if not ids.size:
                continue
            np.testing.assert_allclose(
                probe.pull_embedding_vectors(table, ids), rows,
                rtol=1e-5, atol=1e-6,
            )
            compared += ids.size
        assert compared > 0, "tier held no rows to compare"

        # (c) alert-silent removal, and work remains for a peer
        assert fleet.evaluate() == []
        assert 0 not in servicer.worker_liveness()
        assert not dispatcher.finished()

        # a second worker finishes the job (fresh id: the drained id's
        # tombstone must not block a replacement either)
        worker2 = Worker(
            MasterClient("localhost:%d" % master_port, worker_id=1),
            "elasticdl_tpu.models.deepfm",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=64, wait_sleep_secs=0.1,
            ps_addrs=["localhost:%d" % ps_port],
        )
        worker2.run()
        assert dispatcher.finished()
        assert not dispatcher.job_failed()
    finally:
        monitor.stop()
        server.stop(0)
        if ps_proc.poll() is None:
            ps_proc.kill()
        events.flush()
        events._reset_for_tests()

    from tests.test_utils import load_journal

    merged = load_journal(events_dir)
    acks = [e for e in merged if e["event"] == "drain_ack"]
    assert acks and acks[0]["worker"] == 0
    assert acks[0]["pushes_joined"] and acks[0]["tier_flushed"]
    assert acks[0]["handed_back"] == 0
    # (a) done-exactly-once: nothing was ever requeued
    assert [e for e in merged if e["event"] == "task_requeue"] == []
    assert [e for e in merged if e["event"] == "drain_expired"] == []


STUCK_WORKER = r"""
import signal, sys, time
sys.path.insert(0, %(repo)r)
signal.signal(signal.SIGTERM, signal.SIG_IGN)  # a wedged victim
from elasticdl_tpu.worker.master_client import MasterClient
mc = MasterClient(%(addr)r, worker_id=0)
mc.reset_worker()
task = mc.get_task()
assert task.task_id != 0, "no task to hold"
print("HOLDING", flush=True)
time.sleep(600)  # never reports, never drains
"""


@pytest.mark.slow
def test_drain_deadline_expiry_falls_back_to_requeue_on_death(
    tmp_path, monkeypatch,
):
    """ISSUE 7 acceptance (fallback path): a scale-down victim that
    ignores SIGTERM and never acks. The master's drain deadline expires
    -> requeue-on-death (drain_expired journaled, the held task
    requeues UNCOUNTED, the tombstone says drained: true), the SIGKILL
    fallback reaps the pod, and a surviving worker completes the job —
    done-exactly-once still holds."""
    from elasticdl_tpu.master.autoscaler import DrainManager
    from elasticdl_tpu.master.fleet import FleetMonitor
    from elasticdl_tpu.observability import events

    events_dir = tmp_path / "events"
    events_dir.mkdir()
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(events_dir))
    events.configure("master")

    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=256,
                          seed=0)
    reader = RecordIODataReader(data_dir=str(train_dir))
    dispatcher = TaskDispatcher(
        training_shards=reader.create_shards(), records_per_task=64,
        num_epochs=1, seed=0,
    )
    fleet = FleetMonitor(dead_air_secs=120.0)
    servicer = MasterServicer(dispatcher, None, fleet_monitor=fleet)
    drain = DrainManager(dispatcher, servicer=servicer, fleet=fleet,
                         deadline_secs=3.0)
    servicer.drain_manager = drain
    monitor = TaskMonitor(
        dispatcher, servicer, liveness_timeout_secs=120.0,
        scan_interval_secs=0.2, fleet_monitor=fleet,
        drain_manager=drain,
    )
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    port = find_free_port()
    server.add_insecure_port("localhost:%d" % port)
    server.start()
    monitor.start()
    proc = None
    try:
        script = STUCK_WORKER % {
            "repo": os.path.dirname(os.path.dirname(__file__)),
            "addr": "localhost:%d" % port,
        }
        proc = subprocess.Popen(
            [sys.executable, "-c", script],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE,
        )
        deadline = time.time() + 60
        while time.time() < deadline and not dispatcher.doing_tasks():
            time.sleep(0.1)
        held = dispatcher.doing_tasks()
        assert held, "stuck worker never took a task"
        (held_task,) = held

        # scale-down decision: drain, deliver SIGTERM (ignored)
        drain.begin_drain(0, reason="scale_down")
        proc.send_signal(signal.SIGTERM)
        # the deadline expires on the monitor scan -> requeue fallback
        deadline = time.time() + 30
        while time.time() < deadline and dispatcher.doing_tasks():
            time.sleep(0.2)
        assert not dispatcher.doing_tasks(), "task never recovered"
        assert not drain.is_draining(0)
        # SIGKILL fallback (kubelet's grace-period kill)
        proc.kill()
        proc.wait(timeout=30)

        # the eviction alerted, flagged as a LATE intentional removal
        alerts = fleet.alerts()
        assert any(
            a["alert"] == "dead_air" and a.get("drained") is True
            for a in alerts
        ), alerts

        # a surviving worker drains the job; the held task runs exactly
        # once more (its original holder never trained it)
        worker = Worker(
            MasterClient("localhost:%d" % port, worker_id=2),
            "elasticdl_tpu.models.mnist", reader,
            minibatch_size=32, wait_sleep_secs=0.1,
        )
        worker.run()
        assert dispatcher.finished()
        assert not dispatcher.job_failed(), (
            "the drain fallback burned the retry cap"
        )
    finally:
        monitor.stop()
        server.stop(0)
        if proc is not None and proc.poll() is None:
            proc.kill()
        events.flush()
        events._reset_for_tests()

    from tests.test_utils import load_journal

    merged = load_journal(events_dir)
    expired = [e for e in merged if e["event"] == "drain_expired"]
    assert expired and expired[0]["worker"] == 0
    requeues = [e for e in merged if e["event"] == "task_requeue"]
    assert [e["task"] for e in requeues] == [held_task]
    assert all(e["counted"] is False for e in requeues)
