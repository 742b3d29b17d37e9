"""In-process distributed training: real gRPC master + real Worker.

The workhorse test pattern of the reference
(tests/test_utils.py:286-430 distributed_train_and_evaluate): full
master<->worker protocol over localhost, no cluster.
"""

import os
import threading

from elasticdl_tpu.common.grpc_utils import (
    build_channel,
    build_server,
    find_free_port,
)
from elasticdl_tpu.data.readers import RecordIODataReader
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.proto.services import add_master_servicer_to_server
from elasticdl_tpu.train.metrics import Accuracy
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.worker import Worker
from tests.test_utils import create_mnist_recordio


def start_master(train_dir, valid_dir, export_path, eval_steps=8):
    train_reader = RecordIODataReader(data_dir=train_dir)
    valid_reader = RecordIODataReader(data_dir=valid_dir)
    dispatcher = TaskDispatcher(
        training_shards=train_reader.create_shards(),
        evaluation_shards=valid_reader.create_shards(),
        records_per_task=64,
        num_epochs=2,
        seed=0,
    )
    dispatcher.add_deferred_callback_create_train_end_task(
        {"saved_model_path": export_path}
    )
    evals = EvaluationService(
        dispatcher, lambda: {"accuracy": Accuracy()}, eval_steps=eval_steps
    )
    servicer = MasterServicer(dispatcher, evals)
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    port = find_free_port()
    server.add_insecure_port("localhost:%d" % port)
    server.start()
    return server, dispatcher, evals, port


def test_distributed_train_and_evaluate(tmp_path):
    train_dir = tmp_path / "train"
    valid_dir = tmp_path / "valid"
    train_dir.mkdir()
    valid_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=256, seed=0)
    create_mnist_recordio(str(valid_dir / "f0.rec"), num_records=64, seed=1)
    export_path = str(tmp_path / "export")

    server, dispatcher, evals, port = start_master(
        str(train_dir), str(valid_dir), export_path
    )
    try:
        worker = Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "tests.models.mnist_with_export",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            report_version_steps=4,
            wait_sleep_secs=0.1,
        )
        worker.run()
        assert dispatcher.finished()
        assert not dispatcher.job_failed()
        # step-based eval fired and produced sane accuracy
        assert evals.completed_summaries
        version, summary = evals.completed_summaries[-1]
        assert summary["accuracy"] > 0.8
        # train-end callback exported the model
        assert os.path.exists(os.path.join(export_path, "manifest.json"))
    finally:
        server.stop(None)


def test_two_workers_share_the_queue(tmp_path):
    train_dir = tmp_path / "train"
    valid_dir = tmp_path / "valid"
    train_dir.mkdir()
    valid_dir.mkdir()
    for i in range(2):
        create_mnist_recordio(
            str(train_dir / ("f%d.rec" % i)), num_records=128, seed=i
        )
    create_mnist_recordio(str(valid_dir / "f0.rec"), num_records=64, seed=9)

    server, dispatcher, evals, port = start_master(
        str(train_dir), str(valid_dir), str(tmp_path / "export"), eval_steps=0
    )
    try:
        workers = [
            Worker(
                MasterClient("localhost:%d" % port, worker_id=i),
                "elasticdl_tpu.models.mnist",
                RecordIODataReader(data_dir=str(train_dir)),
                minibatch_size=32,
                wait_sleep_secs=0.1,
            )
            for i in range(2)
        ]
        threads = [
            threading.Thread(target=w.run, daemon=True) for w in workers
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert dispatcher.finished()
        # both workers actually trained (queue was shared)
        assert all(w.model_version > 0 for w in workers)
    finally:
        server.stop(None)


def test_worker_checkpoint_resume_and_fatal_restore(tmp_path):
    """Worker-level restore wiring: save during a training run, resume a
    fresh worker from --checkpoint_dir_for_init (version fast-forwards),
    and die fatally (CheckpointRestoreError) on an unrestorable dir
    rather than silently training from random init."""
    from elasticdl_tpu.worker.worker import CheckpointRestoreError

    train_dir = tmp_path / "train"
    valid_dir = tmp_path / "valid"
    train_dir.mkdir()
    valid_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=256, seed=0)
    create_mnist_recordio(str(valid_dir / "f0.rec"), num_records=64, seed=1)
    ckpt_dir = str(tmp_path / "ckpt")

    # Run 1: train to completion, checkpointing every 2 versions.
    server, dispatcher, evals, port = start_master(
        str(train_dir), str(valid_dir), str(tmp_path / "export"), eval_steps=0
    )
    try:
        w1 = Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "elasticdl_tpu.models.mnist",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            wait_sleep_secs=0.1,
            checkpoint_dir=ckpt_dir,
            checkpoint_steps=2,
        )
        w1.run()
        assert dispatcher.finished()
        saved_version = w1.model_version
        assert saved_version > 0
    finally:
        server.stop(None)

    # Run 2: resume from the checkpoint; version fast-forwards past the
    # last saved snapshot and eval tasks never see random weights.
    server, dispatcher, evals, port = start_master(
        str(train_dir), str(valid_dir), str(tmp_path / "export2"),
        eval_steps=4,
    )
    try:
        w2 = Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "elasticdl_tpu.models.mnist",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            wait_sleep_secs=0.1,
            checkpoint_dir_for_init=ckpt_dir,
        )
        w2.run()
        assert dispatcher.finished()
        # version fast-forwarded to the restored snapshot, then kept
        # counting through run 2's batches
        assert w2.model_version >= saved_version + 1
        assert evals.completed_summaries
        _, summary = evals.completed_summaries[-1]
        assert summary["accuracy"] > 0.8  # resumed weights, not random
    finally:
        server.stop(None)

    # Run 3: empty and nonexistent restore dirs are both fatal, and the
    # job does NOT finish.
    empty = tmp_path / "empty_ckpt"
    empty.mkdir()
    for bad_dir in (str(empty), str(tmp_path / "typo_ckpt")):
        server, dispatcher, evals, port = start_master(
            str(train_dir), str(valid_dir), str(tmp_path / "export3"),
            eval_steps=0,
        )
        try:
            w3 = Worker(
                MasterClient("localhost:%d" % port, worker_id=0),
                "elasticdl_tpu.models.mnist",
                RecordIODataReader(data_dir=str(train_dir)),
                minibatch_size=32,
                wait_sleep_secs=0.1,
                checkpoint_dir_for_init=bad_dir,
            )
            try:
                w3.run()
                raise AssertionError("worker trained from random init")
            except CheckpointRestoreError:
                pass
            assert not dispatcher.finished()
        finally:
            server.stop(None)


def test_mesh_epoch_change_aborts_for_restart(tmp_path):
    """A mesh-epoch bump mid-training must raise MeshEpochChanged out of
    the worker (the process then exits EPOCH_RESTART_EXIT_CODE and the
    pod manager relaunches it into the new mesh)."""
    import pytest

    from elasticdl_tpu.worker.worker import MeshEpochChanged

    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=256, seed=0)

    server, dispatcher, evals, port = start_master(
        str(train_dir), str(train_dir), str(tmp_path / "export")
    )

    class EpochFlipRuntime:
        def __init__(self):
            self.calls = 0

        def epoch_moved(self, seen_epoch):
            self.calls += 1
            return self.calls >= 2  # second probe sees a new epoch

    runtime = EpochFlipRuntime()
    try:
        worker = Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "tests.models.mnist_with_export",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            report_version_steps=2,
            wait_sleep_secs=0.1,
            multihost_runtime=runtime,
        )
        with pytest.raises(MeshEpochChanged):
            worker.run()
        assert runtime.calls >= 2
        # in-flight tasks were requeued on the way out (the relaunched
        # same-id worker keeps liveness fresh, so the master would never
        # see this as a death). A task fetched in the failure window is
        # handed back by the prefetch THREAD — poll briefly for it.
        import time

        deadline = time.time() + 5
        while dispatcher.doing_tasks() and time.time() < deadline:
            time.sleep(0.05)
        assert not dispatcher.finished()
        assert not dispatcher.doing_tasks(), "tasks left orphaned"
    finally:
        server.stop(0)


def test_output_exports_without_declared_callbacks(tmp_path):
    """--output must export for models that declare NO callbacks (the
    default SavedModelExporter; soak regression)."""
    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=128, seed=0)
    export_path = str(tmp_path / "export")

    server, dispatcher, evals, port = start_master(
        str(train_dir), str(train_dir), export_path
    )
    try:
        worker = Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "elasticdl_tpu.models.mnist",  # no callbacks() in module
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            wait_sleep_secs=0.1,
        )
        worker.run()
        assert dispatcher.finished()
        assert os.path.exists(os.path.join(export_path, "manifest.json"))
    finally:
        server.stop(0)


def test_stateless_worker_restores_checkpoint_for_export(tmp_path):
    """A relaunched worker that only ever sees the train-end task must
    restore from checkpoint and export the TRAINED weights (never
    random init)."""
    train_dir = tmp_path / "train"
    train_dir.mkdir()
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=128, seed=0)
    ckpt_dir = str(tmp_path / "ckpt")

    # run 1: train with checkpoints
    server, dispatcher, evals, port = start_master(
        str(train_dir), str(train_dir), str(tmp_path / "unused"),
        eval_steps=0,
    )
    try:
        Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "elasticdl_tpu.models.mnist",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            wait_sleep_secs=0.1,
            checkpoint_dir=ckpt_dir,
            checkpoint_steps=2,
        ).run()
        assert dispatcher.finished()
    finally:
        server.stop(None)

    # run 2: ONLY the train-end task exists; the worker has no state
    from elasticdl_tpu.master.servicer import MasterServicer as MS

    dispatcher2 = TaskDispatcher(
        training_shards={}, records_per_task=64, num_epochs=0
    )
    export_path = str(tmp_path / "export2")
    dispatcher2.add_deferred_callback_create_train_end_task(
        {"saved_model_path": export_path}
    )
    dispatcher2.fire_deferred_callbacks()
    servicer = MS(dispatcher2, None)
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    port = find_free_port()
    server.add_insecure_port("localhost:%d" % port)
    server.start()
    try:
        Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "elasticdl_tpu.models.mnist",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32,
            wait_sleep_secs=0.1,
            checkpoint_dir_for_init=ckpt_dir,
            resume_optional=True,  # the elastic default
        ).run()
        assert dispatcher2.finished()
        assert os.path.exists(os.path.join(export_path, "manifest.json"))
        # exported weights are the TRAINED ones (restored step > 0)
        from elasticdl_tpu.train.export import load_exported

        _, _, step = load_exported(export_path)
        assert step > 0
    finally:
        server.stop(None)


def test_job_completes_when_dataset_not_batch_divisible(tmp_path):
    """Regression: a record tail smaller than one minibatch used to
    deadlock the job — the elastic stream WAIT-loops (never "ends"),
    so batch() held the tail forever while the master waited for its
    task to be reported. The WAIT now emits a pipeline.FLUSH that
    forces the partial (masked) batch out. Found on a digits dataset
    of 1,797 records."""
    train_dir = tmp_path / "train"
    valid_dir = tmp_path / "valid"
    train_dir.mkdir()
    valid_dir.mkdir()
    # 70 records, tasks of 32, minibatch 64: the last stream segment
    # is 6 records — strictly smaller than one minibatch
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=70, seed=0)
    create_mnist_recordio(str(valid_dir / "f0.rec"), num_records=64, seed=1)

    train_reader = RecordIODataReader(data_dir=str(train_dir))
    valid_reader = RecordIODataReader(data_dir=str(valid_dir))
    dispatcher = TaskDispatcher(
        training_shards=train_reader.create_shards(),
        evaluation_shards=valid_reader.create_shards(),
        records_per_task=32,
        num_epochs=1,
        seed=0,
    )
    evals = EvaluationService(
        dispatcher, lambda: {"accuracy": Accuracy()}, eval_steps=0
    )
    servicer = MasterServicer(dispatcher, evals)
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    port = find_free_port()
    server.add_insecure_port("localhost:%d" % port)
    server.start()
    try:
        worker = Worker(
            MasterClient("localhost:%d" % port, worker_id=0),
            "tests.models.mnist_with_export",
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=64,
            report_version_steps=4,
            wait_sleep_secs=0.1,
        )
        done = {}

        def run():
            worker.run()
            done["ok"] = True

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(timeout=120)
        assert done.get("ok"), (
            "job hung: worker never drained the sub-minibatch tail"
        )
        assert dispatcher.finished()
        assert not dispatcher.job_failed()
    finally:
        server.stop(None)
