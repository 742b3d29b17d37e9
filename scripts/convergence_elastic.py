"""Elastic-training convergence experiment (reference parity:
docs/benchmark/report_cn.md:106-117 / data/3-1.csv — the reference's
flagship claim that training quality is unaffected by worker-membership
churn).

Trains the SAME DeepFM CTR job three ways against live PS + master over
gRPC, with workers as real OS processes on the CPU backend:

- fixed-2:  two workers, start to finish
- fixed-4:  four workers, start to finish
- elastic:  start with two, ADD two more at ~1/3 task progress, then
            SIGKILL one at ~2/3 progress (its in-flight tasks are
            recovered by the master's liveness monitor)

Each run records the periodic-eval curve (model_version -> AUC /
accuracy from the master's EvaluationService) and a FINAL eval over the
held-out set at the end-of-job PS state. The experiment asserts the
final metrics agree within tolerance and writes:

- docs/data/elastic_convergence.csv   (the three curves, long format)
- stdout: a JSON summary line

Run: python scripts/convergence_elastic.py [--records 6144]
(~3-6 min on 8 CPUs; set --records 1024 for a quick smoke run.)
"""

import argparse
import csv
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# CPU experiment (workers/PS/eval are all host processes); force it
# before any jax import so no child ever claims the chip
os.environ["JAX_PLATFORMS"] = "cpu"


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
    raise TimeoutError("port %d never came up" % port)


def _spawn_ps(ps_id, num_ps, port, lr):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.Popen(
        [sys.executable, "-m", "elasticdl_tpu.ps.server",
         "--ps_id", str(ps_id), "--num_ps_pods", str(num_ps),
         "--port", str(port),
         "--opt_type", "adam", "--opt_args", "lr=%g" % lr],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def _spawn_worker(idx, master_port, ps_addrs, train_dir, log_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    log = open(log_path, "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "elasticdl_tpu.worker.main",
         "--master_addr", "localhost:%d" % master_port,
         "--worker_id", str(idx),
         "--model_zoo", "elasticdl_tpu.models.deepfm",
         "--training_data", train_dir,
         "--ps_addrs", ps_addrs,
         "--minibatch_size", "64",
         "--report_version_steps", "2"],
        env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
    )


def _final_eval(ps_addrs, valid_dir):
    """Score the END-OF-JOB PS state over the held-out set with a local
    SparseTrainer eval loop (same pull path the workers use)."""
    from elasticdl_tpu.data.pipeline import Dataset
    from elasticdl_tpu.data.readers import RecordIODataReader
    from elasticdl_tpu.models import deepfm
    from elasticdl_tpu.train.sparse import SparseTrainer
    from elasticdl_tpu.worker.ps_client import PSClient
    from elasticdl_tpu.common.constants import Mode

    import numpy as np

    reader = RecordIODataReader(data_dir=valid_dir)
    trainer = SparseTrainer(
        model=deepfm.custom_model(),
        loss_fn=deepfm.loss,
        optimizer=deepfm.optimizer(),
        specs=deepfm.sparse_embedding_specs(batch_size=64),
        ps_client=PSClient(ps_addrs),
        seed=0,
    )
    from collections import namedtuple

    FakeTask = namedtuple("FakeTask", "shard_name start end")
    metrics = deepfm.eval_metrics_fn()
    state = None
    for shard_name, (start, count) in reader.create_shards().items():
        stream = reader.read_records(
            FakeTask(shard_name, start, start + count)
        )
        dataset = deepfm.dataset_fn(
            Dataset(lambda s=stream: s), Mode.EVALUATION, reader.metadata
        )
        for batch in dataset.batch(64):
            state = trainer.ensure_state(state, batch)
            outputs = trainer.eval_step(state, batch)
            from elasticdl_tpu.data.pipeline import batch_real_count

            real = batch_real_count(batch)
            for metric in metrics.values():
                metric.update_state(
                    np.asarray(batch["labels"])[:real],
                    np.asarray(outputs)[:real],
                )
    return {name: float(m.result()) for name, m in metrics.items()}


def run_scenario(name, schedule, train_dir, valid_dir, tmp,
                 records_per_task, num_epochs, eval_steps, lr):
    """schedule: dict with initial worker count and optional elastic
    triggers {"start": 2, "add_at": 0.33, "add": 2, "kill_at": 0.66}."""
    from elasticdl_tpu.common.grpc_utils import (
        build_server, find_free_port,
    )
    from elasticdl_tpu.data.readers import RecordIODataReader
    from elasticdl_tpu.master.evaluation_service import EvaluationService
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.master.task_monitor import TaskMonitor
    from elasticdl_tpu.models import deepfm
    from elasticdl_tpu.proto.services import add_master_servicer_to_server

    train_reader = RecordIODataReader(data_dir=train_dir)
    valid_reader = RecordIODataReader(data_dir=valid_dir)
    dispatcher = TaskDispatcher(
        training_shards=train_reader.create_shards(),
        evaluation_shards=valid_reader.create_shards(),
        records_per_task=records_per_task,
        num_epochs=num_epochs,
        seed=0,
    )
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    total_train_tasks = [0]
    done_train_tasks = [0]

    def on_task_done(task):
        if task.type == pb.TRAINING:
            done_train_tasks[0] += 1

    dispatcher.add_task_completed_callback(on_task_done)
    # total: tasks currently queued (one epoch is lazily materialized
    # at a time; fraction-of-first-epoch is a fine trigger)
    evals = EvaluationService(
        dispatcher, deepfm.eval_metrics_fn, eval_steps=eval_steps
    )
    servicer = MasterServicer(dispatcher, evals)
    monitor = TaskMonitor(
        dispatcher, servicer, liveness_timeout_secs=8.0,
        scan_interval_secs=0.5,
    )
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    master_port = find_free_port()
    server.add_insecure_port("localhost:%d" % master_port)
    server.start()
    monitor.start()

    num_ps = 2
    ps_ports = [find_free_port() for _ in range(num_ps)]
    ps_procs = [
        _spawn_ps(i, num_ps, p, lr) for i, p in enumerate(ps_ports)
    ]
    ps_addrs = ["localhost:%d" % p for p in ps_ports]
    workers = {}
    try:
        for p in ps_ports:
            _wait_port(p)
        for i in range(schedule["start"]):
            workers[i] = _spawn_worker(
                i, master_port, ",".join(ps_addrs), train_dir,
                os.path.join(tmp, "%s_w%d.log" % (name, i)),
            )

        # epoch 1's task count is known once created
        time.sleep(1.0)
        with dispatcher._lock:
            total_train_tasks[0] = len(dispatcher._todo) + len(
                dispatcher._doing
            )
        added = killed = False
        deadline = time.time() + 900
        while not dispatcher.finished():
            if time.time() > deadline:
                raise TimeoutError("%s never finished" % name)
            progress = done_train_tasks[0] / max(
                1, total_train_tasks[0] * num_epochs
            )
            if (
                not added
                and "add_at" in schedule
                and progress >= schedule["add_at"]
            ):
                base = len(workers)
                for j in range(schedule["add"]):
                    idx = base + j
                    workers[idx] = _spawn_worker(
                        idx, master_port, ",".join(ps_addrs), train_dir,
                        os.path.join(tmp, "%s_w%d.log" % (name, idx)),
                    )
                added = True
                print("[%s] +%d workers at %.0f%%"
                      % (name, schedule["add"], progress * 100))
            if (
                not killed
                and "kill_at" in schedule
                and progress >= schedule["kill_at"]
            ):
                victim = sorted(workers)[0]
                workers[victim].send_signal(signal.SIGKILL)
                killed = True
                print("[%s] SIGKILL worker %d at %.0f%%"
                      % (name, victim, progress * 100))
            time.sleep(0.5)
        assert not dispatcher.job_failed(), "%s job failed" % name
        # the elastic scenario must really have churned: a silent
        # no-trigger run would measure fixed-N and call it elastic
        if "add_at" in schedule:
            assert added, "%s: add trigger never fired" % name
        if "kill_at" in schedule:
            assert killed, "%s: kill trigger never fired" % name

        final = _final_eval(ps_addrs, valid_dir)
        curve = [
            (int(version), {k: float(v) for k, v in summary.items()})
            for version, summary in evals.completed_summaries
        ]
        return {"final": final, "curve": curve,
                "workers_seen": len(workers),
                "train_tasks": done_train_tasks[0]}
    finally:
        for proc in workers.values():
            if proc.poll() is None:
                proc.kill()
        for proc in ps_procs:
            proc.terminate()
        for proc in ps_procs:
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
        monitor.stop()
        server.stop(0)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--records", type=int, default=6144)
    parser.add_argument("--valid_records", type=int, default=1024)
    parser.add_argument("--records_per_task", type=int, default=256)
    parser.add_argument("--num_epochs", type=int, default=2)
    parser.add_argument("--eval_steps", type=int, default=8)
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--tolerance", type=float, default=0.03,
                        help="max allowed final-AUC gap vs fixed-2")
    parser.add_argument("--out_csv",
                        default=os.path.join(
                            REPO, "docs", "data",
                            "elastic_convergence.csv"))
    args = parser.parse_args()

    from tests.test_utils import create_ctr_recordio

    tmp = tempfile.mkdtemp(prefix="edl_elastic_")
    train_dir = os.path.join(tmp, "train")
    valid_dir = os.path.join(tmp, "valid")
    os.makedirs(train_dir)
    os.makedirs(valid_dir)
    create_ctr_recordio(
        os.path.join(train_dir, "f0.rec"),
        num_records=args.records, seed=0,
    )
    create_ctr_recordio(
        os.path.join(valid_dir, "f0.rec"),
        num_records=args.valid_records, seed=1,
    )

    scenarios = {
        "fixed2": {"start": 2},
        "fixed4": {"start": 4},
        "elastic": {"start": 2, "add_at": 0.33, "add": 2,
                    "kill_at": 0.66},
    }
    results = {}
    for name, schedule in scenarios.items():
        t0 = time.time()
        results[name] = run_scenario(
            name, schedule, train_dir, valid_dir, tmp,
            args.records_per_task, args.num_epochs, args.eval_steps,
            args.lr,
        )
        results[name]["wall_secs"] = round(time.time() - t0, 1)
        print("[%s] final=%s (%.1fs)" % (
            name, results[name]["final"], results[name]["wall_secs"]))

    os.makedirs(os.path.dirname(args.out_csv), exist_ok=True)
    with open(args.out_csv, "w", newline="") as f:
        writer = csv.writer(f)
        writer.writerow(["scenario", "model_version", "metric", "value"])
        for name, r in results.items():
            for version, summary in r["curve"]:
                for metric, value in summary.items():
                    writer.writerow([name, version, metric, round(value, 5)])
            for metric, value in r["final"].items():
                writer.writerow([name, "final", metric, round(value, 5)])

    metric_key = "auc"
    baselinev = results["fixed2"]["final"][metric_key]
    gaps = {
        name: abs(r["final"][metric_key] - baselinev)
        for name, r in results.items()
    }
    ok = all(gap <= args.tolerance for gap in gaps.values())
    print(json.dumps({
        "metric": metric_key,
        "final": {n: round(r["final"][metric_key], 4)
                  for n, r in results.items()},
        "max_gap": round(max(gaps.values()), 4),
        "tolerance": args.tolerance,
        "converged_equivalently": ok,
        "csv": args.out_csv,
    }))
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
