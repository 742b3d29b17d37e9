"""Dense data plane smoke: 2-process jax.distributed CPU mesh, real
master + PS + workers — dense gradients provably never touch the PS.

The ISSUE 20 acceptance lane (ci.sh tier 1g). The reference framework's
two dense strategies both put every dense byte on the wire every step
(push_gradient to the PS, or Horovod allreduce over the NIC). The GSPMD
rebuild keeps dense parameters and optimizer state sharded over the
mesh — the jitted step reduces gradients as compiler-inserted
collectives — and the PS serves only sparse embedding rows. This smoke
asserts that split MECHANICALLY, not by code inspection:

- a real 2-worker DeepFM job (``jax.distributed`` spanning the two
  worker processes, dp=2 mesh, lockstep rounds) trains to completion
  against an in-process master and a live PS subprocess;
- the PS's byte counters are scraped off its /metrics port at the end:
  ``edl_ps_push_bytes_total`` (embedding-row payload) must be nonzero —
  the sparse plane really rode the PS — while
  ``edl_ps_push_dense_bytes_total`` (dense TensorBlobs arriving over
  push_gradients, the reference's dense path) must be exactly 0;
- the master's FleetMonitor must have seen both workers report the
  dense-plane telemetry (mesh_shape=dp=2, collective_bytes_per_step)
  — the same fields /statusz and postmortem.py surface;
- the mesh epoch must not have moved: this is the steady-state lane
  (a reshape mid-job is the elastic tests' subject, not this one's).

Prints one JSON line. CPU backend; runs in ~1-3 min.
"""

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"


def _spawn_worker(idx, master_port, coordinator_port, train_dir,
                  ps_addrs, ckpt_dir, log_path):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        EDL_FAULTHANDLER="1",
        PYTHONPATH=REPO,
        # one virtual device per worker process: the global mesh is the
        # 2-process dp=2 mesh, every dense reduction crosses processes
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    log = open(log_path, "ab")
    log.write(b"\n===== incarnation spawn =====\n")
    log.flush()
    return subprocess.Popen(
        [sys.executable, "-m", "elasticdl_tpu.worker.main",
         "--master_addr", "localhost:%d" % master_port,
         "--worker_id", str(idx),
         "--model_zoo", "elasticdl_tpu.models.deepfm",
         "--training_data", train_dir,
         "--minibatch_size", "64",
         "--multihost", "1",
         "--coordinator_port", str(coordinator_port),
         "--worker_host", "localhost:%d" % (63000 + idx),
         "--ps_addrs", ps_addrs,
         "--checkpoint_dir", ckpt_dir,
         "--checkpoint_steps", "4",
         "--report_version_steps", "2"],
        env=env,
        stdout=log,
        stderr=subprocess.STDOUT,
        cwd=REPO,
    )


def _scrape_counters(metrics_port):
    """Sum each byte counter's series off the PS /metrics exposition.
    Returns {metric_name: summed_value}; a registered-but-untouched
    unlabeled counter renders an explicit 0 line (servicer touches the
    dense series at construction exactly so this scrape can tell
    'provably zero' from 'not exported')."""
    body = urllib.request.urlopen(
        "http://localhost:%d/metrics" % metrics_port, timeout=10
    ).read().decode()
    wanted = ("edl_ps_push_bytes_total", "edl_ps_push_dense_bytes_total",
              "edl_ps_pull_bytes_total")
    sums = {}
    for line in body.splitlines():
        if line.startswith("#"):
            continue
        for name in wanted:
            if line.startswith(name) and (
                line[len(name):len(name) + 1] in ("", " ", "{")
            ):
                sums[name] = sums.get(name, 0.0) + float(line.split()[-1])
    return sums, body


def main():
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--records", type=int, default=2048)
    parser.add_argument("--records_per_task", type=int, default=256)
    parser.add_argument("--num_epochs", type=int, default=1)
    parser.add_argument("--deadline_secs", type=float, default=420.0)
    args = parser.parse_args()

    from elasticdl_tpu.common.grpc_utils import build_server, find_free_port
    from elasticdl_tpu.data.readers import RecordIODataReader
    from elasticdl_tpu.master.fleet import FleetMonitor
    from elasticdl_tpu.master.rendezvous import MeshRendezvous
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.master.task_monitor import TaskMonitor
    from elasticdl_tpu.proto.services import add_master_servicer_to_server
    from tests.test_utils import create_ctr_recordio, spawn_ps_process

    tmp = tempfile.mkdtemp(prefix="edl_dense_plane_")
    train_dir = os.path.join(tmp, "train")
    os.makedirs(train_dir)
    create_ctr_recordio(
        os.path.join(train_dir, "f0.rec"), num_records=args.records,
        seed=0,
    )

    reader = RecordIODataReader(data_dir=train_dir)
    dispatcher = TaskDispatcher(
        training_shards=reader.create_shards(),
        records_per_task=args.records_per_task,
        num_epochs=args.num_epochs,
        seed=0,
    )
    fleet = FleetMonitor()
    rendezvous = MeshRendezvous()
    servicer = MasterServicer(
        dispatcher, None, rendezvous=rendezvous, fleet_monitor=fleet
    )
    monitor = TaskMonitor(
        dispatcher, servicer, rendezvous=rendezvous,
        # same budgets as tests/test_multihost_e2e.py: must exceed a
        # worker's relaunch latency or the restart gap itself evicts
        # members and churns the epoch this lane asserts is quiet
        liveness_timeout_secs=30.0,
        scan_interval_secs=0.5,
        mesh_restart_grace_secs=25.0,
    )
    server = build_server()
    add_master_servicer_to_server(servicer, server)
    master_port = find_free_port()
    server.add_insecure_port("localhost:%d" % master_port)
    server.start()
    monitor.start()

    metrics_port = find_free_port()
    ps_proc, ps_port = spawn_ps_process(
        log_path=os.path.join(tmp, "ps.log"),
        extra=("--metrics_port", str(metrics_port)),
    )
    ps_addrs = "localhost:%d" % ps_port
    coordinator_port = find_free_port()
    ckpt_dir = os.path.join(tmp, "ckpt")
    logs = {i: os.path.join(tmp, "worker%d.log" % i) for i in (0, 1)}
    workers = {}
    relaunches = {0: 0, 1: 0}
    max_hosts_seen = 0
    try:
        for i in (0, 1):
            workers[i] = _spawn_worker(
                i, master_port, coordinator_port, train_dir, ps_addrs,
                ckpt_dir, logs[i],
            )

        def supervise():
            # pod-manager stand-in: a late jax.distributed joiner can
            # abort fatally against a not-yet-ready coordinator; the
            # recovery model is relaunch-and-rejoin (test_multihost_e2e)
            for i, proc in list(workers.items()):
                if proc.poll() is None:
                    continue
                relaunches[i] += 1
                if relaunches[i] >= 8:
                    raise SystemExit(
                        "FAIL: worker %d restart-looped; log tail:\n%s"
                        % (i, open(logs[i]).read()[-2500:])
                    )
                workers[i] = _spawn_worker(
                    i, master_port, coordinator_port, train_dir,
                    ps_addrs, ckpt_dir, logs[i],
                )

        started = time.time()
        deadline = started + args.deadline_secs
        while time.time() < deadline and not dispatcher.finished():
            supervise()
            max_hosts_seen = max(max_hosts_seen, len(rendezvous.hosts()))
            time.sleep(0.5)
        elapsed = time.time() - started
        if not dispatcher.finished():
            raise SystemExit(
                "FAIL: job never finished in %.0fs; worker log tail:\n%s"
                % (args.deadline_secs, open(logs[0]).read()[-2500:])
            )
        if dispatcher.job_failed():
            raise SystemExit("FAIL: job failed")

        counters, raw = _scrape_counters(metrics_port)
        snapshot = fleet.snapshot()
    finally:
        for proc in workers.values():
            if proc.poll() is None:
                proc.kill()
        ps_proc.terminate()
        try:
            ps_proc.wait(timeout=10)
        except Exception:
            ps_proc.kill()
        monitor.stop()
        server.stop(0)

    sparse_bytes = counters.get("edl_ps_push_bytes_total", 0.0)
    dense_bytes = counters.get("edl_ps_push_dense_bytes_total")
    dense_plane = snapshot.get("dense_plane", {})
    summary = {
        "elapsed_s": round(elapsed, 1),
        "workers": 2,
        "max_hosts_seen": max_hosts_seen,
        "mesh_epoch": rendezvous.mesh_epoch,
        "ps_push_bytes": int(sparse_bytes),
        "ps_push_dense_bytes": (
            None if dense_bytes is None else int(dense_bytes)
        ),
        "ps_pull_bytes": int(
            counters.get("edl_ps_pull_bytes_total", 0.0)
        ),
        "dense_plane": dense_plane,
        "relaunches": dict(relaunches),
    }
    print(json.dumps(summary))

    failures = []
    if max_hosts_seen != 2:
        failures.append(
            "mesh never spanned 2 processes (max hosts %d)"
            % max_hosts_seen
        )
    if sparse_bytes <= 0:
        failures.append("no embedding-row push bytes reached the PS")
    if dense_bytes is None:
        failures.append(
            "edl_ps_push_dense_bytes_total missing from /metrics:\n%s"
            % raw[:1500]
        )
    elif dense_bytes != 0:
        failures.append(
            "DENSE GRADIENTS HIT THE PS: %d bytes over push_gradients"
            % dense_bytes
        )
    reported = [
        entry for entry in dense_plane.values()
        if entry.get("mesh_shape") == "dp=2"
    ]
    if not reported:
        failures.append(
            "no worker reported dense-plane telemetry with mesh dp=2: %r"
            % dense_plane
        )
    elif not any(
        entry.get("collective_bytes_per_step", 0) > 0 for entry in reported
    ):
        failures.append(
            "collective_bytes_per_step never reported >0: %r" % dense_plane
        )
    if failures:
        raise SystemExit("FAIL: " + "; ".join(failures))


if __name__ == "__main__":
    main()
