"""The main path, once, on the chip: master -> worker -> trainer.

``python3 chip_smoke.py`` runs two short training jobs through the
entry points a user calls — ``python -m elasticdl_tpu.master.main``,
``python -m elasticdl_tpu.ps.server`` and
``python -m elasticdl_tpu.worker.main`` as separate OS processes over
real gRPC — and checks what came out:

1. dense: ``elasticdl_tpu.models.transformer`` at its full zoo width
   (12 layers, d=768, 12 heads of 64, vocab 32000, AdamW), sequences of
   1024 tokens, batch 8, bfloat16 compute, 32 steps. On a TPU backend
   the attention must resolve to the Pallas flash kernel.
2. sparse: ``elasticdl_tpu.models.deepfm`` against two parameter-server
   processes (native store built in this run), pipelined stream, device
   embedding tier, batch 512 x 39 Zipf(1.2) ids over a 1M vocabulary,
   40 steps.

Everything is generated from a seed inside the run. One process holds
the chip at a time: this parent never imports jax, the master and the
PS are pinned to the CPU, only the worker gets the chip, and each
phase's processes have exited before the next phase starts.

Exit code 0 and the last stdout line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
— those keys and no others, the device as jax reports it — only when
every check of both phases held on an accelerator. A failed phase on an
accelerator: exit 1 and the same line with ``"ok": false``. No
accelerator (or no program next to this script): non-zero exit and no
result line. The line before the result, ``chip_smoke: summary: {...}``
(also ``chiprun_out/chip_smoke/summary.json``), carries the package
versions and the per-phase figures (seconds to first step, losses,
what attention and the store resolved to, the tier's hits): facts about what
ran, not performance results.

tests/test_chip_smoke.py drives the same phase functions at a tiny
size with the worker on the CPU, so this command is debugged before
chip time is spent on it.
"""

import glob
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
NATIVE_DIR = os.path.join(ROOT, "elasticdl_tpu", "native")
NATIVE_SO = os.path.join(NATIVE_DIR, "libedl_embedding.so")
# logs and the summary land where the chip tool copies results back from
WORK_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

DENSE = dict(
    model_zoo="elasticdl_tpu.models.transformer",
    seq=1024, vocab=32000, minibatch=8, steps_per_task=8, tasks=4,
)
SPARSE = dict(
    model_zoo="elasticdl_tpu.models.deepfm",
    fields=39, vocab=1_000_000, minibatch=512, steps_per_task=10,
    tasks=4,
)
# what a run on the chip must show; the CPU rehearsal passes its own
ON_CHIP = dict(
    platform="tpu",
    # a missing chip is an error, not a CPU run; the host CPU device
    # stays visible for code that pins input work to it
    worker_platforms="tpu,cpu",
    attention="pallas",
)
PHASE_TIMEOUT_SECS = 540
TS_RE = re.compile(r"^(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3}) ")


class SmokeFailure(Exception):
    """A check did not hold; the message says which."""


class Children:
    """Every process the smoke starts, so none outlives it."""

    def __init__(self):
        self._procs = []

    def start(self, argv, env, log_path):
        with open(log_path, "wb") as log:
            proc = subprocess.Popen(
                [sys.executable] + argv, cwd=ROOT, env=env,
                stdout=log, stderr=subprocess.STDOUT,
            )
        self._procs.append(proc)
        return proc

    def stop_all(self):
        for proc in self._procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in self._procs:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        self._procs = []


def child_env(platforms, events_dir=None, **extra):
    env = dict(os.environ, JAX_PLATFORMS=platforms)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if events_dir:
        env["EDL_EVENTS_DIR"] = events_dir
    env.update(extra)
    return env


def free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def wait_port(port, proc, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure(
                "process exited %d before serving :%d"
                % (proc.returncode, port)
            )
        try:
            with socket.create_connection(("127.0.0.1", port), 1.0):
                return
        except OSError:
            time.sleep(0.3)
    raise SmokeFailure("nothing served :%d within %ds" % (port, timeout))


def wait_exit(proc, name, timeout):
    try:
        return proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            "%s still running after %ds" % (name, timeout)
        ) from None


def tail(path, lines=40):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError as e:
        return "(%s)" % e


def read(path):
    with open(path, errors="replace") as f:
        return f.read()


# ---------------------------------------------------------------------
# device probe and native build


def probe_device():
    """What jax finds by default, asked of a throwaway child (the
    parent must never hold the chip): platform, kind, count and the
    package versions."""
    code = (
        "import json, jax, jaxlib\n"
        "from importlib import metadata\n"
        "d = jax.devices()[0]\n"
        "try:\n"
        "    libtpu = metadata.version('libtpu')\n"
        "except metadata.PackageNotFoundError:\n"
        "    libtpu = None\n"
        "print(json.dumps({'device': {'platform': d.platform,"
        " 'kind': d.device_kind, 'count': len(jax.devices())},"
        " 'versions': {'jax': jax.__version__,"
        " 'jaxlib': jaxlib.__version__, 'libtpu': libtpu}}))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
    )
    if out.returncode != 0:
        sys.exit(
            "chip_smoke: jax could not list devices:\n%s"
            % out.stderr[-2000:]
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def result_line(ok, device):
    """The last stdout line: exactly ``ok`` and ``device`` with exactly
    ``platform``, ``kind`` and ``count``. Everything else the run
    learned goes in the summary line before it."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def build_native_store():
    """Start from ``make clean`` so nothing the PS loads predates this
    run (the .so is git-ignored and the chip tool copies the tree as
    it stands); built once here so the PS processes' own ``make`` is a
    no-op instead of two concurrent links."""
    for target in (["clean"], []):
        try:
            subprocess.run(
                ["make", "-C", NATIVE_DIR] + target, check=True,
                capture_output=True, text=True,
            )
        except (OSError, subprocess.CalledProcessError) as e:
            sys.exit(
                "chip_smoke: building the native embedding store "
                "failed (%s): %s" % (e, getattr(e, "stderr", ""))
            )
    return os.path.getmtime(NATIVE_SO)


# ---------------------------------------------------------------------
# data, generated from a seed


def write_token_records(data_dir, cfg, seed=0):
    """Zipf-distributed token ids: the unigram frequencies are
    learnable in a few steps, so the loss falls visibly."""
    import numpy as np

    from elasticdl_tpu.data.gen.converters import convert_rows

    rng = np.random.RandomState(seed)
    n = cfg["minibatch"] * cfg["steps_per_task"] * cfg["tasks"]
    tokens = (
        rng.zipf(1.2, size=(n, cfg["seq"])) % cfg["vocab"]
    ).astype(np.int32)
    convert_rows(
        data_dir, ({"tokens": row} for row in tokens),
        records_per_shard=n,
    )
    return n


def write_ctr_records(data_dir, cfg, seed=0):
    """A CTR id stream — Zipf(1.2) ids over the vocabulary —
    with a planted linear signal in the label so the loss can fall."""
    import numpy as np

    from elasticdl_tpu.data.gen.converters import convert_rows

    rng = np.random.RandomState(seed)
    n = cfg["minibatch"] * cfg["steps_per_task"] * cfg["tasks"]
    ids = (
        rng.zipf(1.2, size=(n, cfg["fields"])) % cfg["vocab"]
    ).astype(np.int64)
    weights = np.random.RandomState(12345).randn(cfg["vocab"])
    score = weights[ids].sum(axis=1) / np.sqrt(cfg["fields"])
    labels = (score + 0.1 * rng.randn(n) > 0).astype(np.int64)
    convert_rows(
        data_dir,
        ({"ids": ids[i], "label": labels[i]} for i in range(n)),
        records_per_shard=n,
    )
    return n


# ---------------------------------------------------------------------
# reading what the processes said


def log_seconds(line):
    m = TS_RE.match(line)
    if not m:
        return None
    return time.mktime(
        time.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")
    ) + int(m.group(2)) / 1e3


def parse_worker_log(text):
    """Steps (timestamp, loss), the device line, what attention
    resolved to, the tier's hits, and the compile ledger lines."""
    facts = {"steps": [], "compiles": {}}
    for line in text.splitlines():
        m = re.search(r"step (\d+) loss (\S+)", line)
        if m:
            facts["steps"].append(
                (int(m.group(1)), log_seconds(line), float(m.group(2)))
            )
            continue
        m = re.search(
            r"devices: platform=(\S+) device_kind=(.+?) "
            r"local_devices=(\d+) global_devices=(\d+)", line)
        if m:
            facts["platform"] = m.group(1)
            facts["device_kind"] = m.group(2)
            facts["device_count"] = int(m.group(4))
            continue
        m = re.search(r"attention impl=auto resolved to (\w+)", line)
        if m:
            facts.setdefault("attention", set()).add(m.group(1))
            continue
        m = re.search(r"device tier closed: hits=(\d+) misses=(\d+)",
                      line)
        if m:
            facts["tier_hits"] = int(m.group(1))
            facts["tier_misses"] = int(m.group(2))
            continue
        m = re.search(
            r"xla compile #1 of (\S+): call ([\d.]+)s, "
            r"cost fetch ([\d.]+)s", line)
        if m:
            facts["compiles"][m.group(1)] = {
                "first_call_secs": float(m.group(2)),
                "cost_fetch_secs": float(m.group(3)),
            }
    return facts


def check_tasks(events_dir, expected_tasks):
    """The master's journal must show every training task dispatched
    once and reported done once, with nothing requeued."""
    dispatched, done, bad = {}, {}, []
    for path in glob.glob(os.path.join(events_dir, "master-*.ndjson")):
        for line in read(path).splitlines():
            event = json.loads(line)
            kind, task = event.get("event"), event.get("task")
            if kind == "task_dispatch":
                dispatched[task] = dispatched.get(task, 0) + 1
            elif kind == "task_report" and event.get("ok"):
                done[task] = done.get(task, 0) + 1
            elif kind in ("task_requeue", "job_failed") or (
                kind == "task_report" and not event.get("ok")
            ):
                bad.append(event)
    problems = []
    if bad:
        problems.append("tasks failed or were requeued: %s" % bad[:3])
    if len(dispatched) != expected_tasks:
        problems.append(
            "%d tasks dispatched, expected %d"
            % (len(dispatched), expected_tasks)
        )
    if any(n != 1 for n in dispatched.values()) or done != dispatched:
        problems.append(
            "not every task done exactly once: dispatched=%s done=%s"
            % (dispatched, done)
        )
    return problems


def check_training(facts, expected_steps, expect, spawn_time):
    """Shared checks + the per-phase report."""
    problems = []
    if facts.get("platform") != expect["platform"]:
        problems.append(
            "worker reports platform %r, expected %r"
            % (facts.get("platform"), expect["platform"])
        )
    steps = facts["steps"]
    losses = [loss for _, _, loss in steps]
    report = {
        "steps": len(steps), "device_count": facts.get("device_count"),
    }
    if [n for n, _, _ in steps] != list(range(1, expected_steps + 1)):
        problems.append(
            "expected steps 1..%d, worker logged %s"
            % (expected_steps, [n for n, _, _ in steps])
        )
    if not all(l == l and abs(l) != float("inf") for l in losses):
        problems.append("non-finite loss: %s" % losses)
    if len(steps) >= 8:
        # window means: one batch's loss is noisy
        k = max(2, len(steps) // 8)
        first, last = sum(losses[:k]) / k, sum(losses[-k:]) / k
        report.update(
            first_loss=round(losses[0], 4), last_loss=round(losses[-1], 4),
            first_window_mean=round(first, 4),
            last_window_mean=round(last, 4),
            secs_to_first_step=round(steps[0][1] - spawn_time, 1),
            secs_rest=round(steps[-1][1] - steps[0][1], 1),
        )
        if not last < first:
            problems.append(
                "loss did not fall: first %d steps mean %.4f, last %d "
                "mean %.4f" % (k, first, k, last)
            )
    report["compiles"] = facts["compiles"]
    return problems, report


# ---------------------------------------------------------------------
# the two phases


def run_job(children, workdir, cfg, expect, worker_args, worker_env=None):
    """master.main + worker.main over the phase's data, then the
    checks every phase shares; returns (problems, worker facts,
    report, logs)."""
    events_dir = os.path.join(workdir, "events")
    os.makedirs(events_dir, exist_ok=True)
    data_dir = os.path.join(workdir, "data")
    logs = {
        "master": os.path.join(workdir, "master.log"),
        "worker": os.path.join(workdir, "worker.log"),
    }
    port = free_port()
    common = [
        "--model_zoo", cfg["model_zoo"],
        "--training_data", data_dir,
        "--minibatch_size", str(cfg["minibatch"]),
    ]
    master = children.start(
        ["-m", "elasticdl_tpu.master.main", "--port", str(port),
         "--records_per_task",
         str(cfg["minibatch"] * cfg["steps_per_task"]),
         "--num_epochs", "1",
         # the first step compiles for a while; not a dead worker
         "--task_timeout_secs", "600"] + common,
        child_env("cpu", events_dir), logs["master"],
    )
    wait_port(port, master)
    spawn_time = time.time()
    worker = children.start(
        ["-m", "elasticdl_tpu.worker.main",
         "--master_addr", "localhost:%d" % port, "--worker_id", "0",
         "--log_loss_steps", "1"] + common + worker_args,
        child_env(expect["worker_platforms"], events_dir,
                  **(worker_env or {})),
        logs["worker"],
    )
    problems = []
    rc = wait_exit(worker, "worker", PHASE_TIMEOUT_SECS)
    if rc != 0:
        problems.append("worker exited %d" % rc)
    rc = wait_exit(master, "master", 60)
    if rc != 0:
        problems.append("master exited %d" % rc)
    problems += check_tasks(events_dir, cfg["tasks"])
    facts = parse_worker_log(read(logs["worker"]))
    more, report = check_training(
        facts, cfg["steps_per_task"] * cfg["tasks"], expect, spawn_time
    )
    return problems + more, facts, report, logs


def run_dense_phase(children, workdir, cfg, expect):
    os.makedirs(workdir)
    write_token_records(os.path.join(workdir, "data"), cfg)
    problems, facts, report, logs = run_job(
        children, workdir, cfg, expect,
        ["--compute_dtype", "bfloat16"],
    )
    attention = sorted(facts.get("attention", ()))
    report["attention"] = attention
    if attention != [expect["attention"]]:
        problems.append(
            "attention resolved to %s, expected only %r"
            % (attention, expect["attention"])
        )
    return problems, report, logs


def run_sparse_phase(children, workdir, cfg, expect, so_mtime):
    os.makedirs(workdir)
    write_ctr_records(os.path.join(workdir, "data"), cfg)
    opt_args = "lr=0.001"
    ps_ports = [free_port(), free_port()]
    ps_procs, logs = [], {}
    for ps_id, port in enumerate(ps_ports):
        logs["ps%d" % ps_id] = os.path.join(workdir, "ps%d.log" % ps_id)
        ps_procs.append(children.start(
            ["-m", "elasticdl_tpu.ps.server", "--ps_id", str(ps_id),
             "--num_ps_pods", "2", "--port", str(port),
             "--opt_type", "adam", "--opt_args", opt_args,
             "--use_async", "1"],
            child_env("cpu"), logs["ps%d" % ps_id],
        ))
    for port, proc in zip(ps_ports, ps_procs):
        wait_port(port, proc)
    problems, facts, report, job_logs = run_job(
        children, workdir, cfg, expect,
        ["--sparse_pipeline", "1", "--ps_addrs",
         ",".join("localhost:%d" % p for p in ps_ports)],
        worker_env={
            "EDL_DEVICE_TIER": "1",
            # the tier trains resident rows with the PS's optimizer
            "EDL_DEVICE_TIER_OPT": "adam",
            "EDL_DEVICE_TIER_OPT_ARGS": opt_args,
        },
    )
    logs.update(job_logs)
    # the PS has no end of job of its own: SIGTERM is its orderly stop
    for ps_id, proc in enumerate(ps_procs):
        proc.terminate()
        rc = wait_exit(proc, "ps-%d" % ps_id, 60)
        if rc != 0:
            problems.append("ps-%d exited %d" % (ps_id, rc))
    backends = []
    for ps_id in range(len(ps_ports)):
        m = re.search(
            r"embedding store backend: (\w+)", read(logs["ps%d" % ps_id])
        )
        backends.append(m.group(1) if m else None)
    report["store_backend"] = backends
    if backends != ["native"] * len(ps_ports):
        problems.append(
            "PS store backends %s, expected native (is make/g++ "
            "missing? see the PS logs)" % backends
        )
    if os.path.getmtime(NATIVE_SO) != so_mtime:
        problems.append("the native store was rebuilt behind the run")
    report["tier_hits"] = facts.get("tier_hits")
    report["tier_misses"] = facts.get("tier_misses")
    if not facts.get("tier_hits"):
        problems.append(
            "device tier reported no hits (%r)" % facts.get("tier_hits")
        )
    return problems, report, logs


# ---------------------------------------------------------------------


def main():
    if not os.path.isdir(os.path.join(ROOT, "elasticdl_tpu")):
        sys.exit(
            "chip_smoke: no elasticdl_tpu package next to %s — this "
            "script drives the program, it is not the program"
            % os.path.basename(__file__)
        )
    probe = probe_device()
    device = probe["device"]
    if device["platform"] != ON_CHIP["platform"]:
        sys.exit(
            "chip_smoke: jax found no accelerator (platform=%s, "
            "JAX_PLATFORMS=%r). This check runs on the chip only and "
            "never trains on the CPU under a device's name."
            % (device["platform"], os.environ.get("JAX_PLATFORMS"))
        )
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    print("chip_smoke: %s" % json.dumps(probe), flush=True)
    so_mtime = build_native_store()

    children = Children()
    summary = dict(probe, ok=False, phases={})
    failed = False
    try:
        for name, run in (
            ("dense", lambda d: run_dense_phase(
                children, d, DENSE, ON_CHIP)),
            ("sparse", lambda d: run_sparse_phase(
                children, d, SPARSE, ON_CHIP, so_mtime)),
        ):
            logs = {}
            try:
                problems, report, logs = run(os.path.join(WORK_DIR, name))
            except SmokeFailure as e:
                problems, report = [str(e)], {}
                logs = {
                    os.path.basename(p)[:-4]: p for p in glob.glob(
                        os.path.join(WORK_DIR, name, "*.log"))
                }
            finally:
                # the chip is free again before the next phase starts
                children.stop_all()
            summary["phases"][name] = report
            print("chip_smoke: %s phase: %s" % (
                name, json.dumps(report)), flush=True)
            if problems:
                failed = True
                print("chip_smoke: %s phase FAILED:" % name,
                      file=sys.stderr)
                for problem in problems:
                    print("  - %s" % problem, file=sys.stderr)
                for role, path in sorted(logs.items()):
                    print("---- tail of %s log (%s) ----\n%s" % (
                        role, path, tail(path)), file=sys.stderr)
    finally:
        children.stop_all()
        summary["ok"] = not failed
        with open(os.path.join(WORK_DIR, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
    print("chip_smoke: summary: %s" % json.dumps(summary, sort_keys=True))
    print(result_line(not failed, device), flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
