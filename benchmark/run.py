"""The benchmark: one cell a run, through the entry points a user calls.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                            --trace <0|1>

This parent never imports jax. It generates the cell's records from
``--seed``, starts ``python -m elasticdl_tpu.master.main`` (CPU), the
parameter servers a configuration asks for (CPU) and
``python -m elasticdl_tpu.worker.main`` (the chip; ``JAX_PLATFORMS=
tpu,cpu``, so a missing chip is a failure, never a CPU run), waits for
the cell's warm-up steps, measures for ``--seconds``, stops the job by
SIGTERM to the worker (its drain path), runs the reference check on the
freed chip and prints one JSON line. Everything a run leaves goes under
``chiprun_out/benchmark/<cell>/``.

Driven by data: ``BENCHMARK.json`` names cells, configurations and
metrics; this file finds ``workloads/<cell>.json``,
``traffic/<traffic>.json``, ``traffic/<generator>.py``,
``metrics/<metric>.py`` and ``flops/<count>.py`` by those names under
the manifest's ``paths``, and a configuration's file names its own
``zoo`` and ``check``. Adding a cell, a configuration, a model family
(FLOPs count, reference, check), a generator or a metric adds files
and edits none (``tests/benchmark_harness/preset`` adds a DeepFM job
over two parameter servers that way).
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import logs, procs, window  # noqa: E402
from benchmark.lib.procs import HarnessFailure  # noqa: E402
from benchmark.lib.refcheck import load_by_path  # noqa: E402

ROOT_MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
FIRST_STEP_TIMEOUT = 1000
DRAIN_TIMEOUT = 90
CHECK_TIMEOUT = 600


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Files:
    """Finds the benchmark's files by name under the manifest's paths."""

    def __init__(self, manifest_path):
        self.manifest = load_json(manifest_path)
        self.paths = self.manifest["paths"]

    def find(self, *parts):
        for path in self.paths:
            candidate = os.path.join(ROOT, path, *parts)
            if os.path.exists(candidate):
                return candidate
        raise HarnessFailure(
            "no %s under any of %s" % (os.path.join(*parts), self.paths)
        )

    def entry(self, section, name):
        for item in self.manifest[section]:
            if item["name"] == name:
                return item
        raise HarnessFailure("%s has no %r" % (section, name))

    def metrics_for(self, section, cell_name):
        return [
            m for m in self.manifest[section]
            if cell_name in m.get("workloads", [cell_name])
        ]

    def module(self, kind, name):
        return load_by_path(
            "edlbench_%s_%s" % (kind, name.replace("-", "_")),
            self.find(kind, name + ".py"),
        )


def params_string(params):
    return ";".join("%s=%s" % (k, v) for k, v in sorted(params.items()))


def start_job(children, run):
    """Master, parameter servers (if the configuration has any) and the
    worker; returns when the worker process exists."""
    cell, config, traffic, out = (
        run["cell"], run["config"], run["traffic"], run["out"]
    )
    events_dir = os.path.join(out, "events")
    os.makedirs(events_dir)
    cache = os.environ.get(CACHE_ENV) or os.path.join(ROOT, ".jax_cache")
    platform = config.get("platform", "tpu")
    zoo = config["zoo"]
    zoo = zoo if "/" not in zoo else os.path.join(ROOT, zoo)
    common = [
        "--model_zoo", zoo,
        "--training_data", run["data_dir"],
        "--minibatch_size", str(traffic["minibatch"]),
    ]
    port = procs.free_port()
    master = children.start(
        ["-m", "elasticdl_tpu.master.main", "--port", str(port),
         "--records_per_task",
         str(traffic["minibatch"] * cell["steps_per_task"]),
         "--num_epochs", "100000",
         # a cold first step compiles for minutes; not a dead worker
         "--task_timeout_secs", "1200"] + common,
        procs.child_env(ROOT, "cpu", EDL_EVENTS_DIR=events_dir),
        os.path.join(out, "master.log"),
    )
    ps_procs, ps_ports = [], []
    for ps_id in range(config.get("roles", {}).get("ps", 0)):
        ps_ports.append(procs.free_port())
        ps_procs.append(children.start(
            ["-m", "elasticdl_tpu.ps.server", "--ps_id", str(ps_id),
             "--num_ps_pods", str(config["roles"]["ps"]),
             "--port", str(ps_ports[-1])] + config.get("ps_flags", []),
            procs.child_env(ROOT, "cpu"),
            os.path.join(out, "ps%d.log" % ps_id),
        ))
    procs.wait_port(port, master)
    for ps_port, proc in zip(ps_ports, ps_procs):
        procs.wait_port(ps_port, proc)
    flags = list(config.get("worker_flags", []))
    if ps_ports:
        flags += ["--ps_addrs",
                  ",".join("localhost:%d" % p for p in ps_ports)]
    if config.get("compute_dtype"):
        flags += ["--compute_dtype", config["compute_dtype"]]
    if cell.get("mesh"):
        flags += ["--mesh", cell["mesh"]]
    if cell.get("model_params"):
        flags += ["--model_params", params_string(cell["model_params"])]
    env = dict(config.get("worker_env", {}))
    env.update({
        "EDL_EVENTS_DIR": events_dir,
        "EDLBENCH_OUT": out,
        "EDLBENCH_CONFIG": run["config_path"],
        "EDLBENCH_EVERY": cell["log_every"],
        "EDLBENCH_TRACE": "1" if run["trace"] else "0",
        "EDLBENCH_TRACE_STEPS": cell.get("trace_steps", 6),
        CACHE_ENV: cache,
        # every program of the run goes to the cache, however fast it
        # compiled, so a warm run compiles nothing
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
    })
    run["env"] = env
    run["worker_platforms"] = "tpu,cpu" if platform == "tpu" else platform
    run["spawn_time"] = time.time()
    worker = children.start(
        ["-m", "elasticdl_tpu.worker.main",
         "--master_addr", "localhost:%d" % port, "--worker_id", "0",
         "--log_loss_steps", str(cell["log_every"])] + common + flags,
        procs.child_env(ROOT, run["worker_platforms"], **env),
        os.path.join(out, "worker.log"),
    )
    return master, ps_procs, worker


def read_text(path):
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def wait_for_step(worker, log_path, step, timeout):
    """Blocks until the worker has logged a step >= ``step``."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        steps = [
            int(m.group(1))
            for m in logs.STEP_RE.finditer(read_text(log_path))
        ]
        if steps and steps[-1] >= step:
            return
        if worker.poll() is not None:
            raise HarnessFailure(
                "worker exited %d before step %d:\n%s"
                % (worker.returncode, step, procs.tail(log_path))
            )
        time.sleep(0.2)
    raise HarnessFailure(
        "worker did not reach step %d within %ds:\n%s"
        % (step, timeout, procs.tail(log_path))
    )


def start_refcheck(children, files, run):
    """The reference check, started beside the warming worker: it
    imports and builds, then waits on its standard input until the chip
    is free (``drive`` then writes it a line). None for a configuration
    that names no ``check``."""
    out, config = run["out"], run["config"]
    if not config.get("check"):
        return None
    spec = {
        "config": config, "cell": run["cell"], "traffic": run["traffic"],
        "seed": run["seed"],
        "zoo": os.path.join(ROOT, config["zoo"]),
        "check": os.path.join(ROOT, config["check"]),
        "reference": os.path.join(ROOT, config["reference"]),
        "generator": files.find(
            "traffic", run["traffic"]["generator"] + ".py"),
    }
    spec_path = os.path.join(out, "refcheck_spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = {k: v for k, v in run["env"].items()
           if not k.startswith("EDLBENCH_")}
    return children.start(
        [os.path.join(ROOT, "benchmark", "lib", "refcheck.py"),
         spec_path, os.path.join(out, "refcheck.json")],
        procs.child_env(ROOT, run["worker_platforms"], **env),
        os.path.join(out, "refcheck.log"), stdin=subprocess.PIPE,
    )


def start_trace_reduce(children, run):
    """In a traced run, the reduction of the newest trace (CPU)."""
    xplanes = []
    for base, _, names in os.walk(os.path.join(run["out"], "trace")):
        xplanes += [
            os.path.join(base, n) for n in names if n.endswith(".xplane.pb")
        ]
    if not xplanes:
        return None
    return children.start(
        [os.path.join(ROOT, "benchmark", "lib", "trace_reduce.py"),
         sorted(xplanes)[-1],
         os.path.join(run["out"], "trace_reduced.json")],
        procs.child_env(ROOT, "cpu"),
        os.path.join(run["out"], "trace_reduce.log"),
    )


def drive(children, files, run):
    """Runs the job and fills ``run`` with its artefacts; returns the
    list of problems that make the result incorrect."""
    cell, out = run["cell"], run["out"]
    problems = []
    generator = files.module("traffic", run["traffic"]["generator"])
    generator.generate(
        run["data_dir"], run["traffic"], run["config"], run["seed"]
    )
    if run["config"].get("roles", {}).get("ps"):
        # built once here so the PS processes' own make is a no-op
        # instead of concurrent links (chip_smoke.build_native_store)
        subprocess.run(
            ["make", "-C", os.path.join(ROOT, "elasticdl_tpu", "native")],
            check=True, capture_output=True,
        )
    master, ps_procs, worker = start_job(children, run)
    checks = {"refcheck": start_refcheck(children, files, run)}
    worker_log = os.path.join(out, "worker.log")
    wait_for_step(worker, worker_log, cell["warmup_steps"],
                  FIRST_STEP_TIMEOUT)
    t0 = time.time()
    if run["trace"]:
        with open(os.path.join(out, "trace.flag"), "w") as f:
            f.write("%f\n" % t0)
    while time.time() - t0 < run["seconds"]:
        if worker.poll() is not None:
            raise HarnessFailure(
                "worker exited %d inside the window:\n%s"
                % (worker.returncode, procs.tail(worker_log))
            )
        time.sleep(0.05)
    t1 = time.time()
    run["window"] = (t0, t1)
    # the worker's own orderly stop: finish the task, deregister, exit
    rc, killed = procs.stop(worker, DRAIN_TIMEOUT)
    if killed or rc != 0:
        problems.append(
            "worker exited %s after SIGTERM%s"
            % (rc, " (killed after %ds)" % DRAIN_TIMEOUT if killed else "")
        )
    if checks["refcheck"]:
        # the chip is free: the check may touch the backend now
        checks["refcheck"].stdin.write(b"go\n")
        checks["refcheck"].stdin.close()
    checks["trace_reduce"] = start_trace_reduce(children, run)
    for proc in [master] + ps_procs:
        procs.stop(proc, 30)
    for name, proc in checks.items():
        if proc is None:
            continue
        try:
            rc = proc.wait(timeout=CHECK_TIMEOUT)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            rc = "timeout"
        if rc != 0:
            problems.append("%s exited %s:\n%s" % (
                name, rc, procs.tail(os.path.join(out, name + ".log"), 15)))
    return problems


def gather(run):
    """Reads the artefacts the metric readers share."""
    out = run["out"]
    run["worker"] = logs.parse_worker_log(
        read_text(os.path.join(out, "worker.log")))
    run["journal"] = logs.read_journal(os.path.join(out, "events"))
    for key, name in (("memory", "memory.json"),
                      ("refcheck", "refcheck.json"),
                      ("reduced_trace", "trace_reduced.json")):
        path = os.path.join(out, name)
        run[key] = load_json(path) if os.path.exists(path) else None
    run["peaks_table"] = load_json(
        os.path.join(ROOT, "benchmark", "lib", "peaks.json"))


def check_correct(run):
    """What has to hold besides the processes' exit codes."""
    problems = []
    config, facts = run["config"], run["worker"]
    t0, t1 = run["window"]
    want = config.get("expect", {}).get("attention")
    if want and facts["attention"] != [want]:
        problems.append("attention resolved to %s, expected only %r"
                        % (facts["attention"], want))
    inside = [c for c in facts["compiles"] if t0 <= c["at"] <= t1]
    if inside:
        problems.append("compiled inside the window: %s" % inside)
    losses = [loss for _, _, loss in facts["steps"]]
    if not all(x == x and abs(x) != float("inf") for x in losses):
        problems.append("non-finite loss: %s" % losses)
    quarter = max(1, len(losses) // 4)
    first = sum(losses[:quarter]) / quarter
    last = sum(losses[-quarter:]) / quarter
    run["loss"] = {"first_quarter_mean": first, "last_quarter_mean": last,
                   "logged_steps": len(losses)}
    if not last < first:
        problems.append("loss did not fall: first quarter mean %.4f, "
                        "last %.4f" % (first, last))
    if config.get("check"):
        check = run["refcheck"]
        if not check or not check["ok"]:
            problems.append("reference check failed: %s"
                            % (check and check["errors"]))
        elif check["device"]["platform"] != facts["platform"]:
            problems.append("reference check ran on %s"
                            % check["device"]["platform"])
    return problems


def check_device(run):
    """No result for a run on another platform or device count than the
    cell names: a CPU run is never reported under a chip's metrics."""
    facts = run["worker"]
    want = run["config"].get("platform", "tpu")
    if (facts.get("platform") != want
            or facts.get("device_count") != run["chips"]):
        raise HarnessFailure(
            "the worker trained on platform=%s with %s devices; the cell "
            "%s needs %s x %d. No result is reported."
            % (facts.get("platform"), facts.get("device_count"),
               run["name"], want, run["chips"])
        )


def check_platform_key(config, manifest_path, name):
    """``platform`` in a configuration's file lets a rehearsal train on
    the CPU; the benchmark's own manifest never honours it, so no cell
    of ``BENCHMARK.json`` can report a CPU run."""
    if "platform" in config and os.path.realpath(
            manifest_path) == os.path.realpath(ROOT_MANIFEST):
        raise HarnessFailure(
            "configuration %s sets \"platform\": only a rehearsal's own "
            "manifest (--manifest) may name a platform; a cell of "
            "BENCHMARK.json trains on the chip. No result is reported."
            % name)


def device_report(run):
    facts = run["worker"]
    device = {"platform": facts["platform"], "kind": facts["device_kind"],
              "count": facts["device_count"]}
    peaks = window.memory_peaks(run)
    if peaks:
        device["memory_peak_bytes"] = max(peaks)
    elif facts["platform"] == "tpu":
        raise HarnessFailure("the worker left no memory statistics")
    else:
        # the CPU backend has no allocator statistics (rehearsals only)
        device["memory_peak_bytes"] = 0
    trace = run["reduced_trace"]
    if run["trace"] and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    return device


def measure(args, t_start):
    """Runs the cell and returns (result line, report for the files)."""
    files = Files(args.manifest)
    entry = files.entry("workloads", args.workload)
    config_path = os.path.join(
        ROOT, files.entry("configs", entry["config"])["file"])
    out = os.path.join(ROOT, "chiprun_out", "benchmark", args.workload)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    run = {
        "name": args.workload, "chips": entry["chips"],
        "cell": load_json(files.find("workloads", args.workload + ".json")),
        "traffic": load_json(
            files.find("traffic", entry["traffic"] + ".json")),
        "config": load_json(config_path), "config_path": config_path,
        "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "out": out,
        "data_dir": os.path.join(out, "data"),
    }
    config = run["config"]
    check_platform_key(config, args.manifest, entry["config"])
    run["flops"] = (
        files.module("flops", config["flops"])
        if config.get("flops") else None)
    children = procs.Children(ROOT)
    try:
        problems = drive(children, files, run)
    finally:
        children.stop_all()
    gather(run)
    check_device(run)
    problems += check_correct(run)
    t0, t1 = run["window"]
    attempted, failed = logs.count_tasks(run["journal"], until=t1)
    device = device_report(run)
    # everything that is not the window is set-up: data, launch, compile
    # or cache load, warm-up, drain, the reference check
    run["setup_s"] = (time.time() - t_start) - (t1 - t0)
    metrics = {}
    section = "per_layer" if run["trace"] else "end_to_end"
    for metric in files.metrics_for(section, args.workload):
        value = files.module("metrics", metric["name"]).read(run)
        if value is not None:
            metrics[metric["name"]] = {
                "value": value, "unit": metric["unit"]}
    result = {
        "correct": not problems, "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    trace = run["reduced_trace"]
    if run["trace"] and trace:
        result["breakdown"] = trace["breakdown"]
    inside = window.steps_inside(run)
    rates = window.interval_rates(run)
    report = {
        "workload": args.workload, "seed": args.seed, "problems": problems,
        "window_s": t1 - t0, "steps_in_window":
        inside[-1][0] - inside[0][0] if inside else 0,
        "tokens_per_s": window.samples_per_second(run)
        * run["traffic"].get("seq_len", 0),
        "interval_samples_per_s": {
            "median": statistics.median(rates), "slowest": min(rates),
            "fastest": max(rates), "intervals": len(rates)},
        "loss": run["loss"], "refcheck": run["refcheck"], "result": result,
    }
    with open(os.path.join(out, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    return result, report


def main(argv=None):
    t_start = time.time()
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest", default=ROOT_MANIFEST)
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "elasticdl_tpu")):
        sys.exit("benchmark: no elasticdl_tpu package in %s: this drives "
                 "the program, it is not the program" % ROOT)
    try:
        result, report = measure(args, t_start)
    except HarnessFailure as e:
        # no result line: a run that cannot be trusted reports nothing
        sys.exit("benchmark: %s" % e)
    for problem in report["problems"]:
        print("benchmark: PROBLEM: %s" % problem, file=sys.stderr)
    print("benchmark: %s" % json.dumps({
        k: report[k] for k in ("workload", "seed", "window_s",
                               "steps_in_window", "tokens_per_s",
                               "interval_samples_per_s", "loss")
    }))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
