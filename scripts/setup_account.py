"""A benchmark run's ``setup_s`` by part, from what the run left: the
two journals' start-up and teardown records (ISSUE 33), the worker's
log, ``refcheck.json`` and ``trace.flag``. The hand account PERF.md
Section 5 prints, and where a ``perf_opt`` issue on ``setup_s`` names
its stage.

    python scripts/setup_account.py chiprun_out/benchmark/<cell> \
        [--start <epoch> --end <epoch>]

``--start`` / ``--end`` are ``time.time()`` just before and after
``benchmark/run.py`` (the harness journals nothing of its own): with
them the account covers the whole command and names the harness's
remainders; without them it starts at the master's process and ends at
its last exit hook. The window's start is ``trace.flag`` (a traced
run) or the warm-up step's log line. Everything is on the epoch clock;
prints one JSON object, ``parts`` in the run's order with seconds
each, nested parts under ``of``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import logs, loop_ledger, setup_ledger  # noqa: E402


def load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def window_of(out, worker, cell):
    """(t0, t1): ``trace.flag`` holds t0 of a traced run; else the
    harness opened the window on seeing the warm-up step's line."""
    report = load(os.path.join(out, "report.json")) or {}
    try:
        with open(os.path.join(out, "trace.flag")) as f:
            t0 = float(f.read())
    except (OSError, ValueError):
        warm = (cell or {}).get("warmup_steps", 0)
        t0 = next(at for number, at, _ in worker["steps"] if number >= warm)
    return t0, t0 + report.get("window_s", 0.0)


def phases_s(event):
    return {name: ns / 1e9 for name, ns in event["phases"].items()}


def account(out, start=None, end=None, cell=None):
    with open(os.path.join(out, "worker.log"), errors="replace") as f:
        worker = logs.parse_worker_log(f.read())
    run = {"out": out, "worker": worker}
    run["window"] = t0, t1 = window_of(out, worker, cell)
    workers = loop_ledger.worker_events(run)
    masters = setup_ledger.master_events(run)
    m_start = setup_ledger.first(masters, "master_startup")
    m_stop = setup_ledger.first(masters, "master_teardown")
    w_start = setup_ledger.first(workers, "worker_startup")
    w_stop = setup_ledger.first(workers, "worker_teardown")
    requested = setup_ledger.first(workers, "drain_requested")
    master = setup_ledger.record_interval(m_start)
    startup = setup_ledger.record_interval(w_start)
    teardown = setup_ledger.record_interval(w_stop)
    stages = setup_ledger.step_stages(run) or {}
    compiles = w_start.get("compiles", {})
    parts = []

    def part(name, seconds, **of):
        parts.append(dict({"part": name, "seconds": round(seconds, 3)},
                          **({"of": of} if of else {})))

    if start is not None:
        part("harness: run.py to the master's process (data)",
             master[0] - start)
    part("master_startup", master[1] - master[0], **phases_s(m_start))
    part("gap: master ready to the worker's process",
         startup[0] - master[1])
    part("worker_startup", startup[1] - startup[0], **phases_s(w_start))
    part("warm-up: first step's return to the window", t0 - startup[1])
    part("window", t1 - t0)
    part("gap: window's end to SIGTERM", requested["signal_ts"] - t1)
    part("worker_exit", teardown[1] - requested["signal_ts"],
         task_in_flight=teardown[0] - requested["signal_ts"],
         **phases_s(w_stop))
    check = (load(os.path.join(out, "refcheck.json")) or {}).get("seconds")
    if end is not None:
        # the harness stops the master once the worker's process is
        # gone: from the worker's last exit hook to there the
        # interpreter and the runtime finish unseen by the program
        part("harness: worker's exit to run.py's end", end - teardown[1],
             **dict(check or {},
                    last_hook_to_master_sigterm=(
                        m_stop["start_ts"] - teardown[1] if m_stop
                        else None),
                    master_teardown=(
                        m_stop["wall_ns"] / 1e9 if m_stop else None)))
    total = sum(p["seconds"] for p in parts)
    program = setup_ledger.outside_window(
        [i for i in setup_ledger.program_intervals(run).values() if i],
        run["window"])
    return {
        "parts": parts,
        "total_s": round(total, 3),
        "outside_window_s": round(total - (t1 - t0), 3),
        "program_setup_s": round(program, 3),
        "step": {k: stages.get(k) for k in (
            "trace_s", "lower_s", "backend_s", "first_run_s", "cache",
            "retrieval_s", "saved_s")},
        "compiles": compiles,
        "programs_before_first_step": sum(
            c["requests"] for c in compiles.values()),
        "cache_misses": [
            {k: e.get(k) for k in ("module", "backend_s", "phase")}
            for e in workers if e.get("event") == "xla_cache_miss"
        ],
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("out")
    parser.add_argument("--start", type=float)
    parser.add_argument("--end", type=float)
    args = parser.parse_args(argv)
    cell = load(os.path.join(
        ROOT, "benchmark", "workloads",
        os.path.basename(os.path.normpath(args.out)) + ".json"))
    print(json.dumps(account(args.out, args.start, args.end, cell),
                     indent=1))


if __name__ == "__main__":
    main()
