"""Runs a cell several times and prints what the driver looks at: each
metric's median and spread (distance between the quartiles over the
median) in each set of runs, and how the sets' medians differ.

    python benchmark/spread.py --workload <cell> [--sets 2] [--runs 6]
                               [--seconds <run_seconds>] [--first-seed 1]

Every run is the benchmark's own command in a new process with another
``--seed``; the result lines are also kept in
``chiprun_out/benchmark/spread-<cell>.jsonl`` and each run's logs, journal
and report under ``chiprun_out/benchmark/spread-<cell>/<seed>/``, so that
a run that falls out of line can be read afterwards. For the chip: one
cell a call (``chiprun -- python benchmark/spread.py --workload <cell>``).
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def quartile_spread(values):
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / statistics.median(values)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--first-seed", type=int, default=1)
    parser.add_argument(
        "--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = parser.parse_args()
    with open(args.manifest) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out_dir, exist_ok=True)
    kept = os.path.join(out_dir, "spread-%s.jsonl" % args.workload)
    sets, seed = [], args.first_seed
    for s in range(args.sets):
        lines = []
        for _ in range(args.runs):
            proc = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--manifest", args.manifest, "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(seconds),
                 "--trace", "0"],
                capture_output=True, text=True, cwd=ROOT,
            )
            seed += 1
            shutil.copytree(
                os.path.join(out_dir, args.workload),
                os.path.join(out_dir, "spread-" + args.workload,
                             str(seed - 1)),
                ignore=shutil.ignore_patterns("data", "trace"),
                dirs_exist_ok=True,
            )
            if proc.returncode != 0:
                print("run failed (%d): %s" % (
                    proc.returncode, proc.stderr[-2000:]), flush=True)
                continue
            line = json.loads(proc.stdout.strip().splitlines()[-1])
            line["set"], line["seed"] = s, seed - 1
            lines.append(line)
            with open(kept, "a") as f:
                f.write(json.dumps(line) + "\n")
            print("set %d seed %d correct=%s %s" % (
                s, seed - 1, line["correct"], json.dumps({
                    k: round(v["value"], 4)
                    for k, v in line["metrics"].items()})), flush=True)
            if not line["correct"]:
                print(proc.stderr[-1500:], flush=True)
        sets.append(lines)
    names = sorted({n for lines in sets for l in lines for n in l["metrics"]})
    for name in names:
        medians = []
        for s, lines in enumerate(sets):
            values = [l["metrics"][name]["value"] for l in lines
                      if name in l["metrics"]]
            if not values:
                continue
            # the first run of a checkout compiles: the driver leaves
            # it out of setup_s
            if name == "setup_s" and s == 0:
                values = values[1:] or values
            medians.append(statistics.median(values))
            print("%s set %d: n=%d median=%.6g spread=%.3f%% min=%.6g "
                  "max=%.6g" % (name, s, len(values), medians[-1],
                                100 * quartile_spread(values),
                                min(values), max(values)), flush=True)
        if len(medians) > 1:
            print("%s second median vs first: %+.3f%%" % (
                name, 100 * (medians[1] / medians[0] - 1)), flush=True)


if __name__ == "__main__":
    main()
