"""Shared by the harness's tests: where things are, how the command is
run here (worker on the CPU, tiny preset), and the contract's key sets."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
PRESET = os.path.join(HERE, "preset", "BENCHMARK.json")

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
TRACED_DEVICE_KEYS = DEVICE_KEYS | {"busy_s", "window_s"}


def load(path):
    with open(path) as f:
        return json.load(f)


def run_cell(cell, trace, tmp_path, manifest=PRESET, seconds=3, cwd=REPO):
    """The whole command, as the driver runs it; returns (process,
    parsed last stdout line or None)."""
    env = dict(os.environ)
    # the children place their compile cache through the environment
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    # conftest gives THIS process eight virtual devices; the preset's
    # configurations say how many their worker gets
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--manifest", manifest, "--workload", cell, "--seed", "3",
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=600,
    )
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    return proc, last
