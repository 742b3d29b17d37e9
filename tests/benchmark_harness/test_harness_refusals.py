"""No result without a chip, none without the program, none for a
worker that trained on another device than the cell names."""

import os
import shutil

import pytest

from benchmark import run as bench_run
from benchmark.lib.procs import HarnessFailure
from tests.benchmark_harness import _common as common


def test_real_cell_without_a_chip_exits_nonzero_and_prints_no_result(
        tmp_path):
    # the sandbox has no accelerator: the worker is started with
    # JAX_PLATFORMS=tpu,cpu and dies, it never trains on the CPU
    proc, line = common.run_cell(
        "pythia1b-s2k", 0, tmp_path, manifest=common.MANIFEST)
    assert proc.returncode != 0
    assert line is None or not (set(line) & common.RESULT_KEYS)
    assert "worker exited" in proc.stderr


def test_alone_with_its_own_files_it_reports_nothing(tmp_path):
    # a directory that holds only BENCHMARK.json and the files under
    # ``paths``: no program to measure
    manifest = common.load(common.MANIFEST)
    shutil.copy(common.MANIFEST, tmp_path / "BENCHMARK.json")
    for path in manifest["paths"]:
        shutil.copytree(
            os.path.join(common.REPO, path), tmp_path / path,
            ignore=shutil.ignore_patterns("__pycache__"))
    proc, line = common.run_cell(
        "pythia1b-s2k", 0, tmp_path, cwd=str(tmp_path),
        manifest=str(tmp_path / "BENCHMARK.json"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no elasticdl_tpu package" in proc.stderr


@pytest.mark.parametrize("facts, chips", [
    ({"platform": "cpu", "device_kind": "cpu", "device_count": 1}, 1),
    ({"platform": "tpu", "device_kind": "TPU v5 lite",
      "device_count": 1}, 4),
    ({}, 1),
])
def test_wrong_device_is_refused(facts, chips):
    run = {"worker": facts, "config": {}, "chips": chips, "name": "c"}
    with pytest.raises(HarnessFailure, match="No result is reported"):
        bench_run.check_device(run)


def test_right_device_is_accepted():
    bench_run.check_device({
        "worker": {"platform": "tpu", "device_kind": "TPU v5 lite",
                   "device_count": 4},
        "config": {}, "chips": 4, "name": "c",
    })


def test_unknown_device_kind_has_no_peak():
    from benchmark.lib import window

    run = {"worker": {"device_kind": "TPU v9"},
           "peaks_table": common.load(os.path.join(
               common.REPO, "benchmark", "lib", "peaks.json"))}
    with pytest.raises(HarnessFailure, match="no published peak"):
        window.peaks(run)
    run["worker"]["device_kind"] = "TPU v5 lite"
    assert window.peaks(run)["bf16_flops_per_s"] == 197e12
