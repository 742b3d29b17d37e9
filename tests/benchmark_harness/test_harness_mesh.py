"""The whole command on four virtual CPU devices with ``--mesh fsdp=4``:
the worker picks ``SpmdTrainer``, the state is sharded, and the harness
reports the four devices the cell names."""

import os

from tests.benchmark_harness import _common as common


def test_fsdp4_cell_on_four_virtual_devices(tmp_path):
    proc, line = common.run_cell("tiny-lm-fsdp4", 0, tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert set(line) == common.RESULT_KEYS
    assert set(line["device"]) == common.DEVICE_KEYS
    assert line["device"]["count"] == 4
    assert line["correct"] is True, proc.stderr[-3000:]
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-lm-fsdp4")
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert "'fsdp': 4" in log
    assert "xla compile #1 of spmd_train_step" in log
    assert "split into 4 shards of (2, 128)" in log
    # the reference check ran on one device of the four
    report = common.load(os.path.join(out, "report.json"))
    assert report["refcheck"]["ok"] is True
    assert report["refcheck"]["device"]["count"] == 4
