"""A cell, a configuration, a model family (its FLOPs count, plain
reference and check), a traffic generator and per-layer metrics added
as new files only (``preset/``): DeepFM over two parameter-server
processes, traced, worker on the CPU."""

from tests.benchmark_harness import _common as common


def test_deepfm_over_two_ps_added_as_files_only(tmp_path):
    proc, line = common.run_cell("tiny-deepfm-zipf", 1, tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # a CPU run has no device trace: busy_s / window_s and the
    # breakdown are left out, and so is every metric that reads them
    assert set(line) == common.RESULT_KEYS
    assert set(line["device"]) == common.DEVICE_KEYS
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    metrics = line["metrics"]
    # the metric the preset added, read from the PS logs
    assert metrics["ps_store_native"] == {"value": 2.0, "unit": "count"}
    assert metrics["compiles_in_window"]["value"] == 0.0
    assert metrics["launch_to_first_step_s"]["value"] > 0
    per_layer = {
        m["name"] for m in common.load(common.PRESET)["per_layer"]
    }
    assert set(metrics) <= per_layer
    assert "device_idle_share" not in metrics
    assert "mfu" not in metrics
    # the family's own count (preset/flops/deepfm.py), read by a metric
    # the preset added: 135,096 FLOPs a record
    assert metrics["required_gflops_per_s"]["unit"] == "GFLOP/s"
    assert metrics["required_gflops_per_s"]["value"] > 0
    # and its own check against its own reference, through the general
    # comparison (lib/refcheck.py)
    report = common.load(
        common.REPO + "/chiprun_out/benchmark/tiny-deepfm-zipf/report.json")
    assert report["refcheck"]["ok"] is True
    assert set(report["refcheck"]["errors"]) == {
        "logits", "loss", "grad:emb_rows", "grad:Dense_0/kernel"}
