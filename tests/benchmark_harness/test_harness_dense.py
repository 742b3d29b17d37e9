"""The whole command on the dense path at a tiny preset, worker on the
CPU: the last stdout line must have the contract's keys exactly (PR
21's lesson: validate the line against the key set in a test)."""

from tests.benchmark_harness import _common as common


def test_untraced_run_prints_the_end_to_end_line(tmp_path):
    proc, line = common.run_cell("tiny-lm-s128", 0, tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert set(line) == common.RESULT_KEYS
    assert set(line["device"]) == common.DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    manifest = common.load(common.PRESET)
    assert set(line["metrics"]) == {
        m["name"] for m in manifest["end_to_end"]
    }
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    for name, metric in line["metrics"].items():
        assert set(metric) == {"value", "unit"}
        assert metric["unit"] == units[name]
        assert isinstance(metric["value"], float) and metric["value"] > 0
    # every line but the last is commentary; none of them is JSON with
    # the result's keys
    assert "tiny-lm-s128" in proc.stdout.splitlines()[-2]
    # the run's artefacts, and nothing of the program still running
    report = common.load(
        common.REPO + "/chiprun_out/benchmark/tiny-lm-s128/report.json")
    assert report["problems"] == []
    assert report["refcheck"]["ok"] is True
    assert max(report["refcheck"]["errors"].values()) < 0.08
