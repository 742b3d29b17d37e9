"""The log and journal readers and the window's arithmetic."""

import statistics
import time

import pytest

from benchmark.lib import logs, window
from benchmark.lib.procs import HarnessFailure

LOG = """\
2026-09-26 17:49:34,980 INFO elasticdl_tpu.worker.main: devices: platform=tpu device_kind=TPU v5 lite local_devices=4 global_devices=4 processes=1
2026-09-26 17:49:36,725 INFO elasticdl_tpu.ops.attention: attention impl=auto resolved to pallas (backend=tpu, q=(4, 8, 2048, 256) bhsd)
2026-09-26 17:49:41,224 INFO elasticdl_tpu.observability.device: xla compile #1 of spmd_train_step: call 36.02s, cost fetch 0.04s
2026-09-26 17:49:42,000 INFO elasticdl_tpu.worker.worker: step 8 loss 10.500000
2026-09-26 17:49:44,000 INFO elasticdl_tpu.worker.worker: step 16 loss 9.250000
2026-09-26 17:49:45,100 WARNING elasticdl_tpu.observability.device: xla recompile #2 of spmd_train_step (1.25s): features: int32[4,128] -> int32[3,128]
2026-09-26 17:49:46,500 INFO elasticdl_tpu.worker.worker: step 24 loss 8.000000
"""


def test_parse_worker_log():
    facts = logs.parse_worker_log(LOG)
    assert (facts["platform"], facts["device_kind"]) == (
        "tpu", "TPU v5 lite")
    assert facts["device_count"] == 4 and facts["local_devices"] == 4
    assert facts["attention"] == ["pallas"]
    assert [(n, loss) for n, _, loss in facts["steps"]] == [
        (8, 10.5), (16, 9.25), (24, 8.0)]
    assert facts["steps"][1][1] - facts["steps"][0][1] == pytest.approx(2.0)
    first, second = facts["compiles"]
    assert (first["fn"], first["n"], first["call_s"]) == (
        "spmd_train_step", 1, 36.02)
    assert (second["fn"], second["n"], second["call_s"]) == (
        "spmd_train_step", 2, 1.25)
    # timestamps are local wall-clock seconds, like time.time()
    assert abs(first["at"] - time.mktime(
        time.strptime("2026-09-26 17:49:41", "%Y-%m-%d %H:%M:%S"))
    ) < 1.0


def test_samples_per_second_over_the_lines_inside_the_window():
    facts = logs.parse_worker_log(LOG)
    t8, t16, t24 = (s[1] for s in facts["steps"])
    run = {"worker": facts, "traffic": {"minibatch": 4},
           "window": (t8 - 0.5, t24 + 0.5)}
    # ISSUE 22's formula: 16 steps of 4 records in the 4.5 s between
    # the first and the last line inside the window
    assert window.samples_per_second(run) == pytest.approx(64 / 4.5)
    # the two intervals ran at 8 steps in 2.0 s and 8 in 2.5 s
    assert window.interval_rates(run) == pytest.approx(
        [4 * 8 / 2.0, 4 * 8 / 2.5])
    run["window"] = (t8 + 0.5, t24 + 0.5)
    assert window.samples_per_second(run) == pytest.approx(32 / 2.5)
    run["window"] = (t16 + 0.5, t24 + 0.5)
    with pytest.raises(HarnessFailure, match="fewer than two"):
        window.samples_per_second(run)


def test_a_stall_lowers_samples_per_second():
    """What the window holds counts: ten seconds lost to the input
    path, a save or a dispatch hiccup lower the judged metric, and the
    interval rates beside it say it was one stall and not a slower
    step."""
    steps = [(8 * k, 100.0 + 2.0 * k, 5.0) for k in range(1, 8)]
    # ten seconds lost between the fourth and the fifth line
    steps = steps[:4] + [(n, t + 10.0, loss) for n, t, loss in steps[4:]]
    run = {"worker": {"steps": steps}, "traffic": {"minibatch": 4},
           "window": (0.0, 1000.0)}
    assert window.samples_per_second(run) == pytest.approx(4 * 48 / 22.0)
    rates = window.interval_rates(run)
    assert sorted(rates)[len(rates) // 2] == pytest.approx(16.0)
    assert min(rates) == pytest.approx(4 * 8 / 12.0)


def test_stall_share_leaves_the_profiler_s_own_stall_out(tmp_path):
    from benchmark.metrics import stall_share

    steps = [(8 * k, 100.0 + 2.0 * k, 5.0) for k in range(1, 8)]
    steps = steps[:4] + [(n, t + 10.0, loss) for n, t, loss in steps[4:]]
    run = {"worker": {"steps": steps}, "traffic": {"minibatch": 4},
           "window": (0.0, 1000.0), "trace": False, "out": str(tmp_path)}
    # untraced: 10 of the 22 s between the first and the last line
    assert stall_share.read(run) == pytest.approx(100 * 10 / 22.0)
    # traced, the profiler stopped at step 32 (its line is written,
    # then the probe's callback stops the trace): the stall before the
    # line of step 40 is its own, and the lines from step 40 on run
    # alike
    run["trace"] = True
    assert stall_share.read(run) is None  # no trace.done: never ended
    (tmp_path / "trace.done").write_text("32\n")
    assert stall_share.read(run) == pytest.approx(0.0, abs=1e-9)
    # a stall after that is the program's
    run["worker"]["steps"] = steps[:6] + [(56, steps[6][1] + 1.0, 5.0)]
    assert stall_share.read(run) == pytest.approx(
        100 * (1 - (16 / 5.0) / statistics.median([4.0, 8 / 3.0])))
    # too few lines after the trace: nothing to read
    (tmp_path / "trace.done").write_text("50\n")
    assert stall_share.read(run) is None


def test_count_tasks():
    events = [
        {"event": "task_dispatch", "task": 1, "ts": 10.0},
        {"event": "task_report", "task": 1, "ok": True, "ts": 11.0},
        {"event": "task_dispatch", "task": 2, "ts": 12.0},
        {"event": "task_report", "task": 2, "ok": False, "ts": 13.0},
        {"event": "task_dispatch", "task": 3, "ts": 14.0},
        {"event": "task_requeue", "task": 3, "ts": 15.0},
        {"event": "task_dispatch", "task": 4, "ts": 30.0},
        {"event": "task_requeue", "task": 4, "ts": 31.0},
    ]
    # task 4 was dispatched after the stop signal: not attempted
    assert logs.count_tasks(events, until=20.0) == (3, 2)
    assert logs.count_tasks(events, until=40.0) == (4, 3)
    assert logs.count_tasks([], until=40.0) == (0, 0)


def test_mfu_is_required_flops_times_rate_over_chips_times_peak():
    import os

    from benchmark.flops import dense_decoder
    from benchmark.metrics import mfu, samples_per_s
    from tests.benchmark_harness import _common as common

    facts = logs.parse_worker_log(LOG)
    t8, _, t24 = (s[1] for s in facts["steps"])
    run = {
        "worker": facts, "window": (t8 - 0.5, t24 + 0.5), "chips": 4,
        "traffic": {"minibatch": 4, "seq_len": 2048},
        "config": common.load(os.path.join(
            common.REPO, "benchmark/configs/pythia-1b/config.json")),
        "peaks_table": common.load(os.path.join(
            common.REPO, "benchmark/lib/peaks.json")),
        "flops": dense_decoder,
    }
    rate = samples_per_s.read(run)
    assert rate == pytest.approx(64 / 4.5)
    # 5.853 GFLOP a token x 2048 tokens a sample
    expected = 100 * 5.852626944e9 * 2048 * rate / (4 * 197e12)
    assert mfu.read(run) == pytest.approx(expected)
    # a configuration that names no count reports no utilization
    run["flops"] = None
    assert mfu.read(run) is None


def test_memory_peak_is_buffers_plus_program_temporaries():
    from benchmark.metrics import peak_hbm_gb

    run = {"memory": {"step": 8, "devices": [
        {"id": 0, "peak_bytes_in_use": 3_000_000_000,
         "peak_bytes_reserved": 12_500_000_000, "bytes_limit": 16.9e9},
        {"id": 1, "peak_bytes_in_use": 3_100_000_000,
         "peak_bytes_reserved": None, "bytes_limit": 16.9e9},
        {"id": 2, "peak_bytes_in_use": None},
    ]}}
    assert window.memory_peaks(run) == [15_500_000_000, 3_100_000_000]
    assert peak_hbm_gb.read(run) == 15.5
    assert window.memory_peaks({"memory": None}) == []
    assert peak_hbm_gb.read({"memory": None}) is None


def test_probe_writes_memory_only_when_the_peaks_grow(tmp_path, monkeypatch):
    """Observation must not disturb: once the allocator's peaks stand
    still (after the warm-up), the probe writes no file, so the window
    holds none of its writes."""
    import json

    from benchmark.lib import probe

    peaks = iter([100, 100, 100, 250, 250])

    class Device:
        id = 0

        def memory_stats(self):
            return {"peak_bytes_in_use": next(peaks), "bytes_limit": 1000}

    monkeypatch.setattr(probe.jax, "local_devices", lambda: [Device()])
    monkeypatch.setenv(probe.OUT_ENV, str(tmp_path))
    monkeypatch.setenv("EDLBENCH_EVERY", "2")
    monkeypatch.setenv("EDLBENCH_TRACE", "0")
    (callback,) = probe.callbacks()
    written = []
    for step in range(0, 10):
        callback.on_batch_end(step, loss=None)
        with open(tmp_path / "memory.json") as f:
            written.append(json.load(f)["step"])
    # asked on steps 0, 2, 4, 6, 8; written on 0 and on 6 (the peak grew)
    assert written == [0, 0, 0, 0, 0, 0, 6, 6, 6, 6]
    with open(tmp_path / "memory.json") as f:
        assert json.load(f)["devices"][0]["peak_bytes_in_use"] == 250
    monkeypatch.delenv(probe.OUT_ENV)
    assert probe.callbacks() == []
