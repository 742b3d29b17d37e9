"""``lib/step_memory.py`` and the five metrics it feeds (PR 47): on the
journal lines a worker wrote on a v5e (``data/step_memory_run``: the
``xla_compile`` event of ``pythia1b-s2k``'s train step, its three
``device_memory`` events and the two records around them; my chip run,
PR 47, seed 2147486001), on journals without them, and through the
whole command on the CPU."""

import importlib
import json
import os
import shutil

import pytest

from benchmark.lib import loop_ledger, step_memory
from tests.benchmark_harness import _common as common

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "step_memory_run")
MEMORY_MANIFEST = os.path.join(HERE, "preset", "MEMORY.json")
CELL = "tiny-lm-memory"
FIVE = ("step_peak_gb", "step_temp_gb", "step_arguments_gb",
        "worker_hbm_peak_gb", "peak_live_named_share")
# read off the recorded lines by hand
EXPECTED = {
    "step_peak_gb": 12.551736832,
    "step_temp_gb": 5.40451072,
    "step_arguments_gb": 7.305283072,
    # at teardown: peak_in_use 7,462,874,624 + peak_reserved
    # 5,326,143,488; the harness's own callback read the same
    # (``memory_peak_bytes`` 12,789,018,112 in that run's result line)
    "worker_hbm_peak_gb": 12.789018112,
    # walk_peak 12,853,719,578 less ``other`` 595,759,636 and
    # ``unnamed`` 32,770
    "peak_live_named_share": 95.36482492569903,
}


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


def recorded_run(out=RECORDED):
    return {"out": out, "window": (0.0, 1e12), "trace": True}


def journal_run(tmp_path, events):
    events_dir = tmp_path / "events"
    events_dir.mkdir(exist_ok=True)
    with open(events_dir / "worker-0-77.events.ndjson", "w") as f:
        for event in events:
            f.write(json.dumps(event) + "\n")
        f.write('{"event": "device_mem')  # torn by the kill at the end
    return recorded_run(str(tmp_path))


def recorded_events():
    return list(loop_ledger.worker_events(recorded_run()))


@pytest.fixture
def scratch_copy(tmp_path):
    """The recorded run where a reader may leave a file."""
    shutil.copytree(RECORDED, tmp_path / "run")
    return recorded_run(str(tmp_path / "run"))


@pytest.mark.parametrize("name", FIVE)
def test_each_reader_over_the_recorded_journal(name, scratch_copy):
    assert reader(name).read(scratch_copy) == pytest.approx(
        EXPECTED[name], rel=1e-12)


def test_what_the_recorded_lines_say_of_the_chip():
    """The finding the metrics are for: the compiler's peak, what the
    allocator reserved and what it held."""
    event = step_memory.step_event(recorded_run())
    memory, live = event["memory"], event["peak_live"]
    assert event["fn"] == "train_step" and memory["peak_from"] == "compiler"
    assert live["walk_over_compiler"] == 1.0241
    assert len(live["groups"]) <= 12
    assert sum(g["bytes"] for g in live["groups"]) == live["walk_peak"]
    first, second, last = step_memory.device_memory(recorded_run())
    assert [e["at"] for e in (first, second, last)] == [
        "state_init", "first_step", "teardown"]
    (state,), (loaded,) = first["devices"], last["devices"]
    # the state on the device and no step program loaded: the step's
    # arguments less the batch, to a megabyte
    assert state["in_use"] == pytest.approx(memory["arguments"], abs=2e6)
    assert state["reserved"] < 2e6
    # once the step is loaded: its code beside the buffers, and a
    # reserve the size of its temporaries
    assert loaded["in_use"] - state["in_use"] == pytest.approx(
        memory["code"], abs=2e6)
    assert loaded["reserved"] == pytest.approx(
        memory["temporaries"], rel=0.02)
    # the allocator's peak lies over the compiler's by the code and by
    # what the reserve holds beyond the step's fullest point
    over = loaded["peak_in_use"] + loaded["peak_reserved"] - memory["peak"]
    assert 0 < over < 0.03 * memory["peak"]
    assert last["limit_bytes"] == 16909336064 and last["fullest"] == 0


def test_the_reader_leaves_step_memory_json(scratch_copy):
    reader("peak_live_named_share").read(scratch_copy)
    body = common.load(os.path.join(scratch_copy["out"], "step_memory.json"))
    assert body["fn"] == "train_step"
    assert body["memory"]["peak"] == 12551736832
    assert body["peak_live"]["instruction"] == "fusion.2005"
    assert [e["at"] for e in body["device_memory"]] == [
        "state_init", "first_step", "teardown"]
    assert body["cost_fetch_seconds"] == 0.4301


@pytest.mark.parametrize("name", FIVE)
def test_absent_records_give_none(tmp_path, name):
    run = journal_run(tmp_path, [
        {"event": "worker_startup", "ts": 1.0, "phases": {}},
        # the parent's event: no count of memory
        {"event": "xla_compile", "fn": "train_step", "compiles": 1,
         "collectives": None},
        # a compile whose program was not read carries null
        {"event": "xla_compile", "fn": "spmd_train_step", "compiles": 1,
         "memory": None, "peak_live": None},
    ])
    assert reader(name).read(run) is None
    assert not os.path.exists(tmp_path / "step_memory.json")
    shutil.rmtree(tmp_path / "events")
    assert reader(name).read(recorded_run(str(tmp_path))) is None


def test_only_the_train_step_s_event_counts(tmp_path):
    step = next(e for e in recorded_events() if e["event"] == "xla_compile")
    other = dict(step, fn="spmd_eval_step",
                 memory=dict(step["memory"], peak=7))
    run = journal_run(tmp_path, [other, dict(step, fn="spmd_train_step")])
    assert reader("step_peak_gb").read(run) == EXPECTED["step_peak_gb"]


@pytest.mark.parametrize("ratio, reports", [
    (0.8499, False), (0.85, True), (1.0, True), (1.15, True),
    (1.1501, False), (None, False)])
def test_an_uncalibrated_walk_reports_nothing(tmp_path, ratio, reports):
    step = next(e for e in recorded_events() if e["event"] == "xla_compile")
    step = dict(step, peak_live=dict(
        step["peak_live"], walk_over_compiler=ratio))
    value = reader("peak_live_named_share").read(
        journal_run(tmp_path, [step]))
    assert (value is not None) == reports
    # the file is left either way: what the walk found is the lead
    assert os.path.exists(tmp_path / "step_memory.json")


def four_chips(at, peaks):
    return {"event": "device_memory", "at": at, "source": "allocator",
            "fullest": max(range(4), key=lambda i: peaks[i]),
            "devices": [
                {"id": i, "in_use": 6 * 10 ** 9, "reserved": 8 * 10 ** 9,
                 "peak_in_use": peak, "peak_reserved": 8 * 10 ** 9,
                 "limit": 16909336064} for i, peak in enumerate(peaks)]}


def test_four_devices_give_the_fullest_one_s_peak_not_a_sum(tmp_path):
    run = journal_run(tmp_path, [
        four_chips("first_step", [6.0e9, 6.1e9, 6.0e9, 6.0e9]),
        four_chips("teardown", [6.1e9, 6.2e9, 7.5e9, 6.0e9]),
    ])
    assert reader("worker_hbm_peak_gb").read(run) == pytest.approx(15.5)


def test_without_a_teardown_the_last_one_journaled_counts(tmp_path):
    """A worker that was killed journals no teardown."""
    run = journal_run(tmp_path, [
        four_chips("state_init", [5e9] * 4),
        four_chips("first_step", [6.0e9, 6.4e9, 6.0e9, 6.0e9]),
    ])
    assert reader("worker_hbm_peak_gb").read(run) == pytest.approx(14.4)


def test_a_backend_without_an_allocator_gives_no_device_s_peak(tmp_path):
    run = journal_run(tmp_path, [
        {"event": "device_memory", "at": "teardown",
         "source": "live_arrays", "devices": [], "fullest": None,
         "bytes_in_use": 1973776, "peak_bytes": 1973797,
         "limit_bytes": 0}])
    assert reader("worker_hbm_peak_gb").read(run) is None


def test_the_five_are_the_last_entries_of_the_manifest():
    per_layer = common.load(common.MANIFEST)["per_layer"]
    assert tuple(m["name"] for m in per_layer[-5:]) == FIVE
    layers = {m["name"]: m["layer"] for m in per_layer}
    for metric in per_layer[-5:]:
        # no ``workloads``: read in every cell, and no cell's pinned
        # list of its own entries is touched
        assert set(metric) == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] == "program_counter"
        assert metric["moves"] == "samples_per_s"
    assert {layers[n] for n in (
        "step_peak_gb", "step_temp_gb", "peak_live_named_share")} == {
            layers["step_compile_s"]}
    assert {layers[n] for n in (
        "step_arguments_gb", "worker_hbm_peak_gb")} == {
            layers["peak_hbm_gb"]}
    rehearsal = {
        m["name"] for m in common.load(MEMORY_MANIFEST)["per_layer"]}
    assert set(FIVE) <= rehearsal
    for name in FIVE:
        assert callable(reader(name).read) and name in reader(name).__doc__


# ---------------------------------------------------------------------
# the whole command


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    base = tmp_path_factory.mktemp("memory_rehearsal")
    proc, line = common.run_cell(
        CELL, 1, base, manifest=MEMORY_MANIFEST, seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    out = os.path.join(common.REPO, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    return {
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "worker": list(loop_ledger.worker_events({"out": out})),
        "memory": common.load(os.path.join(out, "step_memory.json")),
        "log": log, "out": out,
    }


def test_rehearsal_reports_the_compiler_s_three(rehearsal):
    metrics = rehearsal["metrics"]
    assert {"step_peak_gb", "step_temp_gb", "step_arguments_gb"} <= set(
        metrics)
    assert 0 < metrics["step_arguments_gb"] <= metrics["step_peak_gb"]
    # the CPU has no allocator, and its compiler's peak is not the
    # walk's: no device's peak and no share are reported from it
    assert "worker_hbm_peak_gb" not in metrics
    assert "peak_hbm_gb" not in metrics
    ratio = rehearsal["memory"]["peak_live"]["walk_over_compiler"]
    assert ("peak_live_named_share" in metrics) == (0.85 <= ratio <= 1.15)


def test_rehearsal_journals_the_device_s_memory_three_times(rehearsal):
    found = [e for e in rehearsal["worker"]
             if e["event"] == "device_memory"]
    assert [e["at"] for e in found] == [
        "state_init", "first_step", "teardown"]
    assert all(e["source"] == "live_arrays" for e in found)
    kinds = [e["event"] for e in rehearsal["worker"] if e["event"] in (
        "device_memory", "worker_startup", "worker_teardown")]
    assert kinds == [
        "device_memory", "worker_startup", "device_memory",
        "device_memory", "worker_teardown"]
    assert [e["at"] for e in rehearsal["memory"]["device_memory"]] == [
        "state_init", "first_step", "teardown"]


def test_rehearsal_s_log_has_a_memory_line_after_the_compile_line(
        rehearsal):
    lines = rehearsal["log"].splitlines()
    (at,) = [i for i, line in enumerate(lines)
             if "xla compile #1 of train_step: call " in line]
    assert "xla memory of train_step: arguments " in lines[at + 1]
    assert "; live at the peak (" in lines[at + 1]
    (event,) = [e for e in rehearsal["worker"]
                if e["event"] == "xla_compile"
                and e["fn"].endswith("train_step")]
    assert len(event["peak_live"]["groups"]) <= 12
    assert set(event) >= {"stages", "collectives", "kernels", "memory",
                          "peak_live", "cost_fetch_seconds"}
