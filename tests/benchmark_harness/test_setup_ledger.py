"""``lib/setup_ledger.py`` and the nine metrics it feeds (PR 33): on a
journal recorded from a CPU rehearsal whose answers are known
(``data/setup_run``: a cold run, 14 programs compiled), on journals
without the records, and through the whole command on the CPU, twice
against one compile cache."""

import importlib
import json
import os
import shutil

import pytest

from benchmark.lib import loop_ledger, setup_ledger
from benchmark.lib.logs import COMPILE_RE
from tests.benchmark_harness import _common as common

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "setup_run")
SETUP_MANIFEST = os.path.join(HERE, "preset", "SETUP.json")
CELL = "tiny-lm-setup"
# in the table's order (ISSUE 33); these follow ``held_pairs_over_share``
NINE = ("step_trace_lower_s", "step_backend_s", "step_first_run_s",
        "persistent_cache_misses", "init_programs_s", "imports_s",
        "worker_exit_s", "master_ready_s", "program_setup_s")
# the recorded run's window: ``trace.flag`` and the report's window_s
WINDOW = (1790597671.438796, 1790597671.438796 + 2.0061323642730713)
# read off the recorded journal by hand
EXPECTED = {
    "step_trace_lower_s": 0.6263 + 0.1481,
    "step_backend_s": 1.0399,
    "step_first_run_s": 0.014,
    "persistent_cache_misses": 14.0,
    # worker_init and state_init: trace, lower, backend of each
    "init_programs_s": (0.0042 + 0.0729 + 0.0384
                        + 0.185 + 0.2081 + 1.262),
    "imports_s": 3.180635964,
    # signal_ts to worker_teardown's start_ts + wall_ns
    "worker_exit_s": 1790597673.5090697 + 0.046726992 - 1790597673.453091,
    "master_ready_s": 3.895978196,
    # master_startup 3.896 + worker_startup 8.283 + warm-up 0.144 +
    # worker exit 0.103 + master_teardown 0.003: nothing overlaps here
    "program_setup_s": (3.895978196 + 8.283288095
                        + (WINDOW[0] - 1790597671.2949576)
                        + (1790597673.5557967 - 1790597673.453091)
                        + 0.003489235),
}


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


def recorded_run(out=RECORDED):
    return {"out": out, "window": WINDOW, "trace": True}


@pytest.mark.parametrize("name", NINE)
def test_each_reader_over_the_recorded_journal(name):
    assert reader(name).read(recorded_run()) == pytest.approx(
        EXPECTED[name], abs=1e-6)


def test_the_step_s_three_stages_are_its_call():
    run = recorded_run()
    (event,) = [
        e for e in loop_ledger.worker_events(run)
        if e["event"] == "xla_compile" and e["fn"] == "train_step"]
    three = sum(reader(name).read(run) for name in NINE[:3])
    assert three == pytest.approx(event["seconds"], abs=1e-3)
    # and the log line the older metric reads gives the same seconds
    line = ("xla compile #1 of train_step: call %.2fs, cost fetch 0.02s; "
            "stages trace 0.63s" % event["seconds"])
    assert float(COMPILE_RE.search(line).group(4)) == pytest.approx(
        three, abs=0.01)


def _copy_without(tmp_path, drop=(), strip=()):
    """The recorded run with some kinds of event left out and some
    fields stripped: a program from before the records existed."""
    out = tmp_path / "run"
    shutil.copytree(RECORDED, out)
    for path in (out / "events").glob("*.ndjson"):
        kept = []
        for line in path.read_text().splitlines():
            event = json.loads(line)
            if event["event"] in drop:
                continue
            for key in strip:
                event.pop(key, None)
            kept.append(json.dumps(event))
        path.write_text("\n".join(kept) + "\n")
    return str(out)


@pytest.mark.parametrize("name", NINE)
def test_absent_records_give_none(tmp_path, name):
    """The parent of PR 33 journals ``worker_startup`` with its phases
    and ``xla_compile`` without ``stages``, and none of the rest: only
    ``imports_s`` finds what it reads."""
    out = _copy_without(
        tmp_path,
        drop=("xla_cache_miss", "drain_requested", "master_startup",
              "master_teardown"),
        strip=("stages", "compiles", "start_ts"))
    value = reader(name).read(recorded_run(out))
    if name == "imports_s":
        assert value == pytest.approx(EXPECTED[name])
    else:
        assert value is None


@pytest.mark.parametrize("name", NINE)
def test_an_empty_journal_gives_none(tmp_path, name):
    (tmp_path / "events").mkdir()
    (tmp_path / "events" / "worker-0-5.events.ndjson").write_text(
        json.dumps({"event": "role_start", "ts": 120.0}) + "\n")
    assert reader(name).read(recorded_run(str(tmp_path))) is None


def test_a_warm_run_with_one_late_miss_counts_only_what_came_before(
        tmp_path):
    out = _copy_without(tmp_path)
    (path,) = (tmp_path / "run" / "events").glob("worker-*.ndjson")
    with open(path, "a") as f:
        f.write(json.dumps({
            "event": "xla_cache_miss", "ts": WINDOW[0] + 1.0,
            "module": "jit(late)", "backend_s": 0.2, "phase": None}) + "\n")
    # inside the window: ``compiles_in_window``'s to judge, not this one's
    assert reader("persistent_cache_misses").read(
        recorded_run(out)) == 14.0


def _account(tmp_path, traced, **edges):
    """``scripts/setup_account.py`` over the recorded run, as a run
    directory of the harness holds it: the window's start is in
    ``trace.flag`` in a traced run, else on the warm-up step's log
    line."""
    import sys
    import time

    sys.path.insert(0, os.path.join(common.REPO, "scripts"))
    import setup_account

    out = _copy_without(tmp_path)
    with open(os.path.join(out, "report.json"), "w") as f:
        json.dump({"window_s": WINDOW[1] - WINDOW[0]}, f)
    stamp = time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(WINDOW[0]))
    with open(os.path.join(out, "worker.log"), "w") as f:
        f.write("%s,%03d INFO step 3 loss 5.1\n" % (
            stamp, int(WINDOW[0] % 1 * 1e3)))
    if traced:
        with open(os.path.join(out, "trace.flag"), "w") as f:
            f.write("%f\n" % WINDOW[0])
    return setup_account.account(out, cell={"warmup_steps": 3}, **edges)


@pytest.mark.parametrize("traced", [True, False])
def test_the_account_script_names_every_second(tmp_path, traced):
    """A millisecond of slack where the window's start is read off a
    log line."""
    slack = 1e-3 if traced else 3e-3
    start, end = WINDOW[0] - 20.0, WINDOW[1] + 5.0
    account = _account(tmp_path, traced, start=start, end=end)
    seconds = {p["part"]: p["seconds"] for p in account["parts"]}
    assert all(value >= -slack for value in seconds.values()), seconds
    # run.py's start to its end, with no second left out or twice
    assert account["total_s"] == pytest.approx(end - start, abs=0.01)
    assert account["outside_window_s"] == pytest.approx(
        end - start - (WINDOW[1] - WINDOW[0]), abs=0.01)
    assert seconds["master_startup"] == pytest.approx(
        EXPECTED["master_ready_s"], abs=slack)
    assert seconds["worker_exit"] == pytest.approx(
        EXPECTED["worker_exit_s"], abs=slack)
    assert account["program_setup_s"] == pytest.approx(
        EXPECTED["program_setup_s"], abs=slack)
    assert account["programs_before_first_step"] == 14
    assert len(account["cache_misses"]) == 14
    assert account["step"]["cache"] == "miss"
    # without the harness's own clock the account starts at the
    # master's process and ends at the worker's last exit hook
    bare = _account(tmp_path / "bare", traced)
    assert [p["part"] for p in bare["parts"]] == [
        p["part"] for p in account["parts"][1:-1]]


@pytest.mark.parametrize("intervals,expected", [
    # two records that share three seconds: counted once
    ([(10.0, 20.0), (17.0, 30.0)], 20.0),
    # one inside another
    ([(10.0, 30.0), (12.0, 15.0)], 20.0),
    # the window's own seconds never count
    ([(90.0, 130.0)], 10.0 + 10.0),
    ([(95.0, 105.0), (100.0, 125.0)], 5.0 + 5.0),
    # apart, and one that is empty
    ([(1.0, 2.0), (5.0, 5.0), (130.0, 131.5)], 2.5),
    ([], 0.0),
])
def test_outside_window_counts_an_overlap_once(intervals, expected):
    assert setup_ledger.outside_window(
        intervals, (100.0, 120.0)) == pytest.approx(expected)


def test_program_setup_s_counts_an_overlap_once(tmp_path):
    """A master that was still starting when the worker's process began
    (a launcher that does not wait for the port): the shared seconds
    are the program's once."""
    out = _copy_without(tmp_path)
    (path,) = (tmp_path / "run" / "events").glob("master-*.ndjson")
    lines = []
    for line in path.read_text().splitlines():
        event = json.loads(line)
        if event["event"] == "master_startup":
            event["start_ts"] += 2.0  # now ends 2 s into worker_startup
        lines.append(json.dumps(event))
    path.write_text("\n".join(lines) + "\n")
    overlap = (1790597659.0566766 + 2.0 + 3.895978196) - 1790597663.0116696
    assert 1.9 < overlap < 2.0
    assert reader("program_setup_s").read(recorded_run(out)) == (
        pytest.approx(EXPECTED["program_setup_s"] - overlap, abs=1e-6))


def test_the_nine_follow_held_pairs_over_share_in_the_manifest():
    per_layer = common.load(common.MANIFEST)["per_layer"]
    names = [m["name"] for m in per_layer]
    at = names.index("held_pairs_over_share")
    # "these nine follow that one", so that a later PR can append
    assert tuple(names[at + 1:at + 10]) == NINE
    for metric in per_layer[at + 1:at + 10]:
        assert "workloads" not in metric
        assert (metric["moves"], metric["better"]) == ("setup_s", "lower")
        assert metric["source"] == (
            "program_counter" if metric["unit"] == "count"
            else "program_span")
        assert metric["unit"] in ("s", "count")
    layers = {m["name"]: m["layer"] for m in per_layer}
    assert {layers[n] for n in NINE[:3]} == {layers["step_compile_s"]}
    assert layers["init_programs_s"] == layers["state_init_s"]
    assert layers["worker_exit_s"] == layers["input_wait_ms"]
    assert {layers[n] for n in (
        "persistent_cache_misses", "imports_s", "master_ready_s",
        "program_setup_s")} == {layers["backend_init_s"]}
    rehearsal = {
        m["name"] for m in common.load(SETUP_MANIFEST)["per_layer"]}
    assert set(NINE) <= rehearsal
    for name in NINE:
        assert callable(reader(name).read) and name in reader(name).__doc__


# ---------------------------------------------------------------------
# the whole command, twice against one compile cache


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    """(result line, the run's journals and worker log) of a cold run
    and of a second run that finds the first's programs in the cache."""
    base = tmp_path_factory.mktemp("setup_rehearsal")
    out = os.path.join(common.REPO, "chiprun_out", "benchmark", CELL)
    runs = []
    for _ in range(2):
        proc, line = common.run_cell(
            CELL, 1, base, manifest=SETUP_MANIFEST, seconds=2)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert line["correct"] is True, proc.stderr[-3000:]
        run = {"out": out}
        with open(os.path.join(out, "worker.log")) as f:
            log = f.read()
        runs.append({
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "worker": list(loop_ledger.worker_events(run)),
            "master": list(setup_ledger.master_events(run)),
            "log": log,
        })
    return runs


def events_of(run, role, kind):
    return [e for e in run[role] if e["event"] == kind]


@pytest.mark.parametrize("which", [0, 1])
def test_rehearsal_reports_the_nine(rehearsals, which):
    metrics = rehearsals[which]["metrics"]
    assert set(NINE) <= set(metrics)
    assert {"launch_to_first_step_s", "step_compile_s", "backend_init_s",
            "state_init_s"} <= set(metrics)
    assert (metrics["step_trace_lower_s"] + metrics["step_backend_s"]
            + metrics["step_first_run_s"]) == pytest.approx(
                metrics["step_compile_s"], abs=0.05)
    assert metrics["program_setup_s"] >= (
        metrics["master_ready_s"] + metrics["launch_to_first_step_s"] - 1.0)
    assert metrics["init_programs_s"] > 0 and metrics["imports_s"] > 0
    assert metrics["worker_exit_s"] > 0


def test_the_cold_rehearsal_compiles_and_names_what_it_compiled(
        rehearsals):
    cold = rehearsals[0]
    misses = events_of(cold, "worker", "xla_cache_miss")
    assert cold["metrics"]["persistent_cache_misses"] == len(misses) > 0
    assert "jit(train_step)" in {m["module"] for m in misses}
    assert "(cache miss)" in cold["log"]
    (startup,) = events_of(cold, "worker", "worker_startup")
    assert sum(c["misses"] for c in startup["compiles"].values()) == len(
        [m for m in misses if m["phase"] is not None])
    assert {m["phase"] for m in misses} <= set(startup["phases"]) | {None}


def test_the_warm_rehearsal_loads_every_program(rehearsals):
    """The CPU backend of the pinned jax takes the persistent cache in
    a worker process (``tests/test_compile_stages.py`` holds hit and
    miss in-process)."""
    warm = rehearsals[1]
    assert warm["metrics"]["persistent_cache_misses"] == 0
    assert events_of(warm, "worker", "xla_cache_miss") == []
    (line,) = [x for x in warm["log"].splitlines()
               if "xla compile #1 of train_step" in x]
    assert "(cache hit, retrieval " in line
    (startup,) = events_of(warm, "worker", "worker_startup")
    loaded = sum(c["requests"] for c in startup["compiles"].values())
    assert loaded == sum(c["hits"] for c in startup["compiles"].values())
    (cold_startup,) = events_of(rehearsals[0], "worker", "worker_startup")
    assert loaded == sum(
        c["requests"] for c in cold_startup["compiles"].values())
    assert warm["metrics"]["step_backend_s"] < (
        rehearsals[0]["metrics"]["step_backend_s"])


@pytest.mark.parametrize("role,kind", [
    ("worker", "worker_startup"), ("worker", "worker_teardown"),
    ("master", "master_startup"), ("master", "master_teardown")])
def test_rehearsal_records_sum_to_their_wall_time(rehearsals, role, kind):
    (record,) = events_of(rehearsals[1], role, kind)
    assert sum(record["phases"].values()) == record["wall_ns"]
    # no stretch of the program's own records without a name
    assert record["phases"]["other"] < 0.5e9
    # inside the process's life, on the journal's clock
    first = rehearsals[1][role][0]["ts"]
    end = record["start_ts"] + record["wall_ns"] / 1e9
    assert record["start_ts"] <= end <= record["ts"] + 0.01
    if kind.endswith("_startup"):
        assert first - 30 < record["start_ts"] <= first


def test_rehearsal_sigterm_to_exit(rehearsals):
    warm = rehearsals[1]
    (requested,) = events_of(warm, "worker", "drain_requested")
    (teardown,) = events_of(warm, "worker", "worker_teardown")
    assert requested["seq"] < teardown["seq"]
    assert requested["signal_ts"] <= requested["ts"] <= (
        teardown["start_ts"] + 0.01)
    assert requested["step"] <= requested["finished_step"]
    assert warm["metrics"]["worker_exit_s"] == pytest.approx(
        teardown["start_ts"] + teardown["wall_ns"] / 1e9
        - requested["signal_ts"])
    (master_stop,) = events_of(warm, "master", "master_teardown")
    assert master_stop["start_ts"] >= teardown["start_ts"]
