"""``lib/loop_ledger.py`` and the seven metrics it feeds (PR 23): on a
hand-made journal and hand-made spans whose answers are known, on the
two chip traces recorded before the program had annotations or scopes,
and through the whole command on the CPU."""

import importlib
import json
import os

import pytest

from benchmark.lib import loop_ledger as ll
from tests.benchmark_harness import _common as common

HERE = os.path.dirname(os.path.abspath(__file__))
LEDGER_MANIFEST = os.path.join(HERE, "preset", "LEDGER.json")
MS = 1e6  # nanoseconds
NEW = ("input_wait_ms", "loop_host_ms", "slow_steps_in_window",
       "gap_attributed_share", "optimizer_time_share",
       "backend_init_s", "state_init_s")


def phases_event(ts, first, steps, wall_ms, **phase_ms):
    return {
        "event": "loop_phases", "ts": ts, "first_step": first,
        "last_step": first + steps - 1, "steps": steps,
        "wall_ns": int(wall_ms * MS),
        "phases": {k: int(v * MS) for k, v in phase_ms.items()},
    }


def write_run(tmp_path, trace):
    """A run directory with a worker journal. The window is 100-200 s.
    Three intervals of 8 steps lie inside it (the first ends inside
    the trace when there is one), one before and one after it. A step:
    input_wait 0.1 / 0.3 / 0.2 ms, device_wait 214 ms of a 220 / 221 /
    222 ms iteration."""
    events_dir = tmp_path / "events"
    events_dir.mkdir()
    journal = [
        {"event": "role_start", "ts": 10.0},
        {"event": "worker_startup", "ts": 60.0, "wall_ns": int(52e9),
         "phases": {"imports": int(9e9), "backend_init": int(12.5e9),
                    "state_init": int(3.25e9), "other": int(27.25e9)}},
        phases_event(90.0, 1, 8, 8 * 400, input_wait=8 * 50,
                     device_wait=8 * 100),
        {"event": "slow_step", "ts": 95.0, "step": 7, "wall_ns": 1},
        phases_event(110.0, 9, 8, 8 * 220, input_wait=8 * 0.1,
                     device_wait=8 * 214, dispatch=8 * 3),
        {"event": "slow_step", "ts": 112.0, "step": 14, "wall_ns": 1},
        phases_event(120.0, 17, 8, 8 * 221, input_wait=8 * 0.3,
                     device_wait=8 * 214),
        {"event": "slow_step", "ts": 125.0, "step": 26, "wall_ns": 1},
        {"event": "slow_step", "ts": 126.0, "step": 27, "wall_ns": 1},
        phases_event(130.0, 25, 8, 8 * 222, input_wait=8 * 0.2,
                     device_wait=8 * 214),
        phases_event(210.0, 33, 8, 8 * 900, input_wait=8 * 300),
        {"event": "slow_step", "ts": 215.0, "step": 35, "wall_ns": 1},
    ]
    with open(events_dir / "worker-0-77.events.ndjson", "w") as f:
        for event in journal:
            f.write(json.dumps(event) + "\n")
        f.write('{"event": "loop_pha')  # torn by the kill at the end
    if trace:
        (tmp_path / "trace.done").write_text("16\n")
    return {"out": str(tmp_path), "window": (100.0, 200.0),
            "trace": trace}


# One step program a device, three executions 100 ms apart and 90 ms
# long, so two gaps of 10 ms; the host's loop thread annotates them.
FUSION = "%%fusion.%d = f32[8]{0} fusion(f32[8]{0} %%p), kind=kLoop"


def device_events():
    ops, modules = [], []
    for k in range(3):
        t = 100 * k * MS
        modules.append(("jit_train_step(%d)" % k, t, t + 90 * MS))
        ops += [
            (FUSION % 1, t, t + 30 * MS,
             "jit(train_step)/jvp(forward)/TransformerLM/block_0/mul"),
            (FUSION % 2, t + 30 * MS, t + 35 * MS,
             "jit(train_step)/jvp(loss)/reduce_sum"),
            (FUSION % 3, t + 35 * MS, t + 70 * MS,
             "jit(train_step)/transpose(jvp(forward))/TransformerLM/dot"),
            (FUSION % 4, t + 70 * MS, t + 88 * MS,
             "jit(train_step)/optimizer/add"),
            (FUSION % 5, t + 88 * MS, t + 90 * MS, ""),
            # a container spans its children and counts for nothing
            ("%while.5 = (s32[]) while((s32[]) %t), body=%b", t,
             t + 90 * MS, ""),
        ]
    # another program runs 2 ms inside the first gap: not idle
    ops.append((FUSION % 9, 92 * MS, 94 * MS, "jit(memory)/copy"))
    modules.append(("jit_memory(1)", 92 * MS, 94 * MS))
    return ops, modules


def host_lines():
    """The loop thread. The device's gap starts at t: ``device_wait``
    ends 1 ms in, ``health`` takes 2 ms, ``callbacks`` 3 ms, then 1 ms
    under no phase; at t + 7 the next ``edl/step`` begins with 0.5 ms
    of ``input_wait`` and ``dispatch``, which outlasts the gap. A
    producer thread holds an annotation-shaped event over
    everything."""
    loop = []
    for k in range(3):
        t = (100 * k + 90) * MS  # where the device's gap starts
        loop += [
            ("edl/device_wait", t - 80 * MS, t + 1 * MS),
            ("edl/health", t + 1 * MS, t + 3 * MS),
            ("edl/callbacks", t + 3 * MS, t + 6 * MS),
            ("edl/step", t + 7 * MS, t + 107 * MS),
            ("edl/input_wait", t + 7 * MS, t + 7.5 * MS),
            ("edl/dispatch", t + 7.5 * MS, t + 12 * MS),
            ("$queue.py:122 put", t - 90 * MS, t + 20 * MS),
        ]
    loop.append(("edl/step", -3 * MS, 97 * MS))
    producer = [
        ("edl/input_wait", -1000 * MS, 1000 * MS),
        ("$queue.py:122 put", -1000 * MS, 1000 * MS),
    ]
    return [producer, loop]


def reduced_by_hand():
    return ll.reduce({0: device_events()}, host_lines())


@pytest.mark.parametrize("metric,trace,expected", [
    # medians over the intervals that start inside the window: all
    # three untraced, the two after step 16 traced
    ("input_wait_ms", False, 0.2),
    ("input_wait_ms", True, 0.25),
    ("loop_host_ms", False, 7.0),
    ("loop_host_ms", True, 7.5),
    ("slow_steps_in_window", False, 3),
    ("slow_steps_in_window", True, 2),
    ("backend_init_s", True, 12.5),
    ("state_init_s", True, 3.25),
])
def test_journal_metrics_by_hand(tmp_path, metric, trace, expected):
    run = write_run(tmp_path, trace)
    reader = importlib.import_module("benchmark.metrics." + metric)
    assert reader.read(run) == pytest.approx(expected)


def test_trace_metrics_by_hand():
    reduced = reduced_by_hand()
    assert reduced["annotated"] is True
    assert reduced["step_wall_ms"] == pytest.approx([100.0] * 4)
    device = reduced["devices"]["0"]
    # two gaps of 10 ms, 2 ms of the first busy with another program
    assert device["gap_ns"] == pytest.approx(18 * MS)
    split = device["gap_split_ns"]
    # first gap, 90-100 with the other program on 92-94: device_wait
    # 90-91, health 91-92, callbacks 94-96, nobody's 96-97, input_wait
    # 97-97.5, dispatch 97.5-100; the second gap, 190-200, whole
    assert split["device_wait"] == pytest.approx(1 * MS + 1 * MS)
    assert split["health"] == pytest.approx(1 * MS + 2 * MS)
    assert split["callbacks"] == pytest.approx(2 * MS + 3 * MS)
    assert split["input_wait"] == pytest.approx(2 * 0.5 * MS)
    assert split["dispatch"] == pytest.approx(2 * 2.5 * MS)
    # 96-97 and 196-197: inside a step, under no phase
    assert split["other"] == pytest.approx(2 * MS)
    assert split["outside_step"] == pytest.approx(0)
    assert sum(split.values()) == pytest.approx(device["gap_ns"])
    assert ll.gap_attributed_share(reduced) == pytest.approx(
        100.0 * 16 / 18)
    # the window holds two whole periods: 2 x 18 ms of optimizer, and
    # busy 2 x 90 ms of the step program + 2 ms of the other one
    assert device["scopes_s"] == pytest.approx({
        "forward": 0.060, "loss": 0.010, "backward": 0.070,
        "optimizer": 0.036, "unscoped": 0.006})
    assert ll.optimizer_time_share(reduced) == pytest.approx(
        100.0 * 36 / 182)


def test_an_annotation_on_another_thread_names_nothing():
    steps, phases = ll.loop_thread(host_lines())
    assert len(steps) == 4
    # the producer's hour-long ``edl/input_wait`` is not among them
    assert max(e - s for _, s, e in phases) < 100 * MS
    only_producer = ll.reduce({0: device_events()}, host_lines()[:1])
    assert only_producer["annotated"] is False
    assert ll.gap_attributed_share(only_producer) is None


@pytest.mark.parametrize("name", [
    "tiny_lm_1chip.xplane.pb.gz", "tiny_lm_4chip.xplane.pb.gz"])
def test_traces_that_predate_the_ledger_report_nothing(name):
    """Recorded on the chip by PR 22: no ``edl/`` annotation, no scope.
    The file parses, the devices are found, and both metrics are left
    out (a share of zero would be a lie about a program that has no
    such scope)."""
    reduced = ll.reduce(*ll.load_xspace(os.path.join(HERE, "data", name)))
    assert reduced["annotated"] is False
    assert len(reduced["devices"]) == (4 if "4chip" in name else 1)
    for device in reduced["devices"].values():
        assert device["busy_s"] > 0 and not device["scoped"]
        assert device["scopes_s"]["backward"] > 0
        assert "gap_ns" not in device
    assert ll.gap_attributed_share(reduced) is None
    assert ll.optimizer_time_share(reduced) is None


def test_a_program_without_the_ledger_reports_nothing(tmp_path):
    (tmp_path / "events").mkdir()
    (tmp_path / "events" / "worker-0-5.events.ndjson").write_text(
        json.dumps({"event": "role_start", "ts": 120.0}) + "\n")
    run = {"out": str(tmp_path), "window": (100.0, 200.0), "trace": True}
    for metric in NEW:
        reader = importlib.import_module("benchmark.metrics." + metric)
        assert reader.read(dict(run)) is None, metric


def test_op_names_fall_into_scopes():
    assert ll.scope_of("jit(train_step)/jvp(forward)/M/mul") == "forward"
    assert ll.scope_of(
        "jit(train_step)/transpose(jvp(forward))/M/dot") == "backward"
    assert ll.scope_of("jit(train_step)/jvp(loss)/sub") == "loss"
    assert ll.scope_of("jit(train_step)/transpose(jvp(loss))/sub") == (
        "backward")
    assert ll.scope_of("jit(train_step)/optimizer/add") == "optimizer"
    assert ll.scope_of("jit(train_step)/jit(main)/optimizer") == (
        "optimizer")
    # a module that merely has the word in its name is not the scope
    assert ll.scope_of("jit(f)/jvp(M)/forward_proj/mul") == "unscoped"
    assert ll.scope_of("") == "unscoped"


def test_the_seven_are_in_the_manifest_and_the_rehearsal_s():
    root = {m["name"]: m for m in common.load(common.MANIFEST)["per_layer"]}
    assert list(root)[-7:] == list(NEW)
    assert root["loop_host_ms"]["workloads"] == [
        "pythia1b-s2k", "pythia1b-s16k"]
    rehearsal = common.load(LEDGER_MANIFEST)
    assert set(NEW) <= {m["name"] for m in rehearsal["per_layer"]}
    for name in NEW:
        reader = importlib.import_module("benchmark.metrics." + name)
        assert callable(reader.read) and name in reader.__doc__


def test_traced_rehearsal_reports_the_journal_read_metrics(tmp_path):
    proc, line = common.run_cell(
        "tiny-lm-ledger", 1, tmp_path, manifest=LEDGER_MANIFEST,
        seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    metrics = line["metrics"]
    assert {"input_wait_ms", "loop_host_ms", "slow_steps_in_window",
            "backend_init_s", "state_init_s"} <= set(metrics)
    # a CPU run has no device plane: the two that read one are left out
    assert "gap_attributed_share" not in metrics
    assert "optimizer_time_share" not in metrics
    assert metrics["input_wait_ms"]["value"] >= 0
    assert metrics["loop_host_ms"]["value"] > 0
    assert metrics["backend_init_s"]["value"] > 0
    assert metrics["state_init_s"]["value"] > 0
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-lm-ledger")
    # the child reduced the CPU trace: annotations, no device
    reduced = common.load(os.path.join(out, "loop_reduced.json"))
    assert reduced["annotated"] is True and reduced["devices"] == {}
    # the session starts and stops inside a step's callbacks, so of the
    # cell's 6 trace_steps the whole ones in between are annotated
    assert len(reduced["step_wall_ms"]) >= 5
    journal = ll.worker_events({"out": out})
    kinds = {e["event"] for e in journal}
    assert {"worker_startup", "loop_phases", "worker_teardown"} <= kinds
    (startup,) = [e for e in journal if e["event"] == "worker_startup"]
    assert {"imports", "backend_init", "master_connect", "first_task",
            "state_init", "first_step", "other"} <= set(startup["phases"])
    assert sum(startup["phases"].values()) == startup["wall_ns"]
