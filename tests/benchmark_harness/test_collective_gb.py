"""``metrics/collective_gb_per_step.py`` (PR 24) on the journal line a
worker wrote on four v5e chips (``data/fsdp4_xla_compile.ndjson``: the
``xla_compile`` event of ``pythia1b-fsdp4-s2k``'s train step, my chip
run, PR 24), and on journals that lack it."""

import json
import os
import shutil

from benchmark.metrics import collective_gb_per_step as reader

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "fsdp4_xla_compile.ndjson")


def _run(tmp_path, lines):
    events_dir = tmp_path / "events"
    events_dir.mkdir()
    with open(events_dir / "worker-0-77.events.ndjson", "w") as f:
        for line in lines:
            f.write(line.rstrip("\n") + "\n")
        f.write('{"event": "xla_comp')  # torn by the kill at the end
    return {"out": str(tmp_path), "window": (0.0, 1e12), "trace": True}


def _recorded():
    with open(RECORDED) as f:
        return [line for line in f if line.strip()]


def test_reads_the_train_steps_collectives_off_the_recorded_line(tmp_path):
    (line,) = _recorded()
    event = json.loads(line)
    assert event["event"] == "xla_compile"
    assert event["fn"] == "spmd_train_step" and event["compiles"] == 1
    stats = event["collectives"]
    assert stats["bytes"] == sum(
        kind["bytes"] for kind in stats["by_kind"].values())
    # what the line says of the program: weights move, in pieces no
    # larger than the head, and nothing carries the batch of 12
    assert stats["largest"]["result"] == "bf16[2048,50304]"
    assert stats["largest"]["bytes"] == 2048 * 50304 * 2
    other = json.dumps({
        "event": "xla_compile", "fn": "spmd_eval_step", "compiles": 1,
        "collectives": {"bytes": 7e9}})
    value = reader.read(_run(tmp_path, [other, line]))
    assert value == stats["bytes"] / 1e9
    assert 3.5 < value < 5.0


def test_a_program_without_the_event_reports_nothing(tmp_path):
    lines = [
        json.dumps({"event": "worker_startup", "ts": 1.0, "phases": {}}),
        # a compile whose program was not read carries null
        json.dumps({"event": "xla_compile", "fn": "spmd_train_step",
                    "compiles": 1, "collectives": None}),
    ]
    assert reader.read(_run(tmp_path, lines)) is None
    shutil.rmtree(tmp_path / "events")
    assert reader.read(
        {"out": str(tmp_path), "window": (0.0, 1.0), "trace": True}
    ) is None
