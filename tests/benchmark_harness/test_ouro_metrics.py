"""What the Ouro configuration added to the measurement (PR 55):
``lib/looped_trace.py`` on hand-made operations, the two readers
(``exit_head_time_share``, ``looped_outside_blocks_share``) on what a
run leaves, a program without the scopes (the parent) reading nothing,
the manifest's entries BY NAME (a later PR appends after them), and a
rehearsal of a tiny cell through the whole command."""

import json
import os

import pytest

from benchmark.lib import looped_trace
from benchmark.metrics import (
    exit_head_time_share,
    looped_outside_blocks_share,
)
from tests.benchmark_harness import _common as common

LM = "MoeTransformerLM/MoeTransformerLM._looped/"
FWD = "jit(train_step)/jvp(forward)/" + LM + "while/body/"
BWD = ("jit(train_step)/transpose(jvp(forward))/" + LM
       + "while/body/looped/pass/jvp(forward)/" + LM)
# the method's own scope, outside the scan's loop
OUTSIDE_LOOP = "jit(train_step)/jvp(forward)/" + LM
# a model whose stack is walked once
OTHER = "jit(train_step)/jvp(forward)/MoeTransformerLM/"
CELL = "ouro2.6b-s16k"
CONFIG = "ouro-2.6b-1chip"
NEW_METRICS = ("exit_head_time_share", "looped_outside_blocks_share")
FUSION = "%fusion.1 = bf16[] fusion("
EXIT, OUTSIDE = ["exit"], ["outside_blocks"]


@pytest.mark.parametrize("op_name,kinds", [
    (FWD + "looped/exit_norm/ln_f/mul", OUTSIDE),
    (BWD.replace("pass/jvp", "exit_norm/jvp")
     + "looped/exit_norm/checkpoint/rematted_computation/ln_f/mul", OUTSIDE),
    (FWD + "looped/pass/add", OUTSIDE),
    (FWD + "looped/pass/block_0/attn/query/dot_general", []),
    (BWD + "looped/pass/checkpoint/block_6/block_6._dense_mlp/"
     "dense_mlp/mlp_up/dot_general", []),
    (BWD + "looped/pass/checkpoint/rematted_computation/block_6/"
     "ln_mlp_out/mul", []),
    # no program unrolls its passes: a numbered pass is nobody's scope
    (OTHER + "looped/pass_0/add", []),
    (FWD + "exit/gate/early_exit_gate/dot_general", EXIT),
    ("jit(train_step)/jvp(loss)/exit/head/while/body/checkpoint/"
     "bcd,dv->bcv/dot_general", EXIT),
    ("jit(train_step)/transpose(jvp(loss))/exit/head/while/body/"
     "checkpoint/rematted_computation/reduce_max", EXIT),
    ("transpose(jvp(exit/head))/mul", EXIT),
    # a gate read inside a pass is the exits', not the loop's
    (FWD + "looped/pass/exit/gate/mul", EXIT),
    (FWD + "block_0/attn/query/dot_general", []),
    # the scan's own: the stacked saved set, the loops, the carry
    (FWD + "dynamic_update_slice", OUTSIDE),
    (BWD.split("looped/pass")[0] + "squeeze", OUTSIDE),
    (OUTSIDE_LOOP.rstrip("/"), OUTSIDE),
    (OUTSIDE_LOOP + "add_any", OUTSIDE),
    ("jit(train_step)/optimizer/add", []),
    (OTHER + "wte/jit(_take)/gather", []),
    (OTHER + "looped/other/x", []),
    (OTHER + "unlooped/pass_0/x", []),
    (OTHER + "looped/pass_x/x", []),
    (OTHER + "looped/passes/x", []),
    (OTHER + "MoeTransformerLM._looped_over/x", []),
    (OTHER + "my_exit/head/x", []),
    (OTHER + "exit/headroom/x", []),
])
def test_classify(op_name, kinds):
    assert looped_trace.classify(op_name) == kinds


def hand_made(scoped=True):
    """Two step periods of 200 us: 100 us inside blocks, 10 us of
    end-of-pass norms (forward and backward), 30 of the exits' head and
    10 of the gate, 10 us of optimizer, 40 us idle."""
    ops = []
    for period in range(3):
        t = period * 200_000.0
        events = [
            (FWD + "looped/pass/block_0/attn/flash_fwd/pallas_call",
             100_000),
            (FWD + "looped/exit_norm/ln_f/mul", 6_000),
            (BWD + "looped/exit_norm/ln_f/mul", 4_000),
            ("jit(train_step)/jvp(loss)/exit/head/while/body/dot_general",
             20_000),
            ("jit(train_step)/transpose(jvp(loss))/exit/head/while/body/"
             "dot_general", 10_000),
            (FWD + "exit/gate/early_exit_gate/dot_general", 10_000),
        ] if scoped else [
            ("jit(train_step)/jvp(forward)/MoeTransformerLM/block_0/x",
             150_000)]
        events.append(("jit(train_step)/optimizer/add", 10_000))
        for op_name, length in events:
            ops.append((FUSION, t, t + length, op_name))
            t += length
    modules = [("jit_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 160_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = looped_trace.reduce_device(ops, modules)
    assert device["steps"] == 2
    assert device["busy_s"] == pytest.approx(320e-6)
    assert device["seconds"] == {
        "exit": pytest.approx(80e-6),
        "outside_blocks": pytest.approx(20e-6)}
    reduced = looped_trace.reduce({0: (ops, modules)})
    assert exit_head_time_share.read(
        {"looped_reduced": reduced}) == pytest.approx(100 * 80 / 320)
    assert looped_outside_blocks_share.read(
        {"looped_reduced": reduced}) == pytest.approx(100 * 20 / 320)
    # a while loop's own event holds its body's: not counted twice
    loop = ("%while.3 = (s32[]) while(", 0.0, 150_000.0,
            "jit(train_step)/jvp(loss)/exit/head/while")
    assert looped_trace.reduce_device(
        ops + [loop], modules)["seconds"]["exit"] == pytest.approx(80e-6)


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent of PR 55 and every other configuration: no scope; and
    no trace at all: nothing to reduce, nothing raised."""
    reduced = looped_trace.reduce({0: hand_made(scoped=False)})
    assert not any(reduced["devices"]["0"]["seconds"].values())
    for reader in (exit_head_time_share, looped_outside_blocks_share):
        assert reader.read({"looped_reduced": reduced}) is None
        assert reader.read({"looped_reduced": {"devices": {}}}) is None
        assert reader.read({"looped_reduced": None}) is None
        assert reader.read({"out": str(tmp_path)}) is None


def test_the_manifest_s_entries_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "samples_per_s"
        assert (by_name[name]["unit"], by_name[name]["better"],
                by_name[name]["source"]) == ("%", "lower", "device_trace")
        assert files.find("metrics", name + ".py")
    assert len({by_name[name]["layer"] for name in NEW_METRICS}) == 1
    assert by_name[NEW_METRICS[0]]["layer"].startswith("the looped stack")
    assert len(by_name[NEW_METRICS[0]]["layer"]) <= 200
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "s16k-b1", 1)
    assert len(cell["why"]) <= 200
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["reduced"] == common.load(os.path.join(
        common.REPO, entry["file"]))["reduced"]
    reported = {m["name"] for m in files.metrics_for("per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | {
        "flash_time_share", "flash_roofline", "peak_hbm_gb",
        "optimizer_time_share", "device_idle_share", "step_peak_gb",
        "worker_hbm_peak_gb"}
    assert not reported & {"moe_time_share", "gdn_time_share",
                           "mla_time_share", "bd_overhead_share",
                           "window_attn_time_share", "mhc_time_share",
                           "short_conv_time_share", "loop_host_ms",
                           "indexer_time_share", "dense_mlp_time_share"}
    # nothing older lists the new cell
    older = [m for m in manifest["per_layer"]
             if m["name"] not in NEW_METRICS]
    assert not any(CELL in m.get("workloads", []) for m in older)
    assert {m["name"] for m in files.metrics_for("end_to_end", CELL)} == {
        "samples_per_s", "mfu", "setup_s"}


def test_rehearsal_of_a_tiny_ouro_cell(tmp_path):
    """The Ouro zoo through ``worker.main``, its reference check (the
    last positions), the ``looped_exit`` and ``loss_terms`` events
    through the worker's loop and the new readers through the whole
    command on the CPU, traced."""
    manifest = os.path.join(common.HERE, "preset", "OURO.json")
    proc, line = common.run_cell(
        "tiny-ouro-s128", 1, tmp_path, manifest=manifest, seconds=3)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    # a CPU run has no device plane: every reader of a trace is left out
    assert set(line["metrics"]) <= {"peak_hbm_gb"}
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-ouro-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "logits:exit_0", "logits:exit_1", "logits:exit_2",
        "exit_probs", "loss", "term:expected_ce", "term:exit_entropy",
        "term:ce_exit_3", "grad:wte/embedding", "grad:lm_head/kernel",
        "grad:block_0/attn/query/kernel", "grad:block_1/mlp_down/kernel",
        "grad:block_1/ln_attn_out/scale", "grad:early_exit_gate/kernel",
        "grad:early_exit_gate/bias", "span_ce"}
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert " ce_exit_0 " in log and " expected_ce " in log
    assert " exit_entropy " in log
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    exits = [e for e in journal if e.get("event") == "looped_exit"]
    terms = [e for e in journal if e.get("event") == "loss_terms"]
    assert exits and len(exits) == len(terms)
    by_step = {e["step"]: e for e in terms}
    for event in exits:
        assert event["passes"] == 4.0
        assert len(event["p_mean"]) == 4 and "ce" not in event
        assert len(event["lambda_mean"]) == 3
        assert sum(event["p_mean"]) == pytest.approx(1.0, abs=1e-4)
        assert 0.0 < event["entropy"] <= 1.3863  # ln 4
        assert event["lambda_mean"][0] == pytest.approx(
            event["p_mean"][0], rel=1e-4)
        of_loss = by_step[event["step"]]
        assert {"ce_exit_%d" % t for t in range(4)} <= set(of_loss)
        assert of_loss["loss"] == pytest.approx(
            of_loss["expected_ce"] - 0.05 * of_loss["exit_entropy"],
            rel=1e-4)
