"""What the LFM2-8B-A1B configuration added to the measurement (PR 49):
``lib/conv_trace.py`` on hand-made operations, the three readers
(``short_conv_time_share``, ``short_conv_gate_roofline``,
``dense_mlp_time_share``) on what a run leaves, a program without the
scopes (the parent) reading nothing, the manifest's entries by name, and
a rehearsal of a tiny cell through the whole command."""

import json
import os

import pytest

from benchmark.flops import conv_moe_decoder
from benchmark.lib import conv_trace
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import (
    dense_mlp_time_share,
    short_conv_gate_roofline,
    short_conv_time_share,
)
from tests.benchmark_harness import _common as common

KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/"
BWD = "jit(train_step)/jit(main)/transpose(jvp(forward))/MoeTransformerLM/"
REMAT = ("jit(train_step)/jit(main)/transpose(jvp(forward))/"
         "MoeTransformerLM/checkpoint/rematted_computation/")
CELL = "lfm2-8b-s32k"
NEW_METRICS = ("short_conv_time_share", "short_conv_gate_roofline",
               "dense_mlp_time_share")
FUSION = "%fusion.1 = bf16[] fusion("


@pytest.mark.parametrize("op_name,kinds", [
    (FWD + "block_0/attn/short_conv/in_proj/in_proj/dot_general",
     ["short_conv/in_proj"]),
    (FWD + "block_3/attn/short_conv/gate/checkpoint/mul",
     ["short_conv/gate"]),
    (BWD + "block_3/attn/short_conv/gate/checkpoint/mul",
     ["short_conv/gate"]),
    (REMAT + "block_4/attn/short_conv/out_proj/proj_out/dot_general",
     ["short_conv/out_proj"]),
    ("transpose(jvp(short_conv/gate))/reduce_sum", ["short_conv/gate"]),
    (FWD + "block_1/dense_mlp/mlp_gate/dot_general", ["dense_mlp"]),
    (BWD + "block_0/dense_mlp/mul", ["dense_mlp"]),
    ("transpose(jvp(dense_mlp))/dot_general", ["dense_mlp"]),
    (FWD + "block_2/moe_mlp/moe/experts/dot_general", []),
    (FWD + "block_2/attn/query/dot_general", []),
    (FWD + "block_0/attn/short_conv/other/x", []),
    (FWD + "block_0/attn/short_convs/gate/x", []),
    (FWD + "block_0/my_short_conv/gate/x", []),
    (FWD + "block_0/dense_mlps/x", []),
    (FWD + "block_0/a_dense_mlp/x", []),
])
def test_classify(op_name, kinds):
    assert conv_trace.classify(FUSION, op_name) == kinds


def test_a_later_kernel_for_the_gates_by_name():
    """A Mosaic kernel named ``short_conv...`` is the gates' wherever it
    was called; another family's under a scope counts by the scope."""
    ours = "%short_conv_fwd.3 = (bf16[32768,2048])" + KERNEL
    assert conv_trace.classify(ours, "jit(train_step)/pallas_call") == [
        "short_conv/gate"]
    other = "%gmm.2 = bf16[]" + KERNEL
    assert conv_trace.classify(other, FWD + "moe/experts/x") == []
    assert conv_trace.classify(
        other, FWD + "block_0/dense_mlp/x") == ["dense_mlp"]


def hand_made(scoped=True):
    """Two step periods of 200 us: under ``short_conv/`` 30 us of
    projections and 20 of gates forward and 10 of gates backward, 40 us
    of the dense MLPs, 60 us of other work, 40 us idle."""
    ops = []
    for period in range(3):
        t = period * 200_000.0
        events = [
            (FWD + "block_0/attn/short_conv/in_proj/dot_general", 20_000),
            (FWD + "block_0/attn/short_conv/out_proj/dot_general", 10_000),
            (FWD + "block_0/attn/short_conv/gate/mul", 20_000),
            (BWD + "block_0/attn/short_conv/gate/mul", 10_000),
            (FWD + "block_0/dense_mlp/mlp_up/dot_general", 40_000),
        ] if scoped else [(FWD + "block_0/mlp_up/dot_general", 100_000)]
        events.append((FWD + "ln_f/mul", 60_000))
        for op_name, length in events:
            ops.append((FUSION, t, t + length, op_name))
            t += length
    modules = [("jit_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 160_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = conv_trace.reduce_device(ops, modules)
    assert device["steps"] == 2
    assert device["busy_s"] == pytest.approx(320e-6)
    assert device["seconds"] == {
        "short_conv/in_proj": pytest.approx(40e-6),
        "short_conv/out_proj": pytest.approx(20e-6),
        "short_conv/gate": pytest.approx(60e-6),
        "dense_mlp": pytest.approx(80e-6)}
    reduced = conv_trace.reduce({0: (ops, modules)})
    assert short_conv_time_share.read(
        {"conv_reduced": reduced}) == pytest.approx(100 * 120 / 320)
    assert dense_mlp_time_share.read(
        {"conv_reduced": reduced}) == pytest.approx(100 * 80 / 320)


def roofline_run(reduced, flops=conv_moe_decoder):
    config = common.load(os.path.join(
        common.REPO, "benchmark", "configs", "lfm2-8b-a1b-1chip",
        "config.json"))
    return {
        "config": config, "traffic": {"seq_len": 32768, "minibatch": 1},
        "chips": 1, "flops": flops, "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": common.load(os.path.join(
            common.REPO, "benchmark", "lib", "peaks.json")),
        "conv_reduced": reduced}


def test_the_roofline_reads_the_bytes_the_gates_need():
    """Two traced steps whose gate passes took the least time the count
    allows read 100%; twice the time, 50%."""
    config = roofline_run(None)["config"]
    _, moved = conv_moe_decoder.kernels(
        config, {"seq_len": 32768, "minibatch": 1})["short_conv_gate"]
    least = moved / 819e9
    seconds = dict.fromkeys(conv_trace.CONV_KINDS + ["dense_mlp"], 0.0)
    seconds["short_conv/gate"] = 2 * least
    seconds["short_conv/in_proj"] = 1.0  # the matmuls are not its time
    device = {"steps": 2, "busy_s": 3.0, "seconds": seconds}
    reduced = {"devices": {"0": device}}
    assert short_conv_gate_roofline.read(
        roofline_run(reduced)) == pytest.approx(100)
    seconds["short_conv/gate"] = 4 * least
    assert short_conv_gate_roofline.read(
        roofline_run(reduced)) == pytest.approx(50)
    # a configuration without a count, a count that names no gate, a
    # program with nothing under the gate's scope
    from benchmark.flops import window_moe_decoder

    assert short_conv_gate_roofline.read(
        roofline_run(reduced, flops=None)) is None
    laguna = dict(roofline_run(reduced, flops=window_moe_decoder))
    laguna["config"] = common.load(os.path.join(
        common.REPO, "benchmark", "configs", "laguna-xs.2-1chip",
        "config.json"))
    assert short_conv_gate_roofline.read(laguna) is None
    seconds["short_conv/gate"] = 0.0
    assert short_conv_gate_roofline.read(roofline_run(reduced)) is None


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent of PR 49 and every other configuration: no scope; and
    no trace at all: nothing to reduce, nothing raised."""
    reduced = conv_trace.reduce({0: hand_made(scoped=False)})
    assert not any(reduced["devices"]["0"]["seconds"].values())
    for reader in (short_conv_time_share, dense_mlp_time_share):
        assert reader.read({"conv_reduced": reduced}) is None
        assert reader.read({"conv_reduced": {"devices": {}}}) is None
        assert reader.read({"out": str(tmp_path)}) is None
    assert short_conv_gate_roofline.read(roofline_run(reduced)) is None
    # a dense block in a model without the mixers: its MLP is read,
    # the mixers' readers stay silent
    ops, modules = hand_made()
    dense_only = conv_trace.reduce({0: (
        [op for op in ops if "short_conv" not in op[3]], modules)})
    assert dense_mlp_time_share.read(
        {"conv_reduced": dense_only}) == pytest.approx(100 * 80 / 200)
    assert short_conv_time_share.read({"conv_reduced": dense_only}) is None
    assert short_conv_gate_roofline.read(roofline_run(dense_only)) is None
    run = roofline_run(None)
    run.pop("conv_reduced")
    run["out"] = str(tmp_path)
    assert short_conv_gate_roofline.read(run) is None


def test_the_manifest_s_entries_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "samples_per_s"
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["source"] == "device_trace"
        assert files.find("metrics", name + ".py")
    assert [by_name[name]["better"] for name in NEW_METRICS] == [
        "lower", "higher", "lower"]
    assert len({by_name[name]["layer"] for name in NEW_METRICS}) == 1
    assert "ShortConv" in by_name["short_conv_time_share"]["layer"]
    # the three are the last entries, the cell and its configuration too
    assert [m["name"] for m in manifest["per_layer"][-3:]] == list(
        NEW_METRICS)
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "lfm2-8b-a1b-1chip", "s32k-b1", 1)
    assert len(cell["why"]) <= 200
    entry = manifest["configs"][-1]
    assert entry["name"] == "lfm2-8b-a1b-1chip"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["reduced"] == common.load(os.path.join(
        common.REPO, entry["file"]))["reduced"]
    reported = {m["name"] for m in files.metrics_for("per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | {
        "flash_time_share", "flash_roofline", "peak_hbm_gb",
        "optimizer_time_share", "device_idle_share"}
    assert not reported & {"moe_time_share", "gdn_time_share",
                           "mla_time_share", "bd_overhead_share",
                           "window_attn_time_share", "mhc_time_share",
                           "held_pairs_over_share", "loop_host_ms"}
    # nothing older lists the new cell
    older = [m for m in manifest["per_layer"]
             if m["name"] not in NEW_METRICS]
    assert not any(CELL in m.get("workloads", []) for m in older)


def test_rehearsal_of_a_tiny_lfm2_cell(tmp_path):
    """The LFM2 zoo through ``worker.main``, its reference check (the
    last positions), the ``moe_routing`` and ``mixer_kinds`` events
    through the worker's loop and the new readers through the whole
    command on the CPU, traced."""
    manifest = os.path.join(common.HERE, "preset", "LFM2.json")
    proc, line = common.run_cell(
        "tiny-lfm2-s128", 1, tmp_path, manifest=manifest, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    # a CPU run has no device plane: every reader of a trace is left out
    assert set(line["metrics"]) <= {"peak_hbm_gb"}
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-lfm2-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "loss", "choices", "dropped_pairs_plus_one",
        "grad:block_0/attn/in_proj/kernel", "grad:block_0/attn/conv_kernel",
        "grad:block_2/attn/key/kernel", "grad:block_2/attn/q_norm/scale",
        "grad:block_5/moe_mlp/w_gate", "grad:wte/embedding"}
    assert check["errors"]["dropped_pairs_plus_one"] == 0
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert "short conv channels=64 taps=3 impl=xla (tokens=128" in log
    assert "moe dispatch resolved to sorted (tokens=512 experts=16" in log
    assert "shared=0 held=4-7 rows=1536, experts' matmul=ragged_dot)" in log
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    routing = [e for e in journal if e.get("event") == "moe_routing"]
    assert routing and all(e["dropped_pairs"] == 0.0 for e in routing)
    assert all(e["held_pairs"] > 0 and e["bias_abs_max"] > 0
               for e in routing)
    # said once, when the state is made, and not with every logged step
    kinds = [e for e in journal if e.get("event") == "mixer_kinds"]
    assert len(kinds) == 1 and "step" not in kinds[0]
    assert {k: kinds[0][k] for k in (
        "conv_layers", "full_layers", "dense_layers", "conv_taps",
        "conv_channels", "head_dim", "kv_heads")} == {
            "conv_layers": 5, "full_layers": 1, "dense_layers": 2,
            "conv_taps": 3, "conv_channels": 64, "head_dim": 16,
            "kv_heads": 2}
