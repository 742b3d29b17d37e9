"""What the Keye-VL-2.0 configuration added to the measurement (PR 51):
``lib/dsa_trace.py`` on hand-made operations, the four readers
(``indexer_time_share``, ``indexer_select_share``,
``indexer_score_roofline``, ``sparse_attn_fill``) on what a run leaves,
a program without the scopes (the parent) reading nothing, the
manifest's entries BY NAME (a later PR appends after them), and a
rehearsal of a tiny cell through the whole command."""

import json
import os

import pytest

from benchmark.flops import dsa_moe_decoder
from benchmark.lib import dsa_trace
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import (
    indexer_score_roofline,
    indexer_select_share,
    indexer_time_share,
    sparse_attn_fill,
)
from tests.benchmark_harness import _common as common

KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/"
BWD = "jit(train_step)/jit(main)/transpose(jvp(forward))/MoeTransformerLM/"
REMAT = ("jit(train_step)/jit(main)/transpose(jvp(forward))/"
         "MoeTransformerLM/checkpoint/rematted_computation/")
CELL = "keye-vl2-30b-s32k"
CONFIG = "keye-vl-2.0-30b-a3b-1chip"
NEW_METRICS = ("indexer_time_share", "indexer_select_share",
               "indexer_score_roofline", "sparse_attn_fill")
FUSION = "%fusion.1 = bf16[] fusion("
LINE = (
    "2026-10-01 17:24:09,443 INFO elasticdl_tpu.ops.sparse_attention: "
    "attention impl=auto resolved to pallas (backend=tpu, q=(1, 32, 32768, "
    "128) bfloat16, kv_heads=4 group=8, indexer heads=16 dim=64, flash "
    "backward=fused, mask=selected(2048) pairs run=528 masked=528 "
    "skipped=496 blocks=1024x1024 (backward run=528 masked=528 skipped=496 "
    "blocks=1024x1024) kept=65012736 fill=0.1174)\n")


@pytest.mark.parametrize("op_name,kinds", [
    (FWD + "block_0/attn/dsa/indexer_proj/indexer_q/dot_general",
     ["dsa/indexer_proj"]),
    (FWD + "block_3/attn/dsa/scores/mul", ["dsa/scores"]),
    (REMAT + "block_3/attn/dsa/scores/pallas_call", ["dsa/scores"]),
    (FWD + "block_1/attn/dsa/select/pallas_call", ["dsa/select"]),
    (BWD + "block_1/attn/dsa/attend/reduce_sum", ["dsa/attend"]),
    (FWD + "block_2/attn/dsa/indexer_loss/mul", ["dsa/indexer_loss"]),
    ("transpose(jvp(dsa/indexer_loss))/mul", ["dsa/indexer_loss"]),
    ("transpose(jvp(dsa/attend))/reduce_sum", ["dsa/attend"]),
    (FWD + "block_2/moe_mlp/moe/experts/dot_general", []),
    (FWD + "block_2/attn/query/dot_general", []),
    (FWD + "block_0/attn/dsa/other/x", []),
    (FWD + "block_0/attn/dsas/select/x", []),
    (FWD + "block_0/my_dsa/select/x", []),
    (FWD + "block_0/attn/dsa/selected/x", []),
])
def test_classify(op_name, kinds):
    assert dsa_trace.classify(FUSION, op_name) == kinds


@pytest.mark.parametrize("kernel,kind", [
    ("dsa_select", "dsa/select"), ("dsa_mask", "dsa/scores"),
    ("dsa_indexer_loss", "dsa/indexer_loss"),
    ("flash_sparse_fwd", "dsa/attend"), ("flash_sparse_bwd", "dsa/attend"),
])
def test_a_kernel_counts_by_its_name_wherever_it_was_called(kernel, kind):
    ours = "%%%s.3 = (bf16[32768,2048])" % kernel + KERNEL
    assert dsa_trace.classify(ours, "jit(train_step)/pallas_call") == [kind]
    # another family's kernel under a scope counts by the scope
    other = "%gmm.2 = bf16[]" + KERNEL
    assert dsa_trace.classify(other, FWD + "moe/experts/x") == []
    assert dsa_trace.classify(
        other, FWD + "block_0/attn/dsa/scores/x") == ["dsa/scores"]
    # the causal kernels are not the sparse ones
    assert dsa_trace.classify(
        "%flash_fwd.1 = (bf16[])" + KERNEL, FWD + "block_0/attn/x") == []


def hand_made(scoped=True):
    """Two step periods of 200 us: 10 us of the indexer's projections,
    20 of scores, 30 of selection, 50 of attention (forward and
    backward) and 10 of the indexer's term, 40 us of other work, 40 us
    idle."""
    ops = []
    for period in range(3):
        t = period * 200_000.0
        events = [
            (FWD + "block_0/attn/dsa/indexer_proj/dot_general", 10_000),
            (FWD + "block_0/attn/dsa/scores/pallas_call", 20_000),
            (FWD + "block_0/attn/dsa/select/pallas_call", 30_000),
            (FWD + "block_0/attn/dsa/attend/pallas_call", 20_000),
            (BWD + "block_0/attn/dsa/attend/pallas_call", 30_000),
            (FWD + "block_0/attn/dsa/indexer_loss/mul", 10_000),
        ] if scoped else [(FWD + "block_0/attn/flash/x", 120_000)]
        events.append((FWD + "ln_f/mul", 40_000))
        for op_name, length in events:
            ops.append((FUSION, t, t + length, op_name))
            t += length
    modules = [("jit_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 160_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = dsa_trace.reduce_device(ops, modules)
    assert device["steps"] == 2
    assert device["busy_s"] == pytest.approx(320e-6)
    assert device["seconds"] == {
        "dsa/indexer_proj": pytest.approx(20e-6),
        "dsa/scores": pytest.approx(40e-6),
        "dsa/select": pytest.approx(60e-6),
        "dsa/attend": pytest.approx(100e-6),
        "dsa/indexer_loss": pytest.approx(20e-6)}
    reduced = dsa_trace.reduce({0: (ops, modules)})
    # everything the indexer costs, and not the attention it selects for
    assert indexer_time_share.read(
        {"dsa_reduced": reduced}) == pytest.approx(100 * 140 / 320)
    assert indexer_select_share.read(
        {"dsa_reduced": reduced}) == pytest.approx(100 * 60 / 320)


def roofline_run(reduced, flops=dsa_moe_decoder, config=CONFIG):
    return {
        "config": common.load(os.path.join(
            common.REPO, "benchmark", "configs", config, "config.json")),
        "traffic": {"seq_len": 32768, "minibatch": 1},
        "chips": 1, "flops": flops, "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": common.load(os.path.join(
            common.REPO, "benchmark", "lib", "peaks.json")),
        "dsa_reduced": reduced}


def test_the_roofline_reads_the_flops_the_scores_need():
    """Two traced steps whose time under ``dsa/scores`` is the least the
    count allows read 100%; twice the time, 50%."""
    run = roofline_run(None)
    flops, moved = dsa_moe_decoder.kernels(
        run["config"], run["traffic"])["indexer_scores"]
    least = flops / 197e12
    assert least > moved / 819e9
    seconds = dict.fromkeys(dsa_trace.KINDS, 0.0)
    seconds["dsa/scores"] = 2 * least
    seconds["dsa/select"] = 1.0  # the selection is not its time
    device = {"steps": 2, "busy_s": 3.0, "seconds": seconds}
    reduced = {"devices": {"0": device}}
    assert indexer_score_roofline.read(
        roofline_run(reduced)) == pytest.approx(100)
    seconds["dsa/scores"] = 4 * least
    assert indexer_score_roofline.read(
        roofline_run(reduced)) == pytest.approx(50)
    # a configuration without a count, a count that names no scores, a
    # program with nothing under the scope
    from benchmark.flops import bd_moe_decoder

    assert indexer_score_roofline.read(
        roofline_run(reduced, flops=None)) is None
    assert indexer_score_roofline.read(roofline_run(
        reduced, flops=bd_moe_decoder, config="sdar-30b-a3b-1chip")) is None
    seconds["dsa/scores"] = 0.0
    assert indexer_score_roofline.read(roofline_run(reduced)) is None


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent of PR 51 and every other configuration: no scope, no
    line; and no trace at all: nothing to reduce, nothing raised."""
    reduced = dsa_trace.reduce({0: hand_made(scoped=False)})
    assert not any(reduced["devices"]["0"]["seconds"].values())
    for reader in (indexer_time_share, indexer_select_share):
        assert reader.read({"dsa_reduced": reduced}) is None
        assert reader.read({"dsa_reduced": {"devices": {}}}) is None
        assert reader.read({"out": str(tmp_path)}) is None
    assert indexer_score_roofline.read(roofline_run(reduced)) is None
    run = roofline_run(None)
    run.pop("dsa_reduced")
    run["out"] = str(tmp_path)
    assert indexer_score_roofline.read(run) is None
    assert sparse_attn_fill.read({"out": str(tmp_path)}) is None
    (tmp_path / "worker.log").write_text(
        "attention impl=auto resolved to pallas (backend=tpu, q=(1, 32, "
        "16384, 128) bfloat16, kv_heads=4 group=8, flash backward=fused, "
        "mask=block_diffusion(8192, 4) pairs run=80 masked=24 skipped=176 "
        "blocks=1024x1024)\n")
    assert sparse_attn_fill.read({"out": str(tmp_path)}) is None


def test_the_fill_from_the_attention_line(tmp_path):
    line = dsa_trace.attention_line(LINE)
    assert line == {
        "seq": 32768, "topk": 2048, "kept": 65012736,
        "forward": (528, 528, 496, 1024, 1024),
        "backward": (528, 528, 496, 1024, 1024)}
    # the kept entries over the causal tiles': 11.74%
    assert dsa_trace.fill(line) == pytest.approx(
        100 * 65012736 / (528 * 1024 * 1024))
    assert dsa_trace.fill(line) == pytest.approx(11.74, abs=0.01)
    # a later program that ran a third of the tiles would read thrice
    third = dict(line, forward=(176, 176, 848, 1024, 1024),
                 backward=(176, 176, 848, 1024, 1024))
    assert dsa_trace.fill(third) == pytest.approx(3 * 11.7426, abs=0.01)
    # counted from seq and topk, not read from the line's own ``kept``
    assert dsa_trace.fill(dict(line, kept=1)) == dsa_trace.fill(line)
    (tmp_path / "worker.log").write_text("x\n" + LINE + "y\n")
    assert sparse_attn_fill.read({"out": str(tmp_path)}) == pytest.approx(
        11.74, abs=0.01)
    # the program's own line and this reader agree
    from elasticdl_tpu.ops import sparse_attention

    facts = sparse_attention.tiles_facts(32768, 2048)
    assert 100 * facts["fill"] == pytest.approx(dsa_trace.fill(line))
    assert facts["forward"] == line["forward"]


def test_the_manifest_s_entries_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "samples_per_s"
        assert by_name[name]["unit"] == "%"
        assert files.find("metrics", name + ".py")
    assert [by_name[name]["better"] for name in NEW_METRICS] == [
        "lower", "lower", "higher", "higher"]
    assert [by_name[name]["source"] for name in NEW_METRICS] == [
        "device_trace", "device_trace", "device_trace", "program_counter"]
    assert len({by_name[name]["layer"] for name in NEW_METRICS}) == 1
    assert "sparse_attention" in by_name["sparse_attn_fill"]["layer"]
    # members, wherever a later PR appends: the cell, its configuration
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "s32k-b1", 1)
    assert len(cell["why"]) <= 200
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
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
                           "short_conv_time_share", "loop_host_ms"}
    # nothing older lists the new cell
    older = [m for m in manifest["per_layer"]
             if m["name"] not in NEW_METRICS]
    assert not any(CELL in m.get("workloads", []) for m in older)


def test_rehearsal_of_a_tiny_keye_cell(tmp_path):
    """The Keye zoo through ``worker.main``, its reference check (the
    last positions), the ``dsa_select``, ``loss_terms`` and
    ``moe_routing`` events through the worker's loop and the new
    readers through the whole command on the CPU, traced."""
    manifest = os.path.join(common.HERE, "preset", "KEYE.json")
    proc, line = common.run_cell(
        "tiny-keye-s128", 1, tmp_path, manifest=manifest, seconds=3)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    # a CPU run has no device plane: every reader of a trace is left
    # out; the attention line is there
    assert set(line["metrics"]) <= {"peak_hbm_gb", "sparse_attn_fill"}
    assert line["metrics"]["sparse_attn_fill"]["value"] == pytest.approx(
        100 * 3600 / 128 ** 2)
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-keye-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "loss", "indexer_loss", "choices", "kept", "scores",
        "kept_count", "kept_after_plus_one", "dropped_pairs_plus_one",
        "grad:block_1/attn/indexer_q/kernel",
        "grad:block_1/attn/indexer_k/kernel",
        "grad:block_1/attn/indexer_w/kernel",
        "grad:block_0/attn/q_norm/scale", "grad:block_1/moe_mlp/w_gate",
        "grad:wte/embedding"}
    for name in ("kept_count", "kept_after_plus_one",
                 "dropped_pairs_plus_one"):
        assert check["errors"][name] == 0
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert "mask=selected(32) pairs run=1 masked=1 skipped=0" in log
    assert "indexer heads=2 dim=8" in log
    assert "shared=0 held=4-7 rows=1536, experts' matmul=ragged_dot)" in log
    assert " indexer_loss " in log
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    routing = [e for e in journal if e.get("event") == "moe_routing"]
    assert routing and all(e["dropped_pairs"] == 0.0 for e in routing)
    assert all(e["held_pairs"] > 0 for e in routing)
    picks = [e for e in journal if e.get("event") == "dsa_select"]
    assert picks and len(picks) == len(routing)
    for event in picks:
        # a list a fact, one entry a layer: min(32, t + 1) on average
        assert event["kept_mean"] == [28.125] * 2
        assert len(event["indexer_loss"]) == len(event["entropy"]) == 2
        assert all(0.0 < share <= 1.0 for share in event["near_share"])
        assert event["tiles_run"] == event["tiles_causal"] == 1.0
    terms = [e for e in journal if e.get("event") == "loss_terms"]
    assert terms and all(
        0 < e["indexer_loss"] < e["loss"] for e in terms)
    by_step = {e["step"]: e for e in picks}
    for event in terms:
        assert event["indexer_loss"] == pytest.approx(
            sum(by_step[event["step"]]["indexer_loss"]), rel=1e-4)
