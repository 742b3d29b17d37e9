"""What the Xing4.0 configuration added to the measurement (PR 37):
``lib/mhc_trace.py`` on hand-made operations, the three readers
(``mhc_time_share``, ``mhc_mix_roofline``, ``mtp_time_share``) on what a
run leaves, a program without the scopes (the parent) reading nothing,
the manifest's entries by name, and a rehearsal of a tiny cell through
the whole command."""

import json
import os

import pytest

from benchmark.flops import hc_mla_moe_decoder
from benchmark.lib import mhc_trace
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import mhc_mix_roofline, mhc_time_share, mtp_time_share
from tests.benchmark_harness import _common as common

KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/"
BWD = "jit(train_step)/jit(main)/transpose(jvp(forward))/MoeTransformerLM/"
CELL = "xing4-29b-s4k"
NEW_METRICS = ("mhc_time_share", "mhc_mix_roofline", "mtp_time_share")


@pytest.mark.parametrize("op_name,kinds", [
    (FWD + "block_0/hc_attn/mhc/coef/while/body/div", ["mhc/coef"]),
    (FWD + "block_0/hc_attn/mhc/pre/mul", ["mhc/pre"]),
    (FWD + "block_3/mhc/post/add", ["mhc/post"]),
    (BWD + "block_3/hc_mlp/mhc/coef/dot_general", ["mhc/coef"]),
    ("transpose(jvp(mhc/post))/mul", ["mhc/post"]),
    (FWD + "mtp/proj/mtp_proj/dot_general", ["mtp/proj"]),
    (FWD + "mtp/head/lm_head/dot_general", ["mtp/head"]),
    # the module's block has hyper-connections of its own: both count
    (FWD + "mtp/block/mtp_block/hc_attn/mhc/pre/mul",
     ["mhc/pre", "mtp/block"]),
    (BWD + "mtp/block/mtp_block/attn/mla/q_down/dot_general", ["mtp/block"]),
    (FWD + "block_0/attn/mla/q_down/dot_general", []),
    (FWD + "mhc/prefix/x", []),
    (FWD + "smhc/pre/x", []),
    (FWD + "mtp/blocks/x", []),
])
def test_classify(op_name, kinds):
    assert mhc_trace.classify("%fusion.1 = bf16[] fusion(", op_name) == kinds


def test_a_kernel_named_mhc_is_the_mixes():
    name = "%mhc_post.3 = bf16[4,4096,3584]" + KERNEL
    assert mhc_trace.classify(name, FWD + "block_0/pallas_call") == [
        "mhc/post"]
    assert mhc_trace.classify(
        "%flash_fwd.3 = bf16[]" + KERNEL, FWD + "block_0/attn/x") == []


def hand_made(scoped=True):
    """Two step periods of 200 us: 20 us under ``mhc/coef``, 10 forward
    and 20 backward under the two mixes, 30 us under ``mtp/block`` of
    which 10 under ``mhc/post`` too, 100 us of other work, 20 us idle."""
    ops = []
    for period in range(3):
        t = period * 200_000.0
        if scoped:
            for op_name, length in (
                    (FWD + "block_0/hc_attn/mhc/coef/dot_general", 20_000),
                    (FWD + "block_0/hc_attn/mhc/pre/mul", 10_000),
                    (BWD + "block_0/hc_attn/mhc/post/mul", 20_000),
                    (FWD + "mtp/block/mtp_block/attn/x", 20_000),
                    (FWD + "mtp/block/mtp_block/hc_mlp/mhc/post/add",
                     10_000)):
                ops.append(("%fusion.1 = bf16[] fusion(", t, t + length,
                            op_name))
                t += length
        else:
            t += 80_000
        ops.append(("%fusion.3 = f32[] fusion(", t, t + 100_000,
                    FWD + "ln_f/mul"))
    modules = [("jit_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 180_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = mhc_trace.reduce_device(ops, modules)
    assert device["steps"] == 2 and device["scoped"]
    assert device["busy_s"] == pytest.approx(360e-6)
    assert device["seconds"] == {
        "mhc/coef": pytest.approx(40e-6), "mhc/pre": pytest.approx(20e-6),
        "mhc/post": pytest.approx(60e-6), "mtp/proj": 0.0,
        "mtp/block": pytest.approx(60e-6), "mtp/head": 0.0}
    reduced = mhc_trace.reduce({0: (ops, modules)})
    assert mhc_time_share.read({"mhc_reduced": reduced}) == pytest.approx(
        100 * 120 / 360)
    assert mtp_time_share.read({"mhc_reduced": reduced}) == pytest.approx(
        100 * 60 / 360)


def roofline_run(reduced, flops=hc_mla_moe_decoder):
    config = common.load(os.path.join(
        common.REPO, "benchmark", "configs", "xing4.0-29b-a4b-1chip",
        "config.json"))
    return {
        "config": config, "traffic": {"seq_len": 4096, "minibatch": 1},
        "chips": 1, "flops": flops, "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": common.load(os.path.join(
            common.REPO, "benchmark", "lib", "peaks.json")),
        "mhc_reduced": reduced}


def test_the_roofline_reads_the_bytes_the_mixes_must_move():
    """Two traced steps whose mixes took the least time the count
    allows read 100%; twice the time, 50%."""
    config = roofline_run(None)["config"]
    flops, moved = hc_mla_moe_decoder.kernels(
        config, {"seq_len": 4096, "minibatch": 1})["mhc_mix"]
    least = max(flops / 197e12, moved / 819e9)
    assert least == pytest.approx(moved / 819e9)  # bytes bound it
    seconds = dict.fromkeys(mhc_trace.MHC_KINDS + mhc_trace.MTP_KINDS, 0.0)
    seconds.update({"mhc/coef": 0.05, "mhc/pre": 2 * least / 4,
                    "mhc/post": 2 * 3 * least / 4})
    reduced = {"devices": {"0": {
        "steps": 2, "busy_s": 1.0, "seconds": seconds, "scoped": True}}}
    assert mhc_mix_roofline.read(roofline_run(reduced)) == pytest.approx(100)
    seconds["mhc/post"] += 2 * least
    assert mhc_mix_roofline.read(roofline_run(reduced)) == pytest.approx(50)
    # a count that names no mixes, a configuration without a count
    from benchmark.flops import mla_moe_decoder

    assert mhc_mix_roofline.read(
        roofline_run(reduced, flops=mla_moe_decoder)) is None
    assert mhc_mix_roofline.read(roofline_run(reduced, flops=None)) is None
    # no module: nothing under mtp/, the share is left out
    assert mtp_time_share.read({"mhc_reduced": reduced}) is None


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent of PR 37 and every other configuration: no scope; and
    no trace at all: nothing to reduce, nothing raised."""
    reduced = mhc_trace.reduce({0: hand_made(scoped=False)})
    assert reduced["devices"]["0"]["scoped"] is False
    for reader in (mhc_time_share, mtp_time_share):
        assert reader.read({"mhc_reduced": reduced}) is None
        assert reader.read({"mhc_reduced": {"devices": {}}}) is None
        assert reader.read({"out": str(tmp_path)}) is None
    assert mhc_mix_roofline.read(roofline_run(reduced)) is None
    run = roofline_run(None)
    run.pop("mhc_reduced")
    run["out"] = str(tmp_path)
    assert mhc_mix_roofline.read(run) is None


def test_the_manifest_s_entries_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "samples_per_s"
        assert (by_name[name]["unit"], by_name[name]["source"]) == (
            "%", "device_trace")
        assert files.find("metrics", name + ".py")
    assert [by_name[name]["better"] for name in NEW_METRICS] == [
        "lower", "higher", "lower"]
    assert by_name["mhc_time_share"]["layer"] == by_name[
        "mhc_mix_roofline"]["layer"]
    assert "HyperConnection" in by_name["mhc_time_share"]["layer"]
    assert "MoeTransformerLM" in by_name["mtp_time_share"]["layer"]
    reported = {m["name"] for m in files.metrics_for("per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | {
        "flash_time_share", "flash_roofline", "peak_hbm_gb",
        "optimizer_time_share", "device_idle_share"}
    assert not reported & {"moe_time_share", "gdn_time_share",
                           "mla_time_share", "bd_overhead_share",
                           "held_pairs_over_share", "loop_host_ms"}
    # nothing older lists the new cell, and nothing older was moved
    older = [m for m in manifest["per_layer"]
             if m["name"] not in NEW_METRICS]
    assert not any(CELL in m.get("workloads", []) for m in older)


def test_rehearsal_of_a_tiny_xing_cell(tmp_path):
    """The Xing4.0 zoo through ``worker.main``, its reference check
    (both losses, the coefficients, the last positions), the ``mhc``
    and ``loss_terms`` events through the worker's loop and the new
    readers through the whole command on the CPU, traced."""
    manifest = os.path.join(common.HERE, "preset", "XING.json")
    proc, line = common.run_cell(
        "tiny-xing-s128", 1, tmp_path, manifest=manifest, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    # a CPU run has no device plane: every reader of one is left out
    assert set(line["metrics"]) <= {"peak_hbm_gb"}
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-xing-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "mtp_logits", "loss", "mtp_loss", "choices",
        "dropped_pairs_plus_one", "h_res:first", "row_err_plus_one:last",
        "grad:block_1/hc_attn/p_res", "grad:mtp_proj/kernel"}
    assert check["errors"]["dropped_pairs_plus_one"] == 0
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert "moe dispatch resolved to sorted (tokens=512 experts=16" in log
    assert "shared=1 held=4-7 rows=1536, experts' matmul=ragged_dot)" in log
    # the second loss follows the first on the line the harness reads
    assert " mtp_loss " in log
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    facts = [e for e in journal if e.get("event") == "mhc"]
    terms = [e for e in journal if e.get("event") == "loss_terms"]
    routing = [e for e in journal if e.get("event") == "moe_routing"]
    assert facts and {e["step"] for e in facts} == {
        e["step"] for e in routing} == {e["step"] for e in terms}
    # three blocks and the module's: one fact each
    assert all(len(e["row_err"]) == len(e["diag_mean"]) == 4 for e in facts)
    assert all(max(e["row_err"]) < 1e-3 for e in facts)
    assert all(min(e["diag_mean"]) > 0.9 for e in facts)
    assert all(0 < e["mtp_loss"] and e["loss"] > 0 for e in terms)
    assert all(e["dropped_pairs"] == 0.0 for e in routing)
