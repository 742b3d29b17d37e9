"""What the Laguna-XS.2 configuration added to the measurement (PR 42):
``lib/window_trace.py`` on hand-made operations and recorded lines, the
three readers (``window_attn_time_share``, ``window_flash_roofline``,
``window_flash_fill``) on what a run leaves, a program without the
scopes or the line (the parent) reading nothing, the manifest's entries
by name, and a rehearsal of a tiny cell through the whole command."""

import json
import os

import pytest

from benchmark.flops import window_moe_decoder
from benchmark.lib import trace_reduce as tr
from benchmark.lib import window_trace
from benchmark.metrics import (
    window_attn_time_share,
    window_flash_fill,
    window_flash_roofline,
)
from tests.benchmark_harness import _common as common

KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/"
BWD = "jit(train_step)/jit(main)/transpose(jvp(forward))/MoeTransformerLM/"
REMAT = ("jit(train_step)/jit(main)/transpose(jvp(forward))/"
         "MoeTransformerLM/checkpoint/rematted_computation/")
CELL = "laguna-xs2-s32k"
NEW_METRICS = ("window_attn_time_share", "window_flash_roofline",
               "window_flash_fill")
FUSION = "%fusion.1 = bf16[] fusion("


@pytest.mark.parametrize("op_name,kind", [
    (FWD + "block_1/attn/attn_window/qkv/query/dot_general",
     "attn_window/qkv"),
    (FWD + "block_1/attn/attn_window/rotary/mul", "attn_window/rotary"),
    (BWD + "block_2/attn/attn_window/gate/mul", "attn_window/gate"),
    (REMAT + "block_3/attn/attn_window/out_proj/dot_general",
     "attn_window/out_proj"),
    ("transpose(jvp(attn_window/flash))/reduce_sum", "attn_window/flash"),
    (FWD + "block_0/attn/attn_full/qkv/key/dot_general", "attn_full/qkv"),
    (BWD + "block_4/attn/attn_full/flash/mul", "attn_full/flash"),
    (FWD + "block_1/moe_mlp/moe/shared/dot_general", None),
    (FWD + "block_1/attn/attn_window/other/x", None),
    (FWD + "block_1/attn/attn_windows/qkv/x", None),
    (FWD + "block_1/my_attn_window/qkv/x", None),
    (FWD + "block_1/attn_linear/qkv/x", None),
])
def test_classify(op_name, kind):
    assert window_trace.classify(FUSION, op_name) == kind


def test_the_band_s_kernels_by_name():
    """A Mosaic kernel named ``flash_band...`` is the band's wherever it
    was called; any other named ``flash...`` is the full layers'."""
    band = "%flash_band_fwd.3 = (bf16[64,32768,128])" + KERNEL
    assert window_trace.classify(band, "jit(train_step)/pallas_call") == (
        "attn_window/flash")
    for name in ("flash_fwd", "flash_bwd", "flash_dq"):
        assert window_trace.classify(
            "%%%s.1 = bf16[]" % name + KERNEL,
            FWD + "block_1/attn/attn_window/flash/pallas_call") == (
                "attn_full/flash")
    # a kernel of another family under the scope counts by the scope
    other = "%gmm.2 = bf16[]" + KERNEL
    assert window_trace.classify(other, FWD + "moe/experts/x") is None


def hand_made(scoped=True, band=True):
    """Two step periods of 200 us: under ``attn_window/`` 20 us of
    projections forward and 10 backward and the band's kernels 30 us
    forward and 20 backward (their ``op_name`` without the scope), 20
    us of the full layers' flash, 60 us of other work, 40 us idle."""
    ops = []
    for period in range(3):
        t = period * 200_000.0
        events = [
            (FUSION, FWD + "block_1/attn/attn_window/qkv/dot_general",
             20_000),
            (FUSION, BWD + "block_1/attn/attn_window/out_proj/dot_general",
             10_000)] if scoped else []
        if band:
            events += [
                ("%flash_band_fwd.1 = bf16[]" + KERNEL,
                 FWD + "block_1/attn/attn_window/flash/pallas_call", 30_000),
                ("%flash_band_bwd.1 = bf16[]" + KERNEL,
                 "jit(train_step)/pallas_call", 20_000)]
        events += [("%flash_fwd.1 = bf16[]" + KERNEL,
                    "jit(train_step)/pallas_call", 20_000)]
        for name, op_name, length in events:
            ops.append((name, t, t + length, op_name))
            t += length
        ops.append(("%fusion.3 = f32[] fusion(", t, t + 60_000,
                    FWD + "ln_f/mul"))
    modules = [("jit_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 160_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = window_trace.reduce_device(ops, modules)
    assert device["steps"] == 2 and device["scoped"]
    assert device["busy_s"] == pytest.approx(320e-6)
    want = dict.fromkeys(window_trace.KINDS, 0.0)
    want.update({"attn_window/qkv": pytest.approx(40e-6),
                 "attn_window/out_proj": pytest.approx(20e-6),
                 "attn_window/flash": pytest.approx(100e-6),
                 "attn_full/flash": pytest.approx(40e-6)})
    assert device["seconds"] == want
    assert device["band_kernels_s"] == pytest.approx(100e-6)
    reduced = window_trace.reduce({0: (ops, modules)})
    assert window_attn_time_share.read(
        {"window_reduced": reduced}) == pytest.approx(100 * 160 / 320)
    assert window_trace.time_share(
        reduced, ["attn_full/flash"]) == pytest.approx(100 * 40 / 320)


def roofline_run(reduced, flops=window_moe_decoder):
    config = common.load(os.path.join(
        common.REPO, "benchmark", "configs", "laguna-xs.2-1chip",
        "config.json"))
    return {
        "config": config, "traffic": {"seq_len": 32768, "minibatch": 1},
        "chips": 1, "flops": flops, "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": common.load(os.path.join(
            common.REPO, "benchmark", "lib", "peaks.json")),
        "window_reduced": reduced}


def test_the_roofline_reads_the_work_the_band_needs():
    """Two traced steps whose band kernels took the least time the
    count allows read 100%; twice the time, 50%."""
    config = roofline_run(None)["config"]
    flops, moved = window_moe_decoder.kernels(
        config, {"seq_len": 32768, "minibatch": 1})["flash_window"]
    least = max(flops / 197e12, moved / 819e9)
    assert least == pytest.approx(flops / 197e12)  # FLOPs bound it
    seconds = dict.fromkeys(window_trace.KINDS, 0.0)
    seconds["attn_window/flash"] = 2 * least + 0.01
    device = {"steps": 2, "busy_s": 1.0, "seconds": seconds,
              "band_kernels_s": 2 * least, "scoped": True}
    reduced = {"devices": {"0": device}}
    assert window_flash_roofline.read(
        roofline_run(reduced)) == pytest.approx(100)
    device["band_kernels_s"] = 4 * least
    assert window_flash_roofline.read(
        roofline_run(reduced)) == pytest.approx(50)
    # a count that names no band, a configuration without a count, a
    # program whose trace has no band kernel
    from benchmark.flops import gdn_moe_decoder

    assert window_flash_roofline.read(roofline_run(reduced, flops=None)) is (
        None)
    qwen = dict(roofline_run(reduced, flops=gdn_moe_decoder))
    qwen["config"] = common.load(os.path.join(
        common.REPO, "benchmark", "configs", "qwen3-next-80b-a3b-1chip",
        "config.json"))
    assert window_flash_roofline.read(qwen) is None
    device["band_kernels_s"] = 0.0
    assert window_flash_roofline.read(roofline_run(reduced)) is None


LINES = {
    "init": (
        "attention impl=auto resolved to pallas (backend=tpu, "
        "q=(1, 64, 32768, 128) float32, heads=64 gate=sigmoid "
        "rotary=128/128, kv_heads=8 group=8, flash backward=fused, "
        "mask=window(512) pairs run=127 masked=127 skipped=3969 "
        "blocks=512x512)"),
    "window": (
        "attention impl=auto resolved to pallas (backend=tpu, "
        "q=(1, 64, 32768, 128) bfloat16, heads=64 gate=sigmoid "
        "rotary=128/128, kv_heads=8 group=8, flash backward=fused, "
        "mask=window(512) pairs run=63 masked=63 skipped=961 "
        "blocks=1024x1024)"),
    "full": (
        "attention impl=auto resolved to pallas (backend=tpu, "
        "q=(1, 48, 32768, 128) bfloat16, heads=48 gate=sigmoid "
        "rotary=64/128 yarn=64, kv_heads=8 group=6, flash backward=fused, "
        "pairs run=528 masked=32 skipped=496)"),
    "split": (
        "attention impl=auto resolved to pallas (backend=tpu, "
        "q=(2, 8, 4096, 128) bfloat16, heads=8, flash backward=split, "
        "mask=window(512) pairs run=15 masked=15 skipped=21 blocks=512x512 "
        "(backward run=29 masked=22 skipped=35 blocks=512x256))"),
    "sdar": (
        "attention impl=auto resolved to pallas (backend=tpu, "
        "q=(1, 32, 16384, 128) bfloat16, kv_heads=4 group=8, flash "
        "backward=fused, mask=block_diffusion(8192, 4) pairs run=80 "
        "masked=24 skipped=176 blocks=1024x1024)"),
}


def test_the_attention_line_and_the_fill():
    """The step's bfloat16 line and not the init's float32 one; the
    full layers' and another mask's lines are not the band's."""
    log = "\n".join(
        "2026-09-29 INFO elasticdl_tpu.ops.attention: " + LINES[name]
        for name in ("init", "full", "window", "sdar"))
    line = window_trace.attention_line(log)
    assert line == {"seq": 32768, "window": 512,
                    "forward": (63, 63, 961, 1024, 1024),
                    "backward": (63, 63, 961, 1024, 1024)}
    kept = 32768 * 512 - 512 * 511 / 2
    assert window_trace.fill(line) == pytest.approx(
        100 * kept / (63 * 1024 * 1024))
    assert 25.0 < window_trace.fill(line) < 25.2
    # a backward with tiles of its own counts its five products there
    split = window_trace.attention_line(LINES["split"])
    assert split["backward"] == (29, 22, 35, 512, 256)
    kept = 4096 * 512 - 512 * 511 / 2
    assert window_trace.fill(split) == pytest.approx(
        100 * 7 * kept / (2 * 15 * 512 * 512 + 5 * 29 * 512 * 256))
    for name in ("full", "sdar", "init"):
        assert window_trace.attention_line(LINES[name]) is None


def test_the_fill_reader_on_a_run_s_log(tmp_path):
    with open(tmp_path / "worker.log", "w") as f:
        f.write(LINES["full"] + "\n" + LINES["window"] + "\n")
    assert window_flash_fill.read({"out": str(tmp_path)}) == pytest.approx(
        25.199, abs=0.001)


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent of PR 42 and every other configuration: flash kernels
    and no scope, no band kernel, no line; and no trace at all: nothing
    to reduce, nothing raised."""
    reduced = window_trace.reduce(
        {0: hand_made(scoped=False, band=False)})
    device = reduced["devices"]["0"]
    assert device["scoped"] is False
    assert device["seconds"]["attn_full/flash"] > 0
    assert window_attn_time_share.read({"window_reduced": reduced}) is None
    assert window_attn_time_share.read(
        {"window_reduced": {"devices": {}}}) is None
    assert window_attn_time_share.read({"out": str(tmp_path)}) is None
    assert window_flash_roofline.read(roofline_run(reduced)) is None
    run = roofline_run(None)
    run.pop("window_reduced")
    run["out"] = str(tmp_path)
    assert window_flash_roofline.read(run) is None
    assert window_flash_fill.read({"out": str(tmp_path)}) is None
    with open(tmp_path / "worker.log", "w") as f:
        f.write(LINES["sdar"] + "\n" + LINES["full"] + "\n")
    assert window_flash_fill.read({"out": str(tmp_path)}) is None


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
        "lower", "higher", "higher"]
    assert [by_name[name]["source"] for name in NEW_METRICS] == [
        "device_trace", "device_trace", "program_counter"]
    # the band's two are the kernels' layer, letter for letter
    assert by_name["window_flash_roofline"]["layer"] == by_name[
        "window_flash_fill"]["layer"] == by_name["flash_roofline"]["layer"]
    assert "Attention" in by_name["window_attn_time_share"]["layer"]
    reported = {m["name"] for m in files.metrics_for("per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | {
        "flash_time_share", "flash_roofline", "peak_hbm_gb",
        "optimizer_time_share", "device_idle_share"}
    assert not reported & {"moe_time_share", "gdn_time_share",
                           "mla_time_share", "bd_overhead_share",
                           "bd_flash_fill", "mhc_time_share",
                           "held_pairs_over_share", "loop_host_ms"}
    # nothing older lists the new cell, and nothing older was moved
    older = [m for m in manifest["per_layer"]
             if m["name"] not in NEW_METRICS]
    assert not any(CELL in m.get("workloads", []) for m in older)


def test_rehearsal_of_a_tiny_laguna_cell(tmp_path):
    """The Laguna-XS.2 zoo through ``worker.main``, its reference check
    (the last positions), the ``moe_routing`` events through the
    worker's loop and the new readers through the whole command on the
    CPU, traced."""
    manifest = os.path.join(common.HERE, "preset", "LAGUNA.json")
    proc, line = common.run_cell(
        "tiny-laguna-s128", 1, tmp_path, manifest=manifest, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    # a CPU run has no device plane and resolves attention to XLA:
    # every reader of a trace or of the band's line is left out
    assert set(line["metrics"]) <= {"peak_hbm_gb"}
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-laguna-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "loss", "choices", "dropped_pairs_plus_one",
        "grad:block_2/attn/query/kernel", "grad:block_4/attn/key/kernel",
        "grad:block_0/attn/query/kernel", "grad:block_4/moe_mlp/w_gate"}
    assert check["errors"]["dropped_pairs_plus_one"] == 0
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert ("layer kinds: full x2 (heads=6 theta=500000 rotary=8 yarn=64), "
            "window x3 (heads=8 theta=10000 window=24)") in log
    assert "moe dispatch resolved to sorted (tokens=512 experts=16" in log
    assert "shared=1 held=4-7 rows=1536, experts' matmul=ragged_dot)" in log
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    routing = [e for e in journal if e.get("event") == "moe_routing"]
    assert routing and all(e["dropped_pairs"] == 0.0 for e in routing)
    assert all(e["held_pairs"] > 0 and e["held_rows_run"] > 0
               for e in routing)
