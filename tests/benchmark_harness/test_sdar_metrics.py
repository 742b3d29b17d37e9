"""What the SDAR configuration added to the measurement (PR 35):
``lib/bd_trace.py`` on hand-made operations and recorded log lines, the
two readers (``bd_flash_fill``, ``bd_overhead_share``) on what a run
leaves, a program without the scopes or the line (the parent) reading
nothing, and ``flash_roofline`` over the count under the mask."""

import os

import pytest

from benchmark.flops import bd_moe_decoder
from benchmark.lib import bd_trace
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import bd_flash_fill, bd_overhead_share, flash_roofline
from tests.benchmark_harness import _common as common

KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/"
BWD = "jit(train_step)/jit(main)/transpose(jvp(forward))/MoeTransformerLM/"


@pytest.mark.parametrize("op_name,kind", [
    (FWD + "bd/noise/random_bits", "bd/noise"),
    (FWD + "bd/noise/lt", "bd/noise"),
    (FWD + "bd/assemble/concatenate", "bd/assemble"),
    (FWD + "bd/assemble/slice", "bd/assemble"),
    (BWD + "bd/assemble/pad", "bd/assemble"),
    ("transpose(jvp(bd/assemble))/pad", "bd/assemble"),
    (FWD + "block_0/attn/pallas_call", None),
    (FWD + "bd/assembler/x", None),
    (FWD + "abd/noise/x", None),
    (FWD + "block_0/moe_mlp/moe/router/top_k", None),
])
def test_classify(op_name, kind):
    assert bd_trace.classify(op_name) == kind


def hand_made(scoped=True):
    """Two step periods of 200 us: 10 us under ``bd/noise``, 10 forward
    and 20 backward under ``bd/assemble``, 60 us of flash, 60 us of
    other work, 40 us idle."""
    ops = []
    for period in range(3):
        t = period * 200_000.0
        if scoped:
            for op_name, length in ((FWD + "bd/noise/random_bits", 10_000),
                                    (FWD + "bd/assemble/concatenate", 10_000),
                                    (BWD + "bd/assemble/pad", 20_000)):
                ops.append(("%fusion.1 = bf16[] fusion(", t, t + length,
                            op_name))
                t += length
        ops.append(("%flash_fwd.2 = bf16[]" + KERNEL, t, t + 60_000,
                    FWD + "block_0/attn/pallas_call"))
        ops.append(("%fusion.3 = f32[] fusion(", t + 60_000, t + 120_000,
                    FWD + "ln_f/mul"))
    modules = [("jit_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 180_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = bd_trace.reduce_device(ops, modules)
    assert device["steps"] == 2 and device["scoped"]
    assert device["busy_s"] == pytest.approx(320e-6)
    assert device["seconds"] == {
        "bd/noise": pytest.approx(20e-6), "bd/assemble": pytest.approx(60e-6)}
    reduced = bd_trace.reduce({0: (ops, modules)})
    assert bd_trace.time_share(reduced) == pytest.approx(100 * 80 / 320)
    assert bd_trace.time_share(reduced, ["bd/noise"]) == pytest.approx(
        100 * 20 / 320)
    assert bd_overhead_share.read({"bd_reduced": reduced}) == pytest.approx(
        25.0)


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent of PR 35 and every other configuration: no scope, no
    line; and no trace at all: nothing to reduce, nothing raised."""
    reduced = bd_trace.reduce({0: hand_made(scoped=False)})
    assert reduced["devices"]["0"]["scoped"] is False
    assert bd_overhead_share.read({"bd_reduced": reduced}) is None
    assert bd_overhead_share.read({"bd_reduced": {"devices": {}}}) is None
    assert bd_overhead_share.read({"out": str(tmp_path)}) is None
    assert bd_flash_fill.read({"out": str(tmp_path)}) is None
    (tmp_path / "worker.log").write_text(CAUSAL_LINE)
    assert bd_flash_fill.read({"out": str(tmp_path)}) is None


PREFIX = ("2026-09-28 19:00:00,000 INFO elasticdl_tpu.ops.attention: "
          "attention impl=auto resolved to pallas (backend=tpu, ")
CAUSAL_LINE = PREFIX + (
    "q=(1, 16, 32768, 256) bfloat16, kv_heads=2 group=8, gate=sigmoid "
    "rotary=64/256, flash backward=split, pairs run=528 masked=32 "
    "skipped=496)\n")
# the model's float32 init traces the layer too: not the step's line
INIT_LINE = PREFIX + (
    "q=(1, 32, 16384, 128) float32, kv_heads=4 group=8, flash "
    "backward=fused, mask=block_diffusion(8192, 4) pairs run=288 "
    "masked=48 skipped=736 blocks=512x512)\n")
LINE_1024 = PREFIX + (
    "q=(1, 32, 16384, 128) bfloat16, kv_heads=4 group=8, flash "
    "backward=fused, mask=block_diffusion(8192, 4) pairs run=80 masked=24 "
    "skipped=176 blocks=1024x1024)\n")
LINE_TWO = PREFIX + (
    "q=(1, 32, 16384, 128) bfloat16, kv_heads=4 group=8, flash "
    "backward=fused, mask=block_diffusion(8192, 4) pairs run=80 masked=24 "
    "skipped=176 blocks=1024x1024 (backward run=288 masked=48 skipped=736 "
    "blocks=512x512))\n")


def test_the_line_is_what_ops_attention_prints():
    """The recorded lines above are the program's own format."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as F
    from elasticdl_tpu.ops.attention import _flash_facts

    q = jnp.zeros((1, 32, 16384, 128), jnp.bfloat16)
    k = jnp.zeros((1, 4, 16384, 128), jnp.bfloat16)
    facts = _flash_facts(q, k, k, F.BlockDiffusion(8192, 4), None, None)
    assert "q=(1, 32, 16384, 128) bfloat16, %s)\n" % facts in LINE_1024


def test_fill_from_recorded_lines(tmp_path):
    assert bd_trace.attention_line(CAUSAL_LINE) is None
    assert bd_trace.attention_line(INIT_LINE) is None
    line = bd_trace.attention_line(CAUSAL_LINE + INIT_LINE + LINE_1024)
    assert line == {"half_len": 8192, "block": 4,
                    "forward": (80, 24, 176, 1024, 1024),
                    "backward": (80, 24, 176, 1024, 1024)}
    needed = 8192 ** 2 + 8192 * 4
    assert bd_trace.fill(line) == pytest.approx(
        100 * needed / (80 * 1024 ** 2))
    # ISSUE 35's count at 512 x 512: (n^2 + n B / T) / (n^2 + 2 n), n = 16
    small = dict(line, forward=(288, 48, 736, 512, 512),
                 backward=(288, 48, 736, 512, 512))
    assert bd_trace.fill(small) == pytest.approx(
        100 * (256 + 16 * 4 / 512) / (256 + 32))
    # a backward with tiles of its own: two products over the
    # forward's tiles, five over the backward's
    two = bd_trace.attention_line(LINE_TWO)
    assert two["backward"] == (288, 48, 736, 512, 512)
    assert bd_trace.fill(two) == pytest.approx(100 * 7 * needed / (
        2 * 80 * 1024 ** 2 + 5 * 288 * 512 ** 2))
    (tmp_path / "worker.log").write_text(INIT_LINE + LINE_1024)
    assert bd_flash_fill.read({"out": str(tmp_path)}) == pytest.approx(
        bd_trace.fill(line))


def test_flash_roofline_reads_the_count_under_the_mask():
    """One traced step of one sample whose flash kernels took the least
    time the count allows reads 100%."""
    config = {
        "hidden_size": 2048, "num_hidden_layers": 6,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "num_experts": 16,
        "published": {"num_experts": 128}, "num_experts_per_tok": 8,
        "moe_intermediate_size": 768, "vocab_size": 18992,
        "assumed": {"block_length": 4}}
    traffic = {"seq_len": 8192, "minibatch": 1}
    flops, bytes_ = bd_moe_decoder.kernels(config, traffic)["flash"]
    least = max(flops / 197e12, bytes_ / 819e9)
    run = {
        "config": config, "traffic": traffic, "chips": 1,
        "flops": bd_moe_decoder,
        "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": common.load(os.path.join(
            common.REPO, "benchmark", "lib", "peaks.json")),
        "reduced_trace": {"devices": [{
            "steps": 1, "busy_s": 1.0,
            "kernels": {"flash_fwd": least / 4, "flash_bwd": 3 * least / 4,
                        "gmm": 0.5}}]},
    }
    assert flash_roofline.read(run) == pytest.approx(100.0, rel=1e-3)


def test_the_manifest_s_entries_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cell = "sdar30b-bd-s8k"
    for name in ("bd_flash_fill", "bd_overhead_share"):
        assert by_name[name]["workloads"] == [cell]
        assert by_name[name]["moves"] == "samples_per_s"
        assert files.find("metrics", name + ".py")
    assert by_name["bd_flash_fill"]["layer"] == by_name[
        "flash_roofline"]["layer"] == "kernels"
    assert (by_name["bd_flash_fill"]["better"],
            by_name["bd_flash_fill"]["source"]) == (
                "higher", "program_counter")
    assert (by_name["bd_overhead_share"]["better"],
            by_name["bd_overhead_share"]["source"]) == (
                "lower", "device_trace")
    assert "ops/block_diffusion.py" in by_name["bd_overhead_share"]["layer"]
    reported = {m["name"] for m in files.metrics_for("per_layer", cell)}
    assert reported >= {"bd_flash_fill", "bd_overhead_share",
                        "flash_time_share", "flash_roofline", "peak_hbm_gb",
                        "optimizer_time_share"}
    assert not reported & {"moe_time_share", "gdn_time_share",
                           "held_pairs_over_share", "loop_host_ms"}
    older = [m for m in manifest["per_layer"]
             if not m["name"].startswith("bd_")]
    assert not any(cell in m.get("workloads", []) for m in older)


def test_rehearsal_of_a_tiny_sdar_cell(tmp_path):
    """The SDAR zoo trained by block diffusion, its reference check
    (the noise exact, the last positions), the ``bd_noise`` event
    through the worker's loop and the new readers through the whole
    command on the CPU, untraced and traced."""
    import json

    manifest = os.path.join(common.HERE, "preset", "SDAR.json")
    proc, line = common.run_cell(
        "tiny-sdar-s128", 0, tmp_path, manifest=manifest, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-sdar-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "loss", "choices", "dropped_pairs_plus_one",
        "noisy_tokens", "weights", "grad:block_3/attn/q_norm/scale"}
    assert check["errors"]["noisy_tokens"] == 0
    assert check["errors"]["weights"] == 0
    assert check["errors"]["dropped_pairs_plus_one"] == 0
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    # both copies reach the expert layer: 4 sequences x 2 x 128 positions
    assert "moe dispatch resolved to sorted (tokens=1024 experts=16" in log
    assert "shared=0 held=4-7 rows=2048, experts' matmul=ragged_dot)" in log

    proc, line = common.run_cell(
        "tiny-sdar-s128", 1, tmp_path, manifest=manifest, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    # a CPU run has no device plane and no Pallas line: every reader of
    # either is left out
    assert line["metrics"] == {}
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    noise = [e for e in journal if e["event"] == "bd_noise"]
    routing = [e for e in journal if e["event"] == "moe_routing"]
    assert noise and {e["step"] for e in noise} == {
        e["step"] for e in routing}
    assert all(0.3 < e["masked_share"] < 0.7 for e in noise)
    assert all(0.4 < e["mean_t"] < 0.6 for e in noise)
    assert all(0.7 < e["weight_mean"] < 1.3 for e in noise)
    assert all(e["dropped_pairs"] == 0.0 for e in routing)
    # 1024 positions x top-3 over ALL 16 experts
    assert all(e["tokens_per_expert_mean"] == 192.0 for e in routing)
