"""What the Qwen3-Next configuration added to the measurement (PR 31):
``lib/gdn_trace.py`` on hand-made operations, the five readers
(``gdn_time_share``, ``gdn_scan_share``, ``gdn_scan_roofline``,
``expert_share_time_share``, ``held_pairs_over_share``) on what the
reduction and the journal leave, a program without the scopes (the
parent) reading nothing, and the whole command with the tiny rehearsal
of the Qwen3-Next zoo."""

import json
import os

import pytest

from benchmark.flops import gdn_moe_decoder
from benchmark.lib import gdn_trace
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import (
    expert_share_time_share,
    gdn_scan_roofline,
    gdn_scan_share,
    gdn_time_share,
    held_pairs_over_share,
)
from tests.benchmark_harness import _common as common

MANIFEST = os.path.join(common.HERE, "preset", "QWEN3NEXT.json")
KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/block_1/"
BWD = ("jit(train_step)/jit(main)/transpose(jvp(forward))/checkpoint/"
       "rematted_computation/block_0/")


@pytest.mark.parametrize("name,op_name,kind", [
    ("%fusion.1 = bf16[1,32768,12288] fusion(",
     FWD + "attn/gdn/in_proj/in_proj_qkvz/dot_general", "gdn/in_proj"),
    ("%fusion.2 = bf16[1,32768,8192] fusion(",
     BWD + "attn/gdn/conv/mul", "gdn/conv"),
    ("%fusion.3 = f32[] fusion(", FWD + "attn/gdn/gates/exp", "gdn/gates"),
    # inside the scan's while loop, forward and backward
    ("%fusion.4 = f32[1,16,2,128,128] fusion(",
     FWD + "attn/gdn/scan/while/body/closed_call/dot_general", "gdn/scan"),
    ("%fusion.5 = f32[16,2,128,64,64] fusion(",
     BWD + "attn/gdn/scan/while/body/checkpoint/dot_general", "gdn/scan"),
    ("%fusion.6 = bf16[] fusion(", FWD + "attn/gdn/out_norm/mul",
     "gdn/out_norm"),
    ("%fusion.7 = bf16[] fusion(",
     "transpose(jvp(gdn/out_proj))/dot_general", "gdn/out_proj"),
    # a later Pallas kernel for the state, wherever it is called
    ("%gdn_state.8 = f32[32,128,128]" + KERNEL, FWD + "attn/pallas_call",
     "gdn/scan"),
    ("%fusion.9 = bf16[] fusion(", FWD + "moe_mlp/moe/router/top_k",
     "moe/router"),
    ("%fusion.10 = bf16[] fusion(", FWD + "moe_mlp/moe/dispatch/sort",
     "moe/dispatch"),
    ("%gmm.11 = bf16[40960,512]" + KERNEL,
     FWD + "moe_mlp/moe/experts/jit(gmm)/pallas_call", "moe/experts"),
    ("%ragged-dot.12 = bf16[40960,512] fusion(", "", "moe/experts"),
    ("%fusion.13 = f32[] fusion(", BWD + "moe_mlp/moe/combine/scatter-add",
     "moe/combine"),
    ("%fusion.14 = bf16[] fusion(",
     FWD + "moe_mlp/moe/shared/shared_up/dot_general", "moe/shared"),
    ("%flash_fwd.15 = (bf16[16,32768,256])" + KERNEL,
     FWD + "attn/pallas_call", None),
    ("%fusion.16 = bf16[] fusion(", FWD + "attn/gdn/scanner/x", None),
    ("%fusion.17 = bf16[] fusion(", FWD + "ln_mlp/mul", None),
])
def test_classify(name, op_name, kind):
    assert gdn_trace.classify(name, op_name) == kind


def hand_made():
    """Two step periods of 200 us: 10 us under each of the eleven
    scopes, 30 us under ``gdn/scan`` inside its loop, 20 us of flash, 20
    us of other work, 20 us idle."""
    scoped = [FWD + "attn/gdn/%s/x" % s for s in gdn_trace.GDN_SCOPES]
    scoped += [FWD + "moe_mlp/moe/%s/x" % s for s in gdn_trace.MOE_SCOPES]
    ops = []
    for period in range(3):
        t = period * 200_000.0
        for op_name in scoped:
            ops.append(("%fusion.1 = bf16[] fusion(", t, t + 10_000, op_name))
            t += 10_000
        # the loop is a container: its body's operations are the time
        ops.append(("%while.2 = () while(", t, t + 30_000,
                    FWD + "attn/gdn/scan/while"))
        for i in range(3):
            ops.append(("%fusion.3 = f32[] fusion(", t + i * 10_000,
                        t + (i + 1) * 10_000,
                        FWD + "attn/gdn/scan/while/body/dot_general"))
        t += 30_000
        ops.append(("%flash_fwd.4 = bf16[]" + KERNEL, t, t + 20_000,
                    FWD + "attn/pallas_call"))
        ops.append(("%fusion.5 = f32[] fusion(", t + 20_000, t + 40_000,
                    FWD + "ln_f/mul"))
    modules = [("jit_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 180_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = gdn_trace.reduce_device(ops, modules)
    assert device["steps"] == 2 and device["scoped"]
    assert device["busy_s"] == pytest.approx(360e-6)
    for kind, seconds in device["seconds"].items():
        want = 80e-6 if kind == "gdn/scan" else 20e-6
        assert seconds == pytest.approx(want), kind
    reduced = gdn_trace.reduce({0: (ops, modules)})
    assert gdn_trace.time_share(reduced, gdn_trace.GDN_KINDS) == (
        pytest.approx(100 * 180 / 360))
    assert gdn_trace.time_share(reduced, ["gdn/scan"]) == (
        pytest.approx(100 * 80 / 360))
    assert gdn_trace.time_share(reduced, gdn_trace.MOE_KINDS) == (
        pytest.approx(100 * 100 / 360))


CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 4, "full_attention_interval": 4,
    "linear_num_key_heads": 1, "linear_num_value_heads": 2,
    "linear_key_head_dim": 4, "linear_value_head_dim": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
    "num_experts": 2, "published": {"num_experts": 8},
    "num_experts_per_tok": 4, "moe_intermediate_size": 4,
    "shared_expert_intermediate_size": 4, "vocab_size": 100,
    "assumed": {"gdn_chunk": 8},
}


def run_of(reduced, **more):
    run = {
        "gdn_reduced": reduced, "config": CONFIG, "chips": 1,
        "traffic": {"seq_len": 16, "minibatch": 2}, "flops": gdn_moe_decoder,
        "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": {"TPU v5 lite": {
            "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}},
    }
    run.update(more)
    return run


def test_the_readers_read_what_the_reduction_left(tmp_path):
    ops, modules = hand_made()
    run = run_of(gdn_trace.reduce({0: (ops, modules)}))
    assert gdn_time_share.read(run) == pytest.approx(50.0)
    assert gdn_scan_share.read(run) == pytest.approx(100 * 80 / 360)
    assert expert_share_time_share.read(run) == pytest.approx(100 * 100 / 360)
    # the rule's needed work a sample: 119,808 FLOPs at 1e12 is 0.12 us,
    # 9,216 bytes at 1e9 is 9.2 us: bytes bound it; two steps of two
    # samples over 80 us under gdn/scan
    need = gdn_moe_decoder.kernels(CONFIG, run["traffic"])["gdn_scan"]
    assert need == (119_808, 9_216)
    assert gdn_scan_roofline.read(run) == pytest.approx(
        100 * 4 * 9_216e-9 / 80e-6)
    # a configuration whose count names no such kernel
    class Other:
        kernels = staticmethod(lambda config, traffic: {"flash": (1.0, 1.0)})

    assert gdn_scan_roofline.read(dict(run, flops=Other)) is None
    # no trace at all: nothing to reduce, nothing raised
    for module in (gdn_time_share, gdn_scan_share, gdn_scan_roofline,
                   expert_share_time_share):
        assert module.read(run_of(None, out=str(tmp_path))) is None


def test_a_program_without_the_scopes_reads_nothing():
    """The parent of PR 31, and every other configuration: the ``moe/``
    scopes and the flash kernels alone do not make a program
    ``scoped``, and no peak is asked of a device that has none."""
    ops = [(n, s, e, op) for n, s, e, op in hand_made()[0] if "gdn/" not in op]
    reduced = gdn_trace.reduce({0: (ops, hand_made()[1])})
    assert reduced["devices"]["0"]["scoped"] is False
    assert reduced["devices"]["0"]["seconds"]["moe/experts"] > 0
    for module in (gdn_time_share, gdn_scan_share, gdn_scan_roofline,
                   expert_share_time_share):
        assert module.read(run_of(reduced, peaks_table={})) is None
    assert gdn_scan_roofline.read(
        run_of({"devices": {}}, peaks_table={})) is None


def test_held_pairs_over_share_reads_a_fixed_range_of_steps(monkeypatch):
    from benchmark.lib import loop_ledger

    events = [
        {"event": "moe_routing", "step": step, "held_pairs": 16.0 + step}
        for step in range(2, 40, 2)
    ] + [{"event": "loop_phases", "step": 6}]
    monkeypatch.setattr(loop_ledger, "worker_events", lambda run: events)
    run = run_of(None, cell={"warmup_steps": 4, "log_every": 2})
    # expected: 16 tokens x 2 samples x 4 choices x 2 / 8 = 32 pairs; the
    # nine logged steps after the warm-up: 6 .. 22, median 14
    assert held_pairs_over_share.read(run) == pytest.approx((16 + 14) / 32)
    # a program that journals no held_pairs (every other configuration)
    monkeypatch.setattr(
        loop_ledger, "worker_events",
        lambda run: [{"event": "moe_routing", "step": 6}])
    assert held_pairs_over_share.read(run) is None
    no_share = dict(run, config={"published": {"vocab_size": 1}})
    assert held_pairs_over_share.read(no_share) is None


def test_rehearsal_of_a_tiny_qwen3next_cell(tmp_path):
    """The Qwen3-Next zoo, its reference check over the last positions,
    the held share's counters through the worker's loop and the new
    readers through the whole command on the CPU, untraced and
    traced."""
    proc, line = common.run_cell(
        "tiny-qwen3next-s128", 0, tmp_path, manifest=MANIFEST, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-qwen3next-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "loss", "choices", "dropped_pairs_plus_one",
        "grad:block_0/attn/A_log"}
    assert check["errors"]["dropped_pairs_plus_one"] == 0
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert "linear attention heads k=2 v=4 dim=16 chunk=16 impl=xla" in log
    assert "moe dispatch resolved to sorted (tokens=512 experts=16" in log
    assert ("shared=1 held=4-7 rows=1024 shared_gate=sigmoid, experts' "
            "matmul=ragged_dot)") in log

    proc, line = common.run_cell(
        "tiny-qwen3next-s128", 1, tmp_path, manifest=MANIFEST, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    # a CPU run has no device plane: every reader of one is left out
    assert set(line["metrics"]) == {"held_pairs_over_share"}
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    routing = [e for e in journal if e["event"] == "moe_routing"]
    assert routing and all(e["dropped_pairs"] == 0.0 for e in routing)
    # 512 tokens x top-3 over ALL 16 experts; 4 of them held
    assert all(e["tokens_per_expert_mean"] == 96.0 for e in routing)
    assert all(0 < e["held_pairs"] < 1024 for e in routing)
    assert 0.3 < line["metrics"]["held_pairs_over_share"]["value"] < 3
