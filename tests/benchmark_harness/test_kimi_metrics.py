"""What the Kimi Linear configuration added to the measurement (PR 58):
``lib/kda_trace.py`` on hand-made operations, the four readers
(``kda_time_share``, ``kda_scan_share``, ``kda_scan_roofline``,
``nope_mla_time_share``) on what the reduction leaves, a program
without the scopes (the parent) reading nothing, the manifest's entries
looked up by name, and the whole command with the tiny rehearsal of the
Kimi Linear zoo."""

import json
import os

import pytest

from benchmark.flops import kda_mla_moe_decoder
from benchmark.lib import kda_trace
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import (
    kda_scan_roofline,
    kda_scan_share,
    kda_time_share,
    nope_mla_time_share,
)
from tests.benchmark_harness import _common as common

MANIFEST = os.path.join(common.HERE, "preset", "KIMI.json")
KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/block_1/"
BWD = ("jit(train_step)/jit(main)/transpose(jvp(forward))/checkpoint/"
       "rematted_computation/block_0/")
READERS = (kda_time_share, kda_scan_share, kda_scan_roofline,
           nope_mla_time_share)


@pytest.mark.parametrize("name,op_name,kind", [
    ("%fusion.1 = bf16[1,32768,12288] fusion(",
     FWD + "attn/kda/in_proj/in_proj_qkv/dot_general", "kda/in_proj"),
    # the convolution's kernels are named for what they do, and sit
    # under the scope, the backward's inside its VJP
    ("%qkv_conv_fwd.2 = (bf16[4,1,32,8192,128])" + KERNEL,
     FWD + "attn/kda/conv/pallas_call", "kda/conv"),
    ("%qkv_conv_bwd.3 = (bf16[1,32768,12288])" + KERNEL,
     BWD + "attn/kda/conv/pallas_call", "kda/conv"),
    ("%fusion.4 = f32[] fusion(", FWD + "attn/kda/gates/softplus",
     "kda/gates"),
    # inside the rule's loops, forward and backward
    ("%fusion.5 = f32[1,32,1,128,128] fusion(",
     FWD + "attn/kda/scan/while/body/closed_call/while/body/dot_general",
     "kda/scan"),
    ("%fusion.6 = f32[32,16,4,16,16,128] fusion(",
     BWD + "attn/kda/scan/while/body/closed_call/checkpoint/exp",
     "kda/scan"),
    ("%fusion.7 = bf16[] fusion(", FWD + "attn/kda/out_norm/mul",
     "kda/out_norm"),
    ("%fusion.8 = bf16[] fusion(",
     "transpose(jvp(kda/out_proj))/dot_general", "kda/out_proj"),
    # a later Pallas kernel for the vector rule, wherever it is called
    ("%kda_scan_fwd.9 = f32[32,128,128]" + KERNEL, FWD + "attn/pallas_call",
     "kda/scan"),
    ("%fusion.10 = bf16[] fusion(", FWD + "attn/mla/kv_up/dot_general",
     "mla/kv_up"),
    ("%fusion.11 = bf16[] fusion(", BWD + "attn/mla/assemble/concatenate",
     "mla/assemble"),
    ("%flash_dkv.12 = (bf16[32,32768,192])" + KERNEL,
     BWD + "attn/pallas_call", "flash"),
    # the scalar rule's kernels and scopes are another layer's
    ("%gdn_scan_fwd.13 = f32[32,128,128]" + KERNEL, FWD + "attn/pallas_call",
     None),
    ("%fusion.14 = bf16[] fusion(", FWD + "attn/gdn/scan/x", None),
    ("%fusion.15 = bf16[] fusion(", FWD + "moe_mlp/moe/shared/x", None),
    ("%fusion.16 = bf16[] fusion(", FWD + "attn/kda/scanner/x", None),
    ("%fusion.17 = bf16[] fusion(", FWD + "ln_mlp/mul", None),
])
def test_classify(name, op_name, kind):
    assert kda_trace.classify(name, op_name) == kind


def hand_made():
    """Three step periods of 200 us: 10 us under each of the six
    ``kda/`` scopes, 30 us under ``kda/scan`` inside its loop, 10 us
    under each of the five ``mla/`` scopes, 20 us of flash, 20 us of
    other work, 20 us idle."""
    scoped = [FWD + "attn/kda/%s/x" % s for s in kda_trace.KDA_SCOPES]
    scoped += [FWD + "attn/%s/x" % kind for kind in kda_trace.MLA_KINDS
               if kind != "flash"]
    ops = []
    for period in range(3):
        t = period * 200_000.0
        for op_name in scoped:
            ops.append(("%fusion.1 = bf16[] fusion(", t, t + 10_000, op_name))
            t += 10_000
        # the loop is a container: its body's operations are the time
        ops.append(("%while.2 = () while(", t, t + 30_000,
                    FWD + "attn/kda/scan/while"))
        for i in range(3):
            ops.append(("%fusion.3 = f32[] fusion(", t + i * 10_000,
                        t + (i + 1) * 10_000,
                        FWD + "attn/kda/scan/while/body/dot_general"))
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
    device = kda_trace.reduce_device(ops, modules)
    assert device["steps"] == 2 and device["scoped"]
    assert device["busy_s"] == pytest.approx(360e-6)
    for kind, seconds in device["seconds"].items():
        want = {"kda/scan": 80e-6, "flash": 40e-6}.get(kind, 20e-6)
        assert seconds == pytest.approx(want), kind
    reduced = kda_trace.reduce({0: (ops, modules)})
    assert kda_trace.time_share(reduced, kda_trace.KDA_KINDS) == (
        pytest.approx(100 * 180 / 360))
    assert kda_trace.time_share(reduced, kda_trace.MLA_KINDS) == (
        pytest.approx(100 * 140 / 360))


CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "intermediate_size": 12,
    "linear_attn_config": {
        "num_heads": 2, "head_dim": 4, "short_conv_kernel_size": 4,
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8]},
    "num_attention_heads": 2, "kv_lora_rank": 6, "qk_nope_head_dim": 4,
    "qk_rope_head_dim": 2, "v_head_dim": 4,
    "num_experts": 2, "published": {"num_experts": 8},
    "num_experts_per_token": 4, "moe_intermediate_size": 4,
    "num_shared_experts": 1, "vocab_size": 100,
    "assumed": {"kda_gate_rank": 4, "kda_chunk": 32, "kda_segment": 1},
}


def run_of(reduced, **more):
    run = {
        "kda_reduced": reduced, "config": CONFIG, "chips": 1,
        "traffic": {"seq_len": 64, "minibatch": 2},
        "flops": kda_mla_moe_decoder,
        "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": {"TPU v5 lite": {
            "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}},
    }
    run.update(more)
    return run


def test_the_readers_read_what_the_reduction_left(tmp_path):
    ops, modules = hand_made()
    run = run_of(kda_trace.reduce({0: (ops, modules)}))
    assert kda_time_share.read(run) == pytest.approx(100 * 180 / 360)
    assert kda_scan_share.read(run) == pytest.approx(100 * 80 / 360)
    assert nope_mla_time_share.read(run) == pytest.approx(100 * 140 / 360)
    # the rule's needed work a sample (tests/benchmark_harness/
    # test_kimi_flops.py counts both by hand): bytes bound it at these
    # peaks; two steps of two samples over 80 us under kda/scan
    flops, nbytes = kda_mla_moe_decoder.kernels(
        CONFIG, run["traffic"])["kda_scan"]
    assert nbytes / 1e9 > flops / 1e12
    assert kda_scan_roofline.read(run) == pytest.approx(
        100 * 4 * nbytes * 1e-9 / 80e-6)

    # a configuration whose count names no such kernel
    class Other:
        kernels = staticmethod(lambda config, traffic: {"flash": (1.0, 1.0)})

    assert kda_scan_roofline.read(dict(run, flops=Other)) is None
    # no trace at all: nothing to reduce, nothing raised
    for module in READERS:
        assert module.read(run_of(None, out=str(tmp_path))) is None


def test_a_program_without_the_scopes_reads_nothing():
    """The parent of PR 58, and every other configuration: the ``mla/``
    scopes and the flash kernels alone do not make a program
    ``scoped``, and no peak is asked of a device that has none."""
    ops = [(n, s, e, op) for n, s, e, op in hand_made()[0] if "kda/" not in op]
    reduced = kda_trace.reduce({0: (ops, hand_made()[1])})
    assert reduced["devices"]["0"]["scoped"] is False
    assert reduced["devices"]["0"]["seconds"]["mla/kv_up"] > 0
    for module in READERS:
        assert module.read(run_of(reduced, peaks_table={})) is None
    assert kda_scan_roofline.read(
        run_of({"devices": {}}, peaks_table={})) is None


def test_the_manifest_names_the_four_and_their_cell():
    """By NAME, never by position: the entries this PR appended."""
    manifest = common.load(common.MANIFEST)
    by_name = lambda section: {e["name"]: e for e in manifest[section]}
    cell = by_name("workloads")["kimi-linear48b-s32k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b-1chip", "s32k-b1", 1)
    config = by_name("configs")["kimi-linear-48b-a3b-1chip"]
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == common.load(
        os.path.join(common.REPO, config["file"]))["source"]
    metrics = by_name("per_layer")
    layers = set()
    for name, better in (("kda_time_share", "lower"),
                         ("kda_scan_share", "lower"),
                         ("kda_scan_roofline", "higher"),
                         ("nope_mla_time_share", "lower")):
        entry = metrics[name]
        assert entry["workloads"] == ["kimi-linear48b-s32k"], name
        assert (entry["better"], entry["unit"], entry["moves"],
                entry["source"]) == (
            better, "%", "samples_per_s", "device_trace"), name
        layers.add(entry["layer"])
        assert os.path.exists(os.path.join(
            common.REPO, "benchmark", "metrics", name + ".py"))
    assert len(layers) == 1
    for section in ("configs", "workloads", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names)), section


def test_rehearsal_of_a_tiny_kimi_cell(tmp_path):
    """The Kimi Linear zoo, its reference check over the last
    positions, the gates' facts and the held share's counters through
    the worker's loop and the new readers through the whole command on
    the CPU (untraced: a CPU run has no device plane, and what the readers
    do without one is ``test_the_readers_read_what_the_reduction_left``'s)."""
    proc, line = common.run_cell(
        "tiny-kimi-s128", 0, tmp_path, manifest=MANIFEST, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-kimi-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "loss", "choices", "dropped_pairs_plus_one",
        "grad:block_0/attn/A_log", "grad:block_1/attn/dt_bias",
        "grad:block_3/attn/kv_down/kernel"}
    assert check["errors"]["dropped_pairs_plus_one"] == 0
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert ("linear attention heads k=4 v=4 dim=16 chunk=32 impl=xla "
            "scan=xla prep=xla (tokens=512) decay=vector") in log
    assert "linear attention conv heads k=4 v=4 dim=16 taps=4 impl=xla" in log
    assert "moe dispatch resolved to sorted (tokens=512 experts=16" in log
    assert "score=sigmoid shared=1 held=4-7 rows=1024" in log

    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    kinds = [e for e in journal if e["event"] == "mixer_kinds"]
    assert len(kinds) == 1 and (
        kinds[0]["kda_layers"], kinds[0]["full_layers"],
        kinds[0]["dense_layers"], kinds[0]["latent_rotary"]) == (
            4, 1, 1, False)
    gates = [e for e in journal if e["event"] == "kda_gates"]
    assert gates and all(
        len(e["decay_mean"]) == len(e["decay_min"]) == len(e["beta_mean"])
        == len(e["underflow_share"]) == 4 for e in gates)
    assert all(0 < lo <= mean < 1 for e in gates
               for lo, mean in zip(e["decay_min"], e["decay_mean"]))
    routing = [e for e in journal if e["event"] == "moe_routing"]
    assert routing and all(e["dropped_pairs"] == 0.0 for e in routing)
    assert all(0 < e["held_pairs"] < 1024 for e in routing)
