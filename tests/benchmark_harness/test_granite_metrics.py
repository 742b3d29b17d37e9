"""What the granite-4.0-h configuration added to the measurement (PR
60): ``lib/ssm_trace.py`` on hand-made operations, the four readers
(``mamba_time_share``, ``ssd_scan_share``, ``ssd_scan_roofline``,
``mamba_bytes_share``) on what the reduction leaves, a program without
the scopes (the parent) reading nothing, the manifest's entries looked
up by name, and the whole command with the tiny rehearsal of the
granite zoo."""

import json
import os

import pytest

from benchmark.flops import ssm_dense_decoder
from benchmark.lib import ssm_trace
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import (
    mamba_bytes_share,
    mamba_time_share,
    ssd_scan_roofline,
    ssd_scan_share,
)
from tests.benchmark_harness import _common as common

MANIFEST = os.path.join(common.HERE, "preset", "GRANITE.json")
KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/block_1/"
BWD = ("jit(train_step)/jit(main)/transpose(jvp(forward))/checkpoint/"
       "rematted_computation/block_0/")
READERS = (mamba_time_share, ssd_scan_share, ssd_scan_roofline,
           mamba_bytes_share)
NEW = (("mamba_time_share", "lower"), ("ssd_scan_share", "lower"),
       ("ssd_scan_roofline", "higher"), ("mamba_bytes_share", "lower"))


@pytest.mark.parametrize("name,op_name,kind", [
    ("%fusion.1 = bf16[1,8192,8512] fusion(",
     FWD + "attn/mamba/in_proj/in_proj/dot_general", "mamba/in_proj"),
    ("%fusion.2 = bf16[1,8192,4352] fusion(", FWD + "attn/mamba/conv/silu",
     "mamba/conv"),
    ("%fusion.3 = bf16[1,8192,4352] fusion(", BWD + "attn/mamba/conv/mul",
     "mamba/conv"),
    ("%fusion.4 = f32[] fusion(", FWD + "attn/mamba/gates/softplus",
     "mamba/gates"),
    # inside the scan's loop over segments, forward and backward
    ("%fusion.5 = f32[1,8,1,64,256,256] fusion(",
     FWD + "attn/mamba/scan/while/body/closed_call/exp", "mamba/scan"),
    ("%fusion.6 = f32[1,8,1,64,64,128] fusion(",
     BWD + "attn/mamba/scan/while/body/closed_call/checkpoint/dot_general",
     "mamba/scan"),
    ("%fusion.7 = bf16[] fusion(", FWD + "attn/mamba/out_norm/mul",
     "mamba/out_norm"),
    ("%fusion.8 = bf16[] fusion(",
     "transpose(jvp(mamba/out_proj))/dot_general", "mamba/out_proj"),
    # a later Pallas kernel for the scan, wherever it is called
    ("%ssd_scan_fwd.9 = f32[64,64,128]" + KERNEL, FWD + "attn/pallas_call",
     "mamba/scan"),
    ("%ssd_chunk_bwd.10 = (bf16[8192,4096])" + KERNEL, BWD + "x/pallas_call",
     "mamba/scan"),
    # another layer's kernels and scopes
    ("%flash_fwd.11 = (bf16[32,8192,64])" + KERNEL,
     FWD + "attn/attn_full/flash/pallas_call", None),
    ("%kda_scan_fwd.12 = f32[32,128,128]" + KERNEL, FWD + "attn/pallas_call",
     None),
    ("%gdn_ssd.13 = f32[32,128,128]" + KERNEL, FWD + "attn/pallas_call",
     None),
    ("%fusion.14 = bf16[] fusion(", FWD + "attn/kda/scan/x", None),
    ("%fusion.15 = bf16[] fusion(", FWD + "dense_mlp/mlp_up/dot_general",
     None),
    ("%fusion.16 = bf16[] fusion(", FWD + "attn/mamba/scanner/x", None),
    ("%fusion.17 = bf16[] fusion(", FWD + "attn/attn_full/qkv/dot_general",
     None),
])
def test_classify(name, op_name, kind):
    assert ssm_trace.classify(name, op_name) == kind


def hand_made():
    """Three step periods of 200 us: 10 us under each of the six
    ``mamba/`` scopes, 30 us more under ``mamba/scan`` inside its loop,
    20 us of flash, 40 us of other work, 50 us idle."""
    scoped = [FWD + "attn/mamba/%s/x" % s for s in ssm_trace.MAMBA_SCOPES]
    ops = []
    for period in range(3):
        t = period * 200_000.0
        for op_name in scoped:
            ops.append(("%fusion.1 = bf16[] fusion(", t, t + 10_000, op_name))
            t += 10_000
        # the loop is a container: its body's operations are the time
        ops.append(("%while.2 = () while(", t, t + 30_000,
                    FWD + "attn/mamba/scan/while"))
        for i in range(3):
            ops.append(("%fusion.3 = f32[] fusion(", t + i * 10_000,
                        t + (i + 1) * 10_000,
                        FWD + "attn/mamba/scan/while/body/dot_general"))
        t += 30_000
        ops.append(("%flash_fwd.4 = bf16[]" + KERNEL, t, t + 20_000,
                    FWD + "attn/attn_full/flash/pallas_call"))
        ops.append(("%fusion.5 = f32[] fusion(", t + 20_000, t + 60_000,
                    FWD + "dense_mlp/mlp_up/dot_general"))
    modules = [("jit_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 180_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = ssm_trace.reduce_device(ops, modules)
    assert device["steps"] == 2 and device["scoped"]
    assert device["busy_s"] == pytest.approx(300e-6)
    assert set(device["seconds"]) == set(ssm_trace.MAMBA_KINDS)
    for kind, seconds in device["seconds"].items():
        want = {"mamba/scan": 80e-6}.get(kind, 20e-6)
        assert seconds == pytest.approx(want), kind
    reduced = ssm_trace.reduce({0: (ops, modules)})
    assert ssm_trace.time_share(reduced, ssm_trace.MAMBA_KINDS) == (
        pytest.approx(100 * 180 / 300))
    assert ssm_trace.time_share(reduced, ssm_trace.BYTES_KINDS) == (
        pytest.approx(100 * 60 / 300))


CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba",
                    "attention"],
    "mamba_n_heads": 4, "mamba_d_head": 4, "mamba_d_state": 8,
    "mamba_n_groups": 2, "mamba_chunk_size": 32,
    "num_attention_heads": 2, "num_key_value_heads": 1,
    "shared_intermediate_size": 12, "vocab_size": 100,
    "assumed": {"scan_segment": 1},
}


def run_of(reduced, **more):
    run = {
        "ssm_reduced": reduced, "config": CONFIG, "chips": 1,
        "traffic": {"seq_len": 64, "minibatch": 2},
        "flops": ssm_dense_decoder,
        "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": {"TPU v5 lite": {
            "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}},
    }
    run.update(more)
    return run


def test_the_readers_read_what_the_reduction_left(tmp_path):
    ops, modules = hand_made()
    run = run_of(ssm_trace.reduce({0: (ops, modules)}))
    assert mamba_time_share.read(run) == pytest.approx(100 * 180 / 300)
    assert ssd_scan_share.read(run) == pytest.approx(100 * 80 / 300)
    assert mamba_bytes_share.read(run) == pytest.approx(100 * 60 / 300)
    # the scan's needed work a sample (tests/benchmark_harness/
    # test_granite_flops.py counts both by hand): bytes bound it at
    # these peaks; two steps of two samples over 80 us under mamba/scan
    flops, nbytes = ssm_dense_decoder.kernels(
        CONFIG, run["traffic"])["ssd_scan"]
    assert nbytes / 1e9 > flops / 1e12
    assert ssd_scan_roofline.read(run) == pytest.approx(
        100 * 4 * nbytes * 1e-9 / 80e-6)

    # a configuration whose count names no such kernel
    class Other:
        kernels = staticmethod(lambda config, traffic: {"flash": (1.0, 1.0)})

    assert ssd_scan_roofline.read(dict(run, flops=Other)) is None
    # no trace at all: nothing to reduce, nothing raised
    for module in READERS:
        assert module.read(run_of(None, out=str(tmp_path))) is None


def test_a_program_without_the_scopes_reads_nothing():
    """The parent of PR 60, and every other configuration: flash
    kernels and dense MLPs alone do not make a program ``scoped``, and
    no peak is asked of a device that has none."""
    ops = [(n, s, e, op) for n, s, e, op in hand_made()[0]
           if "mamba/" not in op]
    reduced = ssm_trace.reduce({0: (ops, hand_made()[1])})
    assert reduced["devices"]["0"]["scoped"] is False
    assert reduced["devices"]["0"]["busy_s"] > 0
    for module in READERS:
        assert module.read(run_of(reduced, peaks_table={})) is None
    assert ssd_scan_roofline.read(
        run_of({"devices": {}}, peaks_table={})) is None


def test_the_manifest_names_the_four_and_their_cell():
    """By NAME, never by position: the entries this PR appended."""
    manifest = common.load(common.MANIFEST)
    by_name = lambda section: {e["name"]: e for e in manifest[section]}
    cell = by_name("workloads")["granite4h-micro-s8k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro-1chip", "s8k-b1", 1)
    config = by_name("configs")["granite-4.0-h-micro-1chip"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["source"] == common.load(
        os.path.join(common.REPO, config["file"]))["source"]
    metrics = by_name("per_layer")
    layers = set()
    for name, better in NEW:
        entry = metrics[name]
        assert entry["workloads"] == ["granite4h-micro-s8k"], name
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
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s8k-b1.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["records"], traffic["zipf_a"]) == (
        "zipf_tokens", 8192, 1, 256, 1.2)
    workload = common.load(os.path.join(
        common.REPO, "benchmark", "workloads", "granite4h-micro-s8k.json"))
    assert workload["last_positions"] == 512 and workload["mesh"] == ""


def test_rehearsal_of_a_tiny_granite_cell(tmp_path):
    """The granite zoo, its reference check over the last positions and
    the gates' facts through the worker's loop and the new readers
    through the whole command on the CPU (untraced: a CPU run has no
    device plane, and what the readers do without one is
    ``test_the_readers_read_what_the_reduction_left``'s)."""
    proc, line = common.run_cell(
        "tiny-granite-s128", 0, tmp_path, manifest=MANIFEST, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-granite-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "grad:wte/embedding", "grad:block_0/attn/A_log",
        "grad:block_1/attn/conv_bias", "grad:block_2/attn/D",
        "grad:block_5/attn/key/kernel"}
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert ("ssd scan heads=8x16 state=16 groups=1 chunk=32 impl=xla "
            "segments=2 (tokens=512)") in log
    assert "layer kinds: full x1 (heads=4 theta=10000), mamba x9" in log

    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    kinds = [e for e in journal if e["event"] == "mixer_kinds"]
    assert len(kinds) == 1 and (
        kinds[0]["mamba_layers"], kinds[0]["full_layers"],
        kinds[0]["dense_layers"], kinds[0]["rotary"]) == (9, 1, 10, False)
    gates = [e for e in journal if e["event"] == "mamba_gates"]
    assert gates and all(
        len(e["decay_mean"]) == len(e["decay_min"]) == len(e["dt_mean"])
        == len(e["dt_max"]) == len(e["underflow_share"]) == 9 for e in gates)
    assert all(0 < lo <= mean < 1 for e in gates
               for lo, mean in zip(e["decay_min"], e["decay_mean"]))
