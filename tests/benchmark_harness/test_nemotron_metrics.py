"""What the Nemotron-3-Nano configuration added to the measurement (PR
64): six readers that sum the rows of ``step_account.json`` by scope or
read the ``moe_routing`` event's new field (no trace reader of their
own), on hand-made rows; a program without the registry, the kernels or
the field reading nothing; and the manifest's entries looked up by
name."""

import os

import pytest

from benchmark.flops import ssm_moe_decoder
from benchmark.lib import step_account
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import (
    mamba_g8_time_share,
    relu2_active_share,
    relu2_gmm_roofline,
    relu2_moe_time_share,
    relu2_shared_time_share,
    ssd_g8_scan_roofline,
)
from tests.benchmark_harness import _common as common
from tests.benchmark_harness.test_nemotron_flops import CONFIG

CELL = "nemotron3-nano-s8k"
TRACE_READERS = (relu2_moe_time_share, relu2_shared_time_share,
                 relu2_gmm_roofline, mamba_g8_time_share,
                 ssd_g8_scan_roofline)
NEW = (("relu2_moe_time_share", "lower", "device_trace"),
       ("relu2_shared_time_share", "lower", "device_trace"),
       ("relu2_gmm_roofline", "higher", "device_trace"),
       ("mamba_g8_time_share", "lower", "device_trace"),
       ("ssd_g8_scan_roofline", "higher", "device_trace"),
       ("relu2_active_share", "lower", "program_counter"))
KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/block_1/"
BWD = ("jit(train_step)/jit(main)/transpose(jvp(forward))/checkpoint/"
       "rematted_computation/block_0/")


def hand_made():
    """Two step periods of 1,000 us a device: under ``moe/`` 100 us of
    the router, 60 of ``gmm`` and 40 of ``tgmm`` kernels (the second's
    ``op_name`` lost its scope: the kernel's name charges it), 150 of
    the shared expert; under ``mamba/`` 50 of ``in_proj`` and 200 of the
    scan, half of it recomputed; 100 of a flash kernel; 300 idle."""
    ops = []
    for period in range(3):
        t = period * 1_000_000.0
        for name, us, op_name in (
                ("%fusion.1 = f32[8192,128] fusion(", 100,
                 FWD + "moe_mlp/moe/router/dot_general"),
                ("%gmm.2 = bf16[8192,1856]" + KERNEL, 60,
                 FWD + "moe_mlp/moe/experts/pallas_call"),
                ("%tgmm.3 = bf16[8,2688,1856]" + KERNEL, 40,
                 "jit(train_step)/jit(main)/jit(tgmm)/pallas_call"),
                ("%fusion.4 = bf16[8192,3712] fusion(", 150,
                 FWD + "moe_mlp/moe/shared/shared_up/dot_general"),
                ("%fusion.5 = bf16[8192,10304] fusion(", 50,
                 FWD + "attn/mamba/in_proj/in_proj/dot_general"),
                ("%fusion.6 = f32[1,8,8,8,128,128] fusion(", 100,
                 FWD + "attn/mamba/scan/while/body/exp"),
                ("%fusion.7 = f32[1,8,8,8,128,128] fusion(", 100,
                 BWD + "attn/mamba/scan/while/body/exp"),
                ("%flash_fwd.8 = (bf16[32,8192,128])" + KERNEL, 100,
                 FWD + "attn/attn_full/flash/pallas_call")):
            ops.append((name, t, t + us * 1000.0, op_name))
            t += us * 1000.0
    modules = [("jit_train_step(%d)" % i, i * 1_000_000.0,
                i * 1_000_000.0 + 700_000) for i in range(3)]
    return ops, modules


def run_of(account, **more):
    run = {
        "step_account": account, "config": CONFIG, "chips": 1,
        "traffic": {"seq_len": 64, "minibatch": 2},
        "flops": ssm_moe_decoder,
        "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": {"TPU v5 lite": {
            "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}},
    }
    run.update(more)
    return run


def test_the_readers_sum_the_account_s_rows():
    account = step_account.reduce({0: hand_made()})
    run = run_of(account)
    busy = 700.0
    assert step_account.speaker(account)["busy_ms"] == pytest.approx(0.7)
    assert relu2_moe_time_share.read(run) == pytest.approx(100 * 350 / busy)
    assert relu2_shared_time_share.read(run) == pytest.approx(
        100 * 150 / busy)
    assert mamba_g8_time_share.read(run) == pytest.approx(100 * 250 / busy)
    need = ssm_moe_decoder.kernels(CONFIG, run["traffic"])
    # bytes bound both at these peaks; a step trains two samples
    flops, nbytes = need["relu2_gmm"]
    assert nbytes / 1e9 > flops / 1e12
    assert relu2_gmm_roofline.read(run) == pytest.approx(
        100 * 2 * nbytes * 1e-9 / 100e-6)
    flops, nbytes = need["ssd_scan"]
    assert ssd_g8_scan_roofline.read(run) == pytest.approx(
        100 * 2 * nbytes * 1e-9 / 200e-6)


def test_what_is_not_there_reads_nothing(tmp_path):
    ops, modules = hand_made()
    # ``ragged_dot`` in the kernels' place: no gmm / tgmm to read
    ragged = [(n.replace("%gmm", "%ragged-dot").replace("%tgmm", "%fusion"),
               s, e, op) for n, s, e, op in ops]
    run = run_of(step_account.reduce({0: (ragged, modules)}))
    assert relu2_gmm_roofline.read(run) is None
    assert relu2_moe_time_share.read(run) > 0
    # no expert and no Mamba layer in the program: a share of 0, a
    # roofline of nothing
    others = [op for op in ops if "flash" in op[0]]
    run = run_of(step_account.reduce({0: (others, modules)}))
    assert relu2_moe_time_share.read(run) == 0
    assert ssd_g8_scan_roofline.read(run) is None

    # a configuration whose count names neither kernel
    class Other:
        kernels = staticmethod(lambda config, traffic: {"flash": (1.0, 1.0)})

    run = run_of(step_account.reduce({0: (ops, modules)}), flops=Other)
    assert relu2_gmm_roofline.read(run) is None
    assert ssd_g8_scan_roofline.read(run) is None
    # no trace at all (an untraced run, the parent): nothing raised
    for module in TRACE_READERS:
        assert module.read(run_of(None)) is None


def test_the_active_share_is_the_window_s_median(tmp_path):
    events = [
        {"event": "moe_routing", "step": step, "relu2_active_share": share}
        for step, share in ((3, 0.9), (5, 0.52), (6, 0.48), (7, 0.50),
                            (9, 0.1))]
    run = {
        "worker_journal": events, "window": (10.0, 20.0),
        "worker": {"steps": [(3, 9.0, 1.0), (5, 11.0, 1.0), (6, 13.0, 1.0),
                             (7, 15.0, 1.0), (9, 21.0, 1.0)]}}
    assert relu2_active_share.read(run) == pytest.approx(50.0)
    # a program that journals no such field (the parent, another body)
    run["worker_journal"] = [
        {"event": "moe_routing", "step": 5, "held_pairs": 3.0}]
    assert relu2_active_share.read(run) is None


def test_the_manifest_names_the_six_and_their_cell():
    """By NAME, never by position: the entries this PR appended."""
    manifest = common.load(common.MANIFEST)
    by_name = lambda section: {e["name"]: e for e in manifest[section]}
    cell = by_name("workloads")[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b-1chip", "s8k-b1", 1)
    config = by_name("configs")["nemotron-3-nano-30b-a3b-1chip"]
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["source"] == common.load(
        os.path.join(common.REPO, config["file"]))["source"]
    metrics, layers = by_name("per_layer"), set()
    for name, better, source in NEW:
        entry = metrics[name]
        assert entry["workloads"] == [CELL], name
        assert (entry["better"], entry["unit"], entry["moves"],
                entry["source"]) == (
            better, "%", "samples_per_s", source), name
        layers.add(entry["layer"])
        assert os.path.exists(os.path.join(
            common.REPO, "benchmark", "metrics", name + ".py"))
    # the expert layer's and the state-space mixers', under the names
    # the manifest already had
    assert layers == {
        metrics["moe_time_share"]["layer"],
        metrics["mamba_time_share"]["layer"]}
    for section in ("configs", "workloads", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names)), section
    # the quarter rule: four-chip cells are at most a quarter
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
