"""What the Mellum2 configuration added to the measurement (PR 45):
``lib/ep_trace.py`` on hand-made operations and recorded events, the
four readers (``ep_exchange_time_share``, ``ep_exchange_exposed_share``,
``ep_exchange_ici_share``, ``ep_rank_load_max_over_mean``) on what a run
leaves, a program without the scope or the counters (the parent)
reading nothing, the manifest's entries by name, and a rehearsal of a
tiny cell over ``ep=4`` through the whole command."""

import json
import os

import pytest

from benchmark.flops import ep_window_moe_decoder, window_moe_decoder
from benchmark.lib import ep_trace
from benchmark.metrics import (
    ep_exchange_exposed_share,
    ep_exchange_ici_share,
    ep_exchange_time_share,
    ep_rank_load_max_over_mean,
)
from tests.benchmark_harness import _common as common

FWD = "jit(spmd_train_step)/jit(main)/forward/MoeTransformerLM/"
BWD = ("jit(spmd_train_step)/jit(main)/transpose(jvp(forward))/"
       "MoeTransformerLM/")
REMAT = BWD + "checkpoint/rematted_computation/"
LAYER = "block_1/moe_mlp/shard_map/"
CELL = "mellum2-ep4-s8k"
NEW_METRICS = ("ep_exchange_time_share", "ep_exchange_exposed_share",
               "ep_exchange_ici_share", "ep_rank_load_max_over_mean")
# as a v5e's trace has it (PR 45): jax's name, a tiled layout
RAGGED = ("%ragged_all_to_all.214 = bf16[65536,2,1152]{2,1,0:T(2,128)"
          "(2,1)} ragged-all-to-all(%a, %b)")
DONE = "%all-to-all-done.2 = bf16[8] all-to-all-done(%all-to-all-start.2)"
FUSION = "%fusion.1 = bf16[] fusion(%ragged_all_to_all.214)"


@pytest.mark.parametrize("name,op_name,want", [
    (RAGGED, FWD + LAYER + "moe/exchange/ragged_all_to_all", True),
    (RAGGED, BWD + LAYER + "moe/exchange/ragged_all_to_all", True),
    (RAGGED, REMAT + LAYER + "moe/exchange/ragged_all_to_all", True),
    (DONE, FWD + LAYER + "moe/exchange/all_to_all", True),
    # the buffer's fill under the scope is compute
    ("%fusion.3 = bf16[131072,2304] fusion(%c)",
     FWD + LAYER + "moe/exchange/broadcast_in_dim", False),
    # an operation that only READS a collective's result
    (FUSION, FWD + LAYER + "moe/exchange/select_n", False),
    # a collective of another scope: the counts' all-gather, the
    # gradients' all-reduce
    ("%all-gather.1 = s32[4,64] all-gather(%d)",
     FWD + LAYER + "moe/dispatch/all_gather", False),
    ("%all-reduce.4 = f32[2304,32,128] all-reduce(%e)",
     BWD + "block_1/attn/attn_window/qkv/dot_general", False),
])
def test_is_exchange(name, op_name, want):
    assert ep_trace.is_exchange(name, op_name) is want


def hand_made(scoped=True):
    """Two step periods of 200 us on one device: an exchange of 30 us
    that runs alone, one of 20 us of which 15 lie beside a fusion, 10
    us of a gradient's all-reduce alone, 100 us of other work."""
    ops = []
    for period in range(3):
        t = period * 200_000.0
        scope = "moe/exchange/" if scoped else "moe/dispatch/"
        ops += [
            (RAGGED, t, t + 30_000,
             FWD + LAYER + scope + "ragged_all_to_all"),
            ("%fusion.7 = f32[] fusion(", t + 30_000, t + 130_000,
             FWD + "ln_f/mul"),
            (RAGGED, t + 115_000, t + 135_000,
             BWD + LAYER + scope + "ragged_all_to_all"),
            ("%all-reduce.4 = f32[8] all-reduce(%e)", t + 140_000,
             t + 150_000, BWD + "block_1/attn/qkv/dot_general"),
        ]
    modules = [("jit_spmd_train_step(%d)" % i, i * 200_000.0,
                i * 200_000.0 + 160_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    device = ep_trace.reduce_device(*hand_made())
    assert device["steps"] == 2 and device["scoped"]
    assert device["exchange_ops"] == 4
    assert device["window_s"] == pytest.approx(400e-6)
    assert device["exchange_s"] == pytest.approx(100e-6)
    # 30 alone and the last 5 of the second, a period
    assert device["exchange_exposed_s"] == pytest.approx(70e-6)
    reduced = ep_trace.reduce({0: hand_made(), 2: hand_made()})
    assert sorted(reduced["devices"]) == ["0", "2"]
    run = {"ep_reduced": reduced}
    assert ep_exchange_time_share.read(run) == pytest.approx(25.0)
    assert ep_exchange_exposed_share.read(run) == pytest.approx(17.5)
    assert ep_trace.seconds_a_step(reduced) == pytest.approx(50e-6)


def journal(tmp_path, events):
    os.makedirs(tmp_path / "events", exist_ok=True)
    with open(tmp_path / "events" / "worker-0.ndjson", "w") as f:
        for event in events:
            f.write(json.dumps(event) + "\n")


def routing(step, **fields):
    return dict({"event": "moe_routing", "role": "worker", "step": step,
                 "dropped_pairs": 0.0}, **fields)


def counted_run(tmp_path, reduced, flops=ep_window_moe_decoder):
    config = common.load(os.path.join(
        common.REPO, "benchmark", "configs", "mellum2-12b-a2.5b-ep4",
        "config.json"))
    return {
        "config": config, "traffic": {"seq_len": 8192, "minibatch": 4},
        "cell": {"warmup_steps": 4, "log_every": 2},
        "chips": 4, "flops": flops, "out": str(tmp_path),
        "journal": None,
        "worker": {"device_kind": "TPU v5 lite"},
        "peaks_table": common.load(os.path.join(
            common.REPO, "benchmark", "lib", "peaks.json")),
        "ep_reduced": reduced}


def test_the_counters_readers_on_recorded_events(tmp_path):
    """The median over the logged steps after the warm-up, the steps
    before it and past the range left out."""
    from benchmark.lib import logs

    events = [routing(4, sent_pairs=1.0, received_pairs_max=9e9,
                      received_pairs_mean=1.0)]
    events += [
        routing(step, sent_pairs=196608.0 + 100 * i,
                received_pairs_max=65536.0 * (1.2 + 0.1 * i),
                received_pairs_mean=65536.0)
        for i, step in enumerate(range(6, 24, 2))]
    events += [routing(24, sent_pairs=1.0, received_pairs_max=9e9,
                       received_pairs_mean=1.0)]
    journal(tmp_path, events)
    reduced = {"devices": {"0": {
        "steps": 2, "window_s": 1.0, "exchange_s": 0.1,
        "exchange_exposed_s": 0.05, "exchange_ops": 96, "scoped": True}}}
    run = counted_run(tmp_path, reduced)
    run["journal"] = logs.read_journal(str(tmp_path / "events"))
    assert ep_rank_load_max_over_mean.read(run) == pytest.approx(1.6)
    # the median step's 197,008 pairs x 2304 x 2 bytes x 4 passes over
    # 50 ms a step over 200 GB/s
    sent = 196608.0 + 400
    assert ep_exchange_ici_share.read(run) == pytest.approx(
        100 * sent * 2304 * 2 * 4 / 0.05 / 200e9)
    assert ep_exchange_ici_share.read(run) < 100
    # a count without ``exchange_bytes`` and a configuration without one
    assert ep_exchange_ici_share.read(
        dict(run, flops=window_moe_decoder)) is None
    assert ep_exchange_ici_share.read(dict(run, flops=None)) is None


def test_a_program_without_the_scope_or_the_counters_reads_nothing(
        tmp_path):
    """The parent of PR 45 and every other configuration: collectives
    and no ``moe/exchange`` scope, ``moe_routing`` events without the
    exchange's fields; and no trace at all: nothing to reduce, nothing
    raised."""
    from benchmark.lib import logs

    reduced = ep_trace.reduce({0: hand_made(scoped=False)})
    assert reduced["devices"]["0"]["scoped"] is False
    journal(tmp_path, [routing(step, tokens_per_expert_max=9.0,
                               tokens_per_expert_mean=4.0)
                       for step in range(6, 24, 2)])
    run = counted_run(tmp_path, reduced)
    run["journal"] = logs.read_journal(str(tmp_path / "events"))
    for reader in (ep_exchange_time_share, ep_exchange_exposed_share,
                   ep_exchange_ici_share, ep_rank_load_max_over_mean):
        assert reader.read(run) is None, reader.__name__
    bare = counted_run(tmp_path, None)
    bare.pop("ep_reduced")
    bare["journal"] = run["journal"]
    for reader in (ep_exchange_time_share, ep_exchange_exposed_share,
                   ep_exchange_ici_share):
        assert reader.read(dict(bare)) is None, reader.__name__
    assert ep_exchange_time_share.read(
        {"ep_reduced": {"devices": {}}}) is None


def test_the_manifest_s_entries_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    layers = set()
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "samples_per_s"
        assert files.find("metrics", name + ".py")
        layers.add(by_name[name]["layer"])
    assert layers == {
        "expert exchange (ops/moe.py, models/moe_transformer.py:MoeMlp)"}
    assert [by_name[name]["unit"] for name in NEW_METRICS] == [
        "%", "%", "%", "ratio"]
    assert [by_name[name]["better"] for name in NEW_METRICS] == [
        "lower", "lower", "higher", "lower"]
    assert [by_name[name]["source"] for name in NEW_METRICS] == [
        "device_trace", "device_trace", "device_trace", "program_counter"]
    reported = {m["name"] for m in files.metrics_for("per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | {
        "flash_time_share", "flash_roofline", "peak_hbm_gb",
        "optimizer_time_share", "device_idle_share"}
    # nothing older lists the new cell: a benchmark PR's to add
    older = [m for m in manifest["per_layer"]
             if m["name"] not in NEW_METRICS]
    assert not any(CELL in m.get("workloads", []) for m in older)
    assert set(NEW_METRICS) <= set(by_name)


def test_rehearsal_of_a_tiny_mellum2_cell_over_ep(tmp_path):
    """The Mellum2 zoo through ``worker.main`` with ``--mesh ep=4`` on
    four virtual devices: ``SpmdTrainer``, the experts' state divided
    over ``ep``, the exchange, the reference check ON that mesh, the
    ``moe_routing`` events with the exchange's counters through the
    worker's loop, and the new readers through the whole command,
    traced."""
    manifest = os.path.join(common.HERE, "preset", "MELLUM2.json")
    proc, line = common.run_cell(
        "tiny-mellum2-ep4", 1, tmp_path, manifest=manifest, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["count"] == 4
    # a CPU run has no device plane: the trace's three are left out,
    # the counter's one is there
    assert set(line["metrics"]) == {"ep_rank_load_max_over_mean"}
    assert 1.0 <= line["metrics"]["ep_rank_load_max_over_mean"][
        "value"] < 4.0
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-mellum2-ep4")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and check["device"]["count"] == 4
    assert set(check["errors"]) >= {
        "logits", "loss", "choices", "dropped_pairs_plus_one",
        "grad:block_1/moe_mlp/w_gate", "grad:block_3/moe_mlp/w_down",
        "grad:block_3/moe_mlp/router/kernel", "grad:lm_head/kernel"}
    assert check["errors"]["dropped_pairs_plus_one"] == 0
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert "'ep': 4} (4-way data parallel)" in log
    assert ("layer kinds: full x1 (heads=8 theta=500000 yarn=16), "
            "window x3 (heads=8 theta=500000 window=24)") in log
    assert ("ep=4 held=2 received_rows=1024 exchange=all_gather, "
            "experts' matmul=ragged_dot)") in log
    assert "split into 4 shards of (1, 128)" in log
    assert "PartitionSpec('ep', 'fsdp', 'tp')" in log
    routing_events = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                routing_events += [
                    json.loads(x) for x in f if x.endswith("}\n")
                    and '"moe_routing"' in x]
    assert routing_events
    assert all(e["dropped_pairs"] == 0.0 for e in routing_events)
    assert all(e["received_pairs_mean"] == 128 * 2 for e in routing_events)
    assert all(0 < e["sent_pairs"] <= 4 * 128 * 2 for e in routing_events)
    assert all(e["exchange_bytes"] == e["sent_pairs"] * 64 * 4 * 4
               for e in routing_events)
