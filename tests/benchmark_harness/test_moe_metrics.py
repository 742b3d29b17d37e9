"""``lib/moe_trace.py`` and the four metrics of the expert layer
(PR 25): on hand-made operations and a hand-made journal whose answers
are known, on a program without the scopes, and through the whole
command on the CPU under a rehearsal manifest of its own."""

import importlib
import json
import os

import pytest

from benchmark.lib import moe_trace
from tests.benchmark_harness import _common as common

HERE = os.path.dirname(os.path.abspath(__file__))
MOE_MANIFEST = os.path.join(HERE, "preset", "MOE.json")
MS = 1e6  # nanoseconds
NEW = ("moe_time_share", "moe_permute_share", "expert_matmul_roofline",
       "expert_load_max_over_mean")
SCOPE = "jit(train_step)/jvp(forward)/MoeTransformerLM/block_0/moe_mlp/"
BACK = ("jit(train_step)/transpose(jvp(forward))/MoeTransformerLM/block_0/"
        "moe_mlp/")
FUSION = "%%fusion.%d = bf16[8]{0} fusion(bf16[8]{0} %%p), kind=kLoop"
RAGGED = ("%%ragged-dot-none.%d = bf16[64,8]{1,0} custom-call(bf16[64,4]{1,0}"
          ' %%a), custom_call_target="tpu_custom_call"')
OWN_KERNEL = ("%%moe_gmm.%d = bf16[64,8]{1,0} custom-call(bf16[64,4]{1,0} "
              '%%a), custom_call_target="tpu_custom_call"')


def device_events():
    """Three executions of one step program, 100 ms apart and 90 ms
    long. A step: router 2 ms, dispatch 6 (a sort and a gather),
    grouped matmuls 40 (XLA's, with no scope left in their op_name),
    the experts' activation 4, a kernel of the repo's own under the
    scope 5, combine 3 forward and 8 backward, attention 12, optimizer
    10."""
    ops, modules = [], []
    for k in range(3):
        t = 100 * k * MS
        modules.append(("jit_train_step(%d)" % k, t, t + 90 * MS))
        steps = [
            (FUSION % 1, 2, SCOPE + "moe/router/dot_general"),
            ("%sort.3 = s32[64]{0} sort(s32[64]{0} %k)", 2,
             SCOPE + "moe/dispatch/sort"),
            (FUSION % 2, 4, SCOPE + "moe/dispatch/gather"),
            (RAGGED % 1, 15, "ragged-dot-none"),
            (RAGGED % 2, 25, "ragged-dot-none"),
            (FUSION % 3, 4, SCOPE + "moe/experts/mul"),
            (OWN_KERNEL % 1, 5, BACK + "moe/experts/pallas_call"),
            (FUSION % 4, 3, SCOPE + "moe/combine/gather"),
            (FUSION % 5, 8, BACK + "moe/combine/gather"),
            (FUSION % 6, 12, "jit(train_step)/jvp(forward)/M/attn/mul"),
            # a module that merely has the word in its name
            (FUSION % 7, 10, "jit(train_step)/optimizer/moe_mlp/add"),
        ]
        for name, ms, op_name in steps:
            ops.append((name, t, t + ms * MS, op_name))
            t += ms * MS
        # a container spans its children and counts for nothing
        ops.append(("%while.5 = (s32[]) while((s32[]) %t), body=%b",
                    100 * k * MS, 100 * k * MS + 90 * MS, ""))
    return ops, modules


def test_operations_fall_into_the_expert_layer_s_scopes():
    cases = [
        (FUSION % 1, SCOPE + "moe/router/dot_general", ("router", False)),
        (FUSION % 1, BACK + "moe/dispatch/gather", ("dispatch", False)),
        (FUSION % 1, "jit(f)/jvp(forward/M/moe/combine)/mul",
         ("combine", False)),
        (FUSION % 1, SCOPE + "moe/experts/mul", ("experts", False)),
        (OWN_KERNEL % 1, SCOPE + "moe/experts/pallas_call",
         ("experts", True)),
        # XLA's own grouped-matmul kernels carry no scope
        (RAGGED % 1, "ragged-dot-none", ("experts", True)),
        (RAGGED % 1, "", ("experts", True)),
        ("%ragged-dot-metadata = (s32[65]{0}) custom-call()",
         "ragged-dot-metadata", ("experts", True)),
        # a kernel elsewhere, a word in a module's name, nothing at all
        (OWN_KERNEL % 1, "jit(f)/jvp(forward)/M/attn/flash_fwd",
         (None, False)),
        (FUSION % 1, "jit(f)/jvp(forward)/M/moe_mlp/router/dot",
         (None, False)),
        (FUSION % 1, "jit(f)/smoe/experts/mul", (None, False)),
        (FUSION % 1, "", (None, False)),
    ]
    for name, op_name, want in cases:
        assert moe_trace.classify(name, op_name) == want, op_name


def test_trace_metrics_by_hand():
    reduced = moe_trace.reduce({0: device_events()})
    device = reduced["devices"]["0"]
    # the window holds two whole periods of 90 ms busy
    assert device["steps"] == 2 and device["scoped"]
    assert device["busy_s"] == pytest.approx(0.180)
    assert device["scopes_s"] == pytest.approx({
        "router": 0.004, "dispatch": 0.012, "experts": 0.098,
        "combine": 0.022})
    assert device["expert_matmul_s"] == pytest.approx(0.090)
    assert moe_trace.time_share(reduced) == pytest.approx(100 * 136 / 180)
    assert moe_trace.time_share(reduced, ("dispatch", "combine")) == (
        pytest.approx(100 * 34 / 180))
    # the worst of two devices
    second = device_events()
    second[0].append((FUSION % 8, 190 * MS, 199 * MS, SCOPE + "moe/router/x"))
    both = moe_trace.reduce({0: device_events(), 1: second})
    assert moe_trace.time_share(both) == pytest.approx(100 * 145 / 189)


def run_with(tmp_path, reduced):
    flops = importlib.import_module("benchmark.flops.moe_decoder")
    return {
        "out": str(tmp_path), "moe_reduced": reduced, "flops": flops,
        "chips": 1, "trace": True, "window": (100.0, 200.0),
        "config": {"hidden_size": 8, "intermediate_size": 4,
                   "num_experts": 6, "num_experts_per_tok": 2,
                   "num_hidden_layers": 3, "num_attention_heads": 2},
        "traffic": {"seq_len": 16, "minibatch": 4},
        "cell": {"warmup_steps": 16, "log_every": 8},
        "worker": {"device_kind": "toy"},
        "peaks_table": {"toy": {"bf16_flops_per_s": 1e6,
                                "hbm_bytes_per_s": 1e9}},
    }


def test_readers_by_hand(tmp_path):
    run = run_with(tmp_path, moe_trace.reduce({0: device_events()}))
    def read(name):
        return importlib.import_module(
            "benchmark.metrics." + name).read(run)

    assert read("moe_time_share") == pytest.approx(100 * 136 / 180)
    assert read("moe_permute_share") == pytest.approx(100 * 34 / 180)
    # 2 steps of 4 samples; a sample needs 55,296 FLOPs (test_moe_flops)
    # at 1 MFLOP/s, bytes are far less: 8 x 0.055296 s over 0.090 s
    assert read("expert_matmul_roofline") == pytest.approx(
        100 * 8 * 0.055296 / 0.090)
    # a count without the entry, a configuration without a count
    run["flops"] = importlib.import_module("benchmark.flops.dense_decoder")
    assert read("expert_matmul_roofline") is None
    run["flops"] = None
    assert read("expert_matmul_roofline") is None


def test_load_ratio_is_the_median_over_a_fixed_range_of_steps(tmp_path):
    events_dir = tmp_path / "events"
    events_dir.mkdir()

    def routing(step, most):
        return {"event": "moe_routing", "ts": 100.0 + step, "step": step,
                "tokens_per_expert_max": most,
                "tokens_per_expert_mean": 100.0,
                "router_entropy": 3.0, "dropped_pairs": 0.0}

    # warm-up 16, logged every 8: the nine events of steps 24 to 88
    # count, whatever the clock said; 8 and 16 are warm-up, 96 on is
    # past the range (a faster program reaches it inside the window)
    load = {8: 900.0, 16: 800.0, 24: 700.0, 32: 600.0, 40: 500.0,
            48: 400.0, 56: 350.0, 64: 300.0, 72: 250.0, 80: 200.0,
            88: 150.0, 96: 100.0, 104: 100.0}
    journal = [routing(step, most) for step, most in load.items()]
    journal.insert(3, {"event": "loop_phases", "ts": 120.0})
    with open(events_dir / "worker-0-77.events.ndjson", "w") as f:
        for event in journal:
            f.write(json.dumps(event) + "\n")
        f.write('{"event": "moe_rou')  # torn by the kill at the end
    reader = importlib.import_module(
        "benchmark.metrics.expert_load_max_over_mean")
    cell = {"warmup_steps": 16, "log_every": 8}
    run = {"out": str(tmp_path), "cell": cell, "window": (0.0, 1.0),
           "trace": False}
    assert reader.read(run) == pytest.approx(3.5)
    # a run cut short reads what the range holds
    short = [e for e in journal if e.get("step", 0) <= 40]
    with open(events_dir / "worker-0-77.events.ndjson", "w") as f:
        for event in short:
            f.write(json.dumps(event) + "\n")
    run = {"out": str(tmp_path), "cell": cell, "window": (0.0, 1.0),
           "trace": True}
    assert reader.read(run) == pytest.approx(6.0)


def test_a_program_without_the_expert_layer_reports_nothing(tmp_path):
    """The dense program's trace has no ``moe/`` scope and its journal
    no ``moe_routing``: every reader returns None and raises nothing
    (a share of zero would be a lie about a program that has no such
    layer)."""
    dense = [
        (name, s, e, "jit(train_step)/jvp(forward)/TransformerLM/mul")
        for name, s, e, op_name in device_events()[0]
        if "while" not in name and "ragged" not in name
    ]
    reduced = moe_trace.reduce({0: (dense, device_events()[1])})
    assert reduced["devices"]["0"]["scoped"] is False
    (tmp_path / "events").mkdir()
    (tmp_path / "events" / "worker-0-5.events.ndjson").write_text(
        json.dumps({"event": "role_start", "ts": 120.0}) + "\n")
    run = run_with(tmp_path, reduced)
    for metric in NEW:
        reader = importlib.import_module("benchmark.metrics." + metric)
        assert reader.read(dict(run)) is None, metric
    # no trace at all (an untraced run)
    empty = run_with(tmp_path, None)
    for metric in NEW[:3]:
        reader = importlib.import_module("benchmark.metrics." + metric)
        assert reader.read(dict(empty)) is None, metric
    assert moe_trace.reduced({"out": str(tmp_path)}) is None


def test_a_recorded_dense_trace_has_no_expert_layer():
    """Recorded on the chip by PR 22 (a dense LM): the file parses
    through the library's own parser and nothing is charged."""
    from benchmark.lib import loop_ledger

    devices, _ = loop_ledger.load_xspace(
        os.path.join(HERE, "data", "tiny_lm_1chip.xplane.pb.gz"))
    reduced = moe_trace.reduce(devices)
    (device,) = reduced["devices"].values()
    assert device["busy_s"] > 0 and not device["scoped"]
    assert device["expert_matmul_s"] == 0.0
    assert moe_trace.time_share(reduced) is None


def test_the_four_are_the_last_entries_of_the_manifest():
    """The benchmark's contract: a PR that changes the program adds
    entries at the END of the manifest's lists. The driver refused the
    four when they stood ahead of PR 23's seven ("the PR changes the
    per-layer metric input_wait_ms": it compares entry by position).
    ``test_loop_ledger.py`` pins those seven to the end of the list,
    so its pinned test is lost until a ``benchmark`` PR makes it
    ``set(NEW) <= set(root)`` (PERF.md Section 7)."""
    from tests.benchmark_harness import test_loop_ledger as pinned

    per_layer = common.load(common.MANIFEST)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert tuple(names[-4:]) == NEW
    assert tuple(names[-11:-4]) == tuple(pinned.NEW)
    for metric in per_layer[-4:]:
        assert metric["workloads"] == ["olmoe1b7b-s4k"]
        assert metric["moves"] == "samples_per_s"
        assert metric["layer"] == (
            "expert layer (ops/moe.py, models/moe_transformer.py:MoeMlp)")
        reader = importlib.import_module(
            "benchmark.metrics." + metric["name"])
        assert callable(reader.read) and metric["name"] in reader.__doc__
    rehearsal = {m["name"] for m in common.load(MOE_MANIFEST)["per_layer"]}
    assert set(NEW) <= rehearsal


def test_pr_23_s_seven_are_in_the_manifest_wherever_they_stand():
    """Makes up for ``test_loop_ledger.py``'s pinned test, which this
    PR loses to the contract's "new entries at the end": everything it
    asserts but the position of the seven."""
    from tests.benchmark_harness import test_loop_ledger as pinned

    root = {m["name"]: m for m in common.load(common.MANIFEST)["per_layer"]}
    assert set(pinned.NEW) <= set(root)
    assert root["loop_host_ms"]["workloads"] == [
        "pythia1b-s2k", "pythia1b-s16k"]
    rehearsal = common.load(pinned.LEDGER_MANIFEST)
    assert set(pinned.NEW) <= {m["name"] for m in rehearsal["per_layer"]}
    for name in pinned.NEW:
        reader = importlib.import_module("benchmark.metrics." + name)
        assert callable(reader.read) and name in reader.__doc__


def test_what_was_in_the_manifest_is_there_entry_for_entry():
    """A new entry is an addition: the parent's lists are the first
    entries of this PR's, equal and in the parent's order (recorded
    here as names; ``git diff`` of the file shows no line removed)."""
    manifest = common.load(common.MANIFEST)
    before = (
        "launch_to_first_step_s", "step_compile_s", "compiles_in_window",
        "dispatch_gap_ms", "stall_share", "peak_hbm_gb",
        "flash_time_share", "flash_roofline", "collective_time_share",
        "collective_exposed_share", "device_idle_share", "input_wait_ms",
        "loop_host_ms", "slow_steps_in_window", "gap_attributed_share",
        "optimizer_time_share", "backend_init_s", "state_init_s")
    names = [m["name"] for m in manifest["per_layer"]]
    assert tuple(names[:len(before)]) == before
    assert tuple(names[len(before):]) == NEW
    root = {m["name"]: m for m in manifest["per_layer"]}
    assert root["loop_host_ms"]["workloads"] == [
        "pythia1b-s2k", "pythia1b-s16k"]
    assert [w["name"] for w in manifest["workloads"]] == [
        "pythia1b-s2k", "pythia1b-s16k", "pythia1b-fsdp4-s2k",
        "olmoe1b7b-s4k"]
    assert [c["name"] for c in manifest["configs"]] == [
        "pythia-1b", "pythia-1b-1chip", "olmoe-1b-7b-1chip"]


def test_rehearsals_of_a_tiny_moe_cell(tmp_path):
    """The OLMoE zoo, its reference check and the journal-read metric
    through the whole command on the CPU, untraced and traced."""
    proc, line = common.run_cell(
        "tiny-moe-s128", 0, tmp_path, manifest=MOE_MANIFEST, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-moe-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "loss", "choices", "grad:block_1/moe_mlp/w_gate"}
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert "moe dispatch resolved to sorted (tokens=512 experts=8" in log
    assert "experts' matmul=ragged_dot)" in log

    proc, line = common.run_cell(
        "tiny-moe-s128", 1, tmp_path, manifest=MOE_MANIFEST, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    metrics = line["metrics"]
    # a CPU run has no device plane: the three that read one are left
    # out, as are the flash kernels' and the optimizer's
    assert set(metrics) == {"expert_load_max_over_mean"}
    assert 1.0 <= metrics["expert_load_max_over_mean"]["value"] <= 8.0
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    routing = [e for e in journal if e["event"] == "moe_routing"]
    assert routing and all(e["dropped_pairs"] == 0.0 for e in routing)
    assert all(e["step"] % 2 == 0 for e in routing)  # log_every 2
    # 512 tokens x top-2 over 8 experts
    assert all(e["tokens_per_expert_mean"] == 128.0 for e in routing)
