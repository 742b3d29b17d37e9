"""The three readers PR 29 brought (``mla_time_share``,
``mla_assemble_share``, ``shared_expert_time_share``) and the
reduction under them (``benchmark/lib/mla_trace.py``): on hand-made
operations, on a step period recorded on the chip from the
``moonlight16b-s8k`` cell, and through the whole command on the CPU
with the tiny rehearsal of the Moonlight zoo."""

import gzip
import json
import os

import pytest

from benchmark.lib import mla_trace, moe_trace
from benchmark.lib import trace_reduce as tr
from benchmark.metrics import (
    mla_assemble_share,
    mla_time_share,
    shared_expert_time_share,
)
from tests.benchmark_harness import _common as common

MANIFEST = os.path.join(common.HERE, "preset", "MOONLIGHT.json")
RECORDED = os.path.join(common.HERE, "data", "moonlight16b_s8k_step.json.gz")
KERNEL = ' custom-call(), ' + tr.MOSAIC_KERNEL
FWD = "jit(train_step)/jit(main)/forward/MoeTransformerLM/block_1/"
BWD = "jit(train_step)/jit(main)/transpose(jvp(forward))/"


@pytest.mark.parametrize("name,op_name,kind", [
    ("%fusion.1 = bf16[2,8192,16,192] fusion(",
     FWD + "attn/mla/q_proj/dot_general", "mla/q_proj"),
    ("%fusion.2 = f32[2,8192,576] fusion(",
     BWD + "MoeTransformerLM/block_0/attn/mla/kv_down/mul", "mla/kv_down"),
    ("%fusion.3 = bf16[] fusion(", FWD + "attn/mla/kv_up/x", "mla/kv_up"),
    ("%copy.4 = bf16[2,16,8192,192] copy(",
     FWD + "attn/mla/assemble/concatenate", "mla/assemble"),
    ("%fusion.5 = bf16[] fusion(",
     "transpose(jvp(mla/out_proj))/dot_general", "mla/out_proj"),
    ("%fusion.6 = bf16[] fusion(",
     FWD + "moe_mlp/moe/shared/shared_up/dot_general", "moe/shared"),
    ("%flash_fwd.7 = (bf16[32,8192,128], f32[32,1,8192])" + KERNEL,
     FWD + "attn/pallas_call", "flash"),
    ("%flash_bwd.8 = (bf16[32,8192,192])" + KERNEL,
     BWD + "attn/pallas_call", "flash"),
    # a grouped matmul is a Mosaic kernel too, and no flash kernel
    ("%gmm.9 = bf16[98304,1408]" + KERNEL,
     FWD + "moe_mlp/moe/experts/jit(gmm)/pallas_call", None),
    ("%fusion.10 = bf16[] fusion(", FWD + "moe_mlp/moe/router/x", None),
    ("%fusion.11 = bf16[] fusion(", FWD + "attn/formula/q_proj", None),
    ("%fusion.12 = bf16[] fusion(", FWD + "moe_mlp/moe/shared_x/y", None),
])
def test_classify(name, op_name, kind):
    assert mla_trace.classify(name, op_name) == kind


def hand_made():
    """Two step periods of 100 us: 10 us under each of the seven
    kinds, 20 us of other work, 10 us idle."""
    kinds = [FWD + "attn/mla/%s/x" % s for s in mla_trace.MLA_SCOPES]
    kinds.append(FWD + "moe_mlp/moe/shared/x")
    ops = []
    for period in range(3):
        t = period * 100_000.0
        for op_name in kinds:
            ops.append(("%fusion.1 = bf16[] fusion(", t, t + 10_000, op_name))
            t += 10_000
        ops.append(("%flash_fwd.2 = bf16[]" + KERNEL, t, t + 10_000,
                    FWD + "attn/pallas_call"))
        ops.append(("%fusion.3 = f32[] fusion(", t + 10_000, t + 30_000,
                    FWD + "ln_f/mul"))
    modules = [("jit_train_step(%d)" % i, i * 100_000.0,
                i * 100_000.0 + 90_000) for i in range(3)]
    return ops, modules


def test_reduce_device_by_hand():
    ops, modules = hand_made()
    device = mla_trace.reduce_device(ops, modules)
    assert device["steps"] == 2 and device["scoped"]
    assert device["busy_s"] == pytest.approx(180e-6)
    for kind, seconds in device["seconds"].items():
        assert seconds == pytest.approx(20e-6), kind
    reduced = mla_trace.reduce({0: (ops, modules)})
    assert mla_trace.time_share(reduced, ["mla/assemble"]) == (
        pytest.approx(100 * 20 / 180))
    assert mla_trace.time_share(reduced, mla_time_share.KINDS) == (
        pytest.approx(100 * 120 / 180))
    assert mla_trace.time_share(reduced, ["moe/shared"]) == (
        pytest.approx(100 * 20 / 180))


def test_a_program_without_the_scopes_reads_nothing():
    """The parent of PR 29, and every other configuration: flash
    kernels alone do not make a program ``scoped``."""
    ops = [(n, s, e, FWD + "attn/pallas_call" if "flash" in n else "x")
           for n, s, e, _ in hand_made()[0]
           if "flash" in n or "fusion.3" in n]
    reduced = mla_trace.reduce({0: (ops, hand_made()[1])})
    assert reduced["devices"]["0"]["scoped"] is False
    assert reduced["devices"]["0"]["seconds"]["flash"] > 0
    for module in (mla_time_share, mla_assemble_share,
                   shared_expert_time_share):
        run = {"mla_reduced": reduced}
        assert module.read(run) is None
        assert module.read({"mla_reduced": None}) is None


def test_the_readers_read_what_the_reduction_left(tmp_path):
    ops, modules = hand_made()
    run = {"mla_reduced": mla_trace.reduce({0: (ops, modules)})}
    assert mla_time_share.read(run) == pytest.approx(100 * 120 / 180)
    assert mla_assemble_share.read(run) == pytest.approx(100 * 20 / 180)
    assert shared_expert_time_share.read(run) == pytest.approx(
        100 * 20 / 180)
    # no trace at all: nothing to reduce, nothing raised
    assert mla_time_share.read({"out": str(tmp_path)}) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        body = json.load(f)
    return ([tuple(op) for op in body["ops"]],
            [tuple(m) for m in body["modules"]])


def test_the_recorded_step_of_the_cell(recorded):
    """One step period of ``moonlight16b-s8k`` on a v5e (PR 29's first
    traced run; ``mla_trace.py --record``): the scopes are there under
    their names, forward and backward, the kernels are found, and the
    shares are that run's report line's. The recording dates from the
    cell's first form, when six expert groups held every row (PERF.md
    Section 6); as the cell is, with all 64 groups filled, the same
    program reads 19.29, 1.31, 3.16 and 12.58 (Section 5)."""
    ops, modules = recorded
    assert len(modules) == 2 and len(ops) > 500
    device = mla_trace.reduce_device(ops, modules)
    assert device["steps"] == 1 and device["scoped"]
    seconds, busy = device["seconds"], device["busy_s"]
    assert all(seconds[k] > 0 for k in seconds), seconds
    # both passes carry the scope: some operation under transpose(
    for scope in ("mla/assemble", "mla/q_proj", "moe/shared"):
        assert any(scope in op and "transpose(" in op
                   for _, _, _, op in ops), scope
    kernels = {n.split(" = ")[0].lstrip("%").rsplit(".", 1)[0]
               for n, _, _, _ in ops if tr.MOSAIC_KERNEL in n}
    assert {"flash_fwd", "flash_bwd"} <= kernels
    # two layers: two forward and two backward flash kernels a step,
    # and the recording holds two executions of the step (the window
    # runs from the first one's start to the second one's)
    flash = [n for n, _, _, _ in ops
             if mla_trace.classify(n, "") == "flash"]
    assert len(flash) == 2 * 4
    share = lambda kinds: 100 * sum(seconds[k] for k in kinds) / busy
    # the whole trace's five periods read 19.93, 1.357, 3.269 and 12.99
    # (the report line of that run)
    assert share(mla_time_share.KINDS) == pytest.approx(19.95, abs=0.1)
    assert share(["mla/assemble"]) == pytest.approx(1.36, abs=0.02)
    assert share(["moe/shared"]) == pytest.approx(3.27, abs=0.02)
    assert share(["flash"]) == pytest.approx(13.0, abs=0.05)
    assert busy == pytest.approx(0.3116, abs=1e-3)
    # the expert layer's four parts are read from the same operations
    moe = moe_trace.reduce_device(ops, modules)
    assert moe["scoped"] and moe["expert_matmul_s"] > 0
    assert all(moe["scopes_s"][s] > 0 for s in moe_trace.SCOPES)
    # the shared experts are outside the four: no double count
    assert sum(moe["scopes_s"].values()) + seconds["moe/shared"] < busy


def test_rehearsal_of_a_tiny_moonlight_cell(tmp_path):
    """The Moonlight zoo, its reference check over the last positions,
    the balancing bias through the worker's loop and the new readers
    through the whole command on the CPU, untraced and traced."""
    proc, line = common.run_cell(
        "tiny-moonlight-s128", 0, tmp_path, manifest=MANIFEST, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0
    out = os.path.join(
        common.REPO, "chiprun_out", "benchmark", "tiny-moonlight-s128")
    check = common.load(os.path.join(out, "refcheck.json"))
    assert check["ok"] and set(check["errors"]) >= {
        "logits", "loss", "choices", "grad:block_1/moe_mlp/shared_gate/kernel",
        "grad:block_0/attn/kv_down/kernel"}
    with open(os.path.join(out, "worker.log")) as f:
        log = f.read()
    assert "moe dispatch resolved to sorted (tokens=512 experts=8" in log
    assert "score=sigmoid shared=2, experts' matmul=ragged_dot)" in log

    proc, line = common.run_cell(
        "tiny-moonlight-s128", 1, tmp_path, manifest=MANIFEST, seconds=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, proc.stderr[-3000:]
    # a CPU run has no device plane: every reader of one is left out
    assert set(line["metrics"]) == {"expert_load_max_over_mean"}
    journal = []
    events_dir = os.path.join(out, "events")
    for name in os.listdir(events_dir):
        if name.startswith("worker-"):
            with open(os.path.join(events_dir, name)) as f:
                journal += [json.loads(x) for x in f if x.endswith("}\n")]
    routing = [e for e in journal if e["event"] == "moe_routing"]
    assert routing and all(e["dropped_pairs"] == 0.0 for e in routing)
    # the bias leaves zero by 0.001 a step at most, and is reported
    assert all(
        0.0 < e["bias_abs_max"] <= 0.001 * e["step"] + 1e-6
        for e in routing)
    assert routing[-1]["bias_abs_max"] > routing[0]["bias_abs_max"]
    # 512 tokens x top-2 over 8 experts
    assert all(e["tokens_per_expert_mean"] == 128.0 for e in routing)
