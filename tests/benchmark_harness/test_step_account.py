"""``benchmark/lib/step_account.py`` and the seven whole-step shares
(ISSUE 62), on hand-made operations: no trace is parsed and no cell is
rehearsed here."""

import importlib
import os

import pytest

from benchmark.lib import step_account as sa
from tests.benchmark_harness import _common as common

STEP = "jit_train_step(1)"
MS = 1e6  # nanoseconds
FWD = "jit(train_step)/jvp(forward)/M/"
BWD = "jit(train_step)/transpose(jvp(forward))/M/"
REMAT = BWD + "jvp(forward)/M/checkpoint/rematted_computation/"
KERNEL = ', custom_call_target="tpu_custom_call"'

NEW = {
    "step_named_share": "higher", "step_mixed_share": "lower",
    "recompute_time_share": "lower", "attention_time_share": "lower",
    "mlp_time_share": "lower", "head_loss_time_share": "lower",
    "mixer_time_share": "lower",
}


def op(name, opcode, at, ms, op_name, operands="%x", more=""):
    """One operation of the ``XLA Ops`` line: its whole HLO text, start
    and end in nanoseconds and its ``op_name`` as the trace has it."""
    text = "%%%s = f32[8,128]{1,0:T(8,128)} %s(f32[8,128]{1,0} %s)%s" % (
        name, opcode, operands, more)
    return (text, at * MS, (at + ms) * MS, op_name and op_name + ":")


def one_step(at):
    """One step's operations from ``at`` ms on: 100 ms of work."""
    return [
        op("convert.1", "convert", at, 2,
           "jit(train_step)/jvp(cast_params)/convert_element_type"),
        op("fusion.10", "fusion", at + 2, 10,
           FWD + "block_0/attn/attn_full/qkv/query/dot_general"),
        op("flash_fwd.3", "custom-call", at + 12, 8,
           FWD + "block_0/attn/attn_full/flash/pallas_call", more=KERNEL),
        op("fusion.11", "fusion", at + 20, 12,
           FWD + "block_0/dense_mlp/mlp_up/dot_general"),
        op("fusion.12", "fusion", at + 32, 6, FWD + "head/lm_head/dot_general"),
        op("fusion.13", "fusion", at + 38, 4, "jit(train_step)/jvp(loss)/sub"),
        # the forward run again, then the backward proper
        op("fusion.20", "fusion", at + 42, 12,
           REMAT + "block_0/dense_mlp/mlp_up/dot_general"),
        op("fusion.21", "fusion", at + 54, 20,
           BWD + "jvp(forward)/M/checkpoint/block_0/dense_mlp/mlp_up/"
           "dot_general"),
        # the backward of a custom_vjp: only its kernel's name is left
        op("flash_bwd.4", "custom-call", at + 74, 12,
           BWD + "block_0/attn/pallas_call", more=KERNEL),
        op("fusion.30", "fusion", at + 86, 6, "jit(train_step)/optimizer/add"),
        # a copy the compiler put in takes its operand's name
        op("copy.5", "copy", at + 92, 2, "", operands="%fusion.30"),
        # no scope on its path, and nothing to inherit from
        op("fusion.40", "fusion", at + 94, 4, FWD + "block_0/attn/q/mul"),
        op("copy.6", "copy", at + 98, 2, "", operands="%parameter.9"),
        # a container's time is its children's
        op("while.1", "while", at, 100, ""),
    ]


def modules(periods, period=110):
    return [(STEP, n * period * MS, (n * period + 100) * MS)
            for n in range(periods)]


def device(periods=3, extra=()):
    ops = [o for n in range(periods) for o in one_step(110 * n)]
    return ops + list(extra), modules(periods)


MIX = {"fusions": 9, "mixed": 2, "dropped": {"rows": 0, "bytes": 0}, "rows": [
    {"op": "fusion.21", "root": "mlp", "bytes": {"mlp": 10, "step": 90},
     "heavy": {"convolution": ["mlp"]}},
    {"op": "fusion.99", "root": "step", "bytes": {"step": 1, "mlp": 1},
     "heavy": {}}]}


@pytest.fixture(scope="module")
def account():
    return sa.reduce({0: device()}, MIX)


def rows_of(account, **want):
    return [row for row in account["devices"]["0"]["rows"]
            if all(row[k] == v for k, v in want.items())]


def test_the_rows_add_up_to_the_operations_time(account):
    dev = account["devices"]["0"]
    # the window holds whole step periods: the last execution is its end
    assert (dev["steps"], dev["period_ms"]) == (2, 110.0)
    assert dev["op_ms"] == pytest.approx(100.0)
    assert sum(row["ms"] for row in dev["rows"]) == pytest.approx(
        dev["op_ms"])
    # nothing overlaps here: busy is the same 100 ms a step
    assert dev["busy_ms"] == pytest.approx(100.0)
    assert dev["overlap_ms"] == pytest.approx(0.0)
    assert sum(row["calls"] for row in dev["rows"]) == 13
    assert dev["inherited_ms"] == pytest.approx(2.0)
    assert account["speaker"] == "0"
    assert account["scope_mix"] == {
        "fusions": 9, "mixed": 2, "dropped": {"rows": 0, "bytes": 0}}


@pytest.mark.parametrize("want, ms", [
    ({"family": "mlp", "scope": "dense_mlp", "direction": "forward"}, 12),
    ({"family": "mlp", "scope": "dense_mlp", "direction": "recompute"}, 12),
    ({"family": "mlp", "scope": "dense_mlp", "direction": "backward"}, 20),
    ({"family": "attention", "scope": "attn_full/qkv"}, 10),
    ({"family": "attention", "scope": "attn_full/flash",
      "direction": "forward"}, 8),
    ({"family": "attention", "scope": "attn_full/flash",
      "direction": "backward"}, 12),
    ({"family": "head_loss", "scope": "head"}, 6),
    ({"family": "head_loss", "scope": "loss"}, 4),
    ({"family": "step", "scope": "cast_params"}, 2),
    # the optimizer's fusion and the copy that inherits its name
    ({"family": "step", "scope": "optimizer", "direction": "forward"}, 8),
    ({"family": "unnamed"}, 6),
])
def test_forward_recompute_backward_and_optimizer_are_told_apart(
        account, want, ms):
    assert sum(row["ms"] for row in rows_of(account, **want)) == (
        pytest.approx(ms))


def test_kernels_are_listed_under_their_row(account):
    (row,) = rows_of(account, scope="attn_full/flash", direction="backward")
    assert row["kernels"] == {"flash_bwd/f32": pytest.approx(12.0)}
    assert row["calls"] == 1


def test_the_unnamed_and_the_longest_operations_are_listed(account):
    dev = account["devices"]["0"]
    assert dev["unnamed"]["ms"] == pytest.approx(6.0)
    assert [(o["op"], o["opcode"], o["ms"]) for o in dev["unnamed"]["ops"]
            ] == [("fusion.40", "fusion", pytest.approx(4.0)),
                  ("copy.6", "copy", pytest.approx(2.0))]
    assert dev["unnamed"]["ops"][0]["op_name"].endswith("block_N/attn/q/mul")
    assert dev["unnamed"]["ops"][0]["shape"].startswith("f32[8,128]")
    top = dev["top_ops"]
    assert len(top) == 13  # at most twenty, and there are thirteen
    assert (top[0]["op"], top[0]["family"], top[0]["scope"],
            top[0]["direction"]) == (
        "fusion.21", "mlp", "dense_mlp", "backward")
    assert [o["ms"] for o in top] == sorted(
        (o["ms"] for o in top), reverse=True)


def test_the_join_with_scope_mix_is_by_instruction(account):
    mixed = account["devices"]["0"]["mixed"]
    # fusion.99 is in the table and not in the trace: it costs nothing
    assert mixed["ms"] == pytest.approx(20.0)
    (listed,) = mixed["ops"]
    assert (listed["op"], listed["root"], listed["others"]) == (
        "fusion.21", "mlp", ["step"])
    assert sa.mixed_share(account) == pytest.approx(20.0)
    # a journal without the table: the account stands, the share is None
    plain = sa.reduce({0: device()})
    assert plain["devices"]["0"]["mixed"] is None
    assert sa.mixed_share(plain) is None
    assert sa.named_share(plain) == pytest.approx(94.0)


@pytest.mark.parametrize("read, expected", [
    (sa.named_share, 94.0),
    (lambda a: sa.family_share(a, "attention"), 30.0),
    (lambda a: sa.family_share(a, "mlp"), 44.0),
    (lambda a: sa.family_share(a, "head_loss"), 10.0),
    (lambda a: sa.family_share(a, "mixer"), 0.0),
    (lambda a: sa.share(a, lambda row: row["direction"] == "recompute"),
     12.0),
])
def test_the_shares_are_of_busy_time(account, read, expected):
    assert read(account) == pytest.approx(expected)


def test_overlap_is_stated_and_the_busiest_device_speaks():
    # a second device whose collective runs BESIDE its compute: the sum
    # of its operations is longer than the time it was busy
    beside = [op("all-gather.1", "all-gather", 110 * n + 20, 30,
                 FWD + "block_0/moe_mlp/moe/exchange/all_gather")
              for n in range(3)]
    # and a third that idles half of every step
    idle = ([o for n in range(3) for o in one_step(110 * n)[:6]],
            modules(3))
    account = sa.reduce({0: device(), 1: device(extra=beside), 2: idle})
    one = account["devices"]["1"]
    assert one["op_ms"] == pytest.approx(130.0)
    assert one["busy_ms"] == pytest.approx(100.0)
    assert one["overlap_ms"] == pytest.approx(30.0)
    assert account["devices"]["2"]["busy_ms"] == pytest.approx(42.0)
    # equally busy: the first of them; never the idle one
    assert account["speaker"] == "0"
    assert sa.family_share(account, "mlp") == pytest.approx(44.0)


def test_a_trace_without_two_executions_gives_no_device():
    ops, _ = device()
    account = sa.reduce({0: (ops, modules(1))})
    assert account["devices"] == {} and account["speaker"] is None
    assert sa.named_share(account) is None


def test_a_program_without_the_registry_reports_nothing(
        monkeypatch, tmp_path):
    monkeypatch.setattr(sa, "scopes", None)
    run = {"out": str(tmp_path), "trace": True}
    assert sa.reduced(run) is None
    for name in NEW:
        reader = importlib.import_module("benchmark.metrics." + name)
        assert reader.read(run) is None
    # no child ran and nothing was left
    assert os.listdir(tmp_path) == []
    assert sa.main(["no.xplane.pb", str(tmp_path)]) == 0
    assert os.listdir(tmp_path) == []


def test_a_run_without_a_trace_reports_nothing(tmp_path):
    run = {"out": str(tmp_path), "trace": False}
    assert sa.reduced(run) is None
    assert all(importlib.import_module(
        "benchmark.metrics." + name).read(run) is None for name in NEW)


def test_the_journal_s_scope_mix_is_the_train_step_s(tmp_path):
    events = tmp_path / "events"
    events.mkdir()
    (events / "worker-0.ndjson").write_text("\n".join([
        '{"event": "xla_compile", "fn": "eval_step", "scope_mix": '
        '{"rows": [{"op": "fusion.1"}]}}',
        '{"event": "xla_compile", "fn": "train_step", "scope_mix": null}',
        '{"event": "xla_compile", "fn": "train_step", "scope_mix": '
        '{"rows": [{"op": "fusion.2"}]}}',
        '{"event": "xla_compile", "fn": "train_step", "scope_mix": {"ro',
    ]) + "\n")
    assert sa.journal_mix(str(tmp_path)) == {"rows": [{"op": "fusion.2"}]}
    assert sa.journal_mix(str(tmp_path / "nowhere")) is None


def test_the_seven_are_in_the_manifest_with_a_file_each():
    manifest = common.load(common.MANIFEST)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, better in NEW.items():
        entry = by_name[name]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"], entry["layer"]) == (
            "%", better, "device_trace", "samples_per_s",
            "step functions and instrumentation"), name
        reader = importlib.import_module("benchmark.metrics." + name)
        assert callable(reader.read) and name in reader.__doc__
        # every cell, the ones later PRs add too (``mixer_time_share``
        # reads 0.0 where no block has such a mixer: a list of its four
        # cells would name ``qwen3next80b-s32k``, which
        # ``test_published_widths_qwen3next.py`` pins as named by that
        # PR's metrics alone)
        assert "workloads" not in entry
