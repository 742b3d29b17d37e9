"""``lib/trace_reduce.py``: busy union, gaps, kernel sums by name and
collective overlap, on hand-made events whose answers are known and on
a trace recorded on the chip (``data/``)."""

import os

import pytest

from benchmark.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # nanoseconds


FLASH_FWD = (
    "%attn.6 = (bf16[8,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[8,1,1024]"
    "{2,1,0:T(1,128)S(1)}) custom-call(bf16[8,1024,64]{2,1,0:T(8,128)"
    "(2,1)S(1)} %pad_maximum_fusion), custom_call_target="
    '"tpu_custom_call", frontend_attributes={kernel_metadata={}}'
)
FUSION = (
    "%fusion.24 = (f32[2048,50304]{1,0:T(8,128)}, f32[]{:T(128)}) fusion("
    "f32[2048,50304]{1,0:T(8,128)} %p.1), kind=kOutput, calls=%fused.34"
)
ALL_GATHER = (
    "%all-gather-start.3 = (f32[512,2048]{1,0:T(8,128)}, f32[2048,2048]"
    "{1,0:T(8,128)}) all-gather-start(f32[512,2048]{1,0:T(8,128)} %p.2), "
    "channel_id=7, dimensions={0}"
)


def ev(name, start_ms, end_ms):
    return (name, start_ms * MS, end_ms * MS)


def device_lines():
    """Three executions of one step program, 100 ms apart, 80 ms long:
    fusion 0-30, a flash kernel 30-50, an all-gather in flight 45-70
    beside the kernel until 50 and a fusion from 60, for which the core
    waits 50-60, fusion 60-80."""
    ops, modules, background = [], [], []
    for k in range(3):
        t = 100 * k
        modules.append(ev("jit_train_step(%d)" % (7 + k), t, t + 80))
        ops += [
            ev(FUSION, t, t + 30),
            ev(FLASH_FWD, t + 30, t + 50),
            ev("fusion.2", t + 60, t + 80),
            # a container spans its children and counts for nothing
            ev("%while.5 = (s32[]) while((s32[]) %t), body=%b", t, t + 80),
            # an asynchronous collective leaves its start (an instant)
            # and its done (the core's wait) on the core's line; the
            # transfer itself is on the async line
            ev(ALL_GATHER, t + 45, t + 45.001),
            ev(ALL_GATHER.replace("-start", "-done"), t + 50, t + 60),
        ]
        background.append(ev(ALL_GATHER, t + 45, t + 70))
    modules.append(ev("jit_init(1)", -50, -40))
    return {tr.OPS_LINE: ops, tr.MODULES_LINE: modules,
            tr.ASYNC_LINE: background}


def test_labels_and_kernel_names():
    assert tr.opcode(FUSION) == "fusion"
    assert tr.opcode(FLASH_FWD) == "custom-call"
    assert tr.opcode(ALL_GATHER) == "all-gather-start"
    assert tr.label(FLASH_FWD) == "%attn.6 custom-call bf16[8,1024,64]"
    assert tr.label("fusion.2") == "fusion.2"
    assert tr.kernel_name(FLASH_FWD) == "attn/bf16,f32"
    assert tr.kernel_name(FUSION) is None
    # a custom call that is not a Mosaic kernel is not a kernel
    assert tr.kernel_name(
        "%custom-call.7 = f32[4]{0} custom-call(f32[4]{0} %x), "
        'custom_call_target="AllocateBuffer"') is None


def test_interval_arithmetic():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert tr.total(merged) == 6
    assert tr.subtract([[0, 10]], [[2, 3], [5, 8]]) == [
        (0, 2), (3, 5), (8, 10)]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [(0, 3), (7, 9)]
    assert tr.subtract([[0, 4]], []) == [(0, 4)]


def test_one_device_by_hand():
    device = tr.reduce_device(device_lines())
    assert device["program"] == "jit_train_step"
    # the window runs from the first execution's start to the last's:
    # two whole periods of 100 ms
    assert device["steps"] == 2
    assert device["window_s"] == pytest.approx(0.2)
    # each period is busy 0-80 and idle 80-100
    assert device["busy_s"] == pytest.approx(0.16)
    assert device["step_gap_median_ms"] == pytest.approx(20.0)
    assert device["step_gaps_ms"] == pytest.approx([20.0, 20.0])
    assert device["kernels"] == {"attn/bf16,f32": pytest.approx(0.04)}
    assert device["ops"]["%fusion.24 fusion f32[2048,50304]"] == (
        pytest.approx(0.06))
    assert not any("while" in name for name in device["ops"])
    # the all-gather takes 25 ms a step; the kernel covers 45-50 and
    # the fusion 60-70 of it, so 50-60 is exposed
    assert device["collective_s"] == pytest.approx(0.05)
    assert device["collective_exposed_s"] == pytest.approx(0.02)
    assert device["collective_async"] is True


def test_collective_time_is_read_under_one_definition():
    """The profiler writes the asynchronous line for some devices only.
    A device without it knows a collective only while it holds the
    core, so its ``collective_s`` is the exposed time; the share of
    time in flight is read from the devices that show it, never the
    larger of two definitions."""
    from benchmark.metrics import (
        collective_exposed_share,
        collective_time_share,
    )

    seen, blind = device_lines(), device_lines()
    blind[tr.ASYNC_LINE] = []
    # the blind device's core waits 28 ms for each done (50-78): more
    # than the 25 ms the collective is in flight on the other, so a
    # maximum over both definitions would report the wrong one
    blind[tr.OPS_LINE] = [
        (n, s, e + 18 * MS) if "all-gather-done" in n else
        (n, s + 18 * MS, e) if n == "fusion.2" else (n, s, e)
        for n, s, e in blind[tr.OPS_LINE]]
    reduced = tr.reduce({"/device:TPU:0": blind, "/device:TPU:1": seen})
    first, second = reduced["devices"]
    assert (first["collective_async"], second["collective_async"]) == (
        False, True)
    assert first["collective_exposed_s"] == pytest.approx(0.056)
    # its starts (an instant each, under the kernel) are all it adds
    assert first["collective_s"] == pytest.approx(0.056, abs=1e-5)
    assert [d["id"] for d in tr.collective_devices(reduced["devices"])] == [1]
    run = {"reduced_trace": reduced, "chips": 2}
    assert collective_time_share.read(run) == pytest.approx(25.0)
    # the exposed part is on every device's line: the worst of both
    assert collective_exposed_share.read(run) == pytest.approx(28.0)
    # a program without asynchronous collectives: every device counts
    for lines in (seen, blind):
        lines[tr.ASYNC_LINE] = []
    devices = tr.reduce(
        {"/device:TPU:0": blind, "/device:TPU:1": seen})["devices"]
    assert len(tr.collective_devices(devices)) == 2


def test_whole_trace_names_gaps_by_the_host_event_over_them():
    planes = {
        "/device:TPU:0": device_lines(),
        "/device:TPU:1": device_lines(),
        "/host:CPU": {"python": [
            ev("$worker.py:1 run", -100, 400),
            ev("$worker.py:2 _after_train_batch", 79, 99),
            ev("$trainer.py:3 observe", 185, 192),
        ]},
        "/host:metadata": {},
    }
    reduced = tr.reduce(planes)
    assert [d["id"] for d in reduced["devices"]] == [0, 1]
    assert reduced["steps"] == 2
    assert reduced["window_s"] == pytest.approx(0.2)
    assert reduced["busy_s"] == pytest.approx(0.16)
    ops = dict(reduced["breakdown"]["device_ops"])
    assert ops["%fusion.24 fusion f32[2048,50304]"] == pytest.approx(0.06)
    assert len(reduced["breakdown"]["device_ops"]) <= 10
    gaps = reduced["breakdown"]["idle_gaps"]
    assert len(gaps) == 2 and all(s == pytest.approx(0.02) for _, s in gaps)
    # the first gap (80-100) lies under _after_train_batch; the second
    # (180-200) is under no event that covers half of it but ``run``
    assert sorted(name for name, _ in gaps) == [
        "$worker.py:1 run", "$worker.py:2 _after_train_batch"]


def test_no_device_plane_reduces_to_nothing():
    assert tr.reduce({"/host:CPU": {"python": [ev("f", 0, 1)]}}) is None


def load_recorded(name):
    path = os.path.join(HERE, "data", name)
    assert os.path.exists(path), "the recorded traces are part of the repo"
    return tr.reduce(tr.load(path))


@pytest.fixture(scope="module")
def recorded():
    return load_recorded("tiny_lm_1chip.xplane.pb.gz")


def test_recorded_one_chip_trace(recorded):
    """``data/tiny_lm_1chip.xplane.pb.gz``: six steps of a two-layer LM
    (d 256, 4 heads of 64, 2 x 1024 tokens) traced on one v5e chip in
    PR 22 through this harness. The numbers below are what this
    reduction gave then; a change to it has to explain any that move."""
    assert [d["id"] for d in recorded["devices"]] == [0]
    device = recorded["devices"][0]
    assert device["program"] == "jit_train_step"
    assert recorded["steps"] == device["steps"] == 5
    assert recorded["window_s"] == pytest.approx(0.026120069, rel=1e-6)
    assert recorded["busy_s"] == pytest.approx(0.003272503, rel=1e-6)
    assert device["step_gap_median_ms"] == pytest.approx(3.902395, rel=1e-6)
    assert len(device["step_gaps_ms"]) == 5
    # two layers x five steps of flash forward, dq and dkv
    assert device["kernels"] == {
        "attn/bf16,f32": pytest.approx(0.000517056, rel=1e-6),
        "attn/bf16": pytest.approx(0.000388278, rel=1e-6),
        "attn/bf16,bf16": pytest.approx(0.000497443, rel=1e-6),
    }
    assert device["collective_s"] == 0.0
    assert device["collective_exposed_s"] == 0.0
    breakdown = recorded["breakdown"]
    assert len(breakdown["device_ops"]) == 10
    name, seconds = breakdown["device_ops"][0]
    assert name == "%attn.6 custom-call bf16[8,1024,64]"
    assert seconds == pytest.approx(0.000258528, rel=1e-6)
    assert len(breakdown["idle_gaps"]) == 5
    assert all(
        isinstance(n, str) and len(n) <= 200 and s > 0
        for n, s in breakdown["idle_gaps"])
    # a device this small is idle most of the time, and the readers say so
    from benchmark.metrics import device_idle_share, flash_time_share

    run = {"reduced_trace": recorded}
    assert device_idle_share.read(run) == pytest.approx(87.4713, rel=1e-5)
    assert flash_time_share.read(run) == pytest.approx(42.8656, rel=1e-5)


def test_recorded_four_chip_trace():
    """``data/tiny_lm_4chip.xplane.pb.gz``: three steps of the same LM
    under ``--mesh fsdp=4`` (8 x 1024 tokens a step) traced on the
    four-chip host in PR 22: collectives, their exposed part, and the
    flash kernels under their ``shard_map`` name."""
    reduced = load_recorded("tiny_lm_4chip.xplane.pb.gz")
    assert [d["id"] for d in reduced["devices"]] == [0, 1, 2, 3]
    assert reduced["steps"] == 2
    assert reduced["window_s"] == pytest.approx(0.0145398445, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.00698122825, rel=1e-6)
    first, second = reduced["devices"][:2]
    # only the first device's plane carries asynchronous collectives
    # from start to done: time in flight is read there alone, and the
    # exposed part, which every plane has, agrees across the four
    assert [d["collective_async"] for d in reduced["devices"]] == [
        True, False, False, False]
    assert [d["id"] for d in tr.collective_devices(
        reduced["devices"])] == [0]
    exposed = [d["collective_exposed_s"] for d in reduced["devices"]]
    assert max(exposed) / min(exposed) < 1.01
    assert first["collective_s"] == pytest.approx(0.005573414, rel=1e-6)
    assert first["collective_exposed_s"] == pytest.approx(
        0.003601692, rel=1e-6)
    assert second["collective_s"] == second["collective_exposed_s"] == (
        pytest.approx(0.00359497, rel=1e-6))
    assert set(first["kernels"]) == {
        "shard_map/bf16,f32", "shard_map/bf16", "shard_map/bf16,bf16"}
    assert first["kernels"]["shard_map/bf16,f32"] == pytest.approx(
        0.000206825, rel=1e-6)
    assert reduced["breakdown"]["device_ops"][0][0] == (
        "%all-reduce.41 all-reduce bf16[8,1024,1024]")
    assert reduced["breakdown"]["idle_gaps"][0][0] == (
        "$spmd_trainer.py:252 shard_batch")
    from benchmark.metrics import (
        collective_exposed_share,
        collective_time_share,
        flash_time_share,
    )

    run = {"reduced_trace": reduced, "chips": 4}
    assert collective_time_share.read(run) == pytest.approx(
        38.354355, rel=1e-5)
    assert collective_exposed_share.read(run) == pytest.approx(
        24.785629, rel=1e-5)
    assert flash_time_share.read(run) == pytest.approx(8.0193, rel=1e-3)
    # one chip has no collectives to report
    assert collective_time_share.read(dict(run, chips=1)) is None
