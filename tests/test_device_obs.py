"""Device-runtime observability (ISSUE 18): recompile sentinels, HBM
accounting, cost-model attribution, and the fleet detectors they feed.

The load-bearing contract tested here: ``EDL_DEVICE_OBS=0`` returns
the RAW ``jax.jit`` product (provable inertness), and with the layer
on, every compile/cache-hit/recompile is counted with shape
provenance, journaled, and surfaced through TelemetryBlob ->
FleetMonitor -> /statusz."""

import json
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from elasticdl_tpu.observability import device as device_obs  # noqa: E402
from elasticdl_tpu.observability import events  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))


@pytest.fixture(autouse=True)
def _isolate_device_obs(monkeypatch):
    """Fresh wrapper registry/totals per test; default-on gate."""
    monkeypatch.delenv(device_obs.DEVICE_OBS_ENV, raising=False)
    device_obs.reset_for_tests()
    yield
    device_obs.reset_for_tests()


def _matmul(x):
    return x @ x.T


# ---------------------------------------------------------------------------
# the off switch: provable inertness


def test_disabled_returns_raw_jit_product(monkeypatch):
    monkeypatch.setenv(device_obs.DEVICE_OBS_ENV, "0")
    raw = jax.jit(_matmul)
    wrapped = device_obs.instrumented_jit(_matmul)
    # not a look-alike wrapper: the exact jax.jit product type, so the
    # factory-default program carries zero sentinel frames
    assert type(wrapped) is type(raw)
    assert not isinstance(wrapped, device_obs._InstrumentedJit)
    out = wrapped(jnp.ones((4, 4)))
    assert out.shape == (4, 4)
    assert device_obs.compile_stats() == {}


def test_disabled_telemetry_memory_and_transfers_inert(monkeypatch):
    monkeypatch.setenv(device_obs.DEVICE_OBS_ENV, "0")
    assert device_obs.telemetry() == {}
    assert device_obs.memory_snapshot() == {}
    device_obs.record_transfer("h2d", 1024)
    with device_obs.transfer_span("d2h", 2048):
        pass
    monkeypatch.delenv(device_obs.DEVICE_OBS_ENV)
    assert device_obs.telemetry()["h2d_bytes"] == 0
    assert device_obs.telemetry()["d2h_bytes"] == 0


# ---------------------------------------------------------------------------
# recompile sentinel: counting + provenance


def test_sentinel_counts_compiles_hits_and_recompiles():
    step = device_obs.instrumented_jit(_matmul, name="toy_step")
    x = jnp.ones((8, 4))
    step(x)            # compile 1 (warmup)
    step(x + 1.0)      # same signature: cache hit
    step(jnp.ones((16, 4)))  # new shape: recompile
    assert step.compiles == 2
    assert step.cache_hits == 1
    assert step.recompiles == 1
    stats = device_obs.compile_stats()["toy_step"]
    assert stats["compiles"] == 2 and stats["recompiles"] == 1
    assert stats["cache_hits"] == 1
    assert stats["compile_secs"] > 0
    tel = device_obs.telemetry()
    assert tel["xla_compiles"] == 2 and tel["xla_recompiles"] == 1
    assert tel["xla_compile_secs_total"] > 0


def test_recompile_provenance_names_the_changed_leaf():
    def step(state, batch):
        return state["w"] @ batch["x"].T

    fn = device_obs.instrumented_jit(step, name="prov_step")
    state = {"w": jnp.ones((4, 4))}
    fn(state, {"x": jnp.ones((8, 4))})
    fn(state, {"x": jnp.ones((9, 4))})  # only the batch leaf changed
    assert fn.recompiles == 1
    (change,) = fn.last_changed
    assert "'x'" in change
    assert "float32[8,4] -> float32[9,4]" in change
    # the unchanged state leaf must NOT appear in the diff
    assert "'w'" not in change


def test_recompile_journaled_with_signature(monkeypatch, tmp_path):
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(tmp_path))
    journal = events.configure("worker-0")
    try:
        fn = device_obs.instrumented_jit(_matmul, name="journal_step")
        fn(jnp.ones((4, 4)))
        fn(jnp.ones((5, 4)))
        with open(journal.path, encoding="utf-8") as f:
            records = [json.loads(line) for line in f if line.strip()]
    finally:
        events._reset_for_tests()
    recompiles = [r for r in records if r["event"] == "xla_recompile"]
    assert len(recompiles) == 1
    rec = recompiles[0]
    assert rec["fn"] == "journal_step" and rec["compiles"] == 2
    assert rec["changed"] and "float32[5,4]" in rec["changed"][0]
    assert any("float32[5,4]" in s for s in rec["signature"])


def test_numpy_args_count_h2d_bytes():
    fn = device_obs.instrumented_jit(_matmul, name="h2d_step")
    x = np.ones((8, 4), np.float32)
    fn(x)
    fn(x)  # the cached signature still uploads the host array
    tel = device_obs.telemetry()
    assert tel["h2d_bytes"] == 2 * x.nbytes


# ---------------------------------------------------------------------------
# cost-model attribution


def test_cost_flops_positive_after_compile():
    fn = device_obs.instrumented_jit(_matmul, name="cost_step")
    fn(jnp.ones((32, 32)))
    # 32x32 @ 32x32 matmul: 2*n^3 = 65536 flops; CPU cost_analysis
    # reports the exact program count
    assert fn.cost_flops > 0
    assert device_obs.compile_stats()["cost_step"]["cost_flops"] > 0


def test_cost_analysis_knob_off(monkeypatch):
    monkeypatch.setenv(device_obs.COST_ANALYSIS_ENV, "0")
    fn = device_obs.instrumented_jit(_matmul, name="no_cost_step")
    fn(jnp.ones((8, 8)))
    assert fn.compiles == 1
    assert fn.cost_flops == 0.0


# ---------------------------------------------------------------------------
# the program's Pallas kernels, by name

# lines as the TPU compiler leaves them (tests/test_flash_tpu_compile.py
# reads real ones): autodiff's wrappers around the name, a shard_map
# above it, XLA's own Mosaic kernel (no pallas_call: not one of ours)
KERNEL_HLO = """
  %jvp_flash_fwd_.1 = (bf16[32,2048,256]{2,1,0}, f32[32,1,2048]{2,1,0}) custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(flash_fwd)/pallas_call" stack_frame_id=5}
  %flash_fwd.2 = bf16[32,2048,256]{2,1,0} custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/checkpoint/shard_map/flash_fwd/pallas_call"}
  %bwd = (bf16[32,2048,256]{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(flash_bwd))/pallas_call" stack_frame_id=7}
  %gmm = bf16[512,1024]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/moe/experts/gmm/pallas_call"}
  %rd = bf16[512,1024]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot.3"}
  %add = f32[8]{0} add(%x, %y), metadata={op_name="jit(step)/flash_dq/pallas_call"}
"""


def test_pallas_kernels_counts_by_name():
    assert device_obs.pallas_kernels(KERNEL_HLO) == {
        "flash_fwd": 2, "flash_bwd": 1, "gmm": 1}
    assert device_obs.pallas_kernels("ENTRY %main { }") == {}


def test_compile_line_and_event_name_the_kernels(
        monkeypatch, tmp_path, caplog):
    import logging

    # a CPU program holds none: say what a TPU's would
    monkeypatch.setattr(
        device_obs, "pallas_kernels",
        lambda hlo: {"flash_fwd": 8, "flash_bwd": 8})
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(tmp_path))
    journal = events.configure("worker-0")
    try:
        with caplog.at_level(logging.INFO, logger=device_obs.logger.name):
            device_obs.instrumented_jit(_matmul, name="kernel_step")(
                jnp.ones((4, 4)))
        with open(journal.path, encoding="utf-8") as f:
            records = [json.loads(line) for line in f if line.strip()]
    finally:
        events._reset_for_tests()
    compiled = [r for r in records if r["event"] == "xla_compile"]
    assert compiled[0]["kernels"] == {"flash_fwd": 8, "flash_bwd": 8}
    line = [r.getMessage() for r in caplog.records
            if "xla compile #1 of kernel_step" in r.getMessage()][0]
    assert line.endswith("; kernels flash_fwd x8, flash_bwd x8")


# ---------------------------------------------------------------------------
# transfers


def test_transfer_span_counts_bytes():
    with device_obs.transfer_span("d2h", 4096):
        pass
    device_obs.record_transfer("h2d", 512)
    tel = device_obs.telemetry()
    assert tel["d2h_bytes"] == 4096
    assert tel["h2d_bytes"] == 512


def test_critical_path_maps_compile_and_transfer_segments():
    import critical_path

    assert critical_path.segment_of("compile") == "compile"
    assert critical_path.segment_of("transfer") == "transfer"


# ---------------------------------------------------------------------------
# device-memory accounting


def test_memory_snapshot_live_arrays_fallback(monkeypatch):
    monkeypatch.setenv(device_obs.HBM_LIMIT_ENV, "1000000")
    keep = jnp.ones((128, 128))  # noqa: F841 — pin one live buffer
    snap = device_obs.memory_snapshot()
    # CPU CI has no allocator stats; the live-array walk must carry
    assert snap["source"] in ("allocator", "live_arrays")
    assert snap["live_buffers"] >= 1
    assert snap["bytes_in_use"] >= keep.nbytes
    # the watermark is folded in the same poll, so peak >= in-use holds
    # on both sources
    assert snap["peak_bytes"] >= snap["bytes_in_use"]
    if snap["source"] == "live_arrays":
        assert snap["limit_bytes"] == 1000000


def test_telemetry_carries_memory_fields():
    keep = jnp.ones((64, 64))  # noqa: F841
    tel = device_obs.telemetry()
    assert tel["hbm_bytes_in_use"] > 0
    assert tel["hbm_peak_bytes"] >= tel["hbm_bytes_in_use"]
    assert tel["device_live_buffers"] >= 1


# ---------------------------------------------------------------------------
# trainer bridge: cost props feed the worker MFU gauge


def test_trainer_cost_props_reflect_sentinel():
    class FakeStep:
        cost_flops = 3.5e9
        cost_bytes = 1.2e6

    from elasticdl_tpu.worker.trainer import JaxTrainer

    trainer = JaxTrainer.__new__(JaxTrainer)
    trainer._train_step = FakeStep()
    assert trainer.cost_step_flops == 3.5e9
    assert trainer.cost_step_bytes == 1.2e6


# ---------------------------------------------------------------------------
# fleet detectors (synthetic blobs, the test_observability idiom)


def _blob(**kw):
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    return pb.TelemetryBlob(role="worker-0", **kw)


def _fleet(**kw):
    from elasticdl_tpu.master.fleet import FleetMonitor

    defaults = dict(
        straggler_factor=3.0, dead_air_secs=600.0,
        stuck_round_secs=600.0, version_lag_max=1e9,
        recompile_storm_min=3.0, recompile_storm_secs=0.2,
    )
    defaults.update(kw)
    return FleetMonitor(**defaults)


def test_recompile_storm_raises_and_self_clears():
    import time

    fleet = _fleet()
    fleet.observe(0, _blob(xla_recompiles=0, xla_compiles=1))
    assert fleet.evaluate() == []  # baseline observation marks nothing
    fleet.observe(0, _blob(
        xla_recompiles=4, xla_compiles=5, xla_compile_secs_total=3.1,
    ))
    firing = fleet.evaluate()
    assert [a["alert"] for a in firing] == ["recompile_storm"]
    assert firing[0]["recompiles_in_window"] == 4
    assert firing[0]["xla_recompiles"] == 4
    # the recency window (0.2 s) drains -> the alert self-clears
    time.sleep(0.3)
    assert fleet.evaluate() == []


def test_recompile_counter_regression_is_a_restart_not_a_storm():
    fleet = _fleet()
    fleet.observe(0, _blob(xla_recompiles=5))
    # the counter went BACKWARDS: a restarted worker, baseline resets
    fleet.observe(0, _blob(xla_recompiles=1))
    assert fleet.evaluate() == []
    # +1 from the new baseline stays under the min=3 floor
    fleet.observe(0, _blob(xla_recompiles=2))
    assert fleet.evaluate() == []


def test_hbm_pressure_fires_over_limit_and_never_without_one():
    fleet = _fleet(hbm_pressure_max=0.9)
    fleet.observe(0, _blob(
        hbm_bytes_in_use=95, hbm_limit_bytes=100,
    ))
    firing = fleet.evaluate()
    assert [a["alert"] for a in firing] == ["hbm_pressure"]
    assert firing[0]["fraction"] == pytest.approx(0.95)
    # back under the line -> clears
    fleet.observe(0, _blob(hbm_bytes_in_use=10, hbm_limit_bytes=100))
    assert fleet.evaluate() == []
    # limit 0 = unknown capacity: never fires
    fleet.observe(1, _blob(hbm_bytes_in_use=10**15, hbm_limit_bytes=0))
    assert fleet.evaluate() == []


def test_statusz_snapshot_carries_device_section():
    fleet = _fleet()
    fleet.observe(0, _blob(
        xla_compiles=7, xla_recompiles=2, xla_compile_secs_total=1.25,
        hbm_bytes_in_use=512, hbm_peak_bytes=1024,
        device_live_buffers=3, cost_step_flops=2.5e12,
        h2d_bytes=100, d2h_bytes=50,
    ))
    snap = fleet.snapshot()
    dev = snap["device"]["worker-0"]
    assert dev["xla_compiles"] == 7 and dev["xla_recompiles"] == 2
    assert dev["xla_compile_secs_total"] == 1.25
    assert dev["hbm_peak_bytes"] == 1024
    assert dev["cost_step_flops"] == 2.5e12
    assert dev["h2d_bytes"] == 100 and dev["d2h_bytes"] == 50
    assert snap["thresholds"]["recompile_storm_min"] == 3.0
