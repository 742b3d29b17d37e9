"""ISSUE 6 device-resident embedding tier, end to end: fused
gather/scatter-apply against the PS store's update math,
promotion-after-k-hits, LFU/TTL demotion with eviction writeback,
miss-path pull parity (never-promote config bit-exact vs tier-off),
flush-before-checkpoint ordering, PS-restart flush-then-invalidate,
the push_embedding_rows writeback RPC over live gRPC, and the
Zipfian hit-rate acceptance bound."""

import numpy as np
import pytest

from elasticdl_tpu.models import deepfm
from elasticdl_tpu.ops import embedding_tier as tier_ops
from elasticdl_tpu.ps.local_client import LocalPSClient
from elasticdl_tpu.train.device_tier import (
    DeviceEmbeddingTier,
    DeviceTierConfig,
    resolve_tier_config,
)
from elasticdl_tpu.train.sparse import SparseTrainer

FIELDS = 4
BATCH = 32
VOCAB = 1000


def make_batches(n, seed=0, zipf=1.6, vocab=VOCAB, offset=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        ids = (rng.zipf(zipf, size=(BATCH, FIELDS)) % vocab + offset)
        out.append({
            "features": {"ids": ids.astype(np.int64)},
            "labels": (ids.sum(1) % 2).astype(np.float32),
            "_mask": np.ones(BATCH, np.float32),
        })
    return out


def build_trainer(device_tier, seed=0, **kwargs):
    return SparseTrainer(
        model=deepfm.custom_model(),
        loss_fn=deepfm.loss,
        optimizer=deepfm.optimizer(),
        specs=deepfm.sparse_embedding_specs(
            num_features=FIELDS, batch_size=BATCH
        ),
        ps_client=LocalPSClient(seed=seed, opt_type="adam", lr=0.01),
        seed=seed,
        device_tier=device_tier,
        **kwargs,
    )


def small_config(**overrides):
    base = dict(
        capacity=256, promote_hits=2, ttl=100, stage_budget=64,
        opt_type="adam", opt_args={"lr": 0.01}, writeback_steps=8,
    )
    base.update(overrides)
    return DeviceTierConfig(**base)


# ---------------------------------------------------------------------
# fused kernels


def _rand_state(rng, alloc, dim, opt_type):
    state = tier_ops.init_table_state(alloc, dim, opt_type)
    import jax.numpy as jnp

    state["rows"] = jnp.asarray(rng.rand(alloc, dim).astype(np.float32))
    for key in list(state):
        if key.startswith("slot"):
            state[key] = jnp.asarray(
                rng.rand(alloc, dim).astype(np.float32) * 0.1
            )
    return state


def test_jnp_insert_gather_semantics():
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    state = _rand_state(rng, 9, 8, "adam")
    rows0 = np.asarray(state["rows"])
    slots = jnp.asarray(np.array([0, 3, -1, 5, -1], np.int32))
    miss = rng.rand(5, 8).astype(np.float32)
    ins_slots = jnp.asarray(np.array([7, 8], np.int32))  # 8 = scratch
    ins_rows = rng.rand(2, 8).astype(np.float32)
    ev = jnp.asarray(np.array([1, 8], np.int32))
    new_state, combined, evicted = tier_ops.fused_insert_gather(
        state, ins_slots, jnp.asarray(ins_rows), ev, slots,
        jnp.asarray(miss),
    )
    # victims read out BEFORE inserts land
    assert np.allclose(np.asarray(evicted)[0], rows0[1])
    # staged insert landed (and its opt state reset)
    assert np.allclose(np.asarray(new_state["rows"])[7], ins_rows[0])
    assert np.allclose(np.asarray(new_state["slot0"])[7], 0.0)
    # combined: hits from the table, misses from the pulled buffer
    out = np.asarray(combined)
    assert np.allclose(out[0], rows0[0])
    assert np.allclose(out[1], rows0[3])
    assert np.allclose(out[2], miss[2])
    assert np.allclose(out[3], rows0[5])


@pytest.mark.parametrize("opt_type", ["sgd", "momentum", "adagrad", "adam"])
def test_jnp_scatter_apply_matches_store_math(opt_type):
    """The in-device optimizer step must track the PS store's update
    math — a row trains the same whichever tier holds it."""
    import jax.numpy as jnp

    from elasticdl_tpu.ps.embedding_store import NumpyEmbeddingStore

    rng = np.random.RandomState(1)
    dim, n = 6, 4
    store = NumpyEmbeddingStore(seed=0)
    store.set_optimizer(opt_type, lr=0.05)
    store.create_table("t", dim, init_scale=0.1)
    ids = np.arange(n, dtype=np.int64)
    init_rows = store.lookup("t", ids)  # materialize

    state = tier_ops.init_table_state(n + 1, dim, opt_type)
    state["rows"] = jnp.asarray(
        np.concatenate([init_rows, np.zeros((1, dim), np.float32)])
    )
    slots = jnp.asarray(np.arange(n, dtype=np.int32))
    for _ in range(3):  # multi-step: exercises slot state + step counts
        grads = rng.rand(n, dim).astype(np.float32)
        store.push_gradients("t", ids, grads)
        state = tier_ops.fused_scatter_apply(
            state, slots, jnp.asarray(grads), opt_type=opt_type,
            lr=0.05,
        )
    np.testing.assert_allclose(
        np.asarray(state["rows"])[:n], store.lookup("t", ids),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------
# tier policy


def test_promotion_after_k_hits():
    """An id is promoted only after ``promote_hits`` sightings, and is
    a hit from its promotion step on."""
    client = LocalPSClient(seed=0)
    client.push_embedding_table_infos([("t", 4, "0.05")])
    spec = type("S", (), {"name": "t", "dim": 4})()
    tier = DeviceEmbeddingTier(
        [spec], client, small_config(promote_hits=3, writeback_steps=0)
    )
    ids = np.array([5, 9], np.int64)
    rows = np.zeros((2, 4), np.float32)
    for sighting in range(1, 4):
        tier.advance()
        slots = tier.lookup("t", ids)
        assert (slots < 0).all() or sighting > 3
        promoted, _ = tier.admit("t", ids, rows)
        if sighting < 3:
            assert not promoted.any(), sighting
        else:
            assert promoted.all()
    tier.advance()
    assert (tier.lookup("t", ids) >= 0).all()
    stats = tier.stats()
    assert stats["hits"] == 2 and stats["misses"] == 6
    tier.close()


def test_lfu_pressure_evicts_coldest():
    """Promotion into a full tier evicts the least-frequently-used
    idle slot; the victim's id misses afterwards."""
    client = LocalPSClient(seed=0)
    client.push_embedding_table_infos([("t", 4, "0.05")])
    spec = type("S", (), {"name": "t", "dim": 4})()
    tier = DeviceEmbeddingTier(
        [spec], client,
        small_config(capacity=2, promote_hits=1, writeback_steps=0,
                     ttl=0),
    )
    rows1 = np.ones((2, 4), np.float32)

    tier.advance()
    tier.lookup("t", np.array([1, 2], np.int64))
    tier.admit("t", np.array([1, 2], np.int64), rows1)  # fills the tier
    # heat up id 1 (two more hits); id 2 stays cold
    for _ in range(2):
        tier.advance()
        assert (tier.lookup("t", np.array([1], np.int64)) >= 0).all()
    tier.advance()
    tier.lookup("t", np.array([7], np.int64))
    promoted, _ = tier.admit(
        "t", np.array([7], np.int64), rows1[:1]
    )
    assert promoted.all()
    tier.advance()
    slots = tier.lookup("t", np.array([1, 2, 7], np.int64))
    assert slots[0] >= 0, "hot id 1 must survive LFU pressure"
    assert slots[1] < 0, "cold id 2 must be the LFU victim"
    assert slots[2] >= 0
    assert tier.stats()["evictions"] == 1
    tier.close()


def test_ttl_demotion_writes_back():
    """Rows idle past the TTL are demoted, and a dirty victim's device
    value reaches the PS store (the eviction writeback)."""
    batches = make_batches(3, seed=1)
    trainer = build_trainer(
        small_config(capacity=32, promote_hits=1, ttl=10,
                     writeback_steps=0, stage_budget=16)
    )
    state = None
    for batch in batches:
        state, _ = trainer.train_step(state, batch)
    tier = trainer.device_tier
    hot_ids, hot_rows = tier.table_rows("deepfm_emb")
    assert hot_ids.size > 0
    # disjoint id range: the hot set idles past the TTL (sweep cadence
    # is every 64 clocks)
    for batch in make_batches(80, seed=9, offset=VOCAB + 10):
        state, _ = trainer.train_step(state, batch)
    tier.drain_writebacks()
    assert tier.stats()["evictions"] > 0
    remaining = set(tier.table_rows("deepfm_emb")[0].tolist())
    evicted = [
        (i, row) for i, row in zip(hot_ids, hot_rows)
        if int(i) not in remaining
    ]
    assert evicted, "TTL sweep demoted nothing"
    store = trainer.preparer._ps.store
    for id_, row in evicted[:8]:
        np.testing.assert_allclose(
            store.lookup("deepfm_emb", np.array([id_]))[0], row,
            rtol=1e-6,
        )
    trainer.close()


# ---------------------------------------------------------------------
# trainer integration


def test_ttl_sweep_evicts_clean_flushes_dirty_first():
    """TTL demotion policy after the ordering-barrier review: idle
    CLEAN slots evict directly (their PS copy is exact); idle DIRTY
    slots first force a flush (becoming clean), then a later sweep
    evicts them — a dirty idle slot is never evicted with its
    writeback invisible to the miss-path barrier."""
    client = LocalPSClient(seed=0)
    client.push_embedding_table_infos([("t", 4, "0.05")])
    spec = type("S", (), {"name": "t", "dim": 4})()
    tier = DeviceEmbeddingTier(
        [spec], client,
        small_config(capacity=8, promote_hits=1, ttl=16,
                     writeback_steps=0),
    )
    ids = np.array([5], np.int64)
    rows = client.pull_embedding_vectors("t", ids)
    tier.advance()
    tier.lookup("t", ids)
    tier.admit("t", ids, rows)
    tier.combine("t", np.full((1,), -1, np.int32),
                 np.zeros((1, 4), np.float32))  # land the insert
    # slot is dirty (dirty-from-birth): the first sweep past the TTL
    # must NOT evict it, only force a flush
    for _ in range(70):
        tier.advance()
    assert tier.stats()["evictions"] == 0
    assert tier._force_flush
    tier.maybe_periodic_writeback()  # forced despite writeback_steps=0
    tier.drain_writebacks()
    # now clean: the next sweep (clock multiple of 64) evicts it
    for _ in range(70):
        tier.advance()
    assert tier.stats()["evictions"] == 1
    tier.advance()
    assert (tier.lookup("t", ids) < 0).all()
    tier.close()


def test_never_promote_bit_exact_vs_tier_off():
    """Miss-path parity: with the tier engaged but promotion
    unreachable every id takes the pull/push path — losses must be
    BIT-EXACT vs the tier-off trainer (and by extension vs the
    pre-tier code, which is the same code path)."""
    never = DeviceTierConfig(
        capacity=64, promote_hits=10 ** 9, ttl=0, stage_budget=16,
        writeback_steps=0,
    )
    t_off, t_on = build_trainer(False), build_trainer(never)
    s_off = s_on = None
    for batch in make_batches(8, seed=3):
        s_off, loss_off = t_off.train_step(s_off, batch)
        s_on, loss_on = t_on.train_step(s_on, batch)
        assert float(loss_off) == float(loss_on)
    t_off.close()
    t_on.close()


def test_env_tier_disabled_is_none(monkeypatch):
    monkeypatch.delenv("EDL_DEVICE_TIER", raising=False)
    assert resolve_tier_config(None) is None
    monkeypatch.setenv("EDL_DEVICE_TIER", "0")
    assert resolve_tier_config(None) is None
    monkeypatch.setenv("EDL_DEVICE_TIER", "1")
    monkeypatch.setenv("EDL_DEVICE_TIER_ROWS", "123")
    config = resolve_tier_config(None)
    assert config is not None and config.capacity == 123


def test_flush_before_checkpoint_parity():
    """flush() (the worker checkpoint/export boundary) lands every
    tier-held update in the PS store: resident rows == store rows."""
    trainer = build_trainer(small_config())
    state = None
    for batch in make_batches(25, seed=4):
        state, _ = trainer.train_step(state, batch)
    trainer.flush_device_tier()
    store = trainer.preparer._ps.store
    for table in ("deepfm_emb", "deepfm_linear"):
        ids, rows = trainer.device_tier.table_rows(table)
        assert ids.size > 0
        np.testing.assert_allclose(
            rows, store.lookup(table, ids), rtol=1e-6, atol=1e-7
        )
    trainer.close()


def test_stream_flush_parity_and_hit_rate():
    """The pipelined train_stream path (lookahead prepare thread +
    fold-time applies): flush parity holds, and a Zipfian stream's
    hit rate clears the acceptance bound (>= 0.9) once warm."""
    trainer = build_trainer(
        small_config(capacity=512, promote_hits=2),
        cache_staleness=4,
    )
    batches = make_batches(40, seed=5, zipf=2.0)
    for _ in trainer.train_stream(None, batches, push_interval=2):
        pass
    trainer.flush_device_tier()
    store = trainer.preparer._ps.store
    for table in ("deepfm_emb", "deepfm_linear"):
        ids, rows = trainer.device_tier.table_rows(table)
        np.testing.assert_allclose(
            rows, store.lookup(table, ids), rtol=1e-6, atol=1e-7
        )
    # warm-phase hit rate: measure the tail (cold-start misses
    # excluded by construction — reset tallies, then stream more)
    tier = trainer.device_tier
    tier.hits = tier.misses = 0
    for _ in trainer.train_stream(
        None, make_batches(20, seed=6, zipf=2.0), push_interval=2
    ):
        pass
    assert tier.stats()["hit_rate"] >= 0.9, tier.stats()
    trainer.close()


def test_ps_restart_flush_then_invalidate():
    """Restored-stamp change: the tier's rows (newer than anything the
    PS restored) are written back, then the map invalidates and
    repopulates — the PR 4 chaos contract's no-lost-updates order."""
    trainer = build_trainer(
        small_config(capacity=256, promote_hits=1, writeback_steps=0)
    )
    state = None
    batches = make_batches(16, seed=7)
    for batch in batches[:8]:
        state, _ = trainer.train_step(state, batch)
    tier = trainer.device_tier
    pre_ids, pre_rows = tier.table_rows("deepfm_emb")
    assert pre_ids.size > 0
    store = trainer.preparer._ps.store
    # the store is stale for resident rows before the flush
    stale = store.lookup("deepfm_emb", pre_ids)
    assert not np.allclose(stale, pre_rows)
    epoch0 = tier.epoch
    trainer.preparer._on_ps_restart(0)  # restored-stamp change path
    assert tier.epoch == epoch0 + 1
    # resident map must already be invalid (host half, immediate)
    assert (tier.lookup("deepfm_emb", pre_ids) < 0).all()
    # next step processes the device half: writeback then reset
    for batch in batches[8:]:
        state, _ = trainer.train_step(state, batch)
    tier.drain_writebacks()
    post = store.lookup("deepfm_emb", pre_ids)
    # every pre-restart resident row's latest value reached the store
    # (later training may have updated some again via the normal path;
    # assert none regressed to the stale pre-flush value)
    for k in range(pre_ids.size):
        assert not np.allclose(post[k], stale[k]) or np.allclose(
            pre_rows[k], stale[k]
        ), int(pre_ids[k])
    trainer.close()


def test_restart_with_staged_promotions_writes_host_values():
    """A PS relaunch marked between admit (promotion staged, slot
    dirty-from-birth) and combine (insert lands) must write the staged
    HOST row back — a device read of the never-landed slot would push
    zeros over the restored PS row (review finding)."""
    client = LocalPSClient(seed=0)
    client.push_embedding_table_infos([("t", 4, "0.05")])
    spec = type("S", (), {"name": "t", "dim": 4})()
    tier = DeviceEmbeddingTier(
        [spec], client,
        small_config(capacity=8, promote_hits=1, writeback_steps=0),
    )
    ids = np.array([3, 9], np.int64)
    rows = client.pull_embedding_vectors("t", ids)  # materialize
    staged_rows = rows + 1.0  # pretend the tier's values moved on
    tier.advance()
    tier.lookup("t", ids)
    promoted, _ = tier.admit("t", ids, staged_rows)
    assert promoted.all()
    # relaunch strikes BEFORE any combine lands the staged insert
    tier.mark_restart()
    tier._process_restart()
    tier.drain_writebacks()
    np.testing.assert_allclose(
        client.pull_embedding_vectors("t", ids), staged_rows, rtol=1e-6
    )
    tier.close()


def test_stale_step_context_reprepares():
    """A batch prepared before a PS relaunch must not combine with its
    stale slot context — the trainer re-prepares it (tier epoch
    guard)."""
    trainer = build_trainer(
        small_config(capacity=128, promote_hits=1, writeback_steps=0)
    )
    state = None
    batches = make_batches(6, seed=8)
    for batch in batches[:4]:
        state, _ = trainer.train_step(state, batch)
    # prepare the next batch, THEN signal the relaunch (the async-push
    # thread can interleave exactly like this)
    prepared, pull_info = trainer.preparer.prepare(batches[4])
    trainer.preparer._on_ps_restart(0)
    assert pull_info.tier_epoch != trainer.device_tier.epoch
    # train_step re-prepares internally; the step must still succeed
    state, loss = trainer.train_step(state, batches[5])
    assert np.isfinite(float(loss))
    trainer.close()


# ---------------------------------------------------------------------
# writeback RPC over live gRPC


def test_push_embedding_rows_grpc_roundtrip():
    from elasticdl_tpu.common.grpc_utils import (
        build_server,
        find_free_port,
    )
    from elasticdl_tpu.proto.services import (
        add_pserver_servicer_to_server,
    )
    from elasticdl_tpu.ps.embedding_store import NumpyEmbeddingStore
    from elasticdl_tpu.ps.servicer import PserverServicer
    from elasticdl_tpu.worker.ps_client import PSClient

    servers, addrs = [], []
    for ps_id in range(2):
        store = NumpyEmbeddingStore(seed=ps_id)
        store.set_optimizer("adam", lr=0.01)
        server = build_server()
        add_pserver_servicer_to_server(
            PserverServicer(store, ps_id=ps_id), server
        )
        port = find_free_port()
        server.add_insecure_port("localhost:%d" % port)
        server.start()
        servers.append(server)
        addrs.append("localhost:%d" % port)
    try:
        client = PSClient(addrs)
        client.push_embedding_table_infos([("t", 4, "0.05")])
        ids = np.arange(10, dtype=np.int64)
        client.pull_embedding_vectors("t", ids)  # materialize
        values = np.arange(40, dtype=np.float32).reshape(10, 4)
        client.push_embedding_rows({"t": (ids, values)})
        np.testing.assert_array_equal(
            client.pull_embedding_vectors("t", ids), values
        )
        # id-mod sharding: each shard holds only its slice
        assert servers  # both shards served the overwrite above
    finally:
        for server in servers:
            server.stop(0)


def test_telemetry_blob_tier_fields_reach_statusz():
    from elasticdl_tpu.master.fleet import FleetMonitor
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    monitor = FleetMonitor()
    monitor.observe(0, pb.TelemetryBlob(
        role="worker-0", tier_hit_rate=0.93, tier_occupancy=0.5,
        tier_hits=930, tier_misses=70, tier_evictions=3,
    ))
    snapshot = monitor.snapshot()
    entry = snapshot["fleet"]["worker-0"]
    assert entry["tier_hit_rate"] == pytest.approx(0.93, abs=1e-4)
    assert entry["tier_hits"] == 930
    assert entry["tier_evictions"] == 3
