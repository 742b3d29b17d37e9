"""Fast device-tier A-B for CI tier 1f (ISSUE 6).

DeepFM CTR steps/s with the device-resident embedding tier on vs off
over a synthetic Zipfian id stream, against an in-process PS whose
pull/push/writeback legs charge an EMULATED per-row wire cost
(default 2 us/row + 1 ms/call, the ballpark of the PR 5 measured
deepfm wire path: ~20 steps/s at ~10k rows/step each way). Without
the emulation an in-process A-B is a strawman — there is no gRPC wire
to skip, which is the entire point of the tier — while spawning live
PS processes is too slow for a CI smoke.

Absolute numbers are REPORT-ONLY (journaled by scripts/ci.sh, never
gated — timings flake across boxes); the script hard-fails only when

- the tier-on run measures >3x SLOWER than tier-off in the same run
  (a real fast-path regression, not noise — the wire-micro lane's
  discipline; with the wire model the tier normally WINS, so 3x has
  wide margin), or
- the warm-phase hit rate falls below 0.9 on the Zipfian stream (the
  ISSUE 6 acceptance bound: promotion/demotion stopped keeping the
  hot set resident), or
- the tier run's flushed rows diverge from the PS store (writeback
  correctness, not perf).
"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

PER_ROW_SECS = 2e-6
PER_CALL_SECS = 1e-3


class WireCostClient:
    """LocalPSClient proxy charging the emulated wire cost per leg.

    Every row crossing the emulated wire — pulled, pushed, or written
    back — pays ``per_row``; every RPC-shaped call pays ``per_call``.
    The tier's writebacks pay like everything else: its win must come
    from hit rows genuinely skipping the wire, not from an accounting
    hole."""

    def __init__(self, inner, per_row=PER_ROW_SECS,
                 per_call=PER_CALL_SECS):
        self._inner = inner
        self._per_row = per_row
        self._per_call = per_call
        self.store = inner.store

    @property
    def ps_num(self):
        return self._inner.ps_num

    def _charge(self, rows):
        time.sleep(self._per_call + self._per_row * rows)

    def push_embedding_table_infos(self, infos):
        return self._inner.push_embedding_table_infos(infos)

    def push_dense_init(self, params, version=0):
        return self._inner.push_dense_init(params, version)

    def pull_dense_init(self, version=-1):
        return self._inner.pull_dense_init(version)

    def pull_embedding_vectors(self, name, ids):
        self._charge(np.asarray(ids).size)
        return self._inner.pull_embedding_vectors(name, ids)

    def pull_embedding_batch(self, ids_by_table):
        self._charge(sum(
            np.asarray(ids).size for ids in ids_by_table.values()
        ))
        return self._inner.pull_embedding_batch(ids_by_table)

    def push_gradients(self, grads_by_table, **kwargs):
        self._charge(sum(
            np.asarray(ids).size
            for _, ids in grads_by_table.values()
        ))
        return self._inner.push_gradients(grads_by_table, **kwargs)

    def push_embedding_rows(self, rows_by_table):
        self._charge(sum(
            np.asarray(ids).size
            for ids, _ in rows_by_table.values()
        ))
        return self._inner.push_embedding_rows(rows_by_table)


def make_batches(n, batch=512, fields=16, vocab=10_000, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        # Zipf over a BOUNDED vocab (the %-fold wraps the tail back
        # onto the universe): the whole working set fits the 32k-row
        # tier, so the warm-phase hit rate measures whether the
        # promotion policy actually captured it (>= 0.9 bound below).
        # An unbounded tail would cap unique-id hit rate around the
        # singleton fraction regardless of policy — hit rate counts
        # unique ids, the deduped rows that actually cross the wire.
        ids = (rng.zipf(1.3, size=(batch, fields)) % vocab).astype(
            np.int64
        )
        out.append({
            "features": {"ids": ids},
            "labels": rng.randint(0, 2, batch).astype(np.float32),
            "_mask": np.ones(batch, np.float32),
        })
    return out


def run(device_tier, batches, warmup=10):
    from elasticdl_tpu.models import deepfm
    from elasticdl_tpu.ps.local_client import LocalPSClient
    from elasticdl_tpu.train.sparse import SparseTrainer

    trainer = SparseTrainer(
        model=deepfm.custom_model(),
        loss_fn=deepfm.loss,
        optimizer=deepfm.optimizer(),
        specs=deepfm.sparse_embedding_specs(
            num_features=16, batch_size=256
        ),
        ps_client=WireCostClient(
            LocalPSClient(seed=0, opt_type="adam", lr=0.001)
        ),
        seed=0,
        device_tier=device_tier,
    )
    state = None
    start = None
    for i, batch in enumerate(batches):
        state, loss = trainer.train_step(state, batch)
        if i + 1 == warmup:
            float(loss)
            if trainer.device_tier is not None:
                # measure the warm phase: cold-start promotion misses
                # are start-up cost, not steady-state hit rate
                trainer.device_tier.hits = 0
                trainer.device_tier.misses = 0
            start = time.perf_counter()
    elapsed = time.perf_counter() - start
    steps_per_sec = (len(batches) - warmup) / elapsed
    stats = None
    if trainer.device_tier is not None:
        stats = trainer.device_tier.stats()
        trainer.flush_device_tier()
        store = trainer.preparer._ps.store
        for table in ("deepfm_emb", "deepfm_linear"):
            ids, rows = trainer.device_tier.table_rows(table)
            if ids.size and not np.allclose(
                rows, store.lookup(table, ids), rtol=1e-5, atol=1e-6
            ):
                print(
                    "bench_device_tier: FAIL %s flush parity" % table,
                    file=sys.stderr,
                )
                sys.exit(1)
    trainer.close()
    return steps_per_sec, stats


def main():
    from elasticdl_tpu.train.device_tier import DeviceTierConfig

    batches = make_batches(45)
    tier_off, _ = run(False, batches, warmup=15)
    config = DeviceTierConfig(
        capacity=32768, promote_hits=1, ttl=4096, stage_budget=2048,
        opt_type="adam", opt_args={"lr": 0.001}, writeback_steps=256,
    )
    tier_on, stats = run(config, batches, warmup=15)
    result = {
        "deepfm_ctr_steps_per_sec_device_tier": round(tier_on, 3),
        "deepfm_ctr_steps_per_sec_tier_off": round(tier_off, 3),
        "device_tier_speedup": round(tier_on / tier_off, 3),
        "deepfm_device_tier_hit_rate": round(stats["hit_rate"], 4),
        "device_tier_occupancy": round(stats["occupancy"], 4),
        "device_tier_evictions": stats["evictions"],
        "emulated_wire_us_per_row": PER_ROW_SECS * 1e6,
    }
    print(json.dumps(result))
    if tier_on * 3.0 < tier_off:
        print(
            "bench_device_tier: FAIL tier-on (%.2f steps/s) is >3x "
            "slower than tier-off (%.2f)" % (tier_on, tier_off),
            file=sys.stderr,
        )
        sys.exit(1)
    if stats["hit_rate"] < 0.9:
        print(
            "bench_device_tier: FAIL warm hit rate %.3f < 0.9 on a "
            "Zipfian stream — promotion/demotion policy regression"
            % stats["hit_rate"],
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
