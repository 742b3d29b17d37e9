"""Device-resident embedding tier: the HBM hot set over the host PS.

ROADMAP item 1: after the PR 5 wire overhaul every embedding row still
crossed host RAM and gRPC each step — the PS sat on the hot path for
100% of traffic. CTR id streams are Zipfian (the deepfm id-buffer
already banks on it), so the fix is a two-tier store:

- **device tier** (this module + ops/embedding_tier.py): a
  fixed-capacity slot table per embedding table resident in
  accelerator memory, row-wise shardable over the mesh's ``ep`` axis.
  Hit rows are gathered on device and their gradients are applied to
  their slots by the fused scatter-apply kernel — no host round trip,
  no PS RPC, no wire bytes.
- **spillover tier**: the existing PS, reached only on miss through
  the PR 5 fused ``pull_embedding_batch`` path (and the HotRowCache,
  which generalizes into the miss-path client). Evicted and dirty
  rows write back asynchronously as raw row values
  (``push_embedding_rows``), riding the same single-background-thread
  discipline as ``EDL_ASYNC_PUSH``.

Promotion/demotion runs on the host from the per-step id stream:
an id is promoted after ``promote_hits`` sightings (misses), demoted by
LFU pressure (promotion needs a slot) or TTL idleness (vocab drift).
All bookkeeping is vectorized numpy over sorted id arrays — a per-id
Python loop here is exactly the anti-pattern the ``perf-host-gather``
edlint rule flags.

Consistency contract (docs/PERFORMANCE.md "Device tier"): resident
rows are authoritative; the PS copy of a hot row is stale by at most
``writeback_steps``. ``flush()`` (worker checkpoint/export boundaries)
writes every dirty row back before the boundary proceeds. A PS
relaunch (restored-stamp change, PR 4) triggers flush-then-invalidate:
the tier's rows — strictly newer than anything the PS restored — are
written back first, then the tier drops its map and repopulates, so a
PS SIGKILL loses no tier-held updates. With ``EDL_DEVICE_TIER=0`` (the
default) none of this code runs and training is bit-exact with the
PS-only path.

Sync-PS caveat: the tier applies hit gradients outside the PS's
round/version accounting, so it composes with the ASYNC PS (and the
in-process LocalPSClient); the lockstep/sync trainers leave it off.
"""

import concurrent.futures
import threading
from dataclasses import dataclass, field

import numpy as np

from elasticdl_tpu.common.env_utils import env_int, env_str
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import metrics as obs_metrics
from elasticdl_tpu.ops import embedding_tier as tier_ops

logger = _logger_factory("elasticdl_tpu.train.device_tier")

ENABLE_ENV = "EDL_DEVICE_TIER"
ROWS_ENV = "EDL_DEVICE_TIER_ROWS"
PROMOTE_ENV = "EDL_DEVICE_TIER_PROMOTE"
TTL_ENV = "EDL_DEVICE_TIER_TTL"
STAGE_ENV = "EDL_DEVICE_TIER_STAGE"
OPT_ENV = "EDL_DEVICE_TIER_OPT"
OPT_ARGS_ENV = "EDL_DEVICE_TIER_OPT_ARGS"
WRITEBACK_ENV = "EDL_DEVICE_TIER_WRITEBACK"


@dataclass
class DeviceTierConfig:
    """Knobs, all overridable from the environment (docs/PERFORMANCE.md
    has the operator table)."""

    capacity: int = 65536        # resident rows per table
    promote_hits: int = 2        # sightings before an id is promoted
    ttl: int = 4096              # idle prepares before TTL demotion
    stage_budget: int = 1024     # max promotions/demotions per step
    opt_type: str = "adam"       # tier-side sparse optimizer
    opt_args: dict = field(default_factory=dict)
    writeback_steps: int = 256   # dirty-row writeback cadence (steps)

    @classmethod
    def from_env(cls):
        """None when the tier is disabled (EDL_DEVICE_TIER unset/0)."""
        from elasticdl_tpu.common.args import bool_flag

        raw = env_str(ENABLE_ENV, "").strip()
        if not raw or not bool_flag(raw):
            return None
        config = cls()
        config.capacity = env_int(ROWS_ENV, config.capacity)
        config.promote_hits = env_int(PROMOTE_ENV, config.promote_hits)
        config.ttl = env_int(TTL_ENV, config.ttl)
        config.stage_budget = env_int(STAGE_ENV, config.stage_budget)
        config.opt_type = env_str(OPT_ENV, config.opt_type).lower()
        raw_args = env_str(OPT_ARGS_ENV, "")
        if raw_args:
            from elasticdl_tpu.train.optimizers import parse_opt_args

            config.opt_args = {
                k: float(v) for k, v in parse_opt_args(raw_args).items()
            }
        config.writeback_steps = env_int(
            WRITEBACK_ENV, config.writeback_steps
        )
        return config


def resolve_tier_config(device_tier):
    """Normalize SparseTrainer's ``device_tier`` argument: None reads
    the environment, False disables, True takes env-tuned defaults, a
    DeviceTierConfig passes through."""
    if device_tier is None:
        return DeviceTierConfig.from_env()
    if device_tier is False:
        return None
    if device_tier is True:
        return DeviceTierConfig.from_env() or DeviceTierConfig()
    if isinstance(device_tier, DeviceTierConfig):
        return device_tier
    raise TypeError(
        "device_tier must be None/bool/DeviceTierConfig (got %r)"
        % (device_tier,)
    )


class _TableTier:
    """Host bookkeeping + device state for one table's hot set."""

    __slots__ = (
        "name", "dim", "capacity", "alloc", "scratch", "state",
        "res_ids", "res_slots", "slot_id", "slot_hits", "slot_last",
        "slot_dirty", "free_slots", "cand_ids", "cand_counts",
        "cand_last", "staged_slots", "staged_ids", "staged_rows",
        "evict_ids", "evict_slots", "pending_flush",
    )

    def __init__(self, name, dim, capacity, alloc, opt_type):
        self.name = name
        self.dim = dim
        self.capacity = capacity          # usable slots
        self.alloc = alloc                # rows allocated (>= cap + 1)
        self.scratch = capacity           # first padding row
        self.state = tier_ops.init_table_state(alloc, dim, opt_type)
        self.res_ids = np.empty((0,), np.int64)    # sorted
        self.res_slots = np.empty((0,), np.int32)  # aligned with ids
        self.slot_id = np.full((capacity,), -1, np.int64)
        self.slot_hits = np.zeros((capacity,), np.int64)
        self.slot_last = np.zeros((capacity,), np.int64)
        self.slot_dirty = np.zeros((capacity,), bool)
        self.free_slots = list(range(capacity - 1, -1, -1))  # pop() = 0
        self.cand_ids = np.empty((0,), np.int64)   # sorted
        self.cand_counts = np.empty((0,), np.int64)
        self.cand_last = np.empty((0,), np.int64)
        # staged since the last combine: promotions in, victims out
        self.staged_slots = []
        self.staged_ids = []
        self.staged_rows = []
        self.evict_ids = []
        self.evict_slots = []
        # (ids, slots) snapshotted by mark_restart: dirty rows whose
        # device values must be written back (on the dispatch thread)
        # before the device state resets
        self.pending_flush = None


class DeviceEmbeddingTier:
    """The two-tier embedding store's device half (module docstring).

    Thread contract: ``lookup``/``admit``/``advance`` run on the
    prepare thread (strictly sequential — the lookahead stream
    guarantees ordered prepares), ``combine``/``apply``/``flush`` on
    the dispatch thread; a lock guards the host maps, and device-state
    mutation happens only on the dispatch thread so donated buffers
    are never raced.
    """

    def __init__(self, specs, ps_client, config, mesh=None):
        self._config = config
        self._ps = ps_client
        if not hasattr(ps_client, "push_embedding_rows"):
            raise ValueError(
                "device tier needs a PS client with push_embedding_rows"
                " (eviction/flush writeback); %r has none"
                % type(ps_client).__name__
            )
        self._opt_type = config.opt_type.lower()
        if self._opt_type not in tier_ops.TIER_OPT_SLOTS:
            raise ValueError(
                "device tier supports %s optimizers (got %r); set %s"
                % (sorted(tier_ops.TIER_OPT_SLOTS), self._opt_type,
                   OPT_ENV)
            )
        from elasticdl_tpu.ps.embedding_store import OPTIMIZER_DEFAULTS

        self._opt_args = dict(OPTIMIZER_DEFAULTS)
        self._opt_args.update(config.opt_args or {})
        self._mesh = mesh
        self._ep = 1
        if mesh is not None and "ep" in mesh.shape:
            self._ep = int(mesh.shape["ep"])
        # allocated rows = capacity + scratch pad, rounded so the ep
        # row-sharding divides evenly
        alloc = config.capacity + 1
        if alloc % max(1, self._ep):
            alloc += self._ep - alloc % self._ep
        self._alloc = alloc
        self._lock = threading.Lock()
        self._clock = 0
        self._last_writeback = 0
        # bumped by mark_restart: a step context whose lookups predate
        # the current epoch must be re-prepared, never combined (its
        # slots point into a map that no longer exists)
        self.epoch = 0
        self._tables = {}
        for spec in specs:
            self._tables[spec.name] = _TableTier(
                spec.name, spec.dim, config.capacity, alloc,
                self._opt_type,
            )
            if self._mesh is not None:
                self._tables[spec.name].state = self._shard_state(
                    self._tables[spec.name].state
                )
        # eviction/flush writebacks ride one background thread, the
        # same depth-bounded discipline as EDL_ASYNC_PUSH; failures
        # surface at the next drain (flush/close)
        self._writeback_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tier-writeback"
        )
        self._writeback_futures = []
        # name -> {id: in-flight writeback count} (refcounted; see
        # _submit_writeback)
        self._pending_writeback_ids = {}
        # set by the TTL sweep when idle-but-dirty slots exist: the
        # next maybe_periodic_writeback flushes regardless of cadence
        # so those slots become clean and evictable
        self._force_flush = False
        self._jit_cache = {}
        # cumulative tallies (telemetry + stats()); per-table series in
        # the metrics registry (no-ops when collection is off)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._m_hits = obs_metrics.counter(
            "edl_device_tier_hits_total",
            "Unique ids served from the device-resident hot set",
            ("table",),
        )
        self._m_misses = obs_metrics.counter(
            "edl_device_tier_misses_total",
            "Unique ids that fell through to the PS spillover tier",
            ("table",),
        )
        self._m_evictions = obs_metrics.counter(
            "edl_device_tier_evictions_total",
            "Hot-set rows demoted (LFU pressure or TTL idle)",
            ("table",),
        )
        self._m_hit_rate = obs_metrics.gauge(
            "edl_device_tier_hit_rate",
            "Cumulative device-tier hit rate (hits / lookups)",
            ("table",),
        )
        self._m_occupancy = obs_metrics.gauge(
            "edl_device_tier_occupancy",
            "Resident rows / capacity", ("table",),
        )
        self._t_hits = {}    # per-table cumulative (for the hit-rate
        self._t_misses = {}  # gauge with metrics off -> stats())
        logger.info(
            "device embedding tier: %d tables x %d rows "
            "(%s optimizer, promote@%d, ttl=%d, writeback every %d "
            "steps%s)",
            len(self._tables), config.capacity,
            self._opt_type, config.promote_hits, config.ttl,
            config.writeback_steps,
            ", ep=%d sharded" % self._ep if self._ep > 1 else "",
        )

    # -- device-state helpers ------------------------------------------
    def _shard_state(self, state):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        out = {}
        for key, value in state.items():
            spec = P("ep") if self._ep > 1 else P()
            out[key] = jax.device_put(
                value, NamedSharding(self._mesh, spec)
            )
        return out

    def _state_shardings(self, state):
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P("ep") if self._ep > 1 else P()
        return {
            key: NamedSharding(self._mesh, spec) for key in state
        }

    def _jit_insert_gather(self, table):
        key = ("ig", table.name)
        fn = self._jit_cache.get(key)
        if fn is None:
            kwargs = {}
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                replicated = NamedSharding(self._mesh, P())
                kwargs["out_shardings"] = (
                    self._state_shardings(table.state),
                    replicated,
                    replicated,
                )
            fn = device_obs.instrumented_jit(
                tier_ops.fused_insert_gather,
                name="tier_insert_gather:%s" % table.name,
                donate_argnums=(0,), **kwargs
            )
            self._jit_cache[key] = fn
        return fn

    def _jit_gather_only(self, table):
        import functools

        import jax

        key = ("gather", table.name)
        fn = self._jit_cache.get(key)
        if fn is None:
            def gather(state, slots, miss_rows):
                import jax.numpy as jnp

                hit = slots >= 0
                safe = jnp.where(hit, slots, 0)
                rows = jnp.take(state["rows"], safe, axis=0)
                return jnp.where(hit[:, None], rows, miss_rows)

            kwargs = {}
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                kwargs["out_shardings"] = NamedSharding(self._mesh, P())
            fn = device_obs.instrumented_jit(
                functools.partial(gather),
                name="tier_gather:%s" % table.name, **kwargs
            )
            self._jit_cache[key] = fn
        return fn

    def _jit_apply(self, table):
        import functools

        import jax

        key = ("apply", table.name)
        fn = self._jit_cache.get(key)
        if fn is None:
            args = self._opt_args
            base = functools.partial(
                tier_ops.fused_scatter_apply,
                opt_type=self._opt_type,
                lr=float(args.get("lr", 0.01)),
                momentum=float(args.get("momentum", 0.9)),
                beta1=float(args.get("beta1", 0.9)),
                beta2=float(args.get("beta2", 0.999)),
                epsilon=float(args.get("epsilon", 1e-8)),
            )
            kwargs = {}
            if self._mesh is not None:
                kwargs["out_shardings"] = self._state_shardings(
                    table.state
                )
            fn = device_obs.instrumented_jit(
                base, name="tier_apply:%s" % table.name,
                donate_argnums=(0,), **kwargs
            )
            self._jit_cache[key] = fn
        return fn

    # -- prepare-thread surface ----------------------------------------
    def advance(self):
        """Once per prepare: tick the clock and run the TTL sweep."""
        with self._lock:
            self._clock += 1
            if self._config.ttl <= 0 or self._clock % 64:
                return
            horizon = self._clock - self._config.ttl
            for table in self._tables.values():
                idle = np.nonzero(
                    (table.slot_id >= 0) & (table.slot_last < horizon)
                )[0]
                if not idle.size:
                    continue
                # TTL-evict only CLEAN slots: a clean row's PS copy is
                # exact, so no writeback is needed and a re-miss pulls
                # a correct value. A dirty idle slot evicted here
                # would stage a writeback that is not yet visible to
                # the wait_for_writebacks barrier (it submits at the
                # next combine), and the SAME prepare's pull could
                # read the stale PS row (review finding) — instead,
                # force a flush so the slot becomes clean and a later
                # sweep evicts it.
                dirty_idle = idle[table.slot_dirty[idle]]
                idle = idle[~table.slot_dirty[idle]]
                if dirty_idle.size:
                    self._force_flush = True
                if idle.size:
                    idle = idle[: self._config.stage_budget]
                    self._evict_locked(table, idle.astype(np.int32))

    def lookup(self, name, unique):
        """unique (sorted int64) -> slots int32 [n], -1 = miss. Hit
        slots are touched (LFU count + TTL clock)."""
        table = self._tables[name]
        with self._lock:
            slots = np.full(unique.shape, -1, np.int32)
            if table.res_ids.size:
                pos = np.searchsorted(table.res_ids, unique)
                clipped = np.minimum(pos, table.res_ids.size - 1)
                found = (
                    (pos < table.res_ids.size)
                    & (table.res_ids[clipped] == unique)
                )
                slots[found] = table.res_slots[clipped[found]]
                hit_slots = slots[found]
                table.slot_hits[hit_slots] += 1
                table.slot_last[hit_slots] = self._clock
                # dirty is marked at LOOKUP, not apply: the lookahead
                # prepare may stage this slot's eviction before the
                # in-flight step's apply lands, and the eviction's
                # writeback decision must already see it dirty (the
                # value it reads at combine time is post-apply). An
                # eval hit marks a clean row dirty — one spurious
                # writeback of an unchanged value, harmless.
                table.slot_dirty[hit_slots] = True
            n_hit = int((slots >= 0).sum())
            n_miss = int(unique.size) - n_hit
        self.hits += n_hit
        self.misses += n_miss
        self._t_hits[name] = self._t_hits.get(name, 0) + n_hit
        self._t_misses[name] = self._t_misses.get(name, 0) + n_miss
        if n_hit:
            self._m_hits.labels(table=name).inc(n_hit)
        if n_miss:
            self._m_misses.labels(table=name).inc(n_miss)
        total = self._t_hits[name] + self._t_misses[name]
        if total:
            self._m_hit_rate.labels(table=name).set(
                self._t_hits[name] / total
            )
        return slots

    def admit(self, name, miss_ids, miss_rows):
        """Fold this step's misses into the promotion candidates and
        stage the ids that crossed ``promote_hits`` (their pulled rows
        become the staged insert values). Returns (mask over miss_ids
        of promoted entries, their new slots int32) — promoted ids are
        hits from this very step on, so their gradients apply in-device
        and they leave the PS push set."""
        table = self._tables[name]
        config = self._config
        if miss_ids.size == 0:
            return np.zeros((0,), bool), np.empty((0,), np.int32)
        with self._lock:
            counts = self._bump_candidates_locked(table, miss_ids)
            ready = counts >= config.promote_hits
            budget = min(
                config.stage_budget - len(table.staged_slots),
                config.capacity,
            )
            if budget <= 0:
                ready[:] = False
            elif int(ready.sum()) > budget:
                # promote the hottest first under the stage budget
                order = np.argsort(-counts)
                keep = order[:budget]
                limited = np.zeros_like(ready)
                limited[keep] = ready[keep]
                ready = limited
            n_promote = int(ready.sum())
            if n_promote == 0:
                return ready, np.empty((0,), np.int32)
            slots = self._allocate_slots_locked(
                table, n_promote, protect=miss_ids[ready]
            )
            if slots.size < n_promote:
                # not enough evictable slots (everything is hot this
                # step): promote what fits, keep the rest as candidates
                short = np.nonzero(ready)[0][slots.size:]
                ready[short] = False
                n_promote = slots.size
            if n_promote == 0:
                return ready, np.empty((0,), np.int32)
            ids = miss_ids[ready]
            rows = np.asarray(miss_rows[ready], np.float32)
            # resident map insert (sorted merge)
            merged = np.concatenate([table.res_ids, ids])
            merged_slots = np.concatenate(
                [table.res_slots, slots.astype(np.int32)]
            )
            order = np.argsort(merged, kind="stable")
            table.res_ids = merged[order]
            table.res_slots = merged_slots[order]
            table.slot_id[slots] = ids
            table.slot_hits[slots] = config.promote_hits
            table.slot_last[slots] = self._clock
            # dirty from birth: a promoted id is a hit in THIS step, so
            # its first in-device gradient lands before any later
            # lookup could mark it (same reasoning as the lookup-time
            # marking above)
            table.slot_dirty[slots] = True
            table.staged_slots.extend(slots.astype(np.int64).tolist())
            table.staged_ids.extend(ids.astype(np.int64).tolist())
            table.staged_rows.append(rows)
            self._drop_candidates_locked(table, ids)
        return ready, slots.astype(np.int32)

    def _bump_candidates_locked(self, table, miss_ids):
        """Vectorized candidate-count update; returns this call's count
        per miss id (after the bump)."""
        if table.cand_ids.size:
            pos = np.searchsorted(table.cand_ids, miss_ids)
            clipped = np.minimum(pos, table.cand_ids.size - 1)
            known = (
                (pos < table.cand_ids.size)
                & (table.cand_ids[clipped] == miss_ids)
            )
        else:
            known = np.zeros(miss_ids.shape, bool)
            clipped = np.zeros(miss_ids.shape, np.int64)
        table.cand_counts[clipped[known]] += 1
        table.cand_last[clipped[known]] = self._clock
        fresh = miss_ids[~known]
        if fresh.size:
            # sorted-insert, not concatenate+argsort: miss_ids arrive
            # sorted (np.unique output), so an O(n) merge via
            # np.insert beats an O(n log n) re-sort of the whole
            # candidate set — at CTR vocab sizes the re-sort was the
            # single largest per-step tier cost on host
            pos = np.searchsorted(table.cand_ids, fresh)
            table.cand_ids = np.insert(table.cand_ids, pos, fresh)
            table.cand_counts = np.insert(
                table.cand_counts, pos, 1
            )
            table.cand_last = np.insert(
                table.cand_last, pos, self._clock
            )
            cap = 8 * self._config.capacity
            if table.cand_ids.size > cap:
                # keep the hottest/most recent candidates: vocab drift
                # must not grow this set without bound
                score = table.cand_counts * (2 ** 20) + table.cand_last
                keep = np.argpartition(-score, cap - 1)[:cap]
                keep.sort()
                table.cand_ids = table.cand_ids[keep]
                table.cand_counts = table.cand_counts[keep]
                table.cand_last = table.cand_last[keep]
        pos = np.searchsorted(table.cand_ids, miss_ids)
        clipped = np.minimum(pos, max(table.cand_ids.size - 1, 0))
        found = (
            (pos < table.cand_ids.size)
            & (table.cand_ids[clipped] == miss_ids)
        )
        # an id the size cap just dropped counts as freshly seen
        return np.where(found, table.cand_counts[clipped], 1)

    def _drop_candidates_locked(self, table, ids):
        if not table.cand_ids.size:
            return
        # membership-checked: a promoted id may already be absent from
        # the candidate set (the size cap trimmed it but its count
        # still cleared promote_hits=1) — a blind keep[pos] = False
        # would index out of bounds or delete a neighboring candidate
        pos = np.searchsorted(table.cand_ids, ids)
        clipped = np.minimum(pos, table.cand_ids.size - 1)
        found = (
            (pos < table.cand_ids.size)
            & (table.cand_ids[clipped] == ids)
        )
        keep = np.ones(table.cand_ids.shape, bool)
        keep[clipped[found]] = False
        table.cand_ids = table.cand_ids[keep]
        table.cand_counts = table.cand_counts[keep]
        table.cand_last = table.cand_last[keep]

    def _allocate_slots_locked(self, table, n, protect):
        """n slots for promotions: free list first, then LFU eviction
        among slots idle this step (never an id in ``protect`` — the
        current batch — nor one hit at the current clock)."""
        take = min(n, len(table.free_slots))
        slots = [table.free_slots.pop() for _ in range(take)]
        need = n - take
        if need > 0:
            evictable = np.nonzero(
                (table.slot_id >= 0)
                & (table.slot_last < self._clock)
            )[0]
            if protect.size and evictable.size:
                mask = ~np.isin(table.slot_id[evictable], protect)
                evictable = evictable[mask]
            if evictable.size:
                hits = table.slot_hits[evictable]
                take2 = min(need, evictable.size)
                order = np.argpartition(hits, take2 - 1)[:take2]
                victims = evictable[order].astype(np.int32)
                self._evict_locked(table, victims)
                # _evict_locked pushed the victims onto free_slots
                slots.extend(
                    table.free_slots.pop() for _ in range(victims.size)
                )
        return np.asarray(slots, np.int32)

    def _evict_locked(self, table, victim_slots):
        """Demote ``victim_slots`` (int32, resident): remove from the
        map now; their device values are read out and written back at
        the next combine (they stay readable until the staged inserts
        land)."""
        victim_ids = table.slot_id[victim_slots]
        keep_mask = np.ones(table.res_ids.shape, bool)
        pos = np.searchsorted(table.res_ids, victim_ids)
        keep_mask[pos] = False
        table.res_ids = table.res_ids[keep_mask]
        table.res_slots = table.res_slots[keep_mask]
        dirty = table.slot_dirty[victim_slots]
        table.slot_id[victim_slots] = -1
        table.slot_hits[victim_slots] = 0
        table.slot_dirty[victim_slots] = False
        table.free_slots.extend(victim_slots.astype(np.int64).tolist())
        # only rows a gradient ever landed on need the writeback; a
        # clean row's PS copy is still exact
        dirty_slots = victim_slots[dirty]
        if dirty_slots.size:
            table.evict_ids.extend(
                victim_ids[dirty].astype(np.int64).tolist()
            )
            table.evict_slots.extend(
                dirty_slots.astype(np.int64).tolist()
            )
        self.evictions += int(victim_slots.size)
        self._m_evictions.labels(table=table.name).inc(
            int(victim_slots.size)
        )
        self._m_occupancy.labels(table=table.name).set(
            table.res_ids.size / max(1, table.capacity)
        )

    def mark_restart(self):
        """PS relaunch detected (restored-stamp change; may fire on the
        pull/push threads): invalidate the HOST maps immediately — from
        this instant every lookup misses, so no step trains on a slot
        the restored PS knows nothing about — and snapshot the dirty
        rows' (id, slot) pairs. Their device values are read out and
        written back by ``_process_restart`` on the dispatch thread
        (after any in-flight step's apply has landed, so no update is
        lost), and only then does the device state reset. This is the
        flush-then-invalidate order the PR 4 chaos contract requires,
        split across threads so nothing races the donated device
        buffers."""
        with self._lock:
            self.epoch += 1
            for table in self._tables.values():
                dirty = np.nonzero(table.slot_dirty)[0]
                ids = table.slot_id[dirty]
                live = ids >= 0
                dirty, ids = dirty[live], ids[live]
                # Staged-but-not-combined promotions: their slots are
                # marked dirty but the insert never LANDED on device —
                # a device read there returns zeros (or the previous
                # tenant's row) and would corrupt the restored PS row
                # under the promoted id. Their correct current value
                # is the staged host row; route it through the host
                # half of the snapshot instead. Staged EVICTION
                # victims still read correctly from device (the
                # insert that would overwrite them never landed), so
                # they join the device-read half.
                if table.staged_slots:
                    staged = np.isin(
                        dirty, np.asarray(table.staged_slots, np.int32)
                    )
                    dirty, ids = dirty[~staged], ids[~staged]
                if table.evict_slots:
                    ids = np.concatenate([
                        ids, np.asarray(table.evict_ids, np.int64)
                    ])
                    dirty = np.concatenate([
                        dirty.astype(np.int32),
                        np.asarray(table.evict_slots, np.int32),
                    ])
                host_ids = np.asarray(table.staged_ids, np.int64)
                host_rows = (
                    np.concatenate(table.staged_rows, axis=0)
                    if table.staged_rows
                    else np.empty((0, table.dim), np.float32)
                )
                pending = (
                    ids, dirty.astype(np.int32), host_ids, host_rows
                )
                if table.pending_flush is not None:
                    prev = table.pending_flush
                    pending = tuple(
                        np.concatenate([prev[k], pending[k]])
                        for k in range(4)
                    )
                table.pending_flush = pending
                self._reset_host_maps_locked(table)

    def _reset_host_maps_locked(self, table):
        table.res_ids = np.empty((0,), np.int64)
        table.res_slots = np.empty((0,), np.int32)
        table.slot_id[:] = -1
        table.slot_hits[:] = 0
        table.slot_last[:] = 0
        table.slot_dirty[:] = False
        table.free_slots = list(range(table.capacity - 1, -1, -1))
        table.cand_ids = np.empty((0,), np.int64)
        table.cand_counts = np.empty((0,), np.int64)
        table.cand_last = np.empty((0,), np.int64)
        table.staged_slots, table.staged_ids = [], []
        table.staged_rows = []
        table.evict_ids, table.evict_slots = [], []
        self._m_occupancy.labels(table=table.name).set(0.0)

    def _process_restart(self):
        """Dispatch-thread half of mark_restart: write the snapshotted
        dirty rows back to the (restored) PS, then zero the device
        state. Runs before any combine touches the tables again."""
        for table in self._tables.values():
            with self._lock:
                pending, table.pending_flush = table.pending_flush, None
            if pending is None:
                continue
            ids, slots, host_ids, host_rows = pending
            if ids.size:
                rows = np.asarray(table.state["rows"])[slots]
                self._submit_writeback(table.name, ids, rows)
            if host_ids.size:
                # staged promotions whose insert never landed: their
                # newest known values are the staged host rows
                self._submit_writeback(table.name, host_ids, host_rows)
            table.state = tier_ops.init_table_state(
                table.alloc, table.dim, self._opt_type
            )
            if self._mesh is not None:
                table.state = self._shard_state(table.state)

    # -- dispatch-thread surface ---------------------------------------
    def combine(self, name, slots, rows_buffer):
        """Process staged promotions/demotions and materialize the
        step's combined row buffer on device (one fused dispatch per
        staged chunk). ``slots`` is the capacity-padded int32 slot
        array (-1 for miss/pad); ``rows_buffer`` the host buffer with
        PS-pulled rows at miss positions."""
        import jax.numpy as jnp

        self._process_restart()
        table = self._tables[name]
        budget = self._config.stage_budget
        with self._lock:
            ins_slots = table.staged_slots
            ins_rows = (
                np.concatenate(table.staged_rows, axis=0)
                if table.staged_rows
                else np.empty((0, table.dim), np.float32)
            )
            ev_ids = table.evict_ids
            ev_slots = table.evict_slots
            table.staged_slots, table.staged_ids = [], []
            table.staged_rows = []
            table.evict_ids, table.evict_slots = [], []
            self._m_occupancy.labels(table=name).set(
                table.res_ids.size / max(1, table.capacity)
            )
        if not ins_slots and not ev_slots:
            # steady-state fast path: nothing staged this step — a
            # plain gather-merge, no state donation/rebuild, no
            # scatter of budget-sized padding
            return self._jit_gather_only(table)(
                table.state, jnp.asarray(slots),
                jnp.asarray(rows_buffer),
            )
        combined = None
        offset = 0
        scratch = table.scratch
        n_chunks = max(
            1,
            -(-max(len(ins_slots), len(ev_slots)) // budget),
        )
        jitted = self._jit_insert_gather(table)
        for chunk in range(n_chunks):
            ins_chunk = ins_slots[offset: offset + budget]
            row_chunk = ins_rows[offset: offset + budget]
            ev_chunk = ev_slots[offset: offset + budget]
            ev_id_chunk = ev_ids[offset: offset + budget]
            offset += budget
            pad_ins = np.full((budget,), scratch, np.int32)
            pad_ins[: len(ins_chunk)] = ins_chunk
            pad_rows = np.zeros((budget, table.dim), np.float32)
            pad_rows[: len(row_chunk)] = row_chunk
            pad_ev = np.full((budget,), scratch, np.int32)
            pad_ev[: len(ev_chunk)] = ev_chunk
            state, combined, evicted = jitted(
                table.state, jnp.asarray(pad_ins),
                jnp.asarray(pad_rows), jnp.asarray(pad_ev),
                jnp.asarray(slots), jnp.asarray(rows_buffer),
            )
            table.state = state
            if ev_chunk:
                values = np.asarray(evicted)[: len(ev_chunk)]
                self._submit_writeback(
                    name,
                    np.asarray(ev_id_chunk, np.int64),
                    values,
                )
        return combined

    def apply(self, name, slots, grads):
        """Fused in-device sparse optimizer step for the hit rows;
        ``grads`` stays a device array end to end."""
        import jax.numpy as jnp

        table = self._tables[name]
        table.state = self._jit_apply(table)(
            table.state, jnp.asarray(slots), grads
        )
        # re-mark dirty AFTER the apply dispatch: lookup-time marking
        # alone loses updates when a (periodic or boundary) flush runs
        # in the window between the lookahead prepare's marking and
        # this apply — the flush clears the flag, fetches the
        # pre-apply value, and nothing would re-flag the slot
        with self._lock:
            hit = slots[slots >= 0]
            table.slot_dirty[hit[hit < table.capacity]] = True

    # -- writeback / lifecycle -----------------------------------------
    def _submit_writeback(self, name, ids, values):
        future = self._writeback_pool.submit(
            self._ps.push_embedding_rows, {name: (ids, values)}
        )
        # futures list is touched from the dispatch thread (combine)
        # and from flush callers (boundary/main or resync/prepare
        # thread) — mutate under the lock
        with self._lock:
            self._writeback_futures.append(future)
            # ids with a writeback in flight: a subsequent PS pull of
            # the same id must wait (wait_for_writebacks), or the pull
            # reads the pre-writeback value AND the late-landing raw
            # overwrite would revert any gradient pushed meanwhile.
            # REFCOUNTED, not a set: two overlapping writebacks of one
            # id must keep the marker until the LAST one lands, or the
            # first completion would clear it while the second is
            # still queued (review finding)
            pend = self._pending_writeback_ids.setdefault(name, {})
            id_list = [int(i) for i in ids]
            for i in id_list:
                pend[i] = pend.get(i, 0) + 1
            # bounded: drop futures that already resolved cleanly
            self._writeback_futures = [
                f for f in self._writeback_futures
                if not (f.done() and f.exception() is None)
            ]

        def _clear(_future, name=name, id_list=id_list):
            with self._lock:
                pend = self._pending_writeback_ids.get(name)
                if pend is None:
                    return
                for i in id_list:
                    count = pend.get(i, 0) - 1
                    if count <= 0:
                        pend.pop(i, None)
                    else:
                        pend[i] = count

        future.add_done_callback(_clear)

    def wait_for_writebacks(self, name, miss_ids):
        """Miss-path ordering barrier: if any of ``miss_ids`` has a
        writeback still in flight, drain the writeback queue before
        the caller pulls them from the PS — otherwise the pull reads
        the pre-writeback (stale) value and the overwrite later lands
        ON TOP of gradients pushed in between, silently reverting
        them. Evicted ids are cold by selection, so the pending map is
        almost always empty and this returns after one dict check."""
        with self._lock:
            pend = self._pending_writeback_ids.get(name)
            if not pend:
                return
            # C-speed membership sweep (tolist -> Python ints, hash-
            # compatible with the stored keys); no per-id Python loop
            hit = not set(pend).isdisjoint(
                np.asarray(miss_ids, np.int64).tolist()
            )
        if hit:
            self.drain_writebacks()

    def maybe_periodic_writeback(self):
        """Bounded-staleness writeback cadence. MUST run after the
        step's applies have been dispatched (the trainer calls it from
        the apply/extract path): a pre-apply flush would clear dirty
        flags on slots the in-flight apply is about to update, and the
        final flush would then skip their latest values — measured as
        flush-parity corruption in the smoke harness. A TTL sweep that
        found idle-but-dirty slots forces the flush regardless of
        cadence (even with the periodic knob off) so those slots
        become clean and evictable."""
        with self._lock:
            forced, self._force_flush = self._force_flush, False
        steps = self._config.writeback_steps
        if not forced and (
            steps <= 0 or self._clock - self._last_writeback < steps
        ):
            return
        self._last_writeback = self._clock
        self._flush_dirty(wait=False)

    def _flush_dirty(self, wait):
        """Write every dirty resident row back to the PS. The full-
        table device fetch is one transfer per table (capacity x dim
        floats), cheap at boundary cadence."""
        for name, table in self._tables.items():
            with self._lock:
                dirty = np.nonzero(table.slot_dirty)[0]
                if not dirty.size:
                    continue
                ids = table.slot_id[dirty]
                live = ids >= 0
                dirty, ids = dirty[live], ids[live]
                table.slot_dirty[dirty] = False
            if not dirty.size:
                continue
            rows = np.asarray(table.state["rows"])[dirty]
            self._submit_writeback(name, ids, rows)
        if wait:
            self.drain_writebacks()

    def drain_writebacks(self):
        """Block until queued writebacks land; the first failure
        raises (checkpoint boundaries must not proceed past a lost
        writeback)."""
        with self._lock:
            futures = self._writeback_futures
            self._writeback_futures = []
        error = None
        for future in futures:
            try:
                future.result()
            # every future is drained before the first error surfaces
            except Exception as e:  # edlint: disable=ft-swallowed-except
                if error is None:
                    error = e
        if error is not None:
            raise error

    def flush(self):
        """Checkpoint/export boundary: every tier-held update reaches
        the PS before the caller proceeds (the PS checkpoint or the
        exported model then contains the hot rows' latest values)."""
        self._process_restart()
        self._drain_staged()
        self._flush_dirty(wait=True)

    def _drain_staged(self):
        """Land staged promotions and write back staged victims without
        materializing a combined buffer (flush paths)."""
        for name, table in self._tables.items():
            with self._lock:
                pending = bool(table.staged_slots or table.evict_slots)
            if pending:
                empty_slots = np.full((1,), -1, np.int32)
                empty_rows = np.zeros((1, table.dim), np.float32)
                self.combine(name, empty_slots, empty_rows)

    def invalidate(self):
        """Drop every resident row and candidate (PS-restart resync):
        the map empties, device state zeroes, and the hot set
        repopulates from post-restart pulls. Callers flush() first —
        flush-then-invalidate is the no-lost-updates order."""
        with self._lock:
            self.epoch += 1
            for table in self._tables.values():
                self._reset_host_maps_locked(table)
                table.state = tier_ops.init_table_state(
                    table.alloc, table.dim, self._opt_type
                )
                if self._mesh is not None:
                    table.state = self._shard_state(table.state)

    def flush_and_invalidate(self):
        """PS relaunch detected (restored-stamp change): write the
        tier's rows — strictly newer than the restored checkpoint —
        back first, then invalidate. A failed flush still invalidates
        (stale resident rows must not keep serving), but the error
        propagates."""
        try:
            self.flush()
        finally:
            self.invalidate()

    def close(self):
        try:
            self.flush()
        except Exception:
            logger.exception("device-tier flush failed at close")
        self._writeback_pool.shutdown(wait=True)
        logger.info(
            "device tier closed: hits=%d misses=%d evictions=%d",
            self.hits, self.misses, self.evictions,
        )

    # -- reporting ------------------------------------------------------
    def stats(self):
        """Aggregate tallies for TelemetryBlob / bench reporting."""
        lookups = self.hits + self.misses
        with self._lock:
            resident = sum(
                t.res_ids.size for t in self._tables.values()
            )
            capacity = sum(
                t.capacity for t in self._tables.values()
            )
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "hit_rate": self.hits / lookups if lookups else 0.0,
            "occupancy": resident / capacity if capacity else 0.0,
        }

    def hbm_bytes(self, per_table=False):
        """Device bytes the tier's table state pins (rows + optimizer
        slots), attributed per table when asked — the HBM-accounting
        side of ISSUE 18's device section. Lock-free: table state
        arrays are replaced, never resized, so nbytes is stable."""
        sizes = {
            name: sum(
                int(getattr(value, "nbytes", 0))
                for value in table.state.values()
            )
            for name, table in self._tables.items()
        }
        if per_table:
            return sizes
        return sum(sizes.values())

    def table_rows(self, name):
        """Resident (id, row) snapshot — tests and debugging."""
        table = self._tables[name]
        with self._lock:
            ids = table.res_ids.copy()
            slots = table.res_slots.copy()
        rows = np.asarray(table.state["rows"])[slots]
        return ids, rows
