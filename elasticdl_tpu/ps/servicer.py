"""Pserver gRPC service over the embedding store.

Reference parity: elasticdl/python/ps/servicer.py and go/pkg/ps/server.go
— with the dense hot path removed. What remains host-side:

- sparse embedding pull/push with lazy table creation
  (pull_embedding_vectors / push_gradients)
- async-SGD semantics on the sparse path only: immediate apply,
  version++, staleness-modulated LR ``lr /= max(1, version_diff)``
  (reference: ps/servicer.py:120-165). Lockstep SPMD makes these
  semantics meaningless for dense params, so they survive only here.
- cold-start dense init: the first worker pushes its initialized dense
  params; late joiners pull them instead of re-initializing (reference
  worker.py:297-336 get_model protocol).
- periodic sparse checkpoints + report_version to the master for
  step-based evaluation triggering.
"""

import concurrent.futures
import sys
import threading
import time

import grpc
import numpy as np

from elasticdl_tpu.common import overload
from elasticdl_tpu.common.env_utils import env_float, env_int
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.common.tensor_utils import (
    blob_to_ndarray,
    deduplicate_indexed_slices,
    deserialize_indexed_slices,
    ndarray_to_blob,
    unpack_ids,
    wire_dtype,
)
from elasticdl_tpu.observability import events
from elasticdl_tpu.observability import metrics as obs_metrics
from elasticdl_tpu.observability import trace
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.testing import faults
from elasticdl_tpu.ps.embedding_store import (
    BLOB_DTYPE_CODES,
    BLOB_ITEMSIZE,
)

logger = _logger_factory("elasticdl_tpu.ps.servicer")

# Per-table apply fan-out width for the async push path (ISSUE 11).
# Only pays off with the native store: its blob applies release the
# GIL and lock per TABLE, so a multi-table push really applies in
# parallel; the numpy store holds one store-wide lock (and the GIL),
# so >1 here is wasted threads, not wrong results.
APPLY_THREADS_ENV = "EDL_PS_APPLY_THREADS"
# Admission control (ISSUE 19): apply-backlog depth past which the PS
# answers push/pull RPCs with RESOURCE_EXHAUSTED + a retry-after hint
# instead of queueing more work. 0 disables.
MAX_PENDING_APPLIES_ENV = "EDL_PS_MAX_PENDING_APPLIES"

# packed-id blobs are little-endian; the native fast paths read them
# as host int64, so they are only taken on LE hosts
_LITTLE_ENDIAN = sys.byteorder == "little"

# Off-RPC checkpointing (ISSUE 13): 1 (default) = push handlers only
# enqueue a save request and a dedicated thread does the dirty export
# + serialization + file IO; 0 = saves run inline in the handler (the
# pre-ISSUE-13 behavior, kept for deterministic tests and debugging).
CKPT_ASYNC_ENV = "EDL_CKPT_ASYNC"

# Table-health scan (ISSUE 15): row-norm ceiling past which a sampled
# row counts as exploding, per-table sample size, and the minimum
# seconds between scans (the scan rides the 5 s poll loop but a full
# table export per tick would be wasteful).
ROW_NORM_MAX_ENV = "EDL_HEALTH_ROW_NORM_MAX"
HEALTH_SCAN_SAMPLE_ENV = "EDL_HEALTH_SCAN_SAMPLE"
HEALTH_SCAN_SECS_ENV = "EDL_HEALTH_SCAN_SECS"
HEALTH_SCAN_MAX_ROWS_ENV = "EDL_HEALTH_SCAN_MAX_ROWS"


def _deserialize_gradients(slices):
    """One table's pushed gradients off the wire, upcast to the fp32
    master precision: a reduced wire dtype (EDL_WIRE_DTYPE) covers the
    PAYLOAD only — buffering/merging/applying in bf16 would compound
    rounding across the round's summation, which the knob's contract
    (fp32 master copies on the PS) rules out."""
    values, ids = deserialize_indexed_slices(slices)
    if values.dtype != np.float32:
        values = values.astype(np.float32)
    return values, ids


def _blob_fast_path_ok(store, name, slices):
    """True when one table's pushed slices can route through the
    native store's single-call deserialize+dedup+apply: packed ids, a
    payload dtype the C side decodes, and a shape that matches the
    table — anything else falls back to the numpy-array path (which
    handles legacy repeated ids, exotic dtypes, and ragged junk)."""
    if not _LITTLE_ENDIAN or not slices.ids_blob:
        return False
    blob = slices.concat_tensors
    if blob.dtype not in BLOB_DTYPE_CODES:
        return False
    itemsize = BLOB_ITEMSIZE[blob.dtype]
    try:
        dim = store.table_dim(name)
    except KeyError:
        return False
    n = len(slices.ids_blob) // 8
    return len(blob.content) == n * dim * itemsize


class PserverServicer:
    def __init__(
        self,
        store,
        ps_id=0,
        staleness_modulation=True,
        checkpoint_saver=None,
        checkpoint_steps=0,
        master_client=None,
        # async SGD for the bare constructor (the embedded-PS test
        # surface); the FLAG default is sync=reference parity — the
        # server entry always passes use_async explicitly
        # (ps/server.py:117), so this Python default never reaches a
        # CLI-launched PS
        use_async=True,
        grads_to_wait=1,
        sync_version_tolerance=0,
        restored_version=None,
        lifecycle=None,
    ):
        self._store = store
        self._ps_id = ps_id
        # Embedding lifecycle (ISSUE 12): frequency admission + TTL/LFU
        # eviction over this shard's tables. None (the default) keeps
        # every pre-lifecycle path byte-for-byte untouched — tables
        # grow unbounded, as before.
        self._lifecycle = lifecycle
        # fail a misconfigured EDL_WIRE_DTYPE at boot, not per pull
        # RPC: a PS that passes health probes while every pull raises
        # would crash-loop its workers instead of itself
        wire_dtype()
        # Native data plane (ISSUE 11): when the store exposes the
        # wire-blob C entry points, push/pull payloads route through
        # them — one GIL-released call per table covering
        # deserialize + dedup + apply (or lookup + wire-dtype cast).
        # Duck-typed, not isinstance: tests wrap stores.
        self._native_store = all(
            callable(getattr(store, method, None))
            for method in
            ("push_gradients_blob", "lookup_blob", "import_blob")
        )
        self._backend = "native" if self._native_store else "numpy"
        # said out loud, not only as the edl_ps_native_active gauge:
        # the numpy store is the tests' reference and slower on apply,
        # so a PS that fell back to it (failed native build) must be
        # visible in its log (chip_smoke.py reads this line)
        logger.info(
            "PS %d embedding store backend: %s", ps_id, self._backend
        )
        # Per-table apply fan-out for the async path: with the GIL
        # released inside the native applies, a small pool turns a
        # multi-table push into parallel per-table applies (each
        # guarded by its table's shared_mutex). 0/1/unset = inline.
        apply_threads = env_int(APPLY_THREADS_ENV, 1)
        self._apply_pool = None
        if apply_threads > 1:
            self._apply_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=apply_threads,
                thread_name_prefix="ps-apply",
            )
        # Admission control (ISSUE 19): in-flight push handlers are
        # counted under a small dedicated lock; past the knob the RPC
        # boundary answers RESOURCE_EXHAUSTED + edl-retry-after-ms and
        # the clients' pushback pacing takes over. _overloaded tracks
        # the enter/clear EDGE for journaling (per-reject events would
        # flood the journal in the exact moment it matters most).
        self._max_pending = env_int(MAX_PENDING_APPLIES_ENV, 64)
        self._pending_lock = threading.Lock()
        self._pending_applies = 0
        self._t_overload_rejections = 0
        self._overloaded = False
        # EWMA of admitted apply wall seconds: the retry-after hint is
        # calibrated from this, so pushed-back clients poll at the pace
        # slots ACTUALLY free instead of a fixed guess (a hint far
        # below the real drain time makes every waiter poll-and-miss
        # several times per admission — measured amplification)
        self._apply_ewma_secs = 0.0
        # checkpoint version this PS auto-restored at boot, stamped on
        # push/pull responses (wire encoding: version + 1, 0 = none) so
        # workers detecting a version regression know what state the
        # relaunched PS came back with
        self._restored_wire = (
            int(restored_version) + 1 if restored_version is not None else 0
        )
        self._staleness_modulation = staleness_modulation
        self._checkpoint_saver = checkpoint_saver
        self._checkpoint_steps = checkpoint_steps
        # Off-RPC saves (ISSUE 13): checkpoint triggers only ENQUEUE;
        # the AsyncCheckpointer thread does the brief dirty export
        # under the store lock plus all serialization and file IO off
        # the push path, coalescing bursts. EDL_CKPT_ASYNC=0 keeps the
        # old inline behavior.
        self._ckpt_async = None
        if checkpoint_saver is not None:
            from elasticdl_tpu.ps.checkpoint import AsyncCheckpointer

            if env_int(CKPT_ASYNC_ENV, 1):
                self._ckpt_async = AsyncCheckpointer(
                    self._save_checkpoint_now,
                    name="ps-%d-ckpt" % ps_id,
                )
        self._master_client = master_client
        self._lock = threading.Lock()
        self._dense = {}
        self._dense_version = 0
        self._dense_initialized = False
        # sync-SGD mode (reference ps/servicer.py:166-236): buffer
        # pushes until grads_to_wait arrive, reject grads older than
        # version - sync_version_tolerance, single apply, version++
        self._use_async = use_async
        self._grads_to_wait = max(1, grads_to_wait)
        self._sync_tolerance = max(0, sync_version_tolerance)
        self._push_lock = threading.Lock()
        # Round buffer: a LIST of buffered pushes, each tagged with the
        # pusher's (worker_id, incarnation) when identified. Cleanup
        # rule: a push whose worker_id matches a buffered entry with a
        # DIFFERENT incarnation evicts that entry — the previous
        # incarnation died mid-round, and its orphaned half-round would
        # otherwise pair its round-k grads with peers' round-k+1 grads
        # forever after (one spurious version rejection every round,
        # observed in the SIGKILL chaos test). Same-incarnation and
        # anonymous pushes always APPEND (the reference's counting
        # semantics): a live straggler's double push keeps both
        # gradients, and a lone survivor still completes a
        # grads_to_wait=N round by itself instead of livelocking.
        self._round_buffer = []  # [(worker_key, {name: (vals, ids)}, scale)]
        # round-scoped pairing (lockstep pushers): tag -> entries; a
        # round applies only when its OWN tag's group fills — see
        # _push_gradients_sync
        self._round_groups = {}
        # PS-side domain metrics (ISSUE 2): push/pull rates, the
        # round-buffer fill the "why is the round not filling" question
        # reads first, and version lag between store and pushers. All
        # no-op instruments when metrics collection is off.
        self._m_pull_requests = obs_metrics.counter(
            "edl_ps_pull_requests_total",
            "pull_embedding_vectors RPCs served", ("table",),
        )
        self._m_pull_rows = obs_metrics.counter(
            "edl_ps_pulled_rows_total",
            "Embedding rows served to workers", ("table",),
        )
        self._m_push_requests = obs_metrics.counter(
            "edl_ps_push_requests_total", "push_gradients RPCs received"
        )
        self._m_push_rejected = obs_metrics.counter(
            "edl_ps_push_rejected_total",
            "Pushes rejected as stale (sync mode version check)",
        )
        self._m_overload_rejected = obs_metrics.counter(
            "edl_ps_overload_rejected_total",
            "RPCs rejected by admission control (RESOURCE_EXHAUSTED + "
            "retry-after pushback) once the apply backlog crossed "
            "EDL_PS_MAX_PENDING_APPLIES, by method", ("method",),
        )
        obs_metrics.gauge(
            "edl_ps_pending_applies",
            "Admission-control depth: in-flight push handlers plus "
            "round-buffer entries beyond one full sync round",
        ).set_function(self._pending_depth)
        self._m_push_dropped_dead = obs_metrics.counter(
            "edl_ps_push_dropped_dead_incarnation_total",
            "Pushes dropped as a dead incarnation's delayed delivery "
            "(a sustained nonzero rate on a live worker means its "
            "incarnation ordering is wrong — alert on it)",
        )
        self._m_version_lag = obs_metrics.gauge(
            "edl_ps_version_lag",
            "store version minus the last push's gradient version",
        )
        obs_metrics.gauge(
            "edl_ps_round_buffer_fill",
            "Buffered pushes awaiting a sync round (counting + scoped)",
        ).set_function(self._buffered_count)
        obs_metrics.gauge(
            "edl_ps_store_version", "Embedding store version"
        ).set_function(lambda: self._store.version)
        self._m_table_rows = obs_metrics.gauge(
            "edl_ps_embedding_rows",
            "Materialized rows per embedding table", ("table",),
        )
        # Bytes-on-wire counters (ISSUE 5): gradient/row PAYLOAD bytes
        # (tensor content + packed ids), labeled by the payload dtype so
        # an EDL_WIRE_DTYPE rollout is directly visible as the fp32
        # series flatlining and the bf16 series taking over.
        self._m_push_bytes = obs_metrics.counter(
            "edl_ps_push_bytes_total",
            "Gradient payload bytes received (tensor content + ids), "
            "by wire dtype", ("dtype",),
        )
        self._m_pull_bytes = obs_metrics.counter(
            "edl_ps_pull_bytes_total",
            "Embedding-row payload bytes served, by wire dtype",
            ("dtype",),
        )
        # Dense-plane contract (ISSUE 20): dense gradients reduce
        # on-mesh and never ride the PS — this counter MUST stay 0
        # under the GSPMD trainers. It exists so the contract is a
        # scrapeable fact, not an absence of evidence: the dense-plane
        # smoke (scripts/bench_dense_plane.py) fails if it moves.
        self._m_push_dense_bytes = obs_metrics.counter(
            "edl_ps_push_dense_bytes_total",
            "Dense-gradient payload bytes received over push_gradients "
            "(0 under the GSPMD dense data plane: only embedding rows "
            "ride the PS)",
        )
        # touch the series so /metrics exposes an explicit 0: the
        # contract is "provably zero", not "no evidence either way"
        self._m_push_dense_bytes.inc(0)
        # device-tier writebacks (ISSUE 6): rows overwritten by
        # push_embedding_rows — eviction/flush traffic from workers'
        # HBM hot sets
        self._m_rows_written = obs_metrics.counter(
            "edl_ps_rows_written_total",
            "Embedding rows overwritten by device-tier writebacks",
        )
        # Native data plane (ISSUE 11): which store backend this shard
        # runs (the first postmortem question for a slow PS), and the
        # apply latency it delivers — labeled by backend so an A-B or
        # a mid-fleet native rollout reads directly off one series.
        self._m_apply_seconds = obs_metrics.histogram(
            "edl_ps_apply_seconds",
            "Wall seconds per push's gradient deserialize+apply, by "
            "store backend", ("backend",),
        )
        obs_metrics.gauge(
            "edl_ps_native_active",
            "1 when this PS runs the native (C++) embedding store, "
            "0 on the numpy fallback",
        ).set(1 if self._native_store else 0)
        # Incremental checkpoints (ISSUE 13): save wall time by kind
        # (a delta should be orders of magnitude under a full base on
        # a Zipfian stream), the dirty-row count each delta carried,
        # and the live chain length (deltas since the last base — the
        # restore replay cost, bounded by EDL_CKPT_COMPACT_EVERY).
        self._m_ckpt_seconds = obs_metrics.histogram(
            "edl_ps_checkpoint_seconds",
            "Wall seconds per sparse checkpoint save, by kind",
            ("kind",),
        )
        self._m_ckpt_dirty_rows = obs_metrics.gauge(
            "edl_ps_ckpt_dirty_rows",
            "Rows carried by the most recent checkpoint save "
            "(all resident rows for a full base, dirty rows for a "
            "delta)",
        )
        self._m_ckpt_chain_len = obs_metrics.gauge(
            "edl_ps_ckpt_chain_len",
            "Deltas in the live checkpoint chain since its full base",
        )
        # Fleet-telemetry source (ISSUE 3): plain-int tallies kept
        # INDEPENDENTLY of the metrics registry (telemetry must work
        # with /metrics off), read by telemetry_blob() on the PS's 5 s
        # master poll. Unlocked increments: a GIL-level race costs at
        # most one count in a rate estimate — the detectors compare
        # magnitudes, not exact totals.
        self._t_push_count = 0
        self._t_pull_count = 0
        self._t_push_bytes = 0
        self._t_push_dense_bytes = 0
        self._t_pull_bytes = 0
        self._t_last_push_version = 0
        self._t_ckpt_dirty_rows = 0
        self._t_ckpt_chain_len = 0
        self._t_prev = None  # (timestamp, push_count, pull_count)
        # Table-health scan (ISSUE 15): shard-level aggregates the
        # telemetry blob carries between scans, the per-table gauges,
        # and the scan's rate limit. The scan runs on the poll loop
        # (ps/server.py), never on an RPC handler.
        from elasticdl_tpu.train.health import health_enabled

        self._health_scan_on = health_enabled()
        self._row_norm_max = env_float(ROW_NORM_MAX_ENV, 1e3)
        self._health_sample = max(
            8, env_int(HEALTH_SCAN_SAMPLE_ENV, 256)
        )
        # the sampling rides export_table (one full copy under the
        # per-table lock): past this resident-row count the copy —
        # and the lock hold the data plane pays for it — outweighs
        # the signal, so bigger tables are skipped with a log
        self._health_scan_max_rows = env_int(
            HEALTH_SCAN_MAX_ROWS_ENV, 262_144
        )
        self._health_scan_skipped = set()
        self._health_scan_secs = env_float(HEALTH_SCAN_SECS_ENV, 30.0)
        self._health_scan_at = 0.0
        self._t_row_norm_p50 = 0.0
        self._t_row_norm_p99 = 0.0
        self._t_dead_row_fraction = 0.0
        self._t_exploding_rows = 0
        self._m_row_norm = obs_metrics.gauge(
            "edl_ps_row_norm",
            "Sampled row-norm percentile per table",
            ("table", "quantile"),
        )
        self._m_exploding = obs_metrics.gauge(
            "edl_ps_exploding_rows",
            "Sampled rows with norm beyond EDL_HEALTH_ROW_NORM_MAX",
            ("table",),
        )
        self._m_dead_fraction = obs_metrics.gauge(
            "edl_ps_dead_row_fraction",
            "Evicted rows / (evicted + resident), from the lifecycle "
            "books (0 without a lifecycle)",
        )

    def telemetry_blob(self):
        """Piggyback payload for the PS's get_comm_info liveness poll:
        push/pull rates over the window since the previous blob, the
        store/pusher version lag, and the round-buffer fill the
        stuck-round detector watches."""
        now = time.time()
        push_count, pull_count = self._t_push_count, self._t_pull_count
        push_rate = pull_rate = 0.0
        if self._t_prev is not None:
            since, prev_push, prev_pull = self._t_prev
            window = max(1e-6, now - since)
            push_rate = (push_count - prev_push) / window
            pull_rate = (pull_count - prev_pull) / window
        self._t_prev = (now, push_count, pull_count)
        blob = pb.TelemetryBlob(
            role="ps-%d" % self._ps_id,
            push_rate=push_rate,
            pull_rate=pull_rate,
            version_lag=max(
                0, self._store.version - self._t_last_push_version
            ),
            model_version=self._store.version,
            round_buffer_fill=self._buffered_count(),
            push_bytes=self._t_push_bytes,
            pull_bytes=self._t_pull_bytes,
            ps_native_store=self._native_store,
            ps_ckpt_dirty_rows=self._t_ckpt_dirty_rows,
            ps_ckpt_chain_len=self._t_ckpt_chain_len,
            # table-health scan (ISSUE 15): last scan's shard-level
            # aggregates — sampled row-norm percentiles, dead-row
            # fraction from the lifecycle books, exploding-row count
            ps_row_norm_p50=self._t_row_norm_p50,
            ps_row_norm_p99=self._t_row_norm_p99,
            ps_dead_row_fraction=self._t_dead_row_fraction,
            ps_exploding_rows=self._t_exploding_rows,
            # overload plane (ISSUE 19): cumulative admission rejects
            # + the live backlog depth they key off, so the fleet's
            # ps_overload detector sees pushback without scraping
            ps_overload_rejections=self._t_overload_rejections,
            ps_pending_applies=self._pending_depth(),
        )
        # embedding lifecycle health (ISSUE 12): admission/eviction
        # tallies + the resident-row gauge the bounded-memory contract
        # is about, folded into the fleet /statusz beside the shard's
        # push/pull rates
        if self._lifecycle is not None:
            stats = self._lifecycle.stats()
            blob.ps_rows_admitted = stats["rows_admitted"]
            blob.ps_rows_evicted_ttl = stats["rows_evicted_ttl"]
            blob.ps_rows_evicted_lfu = stats["rows_evicted_lfu"]
            blob.ps_tracked_ids = stats["tracked_ids"]
            blob.ps_resident_rows = stats["resident_rows"]
        return blob

    def _stamp(self, response):
        """Stamp the boot-restore marker on a push/pull response."""
        response.restored_version = self._restored_wire
        return response

    # ------------------------------------------------------------------
    def push_model(self, request, context=None):
        """First writer wins: later pushes are ignored (reference:
        ps/parameters.py:129-159 init_from_model_pb only once)."""
        with self._lock:
            if not self._dense_initialized:
                self._dense = {
                    name: blob_to_ndarray(blob).copy()
                    for name, blob in request.dense_parameters.items()
                }
                self._dense_version = request.version
                self._dense_initialized = True
                logger.info(
                    "Initialized %d dense parameters at version %d",
                    len(self._dense),
                    request.version,
                )
        self._create_tables(request.embedding_table_infos)
        return pb.Empty()

    def push_embedding_table_infos(self, request, context=None):
        self._create_tables(request.embedding_table_infos)
        return pb.Empty()

    def _create_tables(self, infos):
        from elasticdl_tpu.ps.embedding_store import parse_initializer

        for info in infos:
            try:
                kind, param = parse_initializer(info.initializer)
            except ValueError:
                logger.warning(
                    "unknown initializer %r for table %s; using uniform",
                    info.initializer, info.name,
                )
                kind, param = "uniform", 0.05
            self._store.create_table(
                info.name, info.dim, init_scale=param, initializer=kind
            )
            if self._lifecycle is not None:
                # the lifecycle serves pre-admission pulls from the
                # initializer's deterministic cold row, so it needs the
                # parsed (kind, param) the store was created with
                self._lifecycle.register_table(
                    info.name, info.dim, init_kind=kind, init_param=param
                )
            self._m_table_rows.labels(table=info.name).set_function(
                lambda name=info.name: self._store.table_size(name)
            )

    def model_initialized(self):
        """This PS's /readyz milestone: cold-start dense parameters
        arrived, or at least one embedding table exists to serve —
        before either, a pull would hand out garbage."""
        with self._lock:
            if self._dense_initialized:
                return True
        return bool(self._store.table_names())

    def _buffered_count(self):
        # racy read for a gauge: list lengths are snapshots, no lock
        return len(self._round_buffer) + sum(
            len(group) for group in self._round_groups.values()
        )

    # ------------------------------------------------------------------
    def pull_dense_parameters(self, request, context=None):
        response = self._stamp(pb.PullDenseParametersResponse())
        with self._lock:
            response.initialized = self._dense_initialized
            response.version = self._dense_version
            if self._dense_initialized and request.version < self._dense_version:
                for name, array in self._dense.items():
                    ndarray_to_blob(array, response.dense_parameters[name])
        return response

    def _pull_table(self, name, ids, blob=None, reduced_ok=True):
        """Look up one table's rows and serialize them at the wire
        dtype, folding payload bytes into the counters.
        ``reduced_ok=False`` pins the payload to fp32 — for legacy
        clients that predate the wire-dtype contract and cannot decode
        extension dtype names."""
        wd = wire_dtype() if reduced_ok else None
        if self._lifecycle is not None:
            mask = self._lifecycle.filter_pull(name, ids)
            if not mask.all():
                # mixed pull: admitted rows gather from the store,
                # pre-admission ids get the initializer's cold row and
                # NEVER touch the store (a pull is a sighting, not a
                # materialization). The native single-call fast path
                # only applies to all-admitted pulls.
                values = self._lifecycle.cold_rows(name, ids.size)
                if mask.any():
                    values[mask] = self._store.lookup(name, ids[mask])
                blob = ndarray_to_blob(values, blob, wire_dtype=wd)
                payload = len(blob.content)
                self._t_pull_bytes += payload
                self._m_pull_bytes.labels(dtype=blob.dtype).inc(payload)
                self._m_pull_requests.labels(table=name).inc()
                self._m_pull_rows.labels(table=name).inc(int(ids.size))
                return blob
        if (
            self._native_store
            and _LITTLE_ENDIAN
            and (wd is None or wd.name in BLOB_DTYPE_CODES)
        ):
            # native fast path: lazy-init + gather + wire-dtype cast in
            # one GIL-released C call, serialized straight into the
            # response blob — no fp32 intermediate array, no astype
            content, dtype_name = self._store.lookup_blob(
                name, ids, wd.name if wd is not None else None
            )
            if blob is None:
                blob = pb.TensorBlob()
            blob.dtype = dtype_name
            del blob.dims[:]
            blob.dims.extend((int(ids.size), self._store.table_dim(name)))
            blob.content = content
        else:
            values = self._store.lookup(name, ids)
            blob = ndarray_to_blob(values, blob, wire_dtype=wd)
        payload = len(blob.content)
        self._t_pull_bytes += payload
        self._m_pull_bytes.labels(dtype=blob.dtype).inc(payload)
        self._m_pull_requests.labels(table=name).inc()
        self._m_pull_rows.labels(table=name).inc(int(ids.size))
        return blob

    def pull_embedding_vectors(self, request, context=None):
        self._admit_or_abort(context, "pull_embedding_vectors")
        ids = unpack_ids(request)
        self._t_pull_count += 1
        # a request carrying repeated ids (no packed blob) is from a
        # pre-ids_blob client, which also predates EDL_WIRE_DTYPE:
        # serve it plain fp32 or its blob_to_ndarray cannot resolve
        # the extension dtype name ("new servers always serve old
        # clients", docs/PERFORMANCE.md)
        legacy_peer = bool(request.ids) and not request.ids_blob
        return self._pull_table(
            request.name, ids, reduced_ok=not legacy_peer
        )

    def pull_embedding_batch(self, request, context=None):
        """Fused multi-table pull: one RPC serves every table's rows
        for this shard (request: ids-only IndexedSlicesProto per table;
        response: per-table row blobs aligned with the request's id
        order). The legacy per-table pull_embedding_vectors stays
        served for old peers."""
        self._admit_or_abort(context, "pull_embedding_batch")
        response = pb.PullEmbeddingBatchResponse(
            restored_version=self._restored_wire
        )
        self._t_pull_count += 1
        for name, slices in request.tables.items():
            self._pull_table(
                name, unpack_ids(slices), response.tables[name]
            )
        return response

    # ------------------------------------------------------------------
    def _count_push_bytes(self, request):
        """Fold one push's gradient payload bytes (tensor content +
        ids, either encoding) into the counters."""
        payload = 0
        dtype = "none"
        for slices in request.gradients.embedding_tables.values():
            payload += len(slices.concat_tensors.content)
            payload += len(slices.ids_blob) or 8 * len(slices.ids)
            dtype = slices.concat_tensors.dtype or dtype
        self._t_push_bytes += payload
        if payload:
            self._m_push_bytes.labels(dtype=dtype).inc(payload)
        # dense grads on the wire violate the dense-plane contract
        # (ISSUE 20); tally them separately so the violation is a
        # nonzero counter, not traffic blended into the sparse series
        dense_payload = sum(
            len(blob.content)
            for blob in request.gradients.dense_parameters.values()
        )
        if dense_payload:
            self._t_push_dense_bytes += dense_payload
            self._m_push_dense_bytes.inc(dense_payload)

    def _pending_depth(self):
        """Admission-control depth: in-flight push handlers plus the
        round buffer's overflow beyond one full sync round (a buffer
        holding more than grads_to_wait entries means rounds are
        arriving faster than they apply)."""
        with self._pending_lock:
            depth = self._pending_applies
        return depth + max(0, self._buffered_count() - self._grads_to_wait)

    def _admit_or_abort(self, context, method):
        """Admission control (ISSUE 19): once the apply backlog crosses
        EDL_PS_MAX_PENDING_APPLIES, answer with RESOURCE_EXHAUSTED plus
        an ``edl-retry-after-ms`` trailer instead of queueing more work
        — the clients' pushback pacing (common/overload.py) then
        spreads retries at the server's own hint, which is what caps
        retry amplification fleet-wide. In-process calls
        (context=None) are never rejected: admission protects the RPC
        boundary, not local test plumbing."""
        if context is None or self._max_pending <= 0:
            return
        depth = self._pending_depth()
        if depth < self._max_pending:
            if self._overloaded:
                self._overloaded = False
                logger.warning(
                    "PS %d overload cleared (depth %d < %d)",
                    self._ps_id, depth, self._max_pending,
                )
                if events.enabled():
                    events.emit(
                        "ps_overload_clear", ps_id=self._ps_id,
                        depth=depth,
                    )
            return
        self._t_overload_rejections += 1
        self._m_overload_rejected.labels(method=method).inc()
        # hint = (how far past the limit) x (observed seconds per
        # apply): the time until this caller's turn actually comes up,
        # so a paced retry usually lands instead of poll-and-missing
        # several times per freed slot. Floor 50ms before any apply has
        # been timed; clamped so a hint never parks a client longer
        # than a couple of seconds.
        excess = max(1, depth - self._max_pending + 1)
        apply_secs = self._apply_ewma_secs
        retry_ms = int(min(2000, max(50, 1000.0 * apply_secs * excess)))
        if not self._overloaded:
            self._overloaded = True
            logger.warning(
                "PS %d overloaded: apply backlog %d >= %d, pushing "
                "back (retry-after %dms)",
                self._ps_id, depth, self._max_pending, retry_ms,
            )
            if events.enabled():
                events.emit(
                    "ps_overload_enter", ps_id=self._ps_id,
                    depth=depth, max_pending=self._max_pending,
                    method=method,
                )
        context.set_trailing_metadata(
            ((overload.RETRY_AFTER_KEY, str(retry_ms)),)
        )
        context.abort(
            grpc.StatusCode.RESOURCE_EXHAUSTED,
            "apply backlog %d >= %d on ps-%d; retry after %dms"
            % (depth, self._max_pending, self._ps_id, retry_ms),
        )

    def push_gradients(self, request, context=None):
        self._admit_or_abort(context, "push_gradients")
        with self._pending_lock:
            self._pending_applies += 1
        started = time.monotonic()
        try:
            # the overload fault (testing/faults.py) lands HERE, inside
            # an occupied admission slot, so injected latency builds
            # the same backlog real slow applies would (and is timed
            # into the hint calibration like real slowness)
            injected = faults.apply_delay("push_gradients")
            if injected:
                time.sleep(injected)
            return self._push_gradients_admitted(request)
        finally:
            elapsed = time.monotonic() - started
            with self._pending_lock:
                self._pending_applies -= 1
                if self._apply_ewma_secs:
                    self._apply_ewma_secs += 0.2 * (
                        elapsed - self._apply_ewma_secs
                    )
                else:
                    self._apply_ewma_secs = elapsed

    def _push_gradients_admitted(self, request):
        self._t_push_count += 1
        self._t_last_push_version = request.gradients.version
        self._m_push_requests.inc()
        self._count_push_bytes(request)
        self._m_version_lag.set(
            self._store.version - request.gradients.version
        )
        if getattr(self, "_stopped", False):
            # SIGTERM drain already flushed the round buffer and is
            # saving the final checkpoint: an update admitted now
            # would be ACKed yet missing from the state the successor
            # restores. Reject so the worker retries/resyncs against
            # the relaunch instead. (The sync path re-checks under
            # _push_lock, where _stopped is set — this early check is
            # what the lock-free async path gets.)
            return self._stamp(pb.PushGradientsResponse(
                accepted=False, version=self._store.version
            ))
        if not self._use_async:
            return self._push_gradients_sync(request)
        grad_version = request.gradients.version
        lr_scale = 1.0
        if self._staleness_modulation:
            diff = self._store.version - grad_version
            lr_scale = 1.0 / max(1, diff) if diff > 0 else 1.0
        if request.lr_scale > 0:
            lr_scale *= request.lr_scale
        apply_start = time.time()
        self._apply_tables(
            request.gradients.embedding_tables.items(), lr_scale
        )
        self._m_apply_seconds.labels(backend=self._backend).observe(
            time.time() - apply_start
        )
        trace.complete("ps_apply_push", apply_start,
                       version=grad_version)
        self._store.bump_version()
        version = self._store.version
        self._maybe_checkpoint(version)
        self._maybe_report_version(version)
        return self._stamp(
            pb.PushGradientsResponse(accepted=True, version=version)
        )

    def _apply_tables(self, items, lr_scale):
        """Apply every table's pushed gradients, fanning out across
        the EDL_PS_APPLY_THREADS pool when one is configured. Safe to
        parallelize per table: the native store locks per table, the
        numpy store serializes on its store lock — either way each
        table's apply is atomic, and cross-table order never mattered
        (tables are disjoint row spaces)."""
        items = list(items)
        if self._apply_pool is not None and len(items) > 1:
            apply_one = trace.bind_context(self._apply_one)
            list(self._apply_pool.map(
                lambda pair: apply_one(pair[0], pair[1], lr_scale),
                items,
            ))
            return
        for name, slices in items:
            self._apply_one(name, slices, lr_scale)

    def _apply_one(self, name, slices, lr_scale):
        """One table's deserialize+dedup+apply. Native store + packed
        wire payload: a single GIL-released C call. Otherwise:
        numpy-array path with the identical pipeline — dedup first,
        then one vectorized optimizer apply per unique id. (Both
        branches share the dedup-then-apply semantics on purpose: the
        sync path's round merge already dedups, gradient summation
        over duplicates is the IndexedSlices contract, and the parity
        suite asserts the two branches bit-match.)"""
        if self._lifecycle is not None:
            req_ids = unpack_ids(slices)
            mask = self._lifecycle.filter_push(name, req_ids)
            if not mask.all():
                # pre-admission gradients are DROPPED (the admission
                # contract): apply only the admitted subset through
                # the numpy path — the single-call blob path has no
                # row filter
                if not mask.any():
                    return
                values, ids = _deserialize_gradients(slices)
                values, ids = deduplicate_indexed_slices(
                    values[mask], ids[mask]
                )
                self._store.push_gradients(
                    name, ids, values, lr_scale=lr_scale
                )
                return
        if self._native_store and _blob_fast_path_ok(
            self._store, name, slices
        ):
            self._store.push_gradients_blob(
                name,
                np.frombuffer(slices.ids_blob, dtype="<i8"),
                slices.concat_tensors.content,
                slices.concat_tensors.dtype,
                lr_scale=lr_scale,
            )
            return
        values, ids = _deserialize_gradients(slices)
        values, ids = deduplicate_indexed_slices(values, ids)
        self._store.push_gradients(name, ids, values, lr_scale=lr_scale)

    def push_embedding_rows(self, request, context=None):
        """Device-tier writeback (ISSUE 6): raw row values overwrite
        the store — an eviction or flush of the worker's HBM hot set
        handing authority over those rows back to this spillover tier.
        No optimizer math and no version bump: the values already
        carry every update the tier applied in device memory (a bump
        here would also perturb sync-round pairing, and the tier is an
        async-PS feature). Existing rows keep their optimizer slot
        state; rows unseen by this shard materialize fresh."""
        if getattr(self, "_stopped", False):
            # SIGTERM drain: the final checkpoint is (being) written —
            # importing rows now would ACK a flush the successor never
            # restores (and mutate the store mid-save). The client
            # raises on the rejection, so a draining worker's ack
            # honestly reports tier_flushed=False instead of claiming
            # parity that does not hold.
            return self._stamp(pb.PushGradientsResponse(
                accepted=False, version=self._store.version
            ))
        self._m_rows_written.inc(
            sum(
                len(slices.ids) or len(slices.ids_blob) // 8
                for slices
                in request.embedding_tables.values()
            )
        )
        for name, slices in request.embedding_tables.items():
            if self._native_store and _blob_fast_path_ok(
                self._store, name, slices
            ):
                # raw-row import straight from the wire bytes: one
                # GIL-released C call, no numpy intermediates
                self._store.import_blob(
                    name,
                    np.frombuffer(slices.ids_blob, dtype="<i8"),
                    slices.concat_tensors.content,
                    slices.concat_tensors.dtype,
                )
                continue
            values, ids = _deserialize_gradients(slices)
            self._store.import_table(name, ids, values)
        if self._lifecycle is not None:
            # writebacks are authoritative: the rows exist after the
            # import, so they must be admitted (and TTL-refreshed) or
            # the eviction bound would never see them age out — and
            # the device tier's hot set can never be starved by a
            # PS-side eviction racing its writeback
            for name, slices in request.embedding_tables.items():
                self._lifecycle.note_import(name, unpack_ids(slices))
        return self._stamp(pb.PushGradientsResponse(
            accepted=True, version=self._store.version
        ))

    def _push_gradients_sync(self, request):
        """Sync push with the journal I/O outside the push lock:
        events decided while holding ``_push_lock`` are written only
        after it is released (same discipline as task_dispatcher) — a
        slow journal flush must not serialize every worker's push.
        Gradient deserialization is hoisted out of the lock too: it is
        pure per-request CPU work, and under it every peer's push of
        the round serializes behind one worker's decode."""
        tables = {
            name: _deserialize_gradients(slices)
            for name, slices
            in request.gradients.embedding_tables.items()
        }
        journal = []
        try:
            return self._push_gradients_sync_locked_path(
                request, tables, journal
            )
        finally:
            for event, fields in journal:
                events.emit(event, **fields)

    def _push_gradients_sync_locked_path(self, request, tables, journal):
        """Sync SGD: accumulate grads_to_wait pushes, reject stale ones
        (reference ps/servicer.py:166-236; sparse grads are summed, as
        there — each worker contributes disjoint-sign updates to the
        rows it touched).

        Two pairing disciplines:

        - counting (default, reference semantics): the first
          grads_to_wait accepted pushes form a round, whoever sent
          them — right for free-running workers.
        - round-scoped (``request.round_scoped``, set by lockstep
          trainers whose tags are exact global round counters): pushes
          are grouped BY TAG and a round applies only when its own
          tag's group fills. Counting applied to lockstep traffic lets
          one worker's round-r and round-r+1 pushes pair with each
          other whenever its pushes lag its rounds (host contention),
          which drives the store version ahead of the laggard and
          causes chronic spurious rejections.
        """
        grad_version = request.gradients.version
        with self._push_lock:
            version = self._store.version
            if getattr(self, "_stopped", False):
                # lost the lock race against graceful_stop: the round
                # buffer this push would join was already flushed into
                # the final checkpoint — buffering now silently drops
                # an ACKed update
                self._m_push_rejected.inc()
                return self._stamp(pb.PushGradientsResponse(
                    accepted=False, version=version
                ))
            if grad_version < version - self._sync_tolerance:
                self._m_push_rejected.inc()
                journal.append((
                    "stale_push_rejected",
                    dict(
                        worker=(
                            request.worker_id
                            if request.HasField("worker_id") else -1
                        ),
                        version=grad_version, store_version=version,
                    ),
                ))
                return self._stamp(pb.PushGradientsResponse(
                    accepted=False, version=version
                ))
            # Per-push lr_scale cannot be folded into gradient values:
            # Adam's update is invariant to gradient scaling (the scale
            # would be a silent no-op) and for momentum/adagrad scaling
            # corrupts slot-state semantics. Buffer raw grads and carry
            # the mean of the pushes' scales through to the kernel's lr
            # at apply time (workers in a sync round share one schedule,
            # so the mean is the schedule value).
            push_scale = request.lr_scale if request.lr_scale > 0 else 1.0
            key = None
            if request.HasField("worker_id"):
                # Incarnations are MONOTONIC (worker process start
                # time): evict only buffered entries from OLDER
                # incarnations of this worker (dead predecessors'
                # orphaned half-rounds), and symmetric protection — an
                # in-flight push from a dead predecessor delivered
                # AFTER the relaunch's push must not evict the live
                # entry: it is itself the orphan, so it is dropped
                # (accepted=True keeps the dead sender's socket happy;
                # nothing retries it). A push with worker_id but NO
                # incarnation (older client) falls back to the
                # replace-by-worker_id semantics.
                incarnation = (
                    request.incarnation
                    if request.HasField("incarnation")
                    else None
                )
                key = (request.worker_id, incarnation)
                same_worker = [
                    entry for entry in self._buffered_entries()
                    if entry[0] is not None
                    and entry[0][0] == request.worker_id
                    and (incarnation is None
                         or entry[0][1] != incarnation)
                ]
                if incarnation is not None and any(
                    e[0][1] is not None and e[0][1] > incarnation
                    for e in same_worker
                ):
                    self._m_push_dropped_dead.inc()
                    journal.append((
                        "dead_incarnation_dropped",
                        dict(worker=request.worker_id,
                             incarnation=incarnation, version=version),
                    ))
                    logger.warning(
                        "sync PS: dropping a delayed push from worker "
                        "%d's dead incarnation %d (a newer incarnation "
                        "already holds this round). If this worker is "
                        "LIVE, its epoch source is mis-ordered (e.g. a "
                        "master restarted onto a stepped-back clock) — "
                        "restart the job",
                        request.worker_id, incarnation,
                    )
                    return self._stamp(pb.PushGradientsResponse(
                        accepted=True, version=version
                    ))
                for entry in same_worker:
                    self._remove_buffered_locked(entry)
                    logger.warning(
                        "sync PS: worker %d re-pushed at version %d "
                        "under a new incarnation — dropping its dead "
                        "predecessor's buffered half-round",
                        request.worker_id, version,
                    )
            entry = (key, tables, push_scale)
            if events.enabled():
                # round_open on the first push buffered toward THIS
                # round (per-tag for scoped pushers: concurrent tags
                # each get their open, so the postmortem's opened vs
                # closed counts balance), round_fill on every buffered
                # push — the journal answer to "why did the sync round
                # stop filling"
                if request.round_scoped:
                    opened = not self._round_groups.get(grad_version)
                else:
                    opened = not self._round_buffer
                if opened:
                    journal.append(
                        ("round_open", dict(version=grad_version))
                    )
                journal.append((
                    "round_fill",
                    dict(
                        version=grad_version,
                        fill=self._buffered_count() + 1,
                        worker=(
                            request.worker_id
                            if request.HasField("worker_id") else -1
                        ),
                    ),
                ))
            if request.round_scoped:
                group = self._round_groups.setdefault(grad_version, [])
                if key is not None:
                    # tag + (worker_id, incarnation) uniquely identify
                    # a logical lockstep push: a transport-level
                    # re-send (the response was lost after the server
                    # buffered — the at-least-once window in
                    # ps_client's retry) must REPLACE, not count twice
                    group[:] = [e for e in group if e[0] != key]
                group.append(entry)
                if len(group) < self._grads_to_wait:
                    return self._stamp(pb.PushGradientsResponse(
                        accepted=True, version=version
                    ))
                del self._round_groups[grad_version]
                self._apply_round_locked(group, journal)
            else:
                self._round_buffer.append(entry)
                if len(self._round_buffer) < self._grads_to_wait:
                    return self._stamp(pb.PushGradientsResponse(
                        accepted=True, version=version
                    ))
                self._apply_round_locked(self._round_buffer, journal)
                self._round_buffer = []
            self._store.bump_version()
            version = self._store.version
        self._maybe_checkpoint(version)
        self._maybe_report_version(version)
        return self._stamp(
            pb.PushGradientsResponse(accepted=True, version=version)
        )

    def _buffered_entries(self):
        for entry in self._round_buffer:
            yield entry
        for group in self._round_groups.values():
            yield from group

    def _remove_buffered_locked(self, entry):
        # Removal is by IDENTITY, never list equality: entries are
        # (key, {name: numpy arrays}, scale) tuples, and `in`/`remove`
        # would == -compare a key-equal NEIGHBOR on the way to the
        # target (e.g. a straggler's same-incarnation double push),
        # tripping numpy's "truth value of an array is ambiguous"
        # inside the push RPC handler (ADVICE round 5 #2).
        kept = [e for e in self._round_buffer if e is not entry]
        if len(kept) != len(self._round_buffer):
            self._round_buffer[:] = kept
            return
        for tag, group in list(self._round_groups.items()):
            kept = [e for e in group if e is not entry]
            if len(kept) != len(group):
                if kept:
                    group[:] = kept
                else:
                    del self._round_groups[tag]
                return

    def _apply_round_locked(self, entries, journal):
        """Merge and apply one completed round's buffered pushes.
        Caller holds the push lock and bumps the store version;
        ``journal`` collects events the caller emits after release."""
        with trace.span(
            "ps_apply_round", version=self._store.version,
            pushes=len(entries),
        ):
            self._merge_apply_locked(entries, journal)
        journal.append((
            "round_close",
            dict(version=self._store.version, pushes=len(entries)),
        ))
        # GC scoped groups that can never fill: their tag is already
        # older than anything the stale check would admit (the check
        # rejects tags < version - tolerance, and version only grows)
        floor = self._store.version - self._sync_tolerance
        for tag in [t for t in self._round_groups if t < floor]:
            logger.warning(
                "sync PS: dropping %d unfillable buffered push(es) at "
                "stale round tag %d",
                len(self._round_groups[tag]), tag,
            )
            del self._round_groups[tag]

    def _merge_apply_locked(self, entries, journal=None):
        scales = [s for _, _, s in entries]
        apply_scale = sum(scales) / len(scales)
        merged = {}  # name -> ([values...], [ids...])
        for _, tables, scale in entries:
            for name, (values, ids) in tables.items():
                # Unequal per-push scales (e.g. a late joiner
                # mid-warmup admitted by sync_version_tolerance)
                # can't be expressed exactly in one
                # adaptive-optimizer apply; re-weight each push by
                # scale/apply_scale — exact for SGD, and for
                # slot-state optimizers the ratio is 1 in the
                # common equal-schedule case so no corruption is
                # introduced.
                if scale != apply_scale:
                    values = values * (scale / apply_scale)
                bucket = merged.setdefault(name, ([], []))
                bucket[0].append(values)
                bucket[1].append(ids)
        for name, (values_list, ids_list) in merged.items():
            values = np.concatenate(values_list, axis=0)
            ids = np.concatenate(ids_list, axis=0)
            # merge duplicate ids across workers into one apply
            values, ids = deduplicate_indexed_slices(values, ids)
            if self._lifecycle is not None:
                # admission gate under the push lock: journal entries
                # ride the round's journal list (emitted after release)
                mask = self._lifecycle.filter_push(
                    name, ids, journal=journal
                )
                if not mask.any():
                    continue
                values, ids = values[mask], ids[mask]
            self._store.push_gradients(
                name, ids, values, lr_scale=apply_scale
            )

    def graceful_stop(self):
        """SIGTERM drain (ISSUE 7, ps/server.py): the pod manager stops
        PS pods with SIGTERM, which skips atexit — before this, a
        buffered partial sync round and everything since the last
        periodic checkpoint died with the pod. Apply whatever the round
        buffer holds (an under-filled round applied beats losing its
        pushes outright — the relaunch re-anchors at the checkpoint
        version and workers resync, exactly the ISSUE-4 machinery),
        then save a final COMPLETE checkpoint so the successor restores
        the freshest possible state. Idempotent; every step guarded —
        a failed flush must not stop the exit."""
        journal = []
        with self._push_lock:
            if getattr(self, "_stopped", False):
                return
            self._stopped = True
            entries = list(self._buffered_entries())
            if entries:
                logger.warning(
                    "SIGTERM with %d buffered push(es); applying the "
                    "partial round before exit", len(entries),
                )
                try:
                    self._apply_round_locked(entries, journal)
                    self._round_buffer = []
                    self._round_groups = {}
                    self._store.bump_version()
                except Exception:
                    logger.exception(
                        "partial-round flush failed at SIGTERM"
                    )
            version = self._store.version
        for event, fields in journal:
            events.emit(event, **fields)
        if self._ckpt_async is not None:
            # abandon anything pending: the synchronous final FULL
            # save below supersedes every enqueued delta
            self._ckpt_async.stop(drain=False)
        if self._checkpoint_saver is not None:
            try:
                self._save_checkpoint_now(
                    version, "sparse_final", force_full=True
                )
                logger.info(
                    "final sparse checkpoint saved at version %d",
                    version,
                )
            except Exception:
                logger.exception("final sparse checkpoint failed")
        events.flush()

    # edlint: thread=ps-poll
    def lifecycle_tick(self):
        """One TTL/LFU eviction sweep (ps/server.py calls this on its
        5 s master poll). No-op without a lifecycle. Returns the
        sweep's {"ttl": n, "lfu": n} eviction counts."""
        if self._lifecycle is None:
            return None
        return self._lifecycle.sweep()

    # edlint: thread=ps-poll
    def table_health_scan(self, force=False):
        """Table-health scan (ISSUE 15), on the poll loop — NEVER on
        an RPC handler: sampled per-table row-norm percentiles, the
        shard's dead-row fraction from the lifecycle books, and a
        count of sampled rows whose norm exceeds
        EDL_HEALTH_ROW_NORM_MAX. A dead table (norms collapsing to the
        initializer scale) or an exploding one is invisible to
        loss-side sentinels until serving quality craters — the PS
        watches its own rows. Rate-limited by EDL_HEALTH_SCAN_SECS;
        exports each table once per scan (the per-table lock is held
        for the export only), then samples at most
        EDL_HEALTH_SCAN_SAMPLE rows host-side. Returns the scan dict,
        or None when skipped (rate limit / EDL_HEALTH=0)."""
        if not self._health_scan_on:
            return None
        now = time.time()
        if not force and now - self._health_scan_at < self._health_scan_secs:
            return None
        self._health_scan_at = now
        pooled = []
        exploding_total = 0
        per_table = {}
        for name in self._store.table_names():
            try:
                size = self._store.table_size(name)
            except KeyError:
                continue
            if size > self._health_scan_max_rows:
                # export_table copies the WHOLE table under its lock;
                # past the cap that copy stalls the data plane for a
                # 256-row sample — skip, once-logged per table
                if name not in self._health_scan_skipped:
                    self._health_scan_skipped.add(name)
                    logger.warning(
                        "table-health scan skipping %s: %d resident "
                        "rows > %s=%d (the scan's full-table export "
                        "would stall pushes)", name, size,
                        HEALTH_SCAN_MAX_ROWS_ENV,
                        self._health_scan_max_rows,
                    )
                continue
            try:
                _ids, values = self._store.export_table(name)
            except KeyError:
                continue
            if values.shape[0] == 0:
                continue
            if values.shape[0] > self._health_sample:
                stride = values.shape[0] // self._health_sample
                values = values[::stride][: self._health_sample]
            norms = np.sqrt(
                np.sum(np.square(values.astype(np.float32)), axis=1)
            )
            p50 = float(np.percentile(norms, 50))
            p99 = float(np.percentile(norms, 99))
            exploding = int(np.sum(norms > self._row_norm_max))
            self._m_row_norm.labels(table=name, quantile="p50").set(p50)
            self._m_row_norm.labels(table=name, quantile="p99").set(p99)
            self._m_exploding.labels(table=name).set(exploding)
            pooled.append(norms)
            exploding_total += exploding
            per_table[name] = {
                "p50": p50, "p99": p99, "exploding": exploding,
                "sampled": int(norms.size),
            }
        if pooled:
            norms = np.concatenate(pooled)
            self._t_row_norm_p50 = float(np.percentile(norms, 50))
            self._t_row_norm_p99 = float(np.percentile(norms, 99))
        dead_fraction = 0.0
        if self._lifecycle is not None:
            stats = self._lifecycle.stats()
            evicted = (
                stats["rows_evicted_ttl"] + stats["rows_evicted_lfu"]
            )
            alive = stats["resident_rows"]
            if evicted + alive > 0:
                dead_fraction = evicted / float(evicted + alive)
        self._m_dead_fraction.set(dead_fraction)
        self._t_dead_row_fraction = dead_fraction
        if exploding_total > 0 and self._t_exploding_rows == 0:
            # journal the EDGE only: a chronically hot table must not
            # flood the journal once per scan
            events.emit(
                "health_table_exploding", ps=self._ps_id,
                rows=exploding_total,
                tables=sorted(
                    t for t, d in per_table.items() if d["exploding"]
                ),
                norm_max=self._row_norm_max,
            )
        self._t_exploding_rows = exploding_total
        return {
            "tables": per_table,
            "dead_row_fraction": dead_fraction,
            "exploding_rows": exploding_total,
        }

    # edlint: thread=ps-poll
    def maybe_stream_checkpoint(self, watermark, every):
        """Watermark-driven sparse checkpoint cadence (ISSUE 12): in
        streaming mode there are no epoch boundaries and the version
        clock ticks at worker-push rate, so durability rides the
        master's record watermark instead — one checkpoint each time
        it crosses an ``every``-records boundary (EDL_STREAM_
        CHECKPOINT_EVERY, threaded through ps/server.py's poll loop).
        A fresh-boot PS saves from the first crossed boundary; a PS
        that RESTORED a checkpoint anchors at its first observed
        watermark instead — its predecessor already covered those
        boundaries, and re-saving them would burn checkpoint slots on
        state the restore just wrote."""
        if (
            self._checkpoint_saver is None
            or every <= 0
            or watermark <= 0
        ):
            return False
        boundary = int(watermark) // int(every)
        last = getattr(self, "_stream_ckpt_boundary", None)
        if last is None:
            last = boundary if self._restored_wire else 0
            self._stream_ckpt_boundary = last
        if boundary <= last:
            return False
        self._stream_ckpt_boundary = boundary
        version = self._store.version
        events.emit("stream_watermark", watermark=int(watermark),
                    kind="checkpoint")
        logger.info(
            "stream checkpoint at watermark %d (version %d)",
            watermark, version,
        )
        return self._request_checkpoint(version, "sparse_stream")

    def _save_checkpoint_now(self, version, kind, force_full=False):
        """One synchronous checkpoint save + its metrics/journal —
        shared by the inline path, the AsyncCheckpointer thread, and
        the SIGTERM final full save. Raises on failure (callers own
        the degrade-don't-crash decision)."""
        start = time.time()
        result = self._checkpoint_saver.save(
            version, self._store, force_full=force_full
        )
        elapsed = time.time() - start
        self._m_ckpt_seconds.labels(kind=result.kind).observe(elapsed)
        self._m_ckpt_dirty_rows.set(result.rows)
        self._m_ckpt_chain_len.set(result.chain_len)
        self._t_ckpt_dirty_rows = result.rows
        self._t_ckpt_chain_len = result.chain_len
        events.emit(
            "checkpoint_saved", version=version, kind=kind,
            mode=result.kind, rows=result.rows,
            tombstones=result.tombstones, chain_len=result.chain_len,
        )

    def _request_checkpoint(self, version, kind):
        """Trigger a save at ``version``: enqueue on the checkpoint
        thread (the off-RPC default — returns once the request is
        REGISTERED, with bursts coalesced into the newest version), or
        run inline under EDL_CKPT_ASYNC=0. Returns True when the save
        was enqueued/completed; a failed INLINE save logs and returns
        False (a checkpoint failure must never fail the push RPC that
        tripped the cadence)."""
        if self._ckpt_async is not None:
            return self._ckpt_async.request(version, kind)
        try:
            self._save_checkpoint_now(version, kind)
            return True
        except Exception:
            logger.exception("sparse checkpoint failed")
            return False

    def finish_checkpoints(self, timeout=30.0):
        """Drain the checkpoint thread (orderly shutdown paths: the
        master-gone exit must not abandon an enqueued save that the
        relaunch would then have to live without)."""
        if self._ckpt_async is not None:
            self._ckpt_async.stop(drain=True, timeout=timeout)

    def _maybe_checkpoint(self, version):
        if (
            self._checkpoint_saver is not None
            and self._checkpoint_steps > 0
            and version % self._checkpoint_steps == 0
        ):
            self._request_checkpoint(version, "sparse")

    def _maybe_report_version(self, version):
        if self._master_client is not None:
            self._master_client.report_version(version)
