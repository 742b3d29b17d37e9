"""The elastic worker: pulls tasks, runs the jitted JAX step.

Reference parity: elasticdl/python/worker/worker.py (the ~900-line TF2
eager loop). The TPU redesign collapses most of it: there is no
get_model()/report_gradient() PS round trip on the dense path (the
optimizer update happens inside the compiled step, worker-side), so the
hot loop is read records -> parse -> device step. What survives from the
reference is the *protocol*: the continuous task stream with record-level
accounting (task_data_service), eval/predict interleave, the train-end
callback task, and reporting model versions so the master can trigger
evaluations.
"""

import os
import threading
import time

import numpy as np

from elasticdl_tpu.common import overload, timing_utils
from elasticdl_tpu.common.constants import Mode
from elasticdl_tpu.common.env_utils import env_float, env_str
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import events
from elasticdl_tpu.observability import metrics as obs_metrics
from elasticdl_tpu.observability import trace
from elasticdl_tpu.data.pipeline import (
    Dataset,
    batch_real_count,
    normalize_outputs,
)
from elasticdl_tpu.models.registry import get_model_spec
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.train import step_fns
from elasticdl_tpu.train.health import HealthSentinelError
from elasticdl_tpu.worker.task_data_service import TaskDataService
from elasticdl_tpu.worker.trainer import build_trainer

logger = _logger_factory("elasticdl_tpu.worker.worker")


class CheckpointRestoreError(RuntimeError):
    """Fatal: --checkpoint_dir_for_init was given but restore failed."""


class MeshEpochChanged(RuntimeError):
    """The alive-host set changed: this process must restart, rejoin the
    mesh at the new epoch, and resume from the latest checkpoint (the
    elastic-SPMD answer to the reference's Horovod re-init + broadcast,
    allreduce_trainer.py:66-118). Raised out of the training loop;
    worker main exits with EPOCH_RESTART_EXIT_CODE so the pod manager
    relaunches the pod."""


EPOCH_RESTART_EXIT_CODE = 3


class _BatchPoller:
    """Non-blocking view over a (possibly blocking) batch iterator.

    The lockstep loop must never block inside ``next()``: the iterator
    chain ends in the master's get_task, which answers WAIT while a
    peer holds the last task — and the peer is meanwhile blocked in the
    consensus collective waiting for us. A pump thread absorbs the
    blocking; ``poll`` returns (batch|None, ended) within the timeout.
    Iterator exceptions surface on the consuming thread."""

    _END = object()

    def __init__(self, batches):
        import queue

        self._queue = queue.Queue(maxsize=1)
        self._ended = False
        self._thread = threading.Thread(
            target=self._pump, args=(batches,), name="lockstep-batch-pump",
            daemon=True,
        )
        self._thread.start()

    def _pump(self, batches):
        try:
            for batch in batches:
                self._queue.put(batch)
            self._queue.put(self._END)
        # the error IS surfaced: poll() re-raises it on the consumer
        # thread, where the task-failure machinery runs
        except BaseException as e:  # edlint: disable=ft-swallowed-except
            self._queue.put(e)

    def poll(self, timeout):
        import queue

        if self._ended:
            return None, True
        try:
            item = self._queue.get(timeout=timeout)
        except queue.Empty:
            return None, False
        if item is self._END:
            self._ended = True
            return None, True
        if isinstance(item, BaseException):
            self._ended = True
            raise item
        return item, False


class Worker:
    def __init__(
        self,
        master_client,
        model_zoo_module,
        data_reader,
        minibatch_size=32,
        mode=Mode.TRAINING,
        compute_dtype=None,
        report_version_steps=10,
        wait_sleep_secs=2.0,
        seed=0,
        trainer_factory=None,
        mesh_config=None,
        grad_accum_steps=1,
        ps_addrs=None,
        checkpoint_dir="",
        checkpoint_steps=0,
        keep_checkpoint_max=3,
        async_checkpoint=False,
        checkpoint_dir_for_init="",
        multihost_runtime=None,
        resume_optional=False,
        sparse_pipeline=False,
        sparse_cache_staleness=0,
        sparse_push_interval=1,
        consensus_interval=1,
        model_def="",
        model_params="",
        symbol_overrides=None,
        log_loss_steps=100,
        ledger=None,
    ):
        self._mc = master_client
        self.spec = get_model_spec(
            model_zoo_module, model_def=model_def,
            model_params=model_params,
            symbol_overrides=symbol_overrides,
        )
        self._log_loss_steps = log_loss_steps
        self._reader = data_reader
        self._minibatch_size = minibatch_size
        self._mode = mode
        self._report_version_steps = report_version_steps
        self._wait_sleep_secs = wait_sleep_secs
        self.tds = TaskDataService(
            master_client, data_reader, wait_sleep_secs=wait_sleep_secs
        )
        ps_client = None
        if self.spec.sparse_embedding_specs:
            # Sparse model: host-PS embedding tables + dense on device.
            if not ps_addrs:
                raise ValueError(
                    "Model %s declares sparse_embedding_specs; the worker "
                    "needs --ps_addrs pointing at parameter servers"
                    % model_zoo_module
                )
            from elasticdl_tpu.worker.ps_client import PSClient

            ps_client = PSClient(
                ps_addrs, worker_id=self._mc.worker_id,
                # master-assigned relaunch epoch (reset_worker in
                # worker/main.py) so a relaunch on a clock-skewed host
                # still orders after its dead predecessor at the sync PS
                incarnation=getattr(self._mc, "incarnation", None),
            )
        self.trainer = build_trainer(
            self.spec,
            trainer_factory,
            minibatch_size=minibatch_size,
            compute_dtype=compute_dtype,
            seed=seed,
            mesh_config=mesh_config,
            grad_accum_steps=grad_accum_steps,
            ps_client=ps_client,
            cache_staleness=sparse_cache_staleness,
        )
        # lockstep multi-host SPMD: the trainer's mesh spans jax
        # processes and exposes the consensus collective
        # (parallel/multihost_trainer.py)
        self._lockstep = self.trainer.lockstep
        # pipelined sparse stream only where it exists AND the model is
        # sparse (async-PS staleness envelope; sparse.py train_stream)
        self._sparse_pipeline = bool(
            sparse_pipeline
            and self.spec.sparse_embedding_specs
            and self.trainer.streams
        )
        self._sparse_push_interval = max(1, sparse_push_interval)
        self.state = None
        self.stop_training = False
        # graceful drain (ISSUE 7): set by begin_drain (SIGTERM hook /
        # scale-down victim); the run loop finishes the current task,
        # joins pushes, flushes the device tier, and deregisters
        self._draining = False
        self._drain_reason = ""
        self._drain_done = False
        # (epoch seconds the request arrived, the step the loop was
        # in), noted by begin_drain and journaled by _finish_drain
        self._drain_requested = None
        self._version = 0
        # the newest dispatched step while nothing of it has been read:
        # (its number, its batch, its ``trainer.PendingStep``); see
        # ``_after_train_batch``
        self._in_flight = None
        # Dense full-state checkpoints (params + model_state + optimizer
        # slots + step; the reference drops slot state,
        # ps/parameters.py:194-199). Restore happens lazily on the first
        # batch, when the state template/shardings exist.
        self._checkpoint_steps = checkpoint_steps
        self._checkpoint_mgr = None
        self._init_checkpoint_dir = checkpoint_dir_for_init
        self._restore_attempted = not checkpoint_dir_for_init
        # lenient restore: elastic restarts default the init dir to the
        # job's own checkpoint dir, which legitimately holds nothing on
        # first launch — fresh init then, instead of a fatal error. An
        # operator's explicit --checkpoint_dir_for_init stays strict.
        self._resume_optional = resume_optional
        if checkpoint_dir and checkpoint_steps:
            from elasticdl_tpu.train.checkpoint import (
                DenseCheckpointManager,
            )

            if async_checkpoint and self._lockstep:
                # orbax async saves are cross-process coordination on
                # top of cross-process collectives; unproven here —
                # keep the lockstep path on the measured sync mode
                logger.warning(
                    "--async_checkpoint ignored under lockstep "
                    "multi-host (sync saves only)"
                )
            self._checkpoint_mgr = DenseCheckpointManager(
                checkpoint_dir,
                keep_max=keep_checkpoint_max,
                async_save=async_checkpoint and not self._lockstep,
            )
        if checkpoint_dir and not checkpoint_steps:
            logger.warning(
                "--checkpoint_dir=%r given without --checkpoint_steps; "
                "NO checkpoints will be written",
                checkpoint_dir,
            )
        if self.spec.sparse_embedding_specs and (
            self._checkpoint_mgr is not None or checkpoint_dir_for_init
        ):
            # Checkpoint responsibility is split: the worker snapshots the
            # dense TrainState; embedding tables are checkpointed by the
            # parameter servers themselves (--checkpoint_dir on the PS,
            # ps/server.py), as in the reference. Worker flags alone do
            # NOT cover the embeddings.
            logger.warning(
                "Sparse model: worker checkpoint flags cover only the "
                "dense state; pass --checkpoint_dir/--checkpoint_dir_for_"
                "init to the parameter servers to snapshot/restore "
                "embedding tables"
            )
        self._callbacks = list(self.spec.callbacks() or [])
        # --output works for every model, not only those declaring an
        # exporter: add the default (it no-ops unless the train-end task
        # carries saved_model_path; reference behavior, callbacks.py:25)
        from elasticdl_tpu.train.callbacks import SavedModelExporter

        if not any(
            isinstance(cb, SavedModelExporter) for cb in self._callbacks
        ):
            self._callbacks.append(SavedModelExporter())
        self._multihost = multihost_runtime
        # the phase ledger of this worker's loop thread (reference
        # worker.py:298-812 / common/timing_utils.py): worker.main
        # hands over the one that already holds its start-up phases
        self._timing = ledger or timing_utils.Timing(
            interval=log_loss_steps
        )
        # domain gauges fed off the ledger's clock (no second timer):
        # examples/sec from the last iteration + real batch count.
        # No-op instruments when metrics are off.
        self._m_examples_per_sec = obs_metrics.gauge(
            "edl_worker_examples_per_second",
            "Real (unpadded) examples trained per second, last step",
        )
        self._m_version = obs_metrics.gauge(
            "edl_worker_model_version", "This worker's model version"
        )
        for cb in self._callbacks:
            cb.set_worker(self)
        # Heartbeat keeps master-side liveness fresh while the worker is
        # silent for long stretches — on TPU the first train step compiles
        # for 20-40 s, which must not read as worker death.
        self._heartbeat_stop = threading.Event()
        self._heartbeat_thread = None
        # lockstep batch-poll interval: paces consensus rounds while a
        # worker is between tasks (see _train_batches_lockstep)
        self._lockstep_poll_secs = min(0.25, wait_sleep_secs)
        # consensus every k lockstep rounds (amortizes the collective
        # and its pipeline-fencing host fetch; see the loop docstring)
        self._consensus_interval = max(1, int(consensus_interval))
        # last mesh epoch seen by the heartbeat; the training loop reads
        # this instead of issuing its own get_comm_info RPC per probe
        self._seen_mesh_epoch = None
        # Streaming checkpoint cadence (ISSUE 12): the master's record
        # watermark rides the heartbeat's CommInfo; each time it
        # crosses an EDL_STREAM_CHECKPOINT_EVERY boundary this worker
        # joins its in-flight async push, flushes dirty device-tier
        # rows, and (when configured) saves its dense checkpoint —
        # exactly the barrier set the epoch-boundary checkpoint runs,
        # re-clocked from steps to stream records.
        from elasticdl_tpu.common.env_utils import env_int

        self._stream_ckpt_every = env_int(
            "EDL_STREAM_CHECKPOINT_EVERY", 0
        )
        self._stream_ckpt_mark = None
        self._seen_stream_watermark = 0
        # Fleet telemetry (ISSUE 3): a compact blob piggybacked on the
        # master RPCs this worker already makes — the master's
        # straggler/dead-air detectors compare these across the fleet.
        # Cost: two time.time() calls + a few float ops per BATCH (not
        # per compiled step) and one tiny proto per RPC; EDL_TELEMETRY=0
        # opts out entirely.
        self._telemetry_on = env_str("EDL_TELEMETRY", "") != "0"
        self._step_ewma = 0.0
        self._dense_share_ewma = 0.0
        self._last_examples_per_sec = 0.0
        self._telemetry_samples = 0
        self._ewma_outlier_streak = 0
        if self._telemetry_on and hasattr(
            master_client, "telemetry_provider"
        ):
            master_client.telemetry_provider = self._telemetry_blob

    def _start_heartbeat(self, interval_secs=3.0):
        def beat():
            while not self._heartbeat_stop.wait(interval_secs):
                info = self._mc.get_comm_info()
                if info.mesh_epoch >= 0:
                    self._seen_mesh_epoch = info.mesh_epoch
                    self._seen_stream_watermark = getattr(
                        info, "stream_watermark", 0
                    )

        self._heartbeat_thread = threading.Thread(
            target=beat, name="worker-heartbeat", daemon=True
        )
        self._heartbeat_thread.start()

    def _stop_heartbeat(self):
        self._heartbeat_stop.set()

    def _telemetry_blob(self):
        """The piggyback payload for MasterClient RPCs. Called on the
        RPC path (get_task/report/heartbeat), never per step."""
        blob = pb.TelemetryBlob(
            role="worker-%d" % self._mc.worker_id,
            step_time_ewma=self._step_ewma,
            examples_per_sec=self._last_examples_per_sec,
            last_task_seconds=self.tds.last_task_seconds,
            model_version=self._version,
        )
        # device embedding tier (ISSUE 6): hot-set health rides the
        # same piggyback into the master's /statusz fleet view
        tier = self.trainer.device_tier
        if tier is not None:
            stats = tier.stats()
            blob.tier_hit_rate = stats["hit_rate"]
            blob.tier_occupancy = stats["occupancy"]
            blob.tier_hits = stats["hits"]
            blob.tier_misses = stats["misses"]
            blob.tier_evictions = stats["evictions"]
        # training health (ISSUE 15): the numerics sentinels' view of
        # this worker's model — loss EWMA, grad norm, nonfinite tallies
        # — feeding the master's nonfinite_loss / loss_spike /
        # grad_explosion detectors
        tracker = self.trainer.health
        if tracker is not None:
            stats = tracker.stats()
            blob.health_loss_ewma = stats["loss_ewma"]
            blob.health_loss_last = stats["loss_last"]
            blob.health_grad_norm = stats["grad_norm"]
            blob.health_nonfinite_batches = stats["nonfinite_batches"]
            blob.health_nonfinite_streak = stats["nonfinite_streak"]
            blob.health_loss_spikes = stats["loss_spikes"]
            blob.health_grad_explosions = stats["grad_explosions"]
            blob.health_skipped_batches = stats["skipped_batches"]
        # device runtime (ISSUE 18): compile ledger, HBM gauges, and
        # cost-model step attribution — what the recompile_storm /
        # hbm_pressure detectors and the fleet /statusz device section
        # read. Empty dict (obs disabled) leaves the fields zero.
        dev = device_obs.telemetry()
        if dev:
            blob.xla_compiles = dev["xla_compiles"]
            blob.xla_recompiles = dev["xla_recompiles"]
            blob.xla_compile_secs_total = dev["xla_compile_secs_total"]
            blob.hbm_bytes_in_use = dev["hbm_bytes_in_use"]
            blob.hbm_peak_bytes = dev["hbm_peak_bytes"]
            blob.hbm_limit_bytes = dev["hbm_limit_bytes"]
            blob.device_live_buffers = dev["device_live_buffers"]
            blob.h2d_bytes = dev["h2d_bytes"]
            blob.d2h_bytes = dev["d2h_bytes"]
            blob.cost_step_flops = self.trainer.cost_step_flops
            blob.cost_step_bytes = self.trainer.cost_step_bytes
            if tier is not None:
                blob.tier_hbm_bytes = tier.hbm_bytes()
        # overload plane (ISSUE 19): this process's circuit-breaker /
        # retry-budget / brownout tallies, feeding the master's
        # circuit_open detector and the /statusz overload section
        ostats = overload.client_stats()
        blob.circuit_open_count = ostats["circuit_open_count"]
        blob.degraded_pulls = ostats["degraded_pulls"]
        blob.retry_budget_exhausted = ostats["retry_budget_exhausted"]
        blob.brownout_skipped_pushes = (
            self.trainer.brownout_skipped_pushes
        )
        # dense data plane (ISSUE 20): mesh topology + collective
        # traffic of the GSPMD dense step, so /statusz and the
        # postmortem timeline show which bytes ride the ICI instead of
        # the PS. mesh_epoch is the rendezvous epoch this worker is
        # training under (-1 until the first heartbeat lands); the
        # share is the device-step fraction of batch wall time (1.0 on
        # a pure-dense trainer — the PS carries nothing).
        blob.mesh_shape = self.trainer.mesh_shape_str
        blob.mesh_epoch = (
            -1 if self._seen_mesh_epoch is None
            else int(self._seen_mesh_epoch)
        )
        blob.collective_bytes_per_step = (
            self.trainer.collective_bytes_per_step
        )
        blob.dense_step_share = self._dense_share_ewma
        return blob

    def _update_step_telemetry(self, real_count):
        """Fold one finished batch into the telemetry EWMAs. The step
        time is the ledger's last whole loop iteration (in steady state
        the device paces the loop, so that is the step time) — every
        worker measures the same way, which is all the straggler's
        fleet-relative comparison needs.

        Outlier discipline: the first measured batch carries the jit
        compile (20-40 s on TPU) and an iteration can swallow an idle
        task-boundary gap; seeding/folding those would trip the fleet
        straggler detector against a healthy worker. The first sample
        is skipped outright; later samples >10x the EWMA are skipped
        unless three arrive consecutively — a worker that is GENUINELY
        10x degraded re-anchors after three steps, a one-off spike
        never lands."""
        step_secs = self._timing.last_seconds.get(
            timing_utils.STEP_PHASE
        )
        if not step_secs:
            return
        self._telemetry_samples += 1
        if self._telemetry_samples == 1:
            return  # compile-carrying first batch
        if (
            self._step_ewma > 0.0
            and step_secs > 10.0 * self._step_ewma
            and step_secs > 1.0
        ):
            self._ewma_outlier_streak += 1
            if self._ewma_outlier_streak < 3:
                return
            self._step_ewma = step_secs  # sustained: the new reality
        else:
            self._step_ewma = (
                step_secs
                if self._step_ewma == 0.0
                else 0.9 * self._step_ewma + 0.1 * step_secs
            )
        self._ewma_outlier_streak = 0
        # dense-step share (ISSUE 20): fraction of the batch spent in
        # the jitted device step. Sparse trainers time their device
        # portion in their own ledger ("batch_process" there
        # excludes PS pull/push); a trainer without one (JaxTrainer,
        # SpmdTrainer) IS the device step end-to-end, share 1.0.
        trainer_timing = self.trainer.timing
        dense_secs = (
            trainer_timing.last_seconds.get("batch_process")
            if trainer_timing is not None
            else None
        )
        share = (
            1.0 if dense_secs is None
            else min(dense_secs / step_secs, 1.0)
        )
        self._dense_share_ewma = (
            share
            if self._dense_share_ewma == 0.0
            else 0.9 * self._dense_share_ewma + 0.1 * share
        )
        self._last_examples_per_sec = real_count / step_secs

    def _check_mesh_epoch(self):
        """Elastic membership probe on the hot loops (the reference
        re-checks its rendezvous every 20 steps, worker.py:814-819).
        Reads the heartbeat's cached epoch — no RPC on the step path."""
        if self._multihost is not None and self._multihost.epoch_moved(
            self._seen_mesh_epoch
        ):
            # the step in flight is this process's last: read first
            self._finish_in_flight("mesh")
            raise MeshEpochChanged(
                "mesh epoch moved to %s at version %d"
                % (self._seen_mesh_epoch, self._version)
            )

    # ------------------------------------------------------------------
    @property
    def model_version(self):
        return self._version

    def _batches(self, record_stream, mode):
        dataset = self.spec.dataset_fn(
            Dataset(lambda: record_stream), mode, self._reader.metadata
        )
        return dataset.batch(self._minibatch_size).prefetch(2)

    # ------------------------------------------------------------------
    # graceful drain (ISSUE 7)

    def begin_drain(self, reason="sigterm", signal_ts=None):
        """Request a graceful drain: finish the current task, then
        flush and deregister instead of fetching more work. Called from
        the SIGTERM hook (worker/drain.py) on the main thread — it only
        flips flags and arms the deadline watchdog, so it is safe at
        any interrupt point; the run loop does the actual flushing at
        its next task boundary. ``signal_ts`` is when the request
        arrived (epoch seconds; now, if the caller noted nothing).
        Idempotent."""
        if self._draining:
            return
        self._draining = True
        self._drain_reason = reason
        self._drain_requested = (
            time.time() if signal_ts is None else signal_ts, self._version
        )
        # The sequential/pipelined loops drain via the record stream:
        # tds.draining ends it AFTER the current task's records, so the
        # last task completes (reported done, never requeued). They must
        # NOT see stop_training — that breaks mid-task. Lockstep is the
        # exception: a member can't leave a collective mid-round, so the
        # stop converts to the stream-end vote (tasks handed back
        # uncounted) and the drain deadline bounds the wait for peers.
        if self._lockstep:
            self.stop_training = True
        self.tds.draining = True
        logger.warning(
            "Worker %s draining (%s): finishing current task, then "
            "flush + deregister", self._mc.worker_id, reason,
        )
        events.emit(
            "worker_draining", worker=self._mc.worker_id, reason=reason,
            initiator="worker",
        )
        deadline = env_float("EDL_DRAIN_DEADLINE_SECS", 45.0)
        # the watchdog bounds a wedged drain (a stuck collective, a PS
        # that stopped answering): past the deadline the process dies
        # NOW and the master's requeue-on-death fallback takes over —
        # better a requeued task than a pod K8s hard-kills mid-flush
        # with the journal unflushed
        watchdog = threading.Timer(
            deadline, self._drain_deadline_abort, args=(deadline,)
        )
        watchdog.daemon = True
        watchdog.start()

    def _drain_deadline_abort(self, deadline):
        if self._drain_done:
            return
        logger.error(
            "drain did not finish within %.0fs; aborting", deadline
        )
        events.dump("drain_deadline")
        events.flush()
        trace.flush()
        os._exit(1)

    def _finish_drain(self):
        """The drain tail, at a task boundary: join the in-flight async
        push, flush dirty device-tier rows to the PS, hand back any
        tasks that could NOT be finished (uncounted requeue — none on
        the clean path), then send the drain ack. Every step is
        individually guarded: a dead PS must not stop the deregister,
        and a dead master must not stop the exit (old masters without
        the RPC just miss the ack; their liveness fallback requeues)."""
        requested, self._drain_requested = self._drain_requested, None
        if requested is not None:
            # here and not in the signal handler, where a journal write
            # can be lost: the seconds from the request to this line
            # are the task the loop finished first
            events.emit(
                "drain_requested", worker=self._mc.worker_id,
                reason=self._drain_reason, signal_ts=requested[0],
                step=requested[1], finished_step=self._version,
            )
        self._timing.begin_teardown()
        with self._timing.phase("drain"):
            self._drain()

    def _drain(self):
        self._draining = True
        self.tds.draining = True
        reason = self._drain_reason or "master_drain"
        joined = flushed = True
        try:
            self._join_trainer_pushes()
        except Exception:
            joined = False
            logger.exception("drain: joining in-flight pushes failed")
        try:
            self._flush_device_tier()
        except Exception:
            flushed = False
            logger.exception("drain: device-tier flush failed")
        handed_back = 0
        try:
            # count BOTH streams of hand-backs — pending record-stream
            # tasks and parked out-of-band/train-end tasks — so the ack
            # can't call a drain clean while parked work requeued
            handed_back += self.tds.report_pending_failed(
                "requeue: draining"
            )
            handed_back += self.tds.report_parked_failed(
                "requeue: draining"
            )
        except Exception:
            logger.exception("drain: task hand-back failed")
        acked = self._mc.deregister_worker(
            reason,
            pushes_joined=joined,
            tier_flushed=flushed,
            tasks_reported=handed_back,
        )
        if not acked:
            # the canonical drain_ack is journaled by the master on
            # the deregister RPC — never from here, so a response that
            # timed out AFTER the master processed it can't double the
            # ack. This side's record of an unheard flush gets its own
            # event name.
            events.emit(
                "drain_unacked", worker=self._mc.worker_id,
                reason=reason, pushes_joined=joined,
                tier_flushed=flushed, handed_back=handed_back,
            )
        events.flush()
        self._drain_done = True
        logger.info(
            "Worker %s drained at version %d (%s; acked=%s)",
            self._mc.worker_id, self._version, reason, acked,
        )

    # ------------------------------------------------------------------
    def _join_trainer_pushes(self):
        """Depth-1 async-push barrier (train/sparse.py join_pushes) at
        worker-level boundaries — checkpoints, stream/round ends,
        train-end export — so an in-flight push either lands or raises
        here instead of silently outliving the boundary. No-op for
        dense trainers and with async push off."""
        self.trainer.join_pushes()

    def _flush_device_tier(self):
        """Device-tier writeback barrier (train/device_tier.py):
        checkpoint / export / train-end boundaries write the HBM hot
        set's dirty rows back to the PS first, so the PS-side state
        those artifacts derive from carries the tier's updates. No-op
        for dense trainers and with the tier off."""
        self.trainer.flush_device_tier()

    def _save_checkpoint(self):
        # the step in flight produced the state this saves: it is read,
        # observed (a halt raises here, before anything is written) and
        # counted first
        self._finish_in_flight("checkpoint")
        # in-flight sparse pushes land before the version is stamped
        # durable: a checkpoint claiming version V must not precede
        # V's gradients reaching the PS; device-tier rows flush for
        # the same reason (the PS sparse checkpoint must carry them)
        self._join_trainer_pushes()
        self._flush_device_tier()
        state = self.state
        if self._lockstep:
            # orbax's save is itself a cross-process collective
            # (sync_global_processes barriers) — EVERY rank must call it,
            # at the same version, which the lockstep loop guarantees.
            # v2: each rank hands over the GLOBAL jax.Array state and
            # orbax writes the shards this process holds (make_array-
            # aware path; fsdp/tp state is never gathered onto one host).
            state = self.trainer.checkpoint_state(state)
        self._checkpoint_mgr.save(self._version, state)
        events.emit("checkpoint_saved", version=self._version,
                    kind="dense")

    def maybe_stream_checkpoint(self):
        """Watermark-driven checkpoint boundary (ISSUE 12): fires the
        SAME barriers as a step-cadence checkpoint — async pushes
        joined, device-tier rows flushed — each time the heartbeat's
        cached watermark crosses an EDL_STREAM_CHECKPOINT_EVERY
        boundary, so the PS-side state a stream checkpoint snapshots
        carries every update this worker holds in flight. The first
        observed boundary only anchors the marker (a freshly joined
        worker must not burn a checkpoint on a watermark its peers
        already covered). Returns True when a boundary fired."""
        every = self._stream_ckpt_every
        watermark = self._seen_stream_watermark
        if every <= 0 or watermark <= 0:
            return False
        boundary = watermark // every
        if self._stream_ckpt_mark is None:
            self._stream_ckpt_mark = boundary
            return False
        if boundary <= self._stream_ckpt_mark:
            return False
        self._stream_ckpt_mark = boundary
        if self._checkpoint_mgr is not None:
            # _save_checkpoint already runs the join + flush barriers
            self._save_checkpoint()
        else:
            # no dense checkpoint configured: the barriers still run —
            # the PS's own stream checkpoint (cadenced off the same
            # watermark) must carry the async push and tier rows
            self._finish_in_flight("checkpoint")
            self._join_trainer_pushes()
            self._flush_device_tier()
        events.emit(
            "stream_watermark", watermark=int(watermark),
            kind="checkpoint",
        )
        return True

    def _after_train_batch(self, batch, loss):
        """Per-batch bookkeeping shared by every loop shape, called
        right after the step's dispatch. The loop reads a step one
        step LATE: the step just dispatched becomes the step in flight
        (its number, its batch and its ``PendingStep``, nothing of it
        fetched), and only then is the step BEFORE it finished
        (``_finish_step``: its scalars fetched in one transfer and
        observed, its records reported, its line logged), so the device
        holds a queued program while the host does its turn. Then what
        belongs to the newest step: checkpoint, liveness, callbacks.
        Each part is a phase of the ledger.

        The step in flight is finished at once, with nothing queued
        behind it (a DRAIN, counted by reason in ``loop_phases``),
        before anything that reads or persists the state or ends the
        stream: a step-cadence or stream checkpoint (``checkpoint``),
        a moved mesh epoch (``mesh``), ``stop_training`` (``stop``),
        the end of the batches (``end``; ``eval`` where a parked
        evaluation ended them), a batch that is not there yet when the
        loop comes for it (``input``: the master may be waiting for the
        unread step's task before it hands out another) and an
        exception leaving the loop (``error``). Every step is read
        exactly once, in order: under ``halt`` ``HealthSentinelError``
        leaves the loop no later than one step after the non-finite
        step and before a checkpoint holds its state, and a batch's
        records are reported only after its scalars were observed."""
        phase = self._timing.phase
        self._version += 1
        previous = self._in_flight
        self._in_flight = (
            self._version, batch, self.trainer.pending_step(loss)
        )
        if previous is not None:
            self._timing.read_ahead()
            self._finish_step(*previous)
        self._m_version.set(self._version)
        with phase("checkpoint"):
            if (
                self._checkpoint_mgr is not None
                and self._version % self._checkpoint_steps == 0
            ):
                self._save_checkpoint()
            self.maybe_stream_checkpoint()
        with phase("mesh_check"):
            self._check_mesh_epoch()
        with phase("callbacks"):
            # the newest dispatched step's number and loss: a callback
            # that needs the step done waits on the loss itself
            for cb in self._callbacks:
                cb.on_batch_end(self._version, loss)
        if self.stop_training:
            self._finish_in_flight("stop")

    def _finish_in_flight(self, reason=None):
        """Finishes the step in flight now, if there is one, with
        nothing queued behind it; ``reason`` names the drain in the
        ledger (None: a loop that reads every step in its own
        iteration, which is no drain)."""
        in_flight, self._in_flight = self._in_flight, None
        if in_flight is None:
            return
        if reason is not None:
            self._timing.drained(reason)
        self._finish_step(*in_flight)

    def _finish_step(self, number, batch, pending):
        """What the host owes a dispatched step once the device is
        done with it: one fetch of its loss, its health scalars and, on
        a step that logs, its facts (``Trainer.read_step``:
        ``device_wait``, then ``health``), the record accounting, and
        the step's line with its own loss and facts."""
        phase = self._timing.phase
        logs = bool(
            self._log_loss_steps and number % self._log_loss_steps == 0
        )
        if logs or pending.health is not None:
            # reference --log_loss_steps. Where a step has no health
            # scalars (SpmdTrainer) it is read only if it is logged
            loss_value, facts = self.trainer.read_step(
                pending, with_facts=logs
            )
        real = batch_real_count(batch)
        if self._telemetry_on:
            self._update_step_telemetry(real)
        step_secs = self._timing.last_seconds.get(
            timing_utils.STEP_PHASE
        )
        if step_secs:
            self._m_examples_per_sec.set(real / step_secs)
        with phase("report"):
            self.tds.report_record_done(real)
            if (
                self._report_version_steps
                and number % self._report_version_steps == 0
            ):
                self._mc.report_version(number)
        if not logs:
            return
        with phase("log"):
            # what the model handed out of the step comes with the
            # loss, on the steps that log and on no other
            fetched = [
                (fact, fact.journal(facts[fact.key]))
                for fact in step_fns.FACTS if facts.get(fact.key)
            ]
            logger.info(
                "step %d loss %.6f%s", number, loss_value,
                "".join(" %s %.6f" % item
                        for fact, fields in fetched if fact.of_loss
                        for item in sorted(fields.items())),
            )
            for fact, fields in fetched:
                if fact.of_loss:
                    fields = dict(loss=loss_value, **fields)
                events.emit(fact.event, step=number, **fields)

    def _train_batches_pipelined(self, batches):
        """Drive the sparse trainer's pipelined stream: batch N+1's PS
        pull rides under batch N's device step, pushes go out on a
        background thread (train/sparse.py train_stream — async-PS
        mode's answer to reference get_model_steps)."""

        def on_first_batch(batch):
            if not self._restore_attempted:
                self._restore_from_checkpoint(batch)
            return self.state

        import contextlib

        stream = self.trainer.train_stream(
            self.state,
            batches,
            on_first_batch=on_first_batch,
            push_interval=self._sparse_push_interval,
        )
        # deterministic close: the stream's finally drains the in-flight
        # background push even when we break or an exception unwinds
        with contextlib.closing(stream):
            # the trainer's own ledger splits its pipelined step; this
            # one only keeps the iteration for the rates and gauges
            start = self._timing.start()
            for state, loss, batch in stream:
                self.state = state
                self._after_train_batch(batch, loss)
                # the stream's steps are read in their own iteration
                self._finish_in_flight()
                self._timing.end_record(timing_utils.STEP_PHASE, start)
                start = self._timing.start()
                if self.stop_training:
                    break

    def _train_step(self, step, batch):
        """The part of a loop iteration from the batch to the
        bookkeeping, inside the iteration's ledger step (and so, when
        EDL_TRACE_DIR is set, inside the ``train_batch`` root span of a
        distributed trace, ISSUE 9: the PS client's pull/push spans
        become its children, the propagated context crosses the gRPC
        hop, and the PS-side apply lands in the same trace)."""
        step.has_batch(self.tds.current_task_id())
        if not self._restore_attempted:
            with self._timing.phase("restore"):
                self._restore_from_checkpoint(batch)
        self.state, loss = self.trainer.train_step(self.state, batch)
        return loss

    def _train_batches_sequential(self, batches):
        """Dispatch, then read the step before (``_after_train_batch``
        has the order and what drains it)."""
        batches = iter(batches)
        # a prefetched stream says whether its next batch is there
        # (``data/pipeline.py``); any other iterator is asked by next()
        ready = getattr(batches, "ready", None)
        try:
            while True:
                with self._timing.step(
                    self._version + 1, version=self._version
                ) as step:
                    if ready is not None and not ready():
                        self._finish_in_flight("input")
                    with self._timing.phase("input_wait"):
                        batch = next(batches, None)
                    if batch is None:
                        step.cancel()
                        break
                    loss = self._train_step(step, batch)
                    self._after_train_batch(batch, loss)
                if self.stop_training:
                    break
        except HealthSentinelError:
            # a halt: the step dispatched after the one that tripped it
            # started from that step's state, and goes unread
            self._in_flight = None
            raise
        except BaseException:
            self._finish_in_flight("error")
            raise
        self._finish_in_flight(
            "eval" if self.tds.out_of_band_tasks else "end"
        )

    def _train_batches_lockstep(self, batches):
        """Multi-host SPMD: every process must execute the same
        collective sequence (multihost_trainer.py lockstep contract).
        Per iteration: a consensus collective counts processes that
        still hold real batches; partial batches are padded to the
        fixed minibatch size and dried-up processes feed zero-masked
        batches until the count reaches zero, so nobody leaves a peer
        blocked inside a collective.

        Batch acquisition is a NON-BLOCKING poll (_BatchPoller): the
        master answers WAIT whenever the queue is temporarily empty —
        e.g. the peer holds the last task of the epoch, or eval tasks
        are outstanding — and a worker that blocked inside ``next()``
        waiting out that WAIT would leave its peer blocked inside the
        consensus collective: a distributed deadlock (observed: peer in
        consensus, waiter in queue.get). An empty poll is simply an
        "I have nothing this round" vote; the worker keeps the
        collective cadence with zero-masked batches and picks real work
        back up when the master has some.

        Two invariants keep the collective schedules identical across
        processes: (1) parked eval/predict tasks are drained INLINE
        between consensus rounds (local compute only) with the stream
        reopened in place — never by leaving the loop, which would pit
        one process's consensus against a peer's step collective; and
        (2) the only exit is the boundary round where the consensus
        reports every process's stream permanently ended, so everyone
        leaves together.

        The consensus runs every ``consensus_interval`` rounds, not
        every round: its host-side fetch fences the device pipeline
        (each float() blocks until all prior collectives land), so a
        per-round consensus forbids cross-step async dispatch. Within
        a window every process steps unconditionally — a dried-up
        process feeds zero-masked batches it already supports — and
        exit/idle decisions happen only at boundaries. Cost: up to
        k-1 zero-batch steps per dried worker per window at the tail
        of a stream; benefit: the consensus round trip and the
        dispatch fence amortize k-fold (round-3 VERDICT weak #4)."""
        from elasticdl_tpu.data.pipeline import pad_batch, zero_batch_like

        poller = _BatchPoller(batches)
        template = None
        exhausted = False
        stopping = False
        window = max(1, self._consensus_interval)
        round_in_window = 0
        while True:
            with self._timing.step(
                self._version + 1, version=self._version
            ) as step:
                boundary = round_in_window == 0
                if self.stop_training and not stopping:
                    # MaxSteps (or any host-side stop) under lockstep must
                    # NOT break out process-locally: a relaunched peer whose
                    # restored step counter lags would keep issuing
                    # collectives against departed workers (deadlock).
                    # Instead convert the stop into a stream-end VOTE: hand
                    # fetched-but-untrained tasks back (the post-loop
                    # _drain_fast completes them without training), feed
                    # zero batches, and leave at the synchronized all-ended
                    # boundary like any other stream end.
                    stopping = True
                    exhausted = True
                    self.tds.report_pending_failed(
                        "requeue: stopped at max steps"
                    )
                if exhausted and not stopping and self.tds.out_of_band_tasks:
                    # my stream ended because eval/predict tasks were
                    # parked: drain them INLINE, between consensus rounds,
                    # and reopen the stream — all local work, so the
                    # collective cadence is preserved (peers' next
                    # consensus simply blocks a few seconds). Leaving the
                    # loop instead would be unsound: a peer mid-round runs
                    # its STEP collective while we issue a CONSENSUS on
                    # re-entry — mismatched collectives, observed deadlock.
                    self._drain_out_of_band()
                    if self.tds.train_end_task is None:
                        poller = _BatchPoller(
                            self._batches(
                                self.tds.training_record_stream(),
                                Mode.TRAINING,
                            )
                        )
                        exhausted = False
                    # (with a parked train-end task the job is over bar the
                    # export: keep voting ended; the outer loop handles it)
                batch = None
                if not exhausted:
                    # mid-window polls wait just like boundary ones: peers'
                    # dispatched steps simply queue behind ours, and a real
                    # batch a moment late beats burning a zero-batch step
                    # on it (measured: a 0.02s mid-window poll turned every
                    # transient prefetch gap into wasted full steps and
                    # REGRESSED the scaling bench 253 -> 188 ex/s)
                    with self._timing.phase("input_wait"):
                        batch, exhausted = poller.poll(
                            self._lockstep_poll_secs
                        )
                have = batch is not None
                if have:
                    batch = pad_batch(batch, self._minibatch_size)
                    template = batch
                if boundary:
                    # a collective and a fetch of its result: the loop
                    # waits for every earlier step on the device here
                    with self._timing.phase("device_wait"):
                        alive, ended = self.trainer.consensus(
                            have, exhausted
                        )
                    if ended == self.trainer.process_count:
                        # every process's stream is permanently over: the
                        # ONLY loop exit, taken by everyone here together
                        step.cancel()
                        break
                    if alive == 0:
                        # transient: everyone is between tasks (epoch
                        # boundary, master mid-eval); keep polling — the
                        # poll timeout paces the consensus rounds (an
                        # exhausted worker has no poll to pace it, so
                        # sleep explicitly). ``have`` is False for every
                        # process here, so no polled batch is dropped.
                        if exhausted:
                            time.sleep(self._lockstep_poll_secs)
                        step.cancel()
                        continue
                if not have:
                    if template is None:
                        # in a live round without ever having seen a batch
                        # (joined mid-epoch while peers hold every task):
                        # fabricate the shapes from the reader
                        with self._timing.phase("state_init"):
                            template = self._fabricate_template_batch()
                    batch = zero_batch_like(template)
                round_in_window = (round_in_window + 1) % window
                loss = self._train_step(step, batch)
                if stopping:
                    # zero-batch participation rounds while peers finish:
                    # no version/checkpoint/record bookkeeping, and no
                    # step in the ledger
                    step.cancel()
                    continue
                self._after_train_batch(batch, loss)
                # every process reads a step in the round that ran it:
                # the same sequence of collectives and fetches on all
                self._finish_in_flight()

    def _read_template_batch(self):
        """One correctly-shaped batch read straight from the reader's
        first shard (no master round trip)."""
        shards = self._reader.create_shards()
        name, (start, count) = next(iter(shards.items()))
        template_task = pb.Task(
            shard_name=name,
            start=start,
            end=start + min(count, self._minibatch_size),
            type=pb.TRAINING,
        )
        return next(
            iter(
                self._batches(
                    self._reader.read_records(template_task),
                    Mode.TRAINING,
                )
            )
        )

    def _fabricate_template_batch(self):
        """A zero-filled, correctly-shaped batch — the lockstep
        collective needs SHAPES even from a worker that never received
        a task."""
        from elasticdl_tpu.data.pipeline import pad_batch, zero_batch_like

        return zero_batch_like(
            pad_batch(self._read_template_batch(), self._minibatch_size)
        )

    def _run_training_stream(self):
        """Consume one continuous training stream until it pauses."""
        try:
            batches = self._batches(
                self.tds.training_record_stream(), Mode.TRAINING
            )
            if self._lockstep:
                self._train_batches_lockstep(batches)
            elif self._sparse_pipeline:
                self._train_batches_pipelined(batches)
            else:
                self._train_batches_sequential(batches)
            # stream/round boundary: a failed in-flight async push
            # surfaces here and routes through the same handlers as an
            # in-stream failure (tasks get retried, not lost)
            self._join_trainer_pushes()
        except CheckpointRestoreError:
            # fatal for this process; requeue held tasks first (the
            # relaunched same-id worker keeps liveness fresh, so the
            # master would never liveness-recover them) and invalidate
            # the stream so its prefetch thread stops fetching
            self.tds.report_pending_failed("checkpoint restore failed")
            self.tds.report_parked_failed("checkpoint restore failed")
            raise
        except HealthSentinelError as e:
            # EDL_HEALTH_ON_NONFINITE=halt: the task fails LOUDLY —
            # reported with the sentinel's message (a COUNTED failure,
            # so the master requeues it exactly once toward the retry
            # cap), parked work handed back, then the error propagates
            # and the process exits nonzero. Never train past a halt.
            self.tds.report_pending_failed("health halt: %s" % (e,))
            self.tds.report_parked_failed("requeue: health halt")
            raise
        except MeshEpochChanged:
            # requeue in-flight tasks NOW: the relaunched process reuses
            # this worker_id and heartbeats immediately, so the master's
            # liveness scan would never see this "death" and the tasks
            # would rot until the slow task-timeout falsely killed the
            # relaunched worker. Parked out-of-band/train-end tasks go
            # back too — nothing will ever drain them in this process.
            # "requeue:" = lifecycle handback, uncounted (servicer.py).
            self.tds.report_pending_failed("requeue: mesh epoch changed")
            self.tds.report_parked_failed("requeue: mesh epoch changed")
            raise
        except Exception as e:  # report so tasks get retried elsewhere
            logger.exception("Training stream failed")
            if self._lockstep:
                # a lockstep step error is a MESH event (a peer died or
                # restarted mid-collective — the distributed runtime's
                # collective state is unrecoverable in-process), not
                # evidence against the task: hand tasks back uncounted
                # and restart this process to rejoin at the new epoch.
                # Retrying tasks in-process would burn each task's retry
                # cap within seconds of gloo errors and falsely fail
                # the job.
                self.tds.report_pending_failed(
                    "requeue: lockstep peer failure (%s)" % (e,)
                )
                self.tds.report_parked_failed(
                    "requeue: lockstep peer failure"
                )
                raise MeshEpochChanged(
                    "lockstep collective failed: %s" % (e,)
                ) from e
            self.tds.report_pending_failed(str(e))
        finally:
            self._timing.report("training stream")
            if self.trainer.timing is not None:
                self.trainer.timing.report("sparse trainer")

    def _restore_from_checkpoint(self, batch):
        """Resume from --checkpoint_dir_for_init on the first batch.

        The freshly-initialized state is the restore template; restoring
        into the trainer's current shardings re-lays the checkpoint out
        over whatever mesh this worker runs (elastic resume onto a
        different topology). Any restore failure is FATAL to the worker
        (CheckpointRestoreError propagates out of every task handler):
        silently training (or evaluating) from random init after the
        operator asked for a resume would discard real progress. The
        retry path for transient storage errors is pod relaunch.
        """
        from elasticdl_tpu.train.checkpoint import DenseCheckpointManager

        # Shape-only template where the trainer has one: never hold
        # init + restored state at once (a ZeRO-sharded model near HBM
        # capacity would OOM).
        template = self.trainer.abstract_state(batch["features"])
        if template is None:
            self.state = self.trainer.ensure_state(self.state, batch)
            template = self.state
        import os as _os

        if self._resume_optional and not _os.path.isdir(
            self._init_checkpoint_dir
        ):
            # elastic-fallback dir that was never created: legitimate
            # first launch. Leniency covers ONLY "nothing saved yet" —
            # a restore that finds data but fails stays fatal, else a
            # transient storage error would silently train from random
            # init and rotate out the good checkpoints.
            logger.info(
                "No checkpoint dir %r yet; fresh initialization",
                self._init_checkpoint_dir,
            )
            self._restore_attempted = True
            self.state = self.trainer.ensure_state(self.state, batch)
            return
        mgr = None
        try:
            # constructor included: a nonexistent dir (create=False)
            # must also be fatal, not a retryable task failure
            mgr = DenseCheckpointManager(
                self._init_checkpoint_dir, keep_max=0, create=False
            )
            # a lockstep trainer restores directly into the global
            # mesh's shardings (a cross-process collective — every rank
            # reaches this first-batch hook); adopt_restored below
            # passes the already-global result through
            restored = mgr.restore(
                template=template,
                shardings=self.trainer.state_shardings,
            )
        except Exception as e:
            raise CheckpointRestoreError(
                "restore from --checkpoint_dir_for_init=%r failed: %s"
                % (self._init_checkpoint_dir, e)
            ) from e
        finally:
            if mgr is not None:
                mgr.close()
        if restored is None:
            if self._resume_optional:
                # dir exists but holds no complete checkpoint: also a
                # legitimate first-launch state under the elastic default
                logger.info(
                    "No checkpoint in %r yet; fresh initialization",
                    self._init_checkpoint_dir,
                )
                self._restore_attempted = True
                self.state = self.trainer.ensure_state(self.state, batch)
                return
            raise CheckpointRestoreError(
                "--checkpoint_dir_for_init=%r holds no restorable "
                "checkpoint" % self._init_checkpoint_dir
            )
        self._restore_attempted = True
        self.state = restored = self.trainer.adopt_restored(restored)
        self._version = int(restored.step)
        logger.info(
            "Resumed from checkpoint at version %d", self._version
        )

    def _ensure_state_restored(self, batch):
        """ensure_state + one-time checkpoint_dir_for_init restore; used
        by eval/prediction paths so they never score random weights."""
        if not self._restore_attempted:
            self._restore_from_checkpoint(batch)
        else:
            self.state = self.trainer.ensure_state(self.state, batch)

    def _process_eval_task(self, task):
        try:
            for batch in self._batches(
                self.tds.task_record_stream(task), Mode.EVALUATION
            ):
                self._ensure_state_restored(batch)
                outputs = self.trainer.eval_step(self.state, batch)
                real = batch_real_count(batch)
                outputs = normalize_outputs(outputs, real)
                labels = np.asarray(batch["labels"])[:real]
                self._mc.report_evaluation_metrics(
                    task.model_version, outputs, labels
                )
            self._mc.report_task_result(task.task_id)
        except CheckpointRestoreError:
            self._mc.report_task_result(task.task_id, "restore failed")
            raise
        except Exception as e:
            logger.exception("Evaluation task %s failed", task.task_id)
            self._mc.report_task_result(task.task_id, str(e))

    def _process_prediction_task(self, task):
        processor_cls = self.spec.prediction_outputs_processor
        processor = processor_cls() if processor_cls else None
        try:
            for batch in self._batches(
                self.tds.task_record_stream(task), Mode.PREDICTION
            ):
                self._ensure_state_restored(batch)
                outputs = self.trainer.eval_step(self.state, batch)
                real = batch_real_count(batch)
                if processor is not None:
                    processor.process(
                        normalize_outputs(outputs, real),
                        self._mc.worker_id,
                    )
            if processor is not None and hasattr(processor, "close"):
                # flush buffered table writes BEFORE reporting the task
                # done — a task whose outputs are still in flight must
                # not be marked complete (write failures surface here
                # and requeue the task)
                processor.close()
            self._mc.report_task_result(task.task_id)
        except CheckpointRestoreError:
            self._mc.report_task_result(task.task_id, "restore failed")
            raise
        except Exception as e:
            logger.exception("Prediction task %s failed", task.task_id)
            self._mc.report_task_result(task.task_id, str(e))

    def _process_train_end_task(self, task):
        from elasticdl_tpu.train.callbacks import SavedModelExporter

        # the exported artifact must reflect every pushed gradient —
        # and every device-tier row update (export reads the PS tables)
        self._join_trainer_pushes()
        self._flush_device_tier()

        wants_export = bool(task.extended_config.get("saved_model_path"))
        if wants_export and self.state is None:
            # this worker never trained (e.g. relaunched after an
            # elastic restart with only the train-end task left): try
            # to restore state from checkpoint before giving the task up
            self._try_restore_for_export()
        if wants_export and self.state is None:
            # fail the task so the dispatcher re-queues it for a worker
            # that trained (silently reporting success would end the job
            # with its only artifact missing); sleep so the refetch loop
            # can't burn the retry cap in milliseconds
            self._mc.report_task_result(
                task.task_id, "no trained state to export"
            )
            time.sleep(self._wait_sleep_secs)
            return
        export_error = None
        for cb in self._callbacks:
            try:
                cb.on_train_end(self.state, dict(task.extended_config))
            except Exception as e:
                logger.exception("train-end callback failed")
                if isinstance(cb, SavedModelExporter):
                    export_error = e
        if export_error is not None:
            # the export is the job's artifact: a failed exporter fails
            # the task (bounded by the dispatcher's retry cap)
            self._mc.report_task_result(
                task.task_id, "export failed: %s" % export_error
            )
            time.sleep(self._wait_sleep_secs)
            return
        self._mc.report_task_result(task.task_id)

    def _try_restore_for_export(self):
        """Best-effort state restore for a worker that only ever saw the
        train-end task: build a template batch from the reader and run
        the normal checkpoint restore."""
        if not self._init_checkpoint_dir:
            return
        try:
            batch = self._read_template_batch()
            # strict mode: the lenient elastic default would fall back
            # to FRESH init here, and we'd export random weights as if
            # they were the trained model
            previous = self._resume_optional
            self._resume_optional = False
            try:
                self._restore_attempted = False
                self._restore_from_checkpoint(batch)
            finally:
                self._resume_optional = previous
        except Exception:
            logger.exception("restore-for-export failed")

    def _drain_out_of_band(self):
        while self.tds.out_of_band_tasks:
            task = self.tds.out_of_band_tasks.popleft()
            if task.type == pb.EVALUATION:
                self._process_eval_task(task)
            elif task.type == pb.PREDICTION:
                self._process_prediction_task(task)
            else:
                logger.warning("Unexpected out-of-band task type %s", task.type)
                self._mc.report_task_result(task.task_id)

    def _drain_fast(self):
        """After MaxStepsStopping: consume remaining tasks without
        training so the job can finish. Honors a drain request the
        same way the task-mode loop does: once this worker is picked
        as a victim, the master's get_task gate answers WAIT(draining)
        forever, so looping on it would wedge until the watchdog —
        route to _finish_drain instead (no task is held between
        iterations, so any point here is a task boundary)."""
        import time

        while True:
            if self._draining:
                self._finish_drain()
                return
            task = self._mc.get_task()
            if getattr(task, "draining", False):
                self._finish_drain()
                return
            if task.task_id == 0:
                if task.type == pb.WAIT:
                    time.sleep(0.2)
                    continue
                return
            if task.type == pb.TRAIN_END_CALLBACK:
                self._process_train_end_task(task)
            else:
                self._mc.report_task_result(task.task_id)

    # ------------------------------------------------------------------
    def run(self):
        # the trainers reach this loop's ledger through the thread
        previous_ledger = timing_utils.bind(self._timing)
        self._start_heartbeat()
        try:
            self._run()
        finally:
            self._timing.begin_teardown()
            with self._timing.phase("teardown"):
                self._stop_heartbeat()
                # release the sparse trainer's async-push executor
                # (joins its in-flight push; failures were already
                # surfaced at the stream boundary, so close only logs)
                self.trainer.close()
                if self._checkpoint_mgr is not None:
                    # Flush any in-flight orbax commit before process
                    # exit.
                    self._checkpoint_mgr.close()
                    self._checkpoint_mgr = None
            timing_utils.bind(previous_ledger)

    def _run(self):
        if self._mode == Mode.EVALUATION:
            self._run_task_mode(pb.EVALUATION, self._process_eval_task)
            return
        if self._mode == Mode.PREDICTION:
            self._run_task_mode(pb.PREDICTION, self._process_prediction_task)
            return
        while True:
            self._run_training_stream()
            if self._draining or self.tds.draining:
                # graceful drain: the stream ended at a task boundary
                # (current task reported done); flush + deregister,
                # never fetch more work
                self._finish_drain()
                return
            self._drain_out_of_band()
            if self.tds.train_end_task is not None:
                task = self.tds.train_end_task
                self.tds.train_end_task = None
                self._process_train_end_task(task)
                continue
            if self.stop_training:
                self._drain_fast()
                return
            if self.tds.job_over:
                logger.info(
                    "Worker %s done at version %d",
                    self._mc.worker_id,
                    self._version,
                )
                return

    def _run_task_mode(self, task_type, process_fn):
        import time

        while True:
            self._check_mesh_epoch()
            if self._draining:
                self._finish_drain()
                return
            task = self._mc.get_task(task_type)
            if getattr(task, "draining", False):
                self._finish_drain()
                return
            if task.task_id == 0:
                if task.type == pb.WAIT:
                    time.sleep(0.2)
                    continue
                return
            process_fn(task)
