"""gRPC Master service implementation.

Reference parity: elasticdl/python/master/servicer.py:57-161 — get_task
(WAIT when the queue is temporarily empty), report_task_result (feeds task
timing stats + failure counters), report_evaluation_metrics,
report_version (triggers step-based eval), and the comm-info RPC (the
reference's get_comm_rank against the Horovod rendezvous; here the mesh
epoch, see master/rendezvous.py).
"""

import threading
import time

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.observability import events, trace
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

logger = _logger_factory("elasticdl_tpu.master.servicer")


class MasterServicer:
    def __init__(
        self,
        task_dispatcher,
        evaluation_service=None,
        rendezvous=None,
        instance_manager=None,
        auto_join_mesh=True,
        fleet_monitor=None,
        state_journal=None,
        recovered=None,
    ):
        self._task_dispatcher = task_dispatcher
        self._evaluation_service = evaluation_service
        self._rendezvous = rendezvous
        self._instance_manager = instance_manager
        # graceful-drain coordination (master/autoscaler.py): set by the
        # Master after construction. None = the pre-ISSUE-7 behavior
        # (deregister still honored inline below, just without drain
        # bookkeeping).
        self.drain_manager = None
        # fleet telemetry sink (master/fleet.py): every RPC is a
        # liveness sighting, and requests carrying the piggybacked
        # TelemetryBlob update the role's fleet-view entry
        self._fleet = fleet_monitor
        # Membership = live workers: a worker's first get_comm_info joins
        # its host to the mesh. A pod manager that owns membership
        # explicitly (K8s pod events) sets auto_join_mesh=False.
        self._auto_join_mesh = auto_join_mesh
        self._lock = threading.Lock()
        # worker_id -> last RPC timestamp; the liveness signal for the
        # timeout scanner (reference: servicer.py:93-94,104-105)
        self._worker_liveness = {}
        # worker_id -> host (from get_comm_info); lets the task monitor
        # evict a dead worker's host from the mesh rendezvous
        self._worker_hosts = {}
        # workers already answered "job over" by get_task (see
        # Master.run: the server outlives the job until all have been)
        self._told_job_over = set()
        # worker_id -> reset_worker count: the logical relaunch epoch a
        # worker stamps onto its gradient pushes as its incarnation.
        # Master-assigned and monotonic per worker_id, so the sync PS
        # can order a relaunch against its dead predecessor without
        # trusting relaunch hosts' wall clocks (ADVICE round 5 #1).
        self._worker_restarts = {}
        # Epoch base re-anchors monotonicity across MASTER restarts:
        # counts alone restart at 1 with a fresh master, and a PS that
        # survived the restart window would order the relaunch BEHIND
        # (or equal to) its dead predecessor's buffered epochs. The
        # base is the single control plane's own clock at startup —
        # base2 >= base1 + master uptime >> relaunch counts — so no
        # WORKER-host clock trust is introduced. Residual window: a
        # master rescheduled onto a node whose clock reads EARLIER
        # than the dead master's start (NTP step-back / skewed node)
        # can still issue lower epochs than already buffered; the sync
        # PS surfaces that as a loud per-push warning plus the
        # edl_ps_push_dropped_dead_incarnation_total counter, so it is
        # an alertable condition rather than a silent hang. With a
        # state journal (EDL_STATE_DIR) the base IS persisted: a
        # relaunched master re-anchors strictly above its predecessor's
        # base, closing the stepped-back-clock window entirely.
        self._journal = state_journal
        self._restart_epoch_base = int(time.time())
        if recovered is not None:
            self._worker_restarts = {
                int(w): int(c)
                for w, c in recovered.get("worker_restarts", {}).items()
            }
            # strictly above the dead predecessor's base: every epoch
            # granted from here orders AFTER every epoch it granted,
            # whatever this node's clock says
            self._restart_epoch_base = max(
                self._restart_epoch_base,
                int(recovered.get("epoch_base", 0)) + 1,
            )
        if self._journal is not None:
            self._journal.append(
                {"op": "epoch_base", "base": self._restart_epoch_base}
            )
        # Restart detector stamped on responses (Task / CommInfo /
        # ResetWorkerResponse): with a journal, the persisted boot
        # counter; without one, the startup base still moves across
        # restarts, so reconnecting workers re-register either way.
        self._master_epoch = (
            state_journal.master_epoch
            if state_journal is not None
            else self._restart_epoch_base
        )

    # ------------------------------------------------------------------
    def _observe(self, request):
        """Fold one RPC into the fleet view: a liveness sighting always,
        plus the telemetry blob when the sender piggybacked one."""
        self._touch(request.worker_id)
        if self._fleet is not None:
            blob = (
                request.telemetry
                if request.HasField("telemetry")
                else None
            )
            self._fleet.observe(request.worker_id, blob)

    def _touch(self, worker_id):
        with self._lock:
            # monotonic max: extend_liveness may have credited a future
            # horizon (mesh-restart allowance); an ordinary ping must
            # not pull the clock back below it
            self._worker_liveness[worker_id] = max(
                time.time(), self._worker_liveness.get(worker_id, 0.0)
            )

    def worker_liveness(self):
        with self._lock:
            return dict(self._worker_liveness)

    def workers_awaiting_job_over(self, seen_within_secs):
        """Workers (ids >= 0; the PS poll under negative ids) heard
        from within ``seen_within_secs`` that have not yet been handed
        the job-over task."""
        horizon = time.time() - seen_within_secs
        with self._lock:
            return sorted(
                w for w, seen in self._worker_liveness.items()
                if w >= 0 and seen >= horizon
                and w not in self._told_job_over
            )

    def forget_worker(self, worker_id):
        with self._lock:
            self._worker_liveness.pop(worker_id, None)
            self._worker_hosts.pop(worker_id, None)

    def extend_liveness(self, worker_ids, horizon):
        """Credit workers with liveness up to a future ``horizon``: the
        task monitor calls this on a mesh-epoch bump, when every member
        goes dark for its process relaunch (possibly several attempts
        against a not-yet-restarted coordinator). A forward-dated clock
        is churn-proof where deleting the entry is not — stray pings
        from the pre-restart process can't shorten the allowance
        (_touch is monotonic), and eviction resumes automatically once
        the horizon passes (task_monitor.py)."""
        with self._lock:
            for worker_id in worker_ids:
                self._worker_liveness[worker_id] = max(
                    self._worker_liveness.get(worker_id, 0.0), horizon
                )

    def mesh_worker_ids(self):
        """Workers registered as mesh members (sent a worker_host)."""
        with self._lock:
            return list(self._worker_hosts)

    def worker_host(self, worker_id):
        with self._lock:
            return self._worker_hosts.get(worker_id)

    # ------------------------------------------------------------------
    # RPC handlers (also callable in-process without gRPC)

    def get_task(self, request, context=None):
        self._observe(request)
        if self.drain_manager is not None and (
            self.drain_manager.is_draining(request.worker_id)
        ):
            # drain gate (ISSUE 7): a draining worker gets NO new work.
            # WAIT(draining=true) tells it to finish the current task,
            # flush, and deregister — its record stream reads the flag
            # as end-of-stream.
            return pb.Task(
                type=pb.WAIT, master_epoch=self._master_epoch,
                draining=True,
            )
        task_type = request.task_type if request.task_type else None
        dispatch_start = time.time()
        task = self._task_dispatcher.get(request.worker_id, task_type)
        if task is not None:
            # restart detector: constant per process, so mutating the
            # shared record's proto is idempotent
            task.master_epoch = self._master_epoch
            # the master-side anchor of the cross-role task trace:
            # merge_trace.py threads a flow from this span through the
            # worker's train/push spans carrying the same task_id
            trace.complete(
                "dispatch", dispatch_start,
                task_id=task.task_id, worker_id=request.worker_id,
            )
            events.emit(
                "task_dispatch", task=task.task_id,
                worker=request.worker_id,
                type=pb.TaskType.Name(task.type).lower(),
            )
            return task
        if (
            self._task_dispatcher.finished()
            or self._task_dispatcher.job_failed()
        ):
            # Default Task (task_id=0, type=TRAINING): the job is over
            # (success or terminal failure) and the worker should exit.
            # The master distinguishes the two via job_failed().
            with self._lock:
                self._told_job_over.add(request.worker_id)
            return pb.Task(master_epoch=self._master_epoch)
        # Queue temporarily empty (e.g. between epochs or during an eval
        # pass): tell the worker to wait and re-poll.
        return pb.Task(type=pb.WAIT, master_epoch=self._master_epoch)

    def reset_worker(self, request, context=None):
        """A freshly (re)launched worker declares itself: anything still
        assigned to its id belongs to a dead predecessor incarnation
        (the new process holds nothing by definition) — requeue it
        uncounted NOW instead of waiting out the task timeout. The
        liveness clock can't catch this: the successor reuses the
        worker_id and heartbeats immediately.

        Returns this worker_id's relaunch epoch (base + 1, base + 2,
        ...): the worker's push incarnation for the sync PS's
        round-buffer cleanup."""
        self._observe(request)
        with self._lock:
            count = self._worker_restarts.get(request.worker_id, 0) + 1
            self._worker_restarts[request.worker_id] = count
            epoch = self._restart_epoch_base + count
        if self._journal is not None:
            # the grant must be durable BEFORE the worker can stamp it
            # on a push: a master relaunch that forgot the grant would
            # re-issue lower epochs and the sync PS would order live
            # pushes behind dead ones
            self._journal.append({
                "op": "grant", "worker": request.worker_id,
                "count": count,
            })
        events.emit(
            "worker_register", worker=request.worker_id, epoch=epoch,
            relaunch=count > 1,
        )
        self._task_dispatcher.recover_tasks(request.worker_id)
        return pb.ResetWorkerResponse(
            restart_count=epoch, master_epoch=self._master_epoch
        )

    def deregister_worker(self, request, context=None):
        """Graceful-drain ack (ISSUE 7): the worker finished draining —
        current task reported, async push joined, device-tier rows
        flushed — and is about to exit ON PURPOSE. Remove it with no
        dead-air alert and no counted requeue. Works for both
        master-initiated drains (scale-down victims) and self-initiated
        ones (kubelet SIGTERMed the pod; the master hears about the
        preemption through this RPC)."""
        if request.HasField("telemetry"):
            # final telemetry fold (don't _observe: that would re-add
            # the liveness entry the drain is about to remove)
            if self._fleet is not None:
                self._fleet.observe(request.worker_id, request.telemetry)
        if self.drain_manager is None:
            # bare servicer (tests/benches): the ack bookkeeping is the
            # same either way, so construct the manager on first use
            # instead of duplicating its cleanup sequence inline
            from elasticdl_tpu.master.autoscaler import DrainManager

            self.drain_manager = DrainManager(
                self._task_dispatcher, servicer=self,
                fleet=self._fleet, rendezvous=self._rendezvous,
            )
        self.drain_manager.deregister(request)
        return pb.Empty()

    def worker_relaunch_count(self):
        """Relaunches observed across all workers (each reset_worker
        beyond a worker_id's first is a relaunch) — the master's
        ``edl_master_worker_relaunches_total`` gauge."""
        with self._lock:
            return sum(
                max(0, n - 1) for n in self._worker_restarts.values()
            )

    def report_task_result(self, request, context=None):
        self._observe(request)
        success = not request.err_message
        # "requeue:" prefix = mesh-lifecycle handback (worker restarting
        # for a new epoch / lockstep peer died): requeue WITHOUT charging
        # the task's retry cap (task_dispatcher.report docstring)
        count_failure = not request.err_message.startswith("requeue:")
        if not success:
            log = logger.info if not count_failure else logger.warning
            log(
                "Task %s failed: %s", request.task_id, request.err_message
            )
        self._task_dispatcher.report(
            request.task_id, success, worker_id=request.worker_id,
            count_failure=count_failure,
        )
        trace.instant(
            "task_reported", task_id=request.task_id,
            worker_id=request.worker_id, success=success,
        )
        events.emit(
            "task_report", task=request.task_id,
            worker=request.worker_id, ok=success,
            err=request.err_message[:200],
        )
        return pb.Empty()

    def report_evaluation_metrics(self, request, context=None):
        self._touch(request.worker_id)
        if self._evaluation_service is not None:
            self._evaluation_service.report_evaluation_metrics(
                request.model_outputs, request.labels
            )
        return pb.Empty()

    def report_version(self, request, context=None):
        if self._journal is not None:
            self._journal.append(
                {"op": "version", "version": request.model_version}
            )
        if self._evaluation_service is not None:
            self._evaluation_service.add_evaluation_task_if_needed(
                request.model_version
            )
        return pb.Empty()

    def export_worker_state(self):
        """Snapshot section for journal compaction: the relaunch-epoch
        grants and their base (state_store.empty_state keys)."""
        with self._lock:
            return {
                "worker_restarts": dict(self._worker_restarts),
                "epoch_base": self._restart_epoch_base,
            }

    def _stream_watermark(self):
        """The dispatcher's record watermark (streaming mode; 0
        otherwise) — stamped on CommInfo so workers and PS shards
        drive their checkpoint/flush cadence off its progress without
        any extra RPC (the heartbeat/liveness poll already flows)."""
        watermark = getattr(
            self._task_dispatcher, "stream_watermark", None
        )
        return watermark() if callable(watermark) else 0

    def get_comm_info(self, request, context=None):
        self._observe(request)
        if self._rendezvous is None:
            return pb.CommInfo(
                rank=0, world_size=1, mesh_epoch=0,
                master_epoch=self._master_epoch,
                stream_watermark=self._stream_watermark(),
            )
        if request.worker_host:
            with self._lock:
                self._worker_hosts[request.worker_id] = request.worker_host
            if self._auto_join_mesh:
                self._rendezvous.add_worker_host(
                    request.worker_host, reason="worker_join"
                )
        rank, size, epoch, coordinator = self._rendezvous.get_comm_info(
            request.worker_host
        )
        return pb.CommInfo(
            rank=rank,
            world_size=size,
            mesh_epoch=epoch,
            coordinator_addr=coordinator,
            master_epoch=self._master_epoch,
            stream_watermark=self._stream_watermark(),
        )
