"""Serving role entry point.

Usage: python -m elasticdl_tpu.serve.main --model_zoo=... \
    --export_dir=/artifacts/model --port=50052 [--ps_addrs=...]

The full platform treatment of the other roles: /metrics /healthz
/readyz (ready = model loaded), flight-recorder journal, deterministic
fault injection, SIGTERM graceful drain (stop admitting -> flush the
queue -> deregister from the journal's point of view -> exit 0), and —
when a master is running — the same 5 s telemetry piggyback the PS
rides, so /statusz shows the inference side of the fleet.
"""

import argparse
import os
import signal
import sys
import threading
import time

from elasticdl_tpu.common.env_utils import env_str
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory

logger = _logger_factory("elasticdl_tpu.serve.main")


def parse_serve_args(argv=None):
    parser = argparse.ArgumentParser("elasticdl_tpu serve")
    parser.add_argument("--serve_id", type=int, default=0)
    parser.add_argument("--port", type=int, default=50052)
    parser.add_argument("--model_zoo", required=True)
    parser.add_argument("--model_def", default="")
    parser.add_argument("--model_params", default="")
    parser.add_argument(
        "--export_dir", required=True,
        help="train/export.py artifact directory (watched for new "
        "versions; hot-swapped with zero request failures)",
    )
    parser.add_argument(
        "--ps_addrs", default="",
        help="comma-separated PS addresses for sparse-embedding models",
    )
    parser.add_argument(
        "--master_addr", default="",
        help="optional: piggyback serving telemetry on the master's "
        "fleet view (/statusz)",
    )
    parser.add_argument(
        "--router_addr", default="",
        help="fleet mode (ISSUE 17): register with the serving router "
        "at this address and heartbeat telemetry + export versions; "
        "--export_dir then names the VERSIONED export root (one "
        "subdirectory per bundle) and the router directs which "
        "version this replica loads",
    )
    parser.add_argument(
        "--advertise_addr", default="",
        help="address the router should reach this replica at "
        "(default 127.0.0.1:<port> — the local-subprocess topology)",
    )
    # must match the training job's compute dtype for prediction parity
    parser.add_argument("--compute_dtype", default="")
    parser.add_argument(
        "--max_batch", type=int, default=0,
        help="rows per formed batch (0 = EDL_SERVE_MAX_BATCH or 32)",
    )
    parser.add_argument(
        "--max_delay_ms", type=float, default=-1.0,
        help="batch formation window (<0 = EDL_SERVE_MAX_DELAY_MS or 5)",
    )
    parser.add_argument(
        "--queue_depth", type=int, default=0,
        help="admission bound; beyond it requests shed "
        "(0 = EDL_SERVE_QUEUE_DEPTH or 256)",
    )
    parser.add_argument(
        "--deadline_ms", type=float, default=-1.0,
        help="default per-request budget when the RPC carries none "
        "(<0 = EDL_SERVE_DEADLINE_MS or 1000)",
    )
    parser.add_argument(
        "--cache_ttl_secs", type=float, default=-1.0,
        help="embedding row cache TTL (<0 = EDL_SERVE_CACHE_TTL_SECS "
        "or 2; 0 disables the cache)",
    )
    parser.add_argument(
        "--watch_secs", type=float, default=-1.0,
        help="export watch interval (<0 = EDL_SERVE_WATCH_SECS or 2)",
    )
    parser.add_argument("--metrics_port", type=int, default=0)
    return parser.parse_args(argv)


class ServeRole:
    def __init__(self, args):
        from elasticdl_tpu.serve.engine import ServingEngine

        self.args = args
        ps_client = None
        if args.ps_addrs:
            from elasticdl_tpu.worker.ps_client import PSClient

            ps_client = PSClient(args.ps_addrs)
        self.engine = ServingEngine(
            args.model_zoo,
            args.export_dir,
            ps_client=ps_client,
            model_def=args.model_def,
            model_params=args.model_params,
            compute_dtype=args.compute_dtype or None,
            max_batch=args.max_batch or None,
            max_delay_ms=(
                args.max_delay_ms if args.max_delay_ms >= 0 else None
            ),
            queue_depth=args.queue_depth or None,
            deadline_ms=(
                args.deadline_ms if args.deadline_ms >= 0 else None
            ),
            cache_ttl_secs=(
                args.cache_ttl_secs if args.cache_ttl_secs >= 0 else None
            ),
            watch_secs=args.watch_secs if args.watch_secs >= 0 else None,
            directed=bool(args.router_addr),
        )
        self._master_client = None
        if args.master_addr:
            from elasticdl_tpu.worker.master_client import MasterClient

            # worker_host="": the serve role is not a mesh member; the
            # negative id namespace keeps it out of the worker id space
            # (the PS uses -(ps_id+1); serving sits below at -1000)
            self._master_client = MasterClient(
                args.master_addr,
                worker_id=-(1000 + args.serve_id),
                worker_host="",
            )
            if env_str("EDL_TELEMETRY", "") != "0":
                self._master_client.telemetry_provider = self.telemetry_blob
        self.server = None
        self.observability = None
        # fleet link (ISSUE 17): register/heartbeat with the router
        self.replica_id = "serve-%d-%d" % (args.serve_id, os.getpid())
        self._advertise_addr = (
            args.advertise_addr or "127.0.0.1:%d" % args.port
        )
        self._router_stub = None
        self._fleet_thread = None
        self._registered = False
        self._drain_reason = "sigterm"
        self._drained = threading.Event()
        # SIGTERM arrival marker: a plain bool write is the only thing
        # the signal handler does (atomic, lock-free, reentrant-safe);
        # run() polls it and performs the actual drain (_finish_term)
        self._term_flag = False
        self._term_previous = None
        self._qps_window = (time.monotonic(), 0)  # (ts, served_total)

    def telemetry_blob(self):
        from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

        batcher = self.engine.batcher
        now = time.monotonic()
        last_ts, last_served = self._qps_window
        served = batcher.served_total
        elapsed = max(now - last_ts, 1e-6)
        self._qps_window = (now, served)
        info = self.engine.model_info()
        blob = pb.TelemetryBlob(
            role="serve-%d" % self.args.serve_id,
            serve_qps=(served - last_served) / elapsed,
            serve_queue_depth=batcher.pending_count(),
            serve_shed_total=batcher.shed_total,
            model_version=max(info["step"], 0),
            tier_hit_rate=(
                self.engine.cache.hit_rate()
                if self.engine.cache is not None
                else 0.0
            ),
        )
        # device runtime (ISSUE 18): the replica's compile ledger +
        # HBM gauges — a serve recompile means a request batch dodged
        # the padded-shape contract, which the fleet's recompile_storm
        # detector should hear about like any worker's churn
        from elasticdl_tpu.observability import device as device_obs

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
        return blob

    # ------------------------------------------------------------------
    def prepare(self):
        from elasticdl_tpu.common.grpc_utils import build_server
        from elasticdl_tpu.observability import (
            events,
            http_server,
            profiler,
            trace,
        )
        from elasticdl_tpu.proto.services import (
            add_serve_servicer_to_server,
        )
        from elasticdl_tpu.serve.servicer import ServeServicer

        role = "serve-%d" % self.args.serve_id
        trace.configure(role)
        events.configure(role)
        events.emit("role_start", port=self.args.port)
        # continuous profiler (ISSUE 14): always-on when EDL_PROF_HZ is
        # set, served as /profilez on the observability port below
        profiler.maybe_start(role)
        self.engine.start()
        self.server = build_server()
        add_serve_servicer_to_server(ServeServicer(self.engine), self.server)
        self.server.add_insecure_port("[::]:%d" % self.args.port)
        self.server.start()
        self.observability = http_server.maybe_start(
            role, cli_port=self.args.metrics_port
        )
        if self.observability is not None:
            # readiness milestone: a loaded model — before it, predict
            # answers FAILED_PRECONDITION and the pod must hold traffic
            self.observability.add_readiness_check(
                "model_loaded", lambda: self.engine.loaded
            )
        self._install_sigterm_drain()
        if self.args.router_addr:
            self._start_fleet_link()
        logger.info(
            "serve %d on :%d (export %s)",
            self.args.serve_id, self.args.port, self.args.export_dir,
        )
        return self

    # -- fleet link (ISSUE 17) -----------------------------------------
    def _start_fleet_link(self):
        from elasticdl_tpu.common.grpc_utils import build_channel
        from elasticdl_tpu.proto import services

        self._router_stub = services.RouterStub(
            build_channel(self.args.router_addr)
        )
        self._fleet_thread = threading.Thread(
            target=self._fleet_loop, name="edl-serve-fleet", daemon=True
        )
        self._fleet_thread.start()

    def _fleet_loop(self):
        """Register with the router, then heartbeat until drained.

        Heartbeats carry telemetry + the loaded/newest-available export
        versions UP and directives DOWN: ``target_export`` steers the
        directed engine (canary/promote/rollback) and ``drain`` routes
        this replica through the exact SIGTERM drain path a kubelet
        eviction would (stop admitting, flush, deregister, exit 0) —
        the run loop just sees the same flag the signal handler sets."""
        from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
        from elasticdl_tpu.serve.fleet import scan_export_versions

        heartbeat_secs = 2.0
        while not (self._drained.is_set() or self._term_flag):
            try:
                if not self._registered:
                    resp = self._router_stub.register_replica(
                        pb.RegisterReplicaRequest(
                            replica_id=self.replica_id,
                            addr=self._advertise_addr,
                            max_batch=self.engine.batcher.max_batch,
                            model_stamp=self.engine.model_info()["stamp"],
                            telemetry=self.telemetry_blob(),
                        ),
                        timeout=5.0,
                    )
                    if resp.heartbeat_secs > 0:
                        heartbeat_secs = resp.heartbeat_secs
                    if resp.target_export:
                        self.engine.set_target(resp.target_export)
                    self._registered = True
                    logger.info(
                        "registered with router %s as %s",
                        self.args.router_addr, self.replica_id,
                    )
                else:
                    versions = scan_export_versions(self.args.export_dir)
                    newest = versions[-1] if versions else ("", 0, "")
                    info = self.engine.model_info()
                    resp = self._router_stub.heartbeat_replica(
                        pb.ReplicaHeartbeatRequest(
                            replica_id=self.replica_id,
                            loaded_export=self.engine.loaded_export,
                            loaded_stamp=info["stamp"],
                            available_export=newest[0],
                            available_stamp=newest[2],
                            draining=self._term_flag,
                            telemetry=self.telemetry_blob(),
                        ),
                        timeout=5.0,
                    )
                    if not resp.known:
                        # the router restarted (or expired us while
                        # partitioned): re-register from scratch
                        self._registered = False
                        continue
                    if resp.target_export:
                        self.engine.set_target(resp.target_export)
                    if resp.drain:
                        self._drain_reason = "router_drain"
                        self._term_flag = True
                        return
            except Exception:
                # router unreachable: keep trying — the tier outlives
                # a router restart, and re-registration is idempotent
                logger.debug("router link hiccup", exc_info=True)
            time.sleep(heartbeat_secs if self._registered else 1.0)

    def _deregister(self, reason):
        """The exactly-once drain ack (fleet mode): tell the router the
        queue is flushed so it forgets this replica with no
        ``replica_lost`` alert. Best-effort — a dead router just means
        the heartbeat timeout journals the loss instead."""
        if self._router_stub is None or not self._registered:
            return
        self._registered = False
        from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

        try:
            self._router_stub.deregister_replica(
                pb.DeregisterReplicaRequest(
                    replica_id=self.replica_id,
                    reason=reason,
                    served=self.engine.batcher.served_total,
                    shed=self.engine.batcher.shed_total,
                ),
                timeout=5.0,
            )
        except Exception:
            logger.warning(
                "drain ack to router failed (router gone?)", exc_info=True
            )

    def _install_sigterm_drain(self):
        self._term_previous = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            # Flag-only: the handler interrupts the main thread, which
            # may be inside the batcher or the event journal holding
            # their locks — draining here (MicroBatcher.drain takes
            # _cond and joins the batch thread) self-deadlocks until
            # the pod's SIGKILL. run() observes the flag within one
            # poll tick and drains with no lock held (_finish_term).
            self._term_flag = True

        try:
            signal.signal(signal.SIGTERM, _on_term)
        except ValueError:
            logger.warning(
                "not on main thread; serve SIGTERM drain not installed"
            )

    def _finish_term(self):
        """The deferred SIGTERM drain (what the handler used to do
        inline), on the run loop with no lock held; then chains the
        flight-recorder hook (which dumps the ring and exits 0). A
        router drain directive funnels through the same flag with its
        own reason — the ISSUE 7/8 contract: shrink victims exit
        through the graceful path, not a bare kill."""
        self.drain(reason=self._drain_reason)
        previous = self._term_previous
        if callable(previous):
            previous(signal.SIGTERM, None)
        return 0

    def drain(self, reason="shutdown"):
        """Stop admitting, flush the queue, stop the server. Idempotent
        (the SIGTERM handler and an orderly exit may both arrive)."""
        from elasticdl_tpu.observability import events, trace

        if self._drained.is_set():
            return
        self._drained.set()
        flushed = self.engine.drain()
        # drain ack AFTER the flush (the count in the ack is final)
        # and BEFORE the server stops — the router already stopped
        # routing here the moment it directed the drain
        self._deregister(reason)
        # trace flush ARMS here, before the crash hooks run (ISSUE 9):
        # the queue just finished flushing, so every request span is
        # final — a SIGKILL-grace-window race after this line loses
        # nothing. The chained install_crash_hooks handler flushes
        # again; TraceWriter.flush is idempotent on an empty buffer.
        trace.flush()
        if trace.enabled():
            events.emit("trace_flushed", reason=reason)
        try:
            if self.server is not None:
                self.server.stop(grace=2.0)
        except Exception:
            logger.exception("server stop at drain failed")
        events.emit(
            "serve_drained", reason=reason, flushed=flushed,
            served=self.engine.batcher.served_total,
            shed=self.engine.batcher.shed_total,
        )
        events.emit("role_stop", reason=reason)
        events.flush()

    def run(self, poll_secs=5.0):
        """Serve until stopped. Unlike the PS, a master going away does
        NOT stop serving — the inference tier outlives training jobs;
        the poll exists only to feed fleet telemetry while a master is
        around."""
        if self.args.router_addr:
            # fleet mode drains on a router directive too; poll tight
            # enough that a shrink victim leaves within ~a second
            poll_secs = min(poll_secs, 1.0)
        if self._master_client is None:
            # bounded wait so a SIGTERM flag is noticed within one poll
            # even though the handler no longer stops the server itself
            while self.server.wait_for_termination(timeout=poll_secs):
                if self._term_flag:
                    return self._finish_term()
            return 0
        while not self._drained.is_set():
            time.sleep(poll_secs)
            if self._term_flag:
                return self._finish_term()
            try:
                self._master_client.get_comm_info()
            except Exception:
                logger.debug("telemetry poll failed (master gone?)")
        return 0


def main(argv=None):
    from elasticdl_tpu.common.platform import configure_compile_cache

    configure_compile_cache()
    args = parse_serve_args(argv)
    from elasticdl_tpu.testing import faults

    faults.set_role("serve-%d" % args.serve_id)
    if args.metrics_port:
        from elasticdl_tpu.observability import http_server

        # publish before any instrument is constructed: the registry
        # decides enabled/no-op at first touch
        os.environ[http_server.PORT_ENV] = str(args.metrics_port)
    from elasticdl_tpu.observability import events

    # SIGTERM chain order (the PS pattern): crash hooks install first;
    # prepare()'s handler registers last and only flags — run() then
    # drains (stop admitting + flush) off the signal path and chains
    # into the ring dump + exit 0
    events.install_crash_hooks()
    return ServeRole(args).prepare().run()


if __name__ == "__main__":
    sys.exit(main())
