"""Parameter server process.

Reference parity: elasticdl/python/ps/parameter_server.py and
go/cmd/elasticdl_ps/main.go — serves the Pserver gRPC service until the
master goes away (the reference polls the master pod's K8s status every
30 s; here the master channel's health plays that role).
"""

import argparse
import os
import signal
import sys
import time

import grpc

from elasticdl_tpu.common.args import add_bool_argument
from elasticdl_tpu.common.env_utils import env_int, env_str
from elasticdl_tpu.common.grpc_utils import build_server, uds_socket_path
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.observability import events, http_server, profiler, trace
from elasticdl_tpu.ps.checkpoint import SparseCheckpointSaver
from elasticdl_tpu.ps.embedding_store import create_store
from elasticdl_tpu.ps.servicer import PserverServicer
from elasticdl_tpu.proto.services import add_pserver_servicer_to_server
from elasticdl_tpu.train.optimizers import parse_opt_args

logger = _logger_factory("elasticdl_tpu.ps.server")


def parse_ps_args(argv=None):
    parser = argparse.ArgumentParser("elasticdl_tpu ps")
    parser.add_argument("--ps_id", type=int, default=0)
    parser.add_argument("--num_ps_pods", type=int, default=1)
    parser.add_argument("--port", type=int, default=50002)
    parser.add_argument("--master_addr", default="")
    parser.add_argument("--opt_type", default="sgd")
    parser.add_argument(
        "--opt_args", default="", help="k=v;k=v (e.g. lr=0.01;momentum=0.9)"
    )
    parser.add_argument("--checkpoint_dir", default="")
    parser.add_argument("--checkpoint_steps", type=int, default=0)
    parser.add_argument("--keep_checkpoint_max", type=int, default=3)
    parser.add_argument("--checkpoint_dir_for_init", default="")
    parser.add_argument("--use_native_store", type=int, default=1)
    parser.add_argument("--seed", type=int, default=0)
    # sync-SGD controls (reference go/cmd/elasticdl_ps/main.go flags
    # use_async/grads_to_wait/sync_version_tolerance)
    add_bool_argument(parser, "--use_async", default=0)
    parser.add_argument("--grads_to_wait", type=int, default=1)
    parser.add_argument("--sync_version_tolerance", type=int, default=0)
    # async-mode staleness LR modulation lr /= max(1, version_diff)
    # (reference go/cmd/elasticdl_ps/main.go lr_staleness_modulation)
    add_bool_argument(parser, "--lr_staleness_modulation", default=0)
    # benchmarking knob: sleep this long at the top of every RPC handler
    # to emulate network RTT between worker and PS pods (a localhost
    # PS otherwise measures at ~0 RTT)
    parser.add_argument("--inject_rpc_delay_ms", type=float, default=0.0)
    # observability: /metrics + /healthz + /readyz on this port
    # (0/unset = disabled; falls back to EDL_METRICS_PORT)
    parser.add_argument("--metrics_port", type=int, default=0)
    return parser.parse_args(argv)


class _DelayedServicer:
    """Wraps a servicer so every RPC handler sleeps ``delay_ms`` first —
    an injectable stand-in for worker<->PS network latency."""

    def __init__(self, servicer, delay_ms):
        self._servicer = servicer
        self._delay = delay_ms / 1e3

    def __getattr__(self, name):
        attr = getattr(self._servicer, name)
        if not callable(attr) or name.startswith("_"):
            return attr
        delay = self._delay

        def delayed(*args, **kwargs):
            time.sleep(delay)
            return attr(*args, **kwargs)

        return delayed


class ParameterServer:
    def __init__(self, args):
        self.args = args
        # SIGTERM arrival marker: a plain bool write is the only thing
        # the signal handler does (atomic, lock-free, reentrant-safe);
        # run() polls it and performs the actual drain (_finish_term)
        self._term_flag = False
        self._term_previous = None
        if getattr(args, "metrics_port", 0):
            # programmatic construction (no CLI entry ran): publish the
            # knob before the servicer builds its instruments, or the
            # process-global registry freezes disabled
            os.environ.setdefault(
                http_server.PORT_ENV, str(args.metrics_port)
            )
        self.store = create_store(
            seed=args.seed + args.ps_id,
            prefer_native=bool(args.use_native_store),
        )
        opt_args = {
            k: float(v) for k, v in parse_opt_args(args.opt_args).items()
        }
        self.store.set_optimizer(args.opt_type, **opt_args)
        saver = None
        if args.checkpoint_dir:
            saver = SparseCheckpointSaver(
                args.checkpoint_dir,
                shard_id=args.ps_id,
                shard_num=args.num_ps_pods,
                keep_max=args.keep_checkpoint_max,
            )
        # Auto-restore (ISSUE 4): a relaunched PS picks up the newest
        # COMPLETE checkpoint from its own --checkpoint_dir with no
        # operator flag — before this, a same-id relaunch
        # (k8s/instance_manager.py) booted with an empty store unless
        # someone remembered --checkpoint_dir_for_init. The explicit
        # flag still wins (warm-starting from another job's dir).
        self._restored_version = None
        if args.checkpoint_dir_for_init:
            self._restored_version = SparseCheckpointSaver(
                args.checkpoint_dir_for_init,
                shard_id=args.ps_id,
                shard_num=args.num_ps_pods,
            ).restore(self.store)
        elif saver is not None:
            self._restored_version = saver.restore(self.store)
        if self._restored_version is not None:
            # re-anchor the store's version clock at the checkpoint so
            # sync staleness checks and worker version accounting line
            # up with the restored state
            self.store.set_version(self._restored_version)
            logger.info(
                "PS %d auto-restored checkpoint version %d",
                args.ps_id, self._restored_version,
            )
        # Embedding lifecycle (ISSUE 12): admission/eviction policy
        # from the EDL_EMB_* knobs; None when no policy is enabled.
        # Built BEFORE the servicer so the admission gates exist from
        # the first RPC, and re-anchored on the restored store below.
        from elasticdl_tpu.stream.lifecycle import EmbeddingLifecycle

        self.lifecycle = EmbeddingLifecycle.maybe_create(self.store)
        if self.lifecycle is not None and self._restored_version is not None:
            # a restore already materialized tables/rows: register them
            # (the real initializer arrives later with the model's
            # push_embedding_table_infos and updates the cold row) and
            # re-anchor conservatively — every restored row admitted,
            # sketch empty (no phantom rows, no lost admitted rows)
            for name in self.store.table_names():
                self.lifecycle.register_table(
                    name, self.store.table_dim(name)
                )
            self.lifecycle.adopt_store()
        master_client = None
        if args.master_addr:
            from elasticdl_tpu.worker.master_client import MasterClient

            # worker_host="": a PS is not a mesh member (its liveness
            # polls must not auto-join it into the SPMD rendezvous).
            master_client = MasterClient(
                args.master_addr,
                worker_id=-(args.ps_id + 1),
                worker_host="",
            )
        self._master_client = master_client
        self._telemetry_on = (
            env_str("EDL_TELEMETRY", "") != "0"
        )
        self.servicer = PserverServicer(
            self.store,
            ps_id=args.ps_id,
            checkpoint_saver=saver,
            checkpoint_steps=args.checkpoint_steps,
            master_client=master_client,
            use_async=bool(args.use_async),
            grads_to_wait=args.grads_to_wait,
            sync_version_tolerance=args.sync_version_tolerance,
            staleness_modulation=bool(args.lr_staleness_modulation),
            restored_version=self._restored_version,
            lifecycle=self.lifecycle,
        )
        if master_client is not None and self._telemetry_on:
            # piggyback this PS's telemetry (push/pull rates, version
            # lag, round-buffer fill) on the 5 s liveness poll the run
            # loop already makes — the master's stuck-round and
            # version-lag detectors read it from the fleet view
            master_client.telemetry_provider = self.servicer.telemetry_blob
        self.server = None

    def prepare(self):
        self.server = build_server()
        servicer = self.servicer
        if getattr(self.args, "inject_rpc_delay_ms", 0):
            servicer = _DelayedServicer(
                servicer, self.args.inject_rpc_delay_ms
            )
            logger.info(
                "Injecting %.1f ms per-RPC delay (latency experiment)",
                self.args.inject_rpc_delay_ms,
            )
        add_pserver_servicer_to_server(servicer, self.server)
        self.server.add_insecure_port("[::]:%d" % self.args.port)
        # Zero-copy local transport (ISSUE 11): under EDL_PS_UDS_DIR,
        # also serve on a unix-domain socket named by this TCP port —
        # co-located clients (build_channel) prefer it, remote clients
        # keep TCP. A stale socket from a SIGKILLed predecessor is
        # unlinked first so the same-path relaunch binds cleanly and
        # surviving workers reconnect on the path they already hold.
        self._uds_path = uds_socket_path(self.args.port)
        if self._uds_path is not None:
            try:
                os.makedirs(os.path.dirname(self._uds_path), exist_ok=True)
                try:
                    os.unlink(self._uds_path)
                except FileNotFoundError:
                    pass
                if self.server.add_insecure_port("unix:" + self._uds_path):
                    logger.info(
                        "PS %d also serving on %s", self.args.ps_id,
                        self._uds_path,
                    )
                else:
                    logger.warning(
                        "could not bind %s; serving TCP only",
                        self._uds_path,
                    )
                    self._uds_path = None
            except OSError as e:
                logger.warning(
                    "UDS bind failed (%s); serving TCP only", e
                )
                self._uds_path = None
        self.server.start()
        role = "ps-%d" % self.args.ps_id
        trace.configure(role)
        events.configure(role)
        events.emit("role_start", port=self.args.port)
        # continuous profiler (ISSUE 14): always-on when EDL_PROF_HZ is
        # set, served as /profilez on the observability port below
        profiler.maybe_start(role)
        if self._restored_version is not None:
            events.emit(
                "ps_restored", version=self._restored_version,
                ps=self.args.ps_id,
            )
        self.observability = http_server.maybe_start(
            role, cli_port=getattr(self.args, "metrics_port", 0)
        )
        if self.observability is not None:
            # readiness milestone: cold-start dense params arrived or an
            # embedding table exists — before either, pulls serve nothing
            self.observability.add_readiness_check(
                "model_initialized", self.servicer.model_initialized
            )
        # SIGTERM graceful stop (ISSUE 7): the pod manager stops PS
        # pods with SIGTERM, which skips atexit. The handler itself
        # only sets a flag (it may interrupt the poll thread mid-
        # lifecycle_tick with the push lock held); run() notices
        # within one poll tick and performs the drain — flush the
        # round buffer + save a final complete checkpoint (servicer
        # .graceful_stop) — then chains the flight-recorder hook
        # (installed in main() before us), which dumps the event ring,
        # flushes the journal, and exits 0.
        self._install_sigterm_stop()
        logger.info(
            "PS %d/%d serving on :%d",
            self.args.ps_id,
            self.args.num_ps_pods,
            self.args.port,
        )
        return self

    def _cleanup_uds(self):
        """Unlink this PS's unix socket on ORDERLY shutdown. Leaving
        it behind would make a later build_channel to a reused local
        port rewrite onto the dead socket and fail UNAVAILABLE forever
        while a live TCP listener sits on that port — the rewrite
        keys on path existence alone. (A SIGKILL still leaves the
        file; that case is owned by the same-path relaunch, which
        unlinks before rebinding.)"""
        path = getattr(self, "_uds_path", None)
        if path is None:
            return
        self._uds_path = None
        try:
            os.unlink(path)
        except OSError:
            pass

    def _install_sigterm_stop(self):
        self._term_previous = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            # Flag-only: the handler interrupts the poll thread, which
            # may be INSIDE lifecycle_tick/table_health_scan holding
            # the push lock — draining here (graceful_stop re-takes
            # that lock, AsyncCheckpointer.stop joins its thread)
            # self-deadlocks until the pod's SIGKILL. The poll loop
            # observes the flag within one tick and runs the same
            # drain with no servicer lock held (_finish_term).
            self._term_flag = True

        try:
            signal.signal(signal.SIGTERM, _on_term)
        except ValueError:
            # not the main thread (embedded/test use): the write-through
            # journal still covers SIGKILL; only the final-checkpoint
            # convenience is lost
            logger.warning(
                "not on main thread; PS SIGTERM flush not installed"
            )

    def _finish_term(self):
        """The deferred SIGTERM drain (what the handler used to do
        inline): runs on the poll thread between ticks, where no
        servicer lock is held. Same order as before — stop the
        server, round-buffer flush + final checkpoint, then chain the
        flight-recorder hook (which dumps the ring and exits 0)."""
        try:
            # stop taking new pushes; in-flight handlers finish
            # under the push lock graceful_stop is about to take
            self.server.stop(grace=1.0)
        except Exception:
            logger.exception("server stop at SIGTERM failed")
        self._cleanup_uds()
        self.servicer.graceful_stop()
        events.emit("role_stop", reason="sigterm_drain")
        events.flush()
        previous = self._term_previous
        if callable(previous):
            previous(signal.SIGTERM, None)
        return 0

    # edlint: thread=ps-poll
    def run(self, poll_secs=5.0):
        """Serve until the master stops answering (reference: PS pods poll
        the master pod's status, parameter_server.py:129-153).

        The poll is also the lifecycle clock (ISSUE 12): each tick runs
        an eviction sweep (rate-limited by EDL_EMB_SWEEP_SECS) and, in
        streaming mode, checks the master's record watermark against
        the EDL_STREAM_CHECKPOINT_EVERY cadence — the streaming
        replacement for epoch-boundary checkpoints."""
        from elasticdl_tpu.common.env_utils import env_float, env_int

        sweep_secs = env_float("EDL_EMB_SWEEP_SECS", poll_secs)
        stream_ckpt_every = env_int("EDL_STREAM_CHECKPOINT_EVERY", 0)
        last_sweep = time.time()
        if self._master_client is None:
            if self.lifecycle is None:
                # bounded wait so a SIGTERM flag is noticed within one
                # poll even though the handler no longer stops the
                # server itself
                while self.server.wait_for_termination(timeout=poll_secs):
                    if self._term_flag:
                        return self._finish_term()
                self.servicer.finish_checkpoints()
                return 0
            # masterless (embedded/test) but lifecycle on: the sweep
            # still needs a clock — and server termination must still
            # end run() (an embedding host calling server.stop(), or a
            # SIGTERM whose handler couldn't install off-main-thread).
            # NB grpc's wait_for_termination(timeout) returns True on
            # TIMEOUT (still serving) and False once terminated.
            while self.server.wait_for_termination(timeout=sweep_secs):
                if self._term_flag:
                    return self._finish_term()
                self.servicer.lifecycle_tick()
                self.servicer.table_health_scan()
            self.servicer.finish_checkpoints()
            return 0
        # Grace before concluding the master is gone for good: must
        # comfortably cover a master pod relaunch + state-journal
        # replay (ISSUE 4) — the old 3-strike rule (15 s) made every
        # recoverable master restart take the whole PS fleet with it.
        # Seconds-based (ISSUE 19) so the grace survives poll-interval
        # tuning; an explicit EDL_PS_MASTER_GONE_POLLS still wins for
        # back-compat, converted at this run's poll cadence.
        gone_secs = env_float("EDL_PS_MASTER_GONE_SECS", 90.0)
        legacy_polls = env_int("EDL_PS_MASTER_GONE_POLLS", 0)
        if legacy_polls > 0:
            gone_secs = legacy_polls * poll_secs
        gone_since = None
        while True:
            time.sleep(poll_secs)
            if self._term_flag:
                return self._finish_term()
            info = self._master_client.get_comm_info()
            if info.mesh_epoch < 0:  # RPC failure marker
                if gone_since is None:
                    gone_since = time.time()
                if time.time() - gone_since >= gone_secs:
                    logger.info("Master gone; PS exiting")
                    self.server.stop(grace=1.0)
                    self._cleanup_uds()
                    # orderly exit: an enqueued off-RPC save must land
                    # before the process dies, or the relaunch restores
                    # without the job's last pushes
                    self.servicer.finish_checkpoints()
                    events.emit("role_stop", reason="master_gone")
                    events.flush()
                    return 0
            else:
                gone_since = None
                if stream_ckpt_every > 0:
                    self.servicer.maybe_stream_checkpoint(
                        getattr(info, "stream_watermark", 0),
                        stream_ckpt_every,
                    )
            if (
                self.lifecycle is not None
                and time.time() - last_sweep >= sweep_secs
            ):
                last_sweep = time.time()
                self.servicer.lifecycle_tick()
            # table-health scan (ISSUE 15): rides the same poll,
            # rate-limited internally (EDL_HEALTH_SCAN_SECS); its
            # aggregates go out with the next telemetry blob
            self.servicer.table_health_scan()


def main(argv=None):
    args = parse_ps_args(argv)
    from elasticdl_tpu.testing import faults

    # before any channel/server is built: fault specs match on role
    faults.set_role("ps-%d" % args.ps_id)
    if args.metrics_port:
        # publish the knob before any instrument is constructed: the
        # registry decides enabled/no-op at first touch
        os.environ[http_server.PORT_ENV] = str(args.metrics_port)
    # the pod manager stops PS pods with SIGTERM, which skips atexit —
    # the crash hooks dump the event ring and flush the journal AND the
    # trace buffer, then exit 0. prepare() layers the graceful stop on
    # top (round-buffer flush + final checkpoint, then chains here).
    events.install_crash_hooks()
    return ParameterServer(args).prepare().run()


if __name__ == "__main__":
    sys.exit(main())
