"""Worker process entry point.

Reference parity: elasticdl/python/worker/main.py:28-82.
Usage: python -m elasticdl_tpu.worker.main --master_addr=... --worker_id=0 \
    --model_zoo=... --training_data=...
"""

import atexit
import time

# the first statement this module runs: where ``imports`` starts if the
# operating system cannot say when the process did
_MODULE_START_NS = time.perf_counter_ns()

# (ledger, when main returned) of a worker that ran to its end. The
# hook below is registered before anything else is imported, so it is
# the LAST exit hook to run: after the interpreter joined the threads
# and jax, grpc and orbax tore down what they hold. Those seconds are
# the ``exit`` phase of ``worker_teardown``.
_exiting = []


def _close_teardown():
    for ledger, returned_ns in _exiting:
        ledger.end_record("exit", returned_ns)
        ledger.end_teardown()


atexit.register(_close_teardown)

import os  # noqa: E402
import sys  # noqa: E402

from elasticdl_tpu.common import timing_utils  # noqa: E402
from elasticdl_tpu.common.args import (  # noqa: E402
    parse_params_string,
    parse_worker_args,
    symbol_overrides_from_args,
)
from elasticdl_tpu.common.env_utils import env_str  # noqa: E402
from elasticdl_tpu.common.log_utils import (  # noqa: E402
    configure as configure_logging,
)
from elasticdl_tpu.data.readers import create_data_reader  # noqa: E402
from elasticdl_tpu.worker.master_client import MasterClient  # noqa: E402
from elasticdl_tpu.worker.trainer import trainer_class  # noqa: E402
from elasticdl_tpu.worker.worker import Worker  # noqa: E402


def main(argv=None):
    main_start_ns = time.perf_counter_ns()
    if env_str("EDL_FAULTHANDLER", ""):
        # stack dumps on demand (kill -USR1 <pid>): lockstep multi-host
        # hangs are otherwise invisible
        import faulthandler
        import signal

        faulthandler.register(signal.SIGUSR1, all_threads=True)
    from elasticdl_tpu.common import platform

    platform.configure_compile_cache()
    import jax

    args = parse_worker_args(argv)
    configure_logging(args.log_level, args.log_file_path)
    from elasticdl_tpu.common.log_utils import default_logger

    logger = default_logger("elasticdl_tpu.worker.main")
    from elasticdl_tpu.observability import (
        events,
        http_server,
        profiler,
        trace,
    )

    if args.metrics_port:
        # publish the knob before any instrument (or instrumented
        # channel) is constructed: the registry decides enabled/no-op
        # at first touch
        os.environ[http_server.PORT_ENV] = str(args.metrics_port)
    trace.configure("worker-%d" % args.worker_id)
    events.configure("worker-%d" % args.worker_id)
    # before the ledger takes its first reading of them: jax's own
    # account of every program this process traces, compiles or loads
    # from the compile cache, the eager ones of start-up included
    from elasticdl_tpu.observability import device as device_obs

    device_obs.install_listeners()
    # after the metrics knob and the journal: the ledger asks both
    ledger = timing_utils.start_ledger(
        _MODULE_START_NS, main_start_ns, args.log_loss_steps
    )
    # continuous profiler (ISSUE 14): always-on when EDL_PROF_HZ is
    # set, served as /profilez on the observability port below
    profiler.maybe_start("worker-%d" % args.worker_id)
    from elasticdl_tpu.testing import faults

    # before any master/PS channel is built: fault specs match on role
    faults.set_role("worker-%d" % args.worker_id)
    # Eviction discipline (ISSUE 3 + 7), in chain order: the drain hook
    # installs FIRST so install_crash_hooks captures it as the previous
    # handler — a SIGTERM then dumps the event ring / flushes the
    # journal (black box) and CHAINS into the graceful drain, which
    # finishes the current task, joins the in-flight async push,
    # flushes device-tier rows, and deregisters before exit (bounded by
    # EDL_DRAIN_DEADLINE_SECS). Before the worker exists, the chain
    # falls through to the old exit-0 eviction contract.
    from elasticdl_tpu.worker.drain import install_sigterm_drain

    drain_hook = install_sigterm_drain()
    events.install_crash_hooks()
    # the compile cache's place, arguments, logging, the journal, the
    # hooks: main's first statement to here
    ledger.end_record("configure", main_start_ns)
    with ledger.phase("master_connect"):
        master_client = MasterClient(
            args.master_addr,
            worker_id=args.worker_id,
            worker_host=args.worker_host or None,
        )
    observability = http_server.maybe_start(
        "worker-%d" % args.worker_id, cli_port=args.metrics_port
    )
    if observability is not None:
        # readiness milestone: the master channel has carried a
        # successful RPC (reset_worker below, then the heartbeat)
        observability.add_readiness_check(
            "master_channel_ready", master_client.channel_ok
        )
    # fresh incarnation: flush any task a fatally-aborted predecessor
    # with this worker_id still holds (it can't have requeued them).
    # The response carries this worker_id's master-assigned relaunch
    # epoch — the push incarnation the sync PS orders relaunches by.
    with ledger.phase("master_connect"):
        master_client.reset_worker()
    events.emit(
        "role_start", worker=args.worker_id,
        epoch=master_client.incarnation or 0,
    )
    multihost_runtime = None
    with ledger.phase("backend_init"):
        if args.multihost:
            # must run BEFORE any jax backend initialization
            from elasticdl_tpu.parallel.multihost import MultiHostRuntime

            multihost_runtime = MultiHostRuntime(
                master_client, coordinator_port=args.coordinator_port
            )
            multihost_runtime.ensure_runtime()
        # which device this worker trains on — the first question of
        # any chip run (chip_smoke.py reads this line); after the
        # multihost runtime, which must initialize before the backend
        # does. Asking for the devices is what starts the backend
        logger.info("devices: %s", platform.describe_devices())
    # the reader, the model zoo and the worker around them
    with ledger.phase("worker_init"):
        # an elastic restart must resume from the freshest state: default
        # the init dir to the worker's own checkpoint dir, so the relaunch
        # (same command line) picks up everything checkpointed so far
        checkpoint_dir_for_init = args.checkpoint_dir_for_init or (
            args.checkpoint_dir if args.multihost else ""
        )
        if args.multihost and not checkpoint_dir_for_init:
            import warnings

            warnings.warn(
                "--multihost without --checkpoint_dir: a mesh-epoch restart "
                "will lose all training progress",
                stacklevel=1,
            )
        reader_params = parse_params_string(args.data_reader_params)
        data_origin = (
            args.training_data or args.validation_data or args.prediction_data
        )
        reader = create_data_reader(data_origin, **reader_params)
        # --mesh "fsdp=4" etc: explicit axis sizes; dp=-1 absorbs whatever
        # devices remain, so the same flag survives elastic world-size
        # changes (a relaunch at a smaller world just gets a smaller dp).
        mesh_config = None
        if args.mesh:
            from elasticdl_tpu.parallel.mesh import parse_mesh_spec

            mesh_config = parse_mesh_spec(args.mesh)
        worker = Worker(
            master_client,
            args.model_zoo,
            reader,
            mesh_config=mesh_config,
            grad_accum_steps=args.grad_accum_steps,
            minibatch_size=args.minibatch_size,
            mode=args.mode,
            compute_dtype=args.compute_dtype or None,
            report_version_steps=args.report_version_steps,
            trainer_factory=trainer_class(
                jax.process_count(), jax.device_count()
            ),
            ps_addrs=args.ps_addrs or None,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_steps=args.checkpoint_steps,
            async_checkpoint=bool(args.async_checkpoint),
            keep_checkpoint_max=args.keep_checkpoint_max,
            checkpoint_dir_for_init=checkpoint_dir_for_init,
            multihost_runtime=multihost_runtime,
            sparse_pipeline=bool(args.sparse_pipeline),
            sparse_cache_staleness=args.sparse_cache_staleness,
            sparse_push_interval=args.sparse_push_interval,
            model_def=args.model_def,
            model_params=args.model_params,
            symbol_overrides=symbol_overrides_from_args(args),
            log_loss_steps=args.log_loss_steps,
            consensus_interval=args.consensus_interval,
            # the elastic fallback dir is empty on first launch; only an
            # explicit operator resume request is strict
            resume_optional=not args.checkpoint_dir_for_init,
            ledger=ledger,
        )
    # SIGTERM now triggers the graceful drain instead of a bare exit
    drain_hook.bind(worker)
    from elasticdl_tpu.train.health import HealthSentinelError
    from elasticdl_tpu.worker.worker import (
        EPOCH_RESTART_EXIT_CODE,
        MeshEpochChanged,
    )

    try:
        worker.run()
        if multihost_runtime is not None:
            # orderly leave: jax.distributed.shutdown is a barrier; a
            # process that just exits makes peers' shutdown fail and
            # their runtime abort them even though the job completed
            try:
                with ledger.phase("teardown"):
                    multihost_runtime.shutdown()
            except Exception:
                logger.warning(
                    "distributed shutdown barrier failed (peers gone?)"
                )
    except HealthSentinelError as e:
        # sentinel halt (ISSUE 15): the task was already reported
        # failed (requeued once) and health_halt journaled by the
        # tracker; exit nonzero with the buffers flushed so the
        # failure is LOUD, attributable, and postmortem-readable
        logger.error("health sentinel halt: %s", e)
        events.emit(
            "role_stop", worker=args.worker_id, reason="health_halt"
        )
        events.flush()
        trace.flush()
        return 1
    except MeshEpochChanged as e:
        # pod manager relaunches us with the same command line; the
        # restarted process rejoins at the new epoch and resumes from
        # checkpoint_dir_for_init (defaulted to checkpoint_dir above).
        # os._exit, not sys.exit: worker.run() already flushed the
        # checkpoint manager in its finally block, and lingering
        # non-daemon threads (orbax's async machinery, the
        # jax.distributed coordinator) would otherwise block interpreter
        # teardown forever — the process must die NOW so the pod
        # restarts into the new mesh.
        logger.warning("Restarting for new mesh epoch: %s", e)
        import logging

        events.emit(
            "mesh_epoch_restart", worker=args.worker_id,
            epoch=master_client.incarnation or 0, reason=str(e)[:200],
        )
        # os._exit skips atexit; don't lose either buffer
        events.flush()
        trace.flush()
        logging.shutdown()
        os._exit(EPOCH_RESTART_EXIT_CODE)
    events.emit("role_stop", worker=args.worker_id)
    events.flush()
    # ``worker_teardown`` leaves with the last exit hook
    _exiting.append((ledger, ledger.start()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
