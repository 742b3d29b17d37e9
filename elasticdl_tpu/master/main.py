"""Master process entry point.

Reference parity: elasticdl/python/master/main.py:20-24.
Usage: python -m elasticdl_tpu.master.main --model_zoo=... --training_data=...
"""

import atexit
import time

# the first statement this module runs: where ``imports`` starts if the
# operating system cannot say when the process did
_MODULE_START_NS = time.perf_counter_ns()

# (ledger, when main returned), as in worker/main.py: the hook below
# is registered before anything else is imported, so it is the LAST
# exit hook to run, and the seconds to it are the ``exit`` phase of
# ``master_teardown``
_exiting = []


def _close_teardown():
    for ledger, returned_ns in _exiting:
        ledger.end_record("exit", returned_ns)
        ledger.end_teardown()


atexit.register(_close_teardown)

import sys  # noqa: E402

from elasticdl_tpu.common import timing_utils  # noqa: E402
from elasticdl_tpu.common.args import parse_master_args  # noqa: E402
from elasticdl_tpu.master.master import Master  # noqa: E402


def main(argv=None):
    main_start_ns = time.perf_counter_ns()
    import os

    args = parse_master_args(argv)

    from elasticdl_tpu.common.args import symbol_overrides_from_args
    from elasticdl_tpu.common.log_utils import configure

    configure(args.log_level, args.log_file_path)
    # black-box discipline (ISSUE 3): a K8s-evicted master must leave a
    # complete flight record — SIGTERM dumps the event ring and flushes
    # the journal + trace buffer, then exits so Master.run's finally
    # runs stop(). Uncaught exceptions dump the ring too.
    from elasticdl_tpu.observability import events

    events.install_crash_hooks()
    from elasticdl_tpu.testing import faults

    # before the gRPC server is built: fault specs match on role
    faults.set_role("master")
    if args.metrics_port:
        # publish the knob before any instrument is constructed: the
        # registry decides enabled/no-op at first touch
        from elasticdl_tpu.observability.http_server import PORT_ENV

        os.environ[PORT_ENV] = str(args.metrics_port)
    # after the metrics knob: the ledger asks it. ``master_startup``
    # leaves once ``role_start`` has (Master.prepare)
    ledger = timing_utils.start_ledger(
        _MODULE_START_NS, main_start_ns, event="master_startup"
    )
    ledger.end_record("configure", main_start_ns)
    records_per_task = args.records_per_task
    if args.num_minibatches_per_task > 0:
        # reference task sizing (master.py:152)
        records_per_task = (
            args.minibatch_size * args.num_minibatches_per_task
        )
    master = Master(
        model_zoo_module=args.model_zoo,
        training_data=args.training_data,
        validation_data=args.validation_data,
        prediction_data=args.prediction_data,
        records_per_task=records_per_task,
        num_epochs=args.num_epochs,
        port=args.port,
        eval_steps=args.evaluation_steps,
        eval_throttle_secs=args.evaluation_throttle_secs,
        eval_start_delay_secs=args.evaluation_start_delay_secs,
        saved_model_path=args.output,
        task_timeout_secs=args.task_timeout_secs,
        tensorboard_log_dir=args.tensorboard_log_dir or None,
        model_def=args.model_def,
        model_params=args.model_params,
        symbol_overrides=symbol_overrides_from_args(args),
        metrics_port=args.metrics_port,
        ledger=ledger,
    )
    if args.job_name and os.environ.get("KUBERNETES_SERVICE_HOST"):
        # in-cluster: provision and heal worker/PS pods
        from elasticdl_tpu.client.args import parse_envs_string
        from elasticdl_tpu.k8s.pod_manager import K8sPodManager

        master.pod_manager = K8sPodManager(
            args,
            master.task_dispatcher,
            master.rendezvous,
            envs=parse_envs_string(args.envs),
        )
    try:
        master.prepare()
        return master.run()
    finally:
        # ``master_teardown`` (Master.stop opened it) leaves with the
        # last exit hook
        _exiting.append((ledger, ledger.start()))


if __name__ == "__main__":
    sys.exit(main())
