"""worker_exit_s: seconds from SIGTERM's arrival at the worker
(``drain_requested.signal_ts``) to its last exit hook (the end of
``worker_teardown``: ``start_ts`` + ``wall_ns``): the task it was in,
the drain, the teardown and the interpreter's exit."""

from benchmark.lib import setup_ledger


def read(run):
    interval = setup_ledger.worker_exit_interval(run)
    return None if interval is None else interval[1] - interval[0]
