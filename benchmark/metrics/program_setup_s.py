"""program_setup_s: the seconds of the run outside the window that lie
inside one of the program's own records, as a union of intervals on
the epoch clock: ``master_startup``, ``worker_startup`` (process start
to the first step's return), the warm-up steps (to the window's
start), SIGTERM to the worker's last exit hook, ``master_teardown``.
What is left of ``setup_s`` is the harness's (data, the reference
check, reduction) and the gaps between processes."""

from benchmark.lib import setup_ledger


def read(run):
    intervals = setup_ledger.program_intervals(run)
    if intervals is None:
        return None
    return setup_ledger.outside_window(
        [i for i in intervals.values() if i is not None], run["window"])
