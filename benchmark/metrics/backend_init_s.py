"""backend_init_s: seconds the worker took to start its backend, to the
``devices:`` line (``worker_startup.phases.backend_init`` of the
worker's journal, lib/loop_ledger.py)."""

from benchmark.lib import loop_ledger


def read(run):
    return loop_ledger.startup_seconds(run, "backend_init")
