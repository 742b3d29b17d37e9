"""state_init_s: seconds the trainer took to make its first state:
``create_state`` and its compile (``worker_startup.phases.state_init``
of the worker's journal, lib/loop_ledger.py)."""

from benchmark.lib import loop_ledger


def read(run):
    return loop_ledger.startup_seconds(run, "state_init")
