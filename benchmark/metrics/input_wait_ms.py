"""input_wait_ms: milliseconds a step the loop thread waits for its
next batch (the ledger's ``input_wait`` phase, ``next(batches)``):
median over the ``loop_phases`` events of the window (worker journal,
lib/loop_ledger.py). Near zero while the prefetch queue is full."""

from benchmark.lib import loop_ledger


def read(run):
    return loop_ledger.per_step_median_ms(
        run, lambda event: event["phases"].get("input_wait", 0))
