"""loop_host_ms: milliseconds a step the loop thread spends NOT waiting
for the chip: the iteration's wall time less its ``device_wait``,
median over the ``loop_phases`` events of the window (worker journal,
lib/loop_ledger.py). Beside ``dispatch_gap_ms`` it says whether the
device's gap between steps is the serial loop. One-chip cells only: on
a mesh the loop runs ahead and ``dispatch`` holds the waiting."""

from benchmark.lib import loop_ledger


def read(run):
    return loop_ledger.per_step_median_ms(
        run,
        lambda event: event["wall_ns"]
        - event["phases"].get("device_wait", 0))
