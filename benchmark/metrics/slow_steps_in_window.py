"""slow_steps_in_window: ``slow_step`` events the worker journaled
inside the window (a step over 1.5 times the median of the last 64 and
over it by 20 ms; in a traced run only steps after the trace ended).
Each event carries the step's phase split (lib/loop_ledger.py)."""

from benchmark.lib import loop_ledger


def read(run):
    events = loop_ledger.in_window(run, "slow_step", "step")
    if events is None or not loop_ledger.in_window(
            run, "loop_phases", "first_step"):
        # no ledger in this program, or nothing left after the trace:
        # nothing counted, which is not a count of zero
        return None
    return len(events)
