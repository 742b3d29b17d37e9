"""stall_share: the share of the window's seconds that its steps would
not have needed at the median interval's rate: 1 - samples_per_s /
(median over the intervals between logged lines), in percent. Near 0
when every interval runs alike; a stall of the input path, a save or a
host hiccup shows here as it shows in samples_per_s, and a uniformly
slower step does not (worker log, lib/window.py). It is reported in
traced runs, and stopping the profiler stalls the loop itself, so only
the lines after the step at which the probe stopped it count
(``trace.done``); a run whose trace never ended reports nothing."""

import os
import statistics

from benchmark.lib import window
from benchmark.lib.procs import HarnessFailure


def read(run):
    after = None
    if run["trace"]:
        try:
            with open(os.path.join(run["out"], "trace.done")) as f:
                after = int(f.read())
        except (OSError, ValueError):
            return None
    try:
        typical = statistics.median(window.interval_rates(run, after))
        mean = window.samples_per_second(run, after)
    except HarnessFailure:
        # too few lines left after the trace: nothing to read
        return None
    return 100.0 * (1.0 - mean / typical)
