"""expert_load_max_over_mean: (token, choice) pairs of the busiest
expert over the mean of all experts, median over the ``moe_routing``
journal events (one every ``log_every`` steps, read with the logged
loss) of a FIXED range of steps: the ``EVENTS`` logged steps after the
cell's warm-up (steps 24 to 88 in ``olmoe1b7b-s4k``), not the window's
wall time. The ratio falls through a run as the load-balancing loss
acts (7.8 at step 8, 2.3 by step 104: chip runs, PR 25), so a window
that starts at a wall time, or a faster program that gets further in
it, would read another stretch of the fall.

1 is a perfectly even router. This describes the traffic the experts
see; it is not a cost on one chip, where a grouped matmul's time is
its rows and not their split (the run with the most skew was the
fastest, PR 25). It will move ``samples_per_s`` once experts lie over
an ``ep`` axis and the busiest rank holds the others up. Left out for
a program that journals no such event."""

import statistics

from benchmark.lib import loop_ledger

# a 20 s window of the cell as PR 25 measured it holds ten logged steps
EVENTS = 9


def read(run):
    first = run["cell"]["warmup_steps"]
    last = first + EVENTS * run["cell"]["log_every"]
    ratios = [
        e["tokens_per_expert_max"] / e["tokens_per_expert_mean"]
        for e in loop_ledger.worker_events(run)
        if e.get("event") == "moe_routing"
        and first < e.get("step", 0) <= last
        and e.get("tokens_per_expert_mean")
    ]
    return statistics.median(ratios) if ratios else None
