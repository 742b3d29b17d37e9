"""ep_rank_load_max_over_mean: the rows the busiest rank of the expert
group received (its own pairs among them: what its grouped matmuls run)
over the mean of the ranks, in the layer where the busiest received the
most, median over the ``moe_routing`` journal events of a FIXED range
of logged steps after the cell's warm-up (lib/ep_trace.py; the reason
is ``expert_load_max_over_mean``'s). 1 is an even split. Unlike an
expert's load on one chip this IS a cost: every rank waits for the
busiest at the next exchange. Left out for a program that journals no
such counter."""

from benchmark.lib import ep_trace


def ratio(event):
    mean = event.get("received_pairs_mean")
    return event["received_pairs_max"] / mean if mean else None


def read(run):
    return ep_trace.counter_median(run, ratio)
