"""moe_permute_share: device time under ``moe/dispatch`` and
``moe/combine`` alone (the sort, the gathers to expert order and back
and their backward: what the algorithm needs no FLOPs for) over device
busy time, worst device, in percent (lib/moe_trace.py)."""

from benchmark.lib import moe_trace


def read(run):
    return moe_trace.time_share(
        moe_trace.reduced(run), ("dispatch", "combine"))
