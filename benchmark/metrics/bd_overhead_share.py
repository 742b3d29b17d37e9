"""bd_overhead_share: device time under ``bd/noise`` and ``bd/assemble``
(the noise's draw; the two copies side by side, their positions and the
cut of the noisy half before the head; forward and backward) over
device busy time, worst device, in percent (lib/bd_trace.py): work the
objective adds that needs no FLOPs. Left out for a program without the
scopes."""

from benchmark.lib import bd_trace


def read(run):
    return bd_trace.time_share(bd_trace.reduced(run))
