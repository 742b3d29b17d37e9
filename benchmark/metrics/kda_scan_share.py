"""kda_scan_share: device time under ``kda/scan`` alone -- the chunked
delta rule with a decay a channel: the chunks' decayed products and
inverses, the scan that carries the state from chunk to chunk, the
output's matmuls, forward and backward and the segments' recompute --
over device busy time, worst device, in percent (lib/kda_trace.py).
Left out for a program without the scopes."""

from benchmark.lib import kda_trace


def read(run):
    return kda_trace.time_share(kda_trace.reduced(run), ["kda/scan"])
