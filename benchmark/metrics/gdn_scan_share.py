"""gdn_scan_share: device time under ``gdn/scan`` alone -- the chunked
gated delta rule: the chunks' batched matmuls, the scan that carries the
state from chunk to chunk, the output's matmuls, forward and backward
and the segments' recompute -- over device busy time, worst device, in
percent (lib/gdn_trace.py). Left out for a program without the
scopes."""

from benchmark.lib import gdn_trace


def read(run):
    return gdn_trace.time_share(gdn_trace.reduced(run), ["gdn/scan"])
