"""ssd_scan_share: device time under ``mamba/scan`` alone -- the chunked
selective scan: the groups' ``C B^T``, the decay masks, the masked
products, the chunks' states and the skip, forward and backward and the
segments' recompute -- over device busy time, worst device, in percent
(lib/ssm_trace.py). Left out for a program without the scopes."""

from benchmark.lib import ssm_trace


def read(run):
    return ssm_trace.time_share(ssm_trace.reduced(run), ssm_trace.SCAN)
