"""mamba_bytes_share: device time of a Mamba-2 mixer's bytes-bound lines
-- the operations under ``mamba/conv`` (convolution, bias, SiLU, the
split), ``mamba/gates`` (softplus, the log decay, the layer's facts) and
``mamba/out_norm`` (the gate and the norm over all the lanes), forward
and backward: what a fused kernel would take -- over device busy time,
worst device, in percent (lib/ssm_trace.py). Left out for a program
without the scopes."""

from benchmark.lib import ssm_trace


def read(run):
    return ssm_trace.time_share(
        ssm_trace.reduced(run), ssm_trace.BYTES_KINDS)
