"""mamba_time_share: device time of the Mamba-2 state-space mixers -- the
operations under the six ``mamba/`` scopes of ``Mamba2Mixer``
(``in_proj``, ``conv``, ``gates``, ``scan``, ``out_norm``, ``out_proj``;
forward and backward) -- over device busy time, worst device, in
percent. ``ssm_reduced.json`` beside the report has the parts apart
(lib/ssm_trace.py). Left out for a program without the scopes."""

from benchmark.lib import ssm_trace


def read(run):
    return ssm_trace.time_share(
        ssm_trace.reduced(run), ssm_trace.MAMBA_KINDS)
