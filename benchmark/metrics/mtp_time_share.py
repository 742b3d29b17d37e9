"""mtp_time_share: device time of the multi-token-prediction module --
the operations under ``mtp/proj``, ``mtp/block`` and ``mtp/head`` of
``MoeTransformerLM`` (forward and backward; the loss's share of the
second head pass lies under ``loss`` and is not counted) -- over device
busy time, worst device, in percent (lib/mhc_trace.py). The module is
one block of six in this cut and one of 41 in the model, so the share
is about six times a deployment's. Left out for a program without the
scopes and for a configuration without a module."""

from benchmark.lib import mhc_trace


def read(run):
    return mhc_trace.time_share(mhc_trace.reduced(run), mhc_trace.MTP_KINDS)
