"""kda_time_share: device time of the Kimi Delta Attention mixers -- the
operations under the six ``kda/`` scopes of ``KimiDeltaAttention``
(``in_proj``, ``conv``, ``gates``, ``scan``, ``out_norm``, ``out_proj``;
forward and backward) -- over device busy time, worst device, in
percent. ``kda_reduced.json`` beside the report has the parts apart
(lib/kda_trace.py). Left out for a program without the scopes."""

from benchmark.lib import kda_trace


def read(run):
    return kda_trace.time_share(kda_trace.reduced(run), kda_trace.KDA_KINDS)
