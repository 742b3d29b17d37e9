"""mla_time_share: device time of latent attention -- the operations
under the five ``mla/`` scopes of ``LatentAttention`` (``q_proj``,
``kv_down``, ``kv_up``, ``assemble``, ``out_proj``; forward and
backward) plus the flash kernels it calls -- over device busy time,
worst device, in percent. ``mla_reduced.json`` beside the report has
the parts apart (lib/mla_trace.py). Left out for a program without the
scopes."""

from benchmark.lib import mla_trace

KINDS = ["mla/" + s for s in mla_trace.MLA_SCOPES] + [mla_trace.FLASH]


def read(run):
    return mla_trace.time_share(mla_trace.reduced(run), KINDS)
