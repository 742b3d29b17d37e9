"""nope_mla_time_share: device time of latent attention that rotates
nothing, in a model whose other mixers are recurrent -- the operations
under the ``mla/`` scopes of ``LatentAttention`` (``q_proj``,
``kv_down``, ``kv_up``, ``assemble``, ``out_proj``; forward and
backward) plus the flash kernels it calls -- over device busy time,
worst device, in percent (lib/kda_trace.py, which reads them in the
pass that reads the ``kda/`` scopes). Left out for a program without
the ``kda/`` scopes."""

from benchmark.lib import kda_trace


def read(run):
    return kda_trace.time_share(kda_trace.reduced(run), kda_trace.MLA_KINDS)
