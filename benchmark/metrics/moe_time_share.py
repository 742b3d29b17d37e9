"""moe_time_share: device time of the operations under the four
``moe/`` scopes of ``MoeMlp`` (``router``, ``dispatch``, ``experts``,
``combine``; forward and backward; the grouped matmuls' kernels
included, lib/moe_trace.py) over device busy time, worst device, in
percent. ``moe_reduced.json`` beside the report has the four apart."""

from benchmark.lib import moe_trace


def read(run):
    return moe_trace.time_share(moe_trace.reduced(run))
