"""dense_mlp_time_share: device time of the leading dense layers' MLPs
-- the operations under the scope ``dense_mlp`` (a dense block's three
matmuls and its SiLU gate, forward and backward) -- over device busy
time, worst device, in percent (lib/conv_trace.py). Left out for a
program with nothing under the scope (the parent of PR 49, a model
without a dense block)."""

from benchmark.lib import conv_trace


def read(run):
    return conv_trace.time_share(
        conv_trace.reduced(run), [conv_trace.DENSE_MLP])
