"""short_conv_time_share: device time of the gated short convolution
mixers -- the operations under the three ``short_conv/`` scopes of
``ShortConv`` (``in_proj``, ``gate``, ``out_proj``; forward and
backward) -- over device busy time, worst device, in percent.
``conv_reduced.json`` beside the report has the parts apart
(lib/conv_trace.py). Left out for a program without the scopes."""

from benchmark.lib import conv_trace


def read(run):
    return conv_trace.time_share(
        conv_trace.reduced(run), conv_trace.CONV_KINDS)
