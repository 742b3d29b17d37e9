"""exit_head_time_share: device time of a looped stack's exits -- the
operations under ``exit/head`` (one head applied to every pass's exit a
chunk of positions at a time, its log-sum-exp, the expected loss;
forward, the backward's second forming of a chunk's logits, and the
backward) and ``exit/gate`` (the gate and the exit distribution) --
over device busy time, worst device, in percent. The count
(``flops/looped_dense_decoder.py``) needs 12.9% of the cell's FLOPs for
the heads; a chunked head that forms its logits twice executes 4/3 of
that (lib/looped_trace.py). Left out for a program without the
scopes."""

from benchmark.lib import looped_trace


def read(run):
    return looped_trace.time_share(
        looped_trace.reduced(run), looped_trace.EXIT)
