"""shared_expert_time_share: device time under ``moe/shared`` -- the
shared experts' SwiGLU MLP that every token passes, forward and
backward -- over device busy time, worst device, in percent
(lib/mla_trace.py). Left out for a program without the scope."""

from benchmark.lib import mla_trace


def read(run):
    return mla_trace.time_share(mla_trace.reduced(run), ["moe/shared"])
