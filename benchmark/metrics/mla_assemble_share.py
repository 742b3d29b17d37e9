"""mla_assemble_share: device time under ``mla/assemble`` alone -- the
rotary on the rope lanes of q and of the shared key head, that head's
broadcast over all heads, the concatenations to the 192-wide q and k,
the transposes: work that needs no FLOPs -- over device busy time,
forward and backward, worst device, in percent (lib/mla_trace.py). Left
out for a program without the scopes."""

from benchmark.lib import mla_trace


def read(run):
    return mla_trace.time_share(mla_trace.reduced(run), ["mla/assemble"])
