"""looped_outside_blocks_share: device time of what a looped stack runs
outside its blocks -- the operations under ``looped/pass``,
``looped/exit_norm`` or the method's own scope ``._looped`` that lie
under no ``block_<i>`` scope and under no ``exit/`` scope: the stacked
saved set written and read back a pass, the loops over the passes, the
end-of-pass norm (forward, recompute and backward), the carry -- over
device busy time, worst device, in percent: what the loop costs beside
``T x layers`` applications of a block (lib/looped_trace.py, whose
docstring says which names it takes and which it cannot see). Left out
for a program without the scopes."""

from benchmark.lib import looped_trace


def read(run):
    return looped_trace.time_share(
        looped_trace.reduced(run), looped_trace.OUTSIDE)
