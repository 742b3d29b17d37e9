"""mhc_time_share: device time of the hyper-connected residual path --
the operations under the three ``mhc/`` scopes of ``HyperConnection``
(``coef``, ``pre``, ``post``; forward and backward; the prediction
module's block's among them) -- over device busy time, worst device, in
percent. ``mhc_reduced.json`` beside the report has the parts apart
(lib/mhc_trace.py). Left out for a program without the scopes."""

from benchmark.lib import mhc_trace


def read(run):
    return mhc_trace.time_share(mhc_trace.reduced(run), mhc_trace.MHC_KINDS)
