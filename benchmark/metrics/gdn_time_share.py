"""gdn_time_share: device time of the Gated DeltaNet mixers -- the
operations under the six ``gdn/`` scopes of ``GatedDeltaNet``
(``in_proj``, ``conv``, ``gates``, ``scan``, ``out_norm``, ``out_proj``;
forward and backward) -- over device busy time, worst device, in
percent. ``gdn_reduced.json`` beside the report has the parts apart
(lib/gdn_trace.py). Left out for a program without the scopes."""

from benchmark.lib import gdn_trace


def read(run):
    return gdn_trace.time_share(gdn_trace.reduced(run), gdn_trace.GDN_KINDS)
