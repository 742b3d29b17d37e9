"""ep_exchange_ici_share: the exchange's share of its roofline: the
bytes ONE rank sends a step over the chips' links
(``flops/<name>.py:exchange_bytes`` of the ``moe_routing`` events'
counted ``sent_pairs``, the median over the logged steps after the
warm-up: the pairs that left their rank x the row's bytes x the four
passes that need them) over the time the exchange's collectives hold
the core a step on the device where that is longest (lib/ep_trace.py)
over ``peaks.json``'s ``ici_bytes_per_s``, in percent. A forward
rematerialised in the backward sends its rows again: time and no needed
bytes, so it lowers the share, as it should. Left out for a program
without the scope or the counter, and for a count without
``exchange_bytes``."""

from benchmark.lib import ep_trace, window


def read(run):
    count = getattr(run.get("flops"), "exchange_bytes", None)
    seconds = ep_trace.seconds_a_step(ep_trace.reduced(run))
    sent = ep_trace.counter_median(run, lambda e: e.get("sent_pairs"))
    if count is None or not seconds or sent is None:
        return None
    peak = window.peaks(run)["ici_bytes_per_s"]
    return 100.0 * count(run["config"], sent) / seconds / peak
