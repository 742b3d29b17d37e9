"""ep_exchange_exposed_share: of the traced window, the time a
collective of the ``moe/exchange`` scope holds a device's core while no
compute operation runs there, worst device, in percent, as
``collective_exposed_share`` reckons it for every collective
(lib/ep_trace.py, lib/trace_reduce.py). What is left of
``ep_exchange_time_share`` after it ran beside compute. Left out for a
program without the scope."""

from benchmark.lib import ep_trace


def read(run):
    return ep_trace.share(ep_trace.reduced(run), "exchange_exposed_s")
