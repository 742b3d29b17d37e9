"""ep_exchange_time_share: the time the collectives under the
``moe/exchange`` scope (``ops/moe.py:exchange_rows``: the all-to-alls
over ``ep`` that carry an expert layer's rows to the ranks that hold
their experts and back, forward and backward) hold a device's core,
over the traced window, worst device, in percent. By the operations'
own time on the core, not by an asynchronous pair's open span
(lib/ep_trace.py). ``ep_reduced.json`` beside the report has every
device's seconds. Left out for a program without the scope."""

from benchmark.lib import ep_trace


def read(run):
    return ep_trace.share(ep_trace.reduced(run), "exchange_s")
