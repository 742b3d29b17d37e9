"""window_attn_time_share: device time of the sliding-window layers'
mixer -- the operations under the ``attn_window/`` scopes of
``models/transformer.py:Attention`` (``qkv``, ``rotary``, ``flash``,
``gate``, ``out_proj``; forward and backward) and the band's flash
kernels (named ``flash_band...``), counted under ``attn_window/flash``
wherever they were called -- over device busy time, worst device, in
percent. ``window_reduced.json`` beside the report has the parts apart,
and the full layers' (lib/window_trace.py). Left out for a program
without the scopes."""

from benchmark.lib import window_trace


def read(run):
    return window_trace.time_share(window_trace.reduced(run))
