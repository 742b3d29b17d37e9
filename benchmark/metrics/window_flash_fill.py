"""window_flash_fill: the score entries a sliding window keeps, S W - W
(W - 1) / 2 a head, over the entries of the tiles the band's flash
kernels compute (the pairs that run x the tile's area; the forward's
two score-sized products and the backward's five over their own tiles),
in percent, from the counts and the block sizes on the worker's
attention line (``ops/attention.py``; lib/window_trace.py parses it).
100 is a kernel that computes no entry the band drops; smaller tiles
raise it and pay in grid steps. Left out for a program whose log has no
such line."""

import os

from benchmark.lib import window_trace


def read(run):
    try:
        with open(os.path.join(run["out"], "worker.log"),
                  errors="replace") as f:
            line = window_trace.attention_line(f.read())
    except OSError:
        return None
    return window_trace.fill(line) if line else None
