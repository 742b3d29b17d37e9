"""sparse_attn_fill: the score entries a learned indexer's selection
keeps, ``sum_t min(topk, t + 1)`` a head, over the entries of the tiles
the sparse flash kernels compute (the pairs that run x the tile's area;
the forward's two score-sized products and the backward's five over
their own tiles), in percent, from the counts and the block sizes on
the worker's attention line (``ops/sparse_attention.py``;
lib/dsa_trace.py parses it). 100 is a kernel that computes no entry the
selection drops; a program that walks every causal tile reads the kept
share of the causal prefix. Left out for a program whose log has no
such line."""

import os

from benchmark.lib import dsa_trace


def read(run):
    try:
        with open(os.path.join(run["out"], "worker.log"),
                  errors="replace") as f:
            line = dsa_trace.attention_line(f.read())
    except OSError:
        return None
    return dsa_trace.fill(line) if line else None
