"""Operations and bytes a LOOPED dense decoder needs (Ouro: ``layers``
sandwich-normed blocks run ``total_ut_steps`` times over one set of
parameters, one head applied to every pass's exit, one gate), computed
from the configuration's shapes. ``6 N D`` is wrong by construction
here: a block's parameter is used ``T`` times a token and the head's
``T`` times.

Part of the yardstick, as ``dense_decoder.py``, whose attention count
it imports: matrix multiplications only, causal attention at half the
score matrix, backward = 2 x forward, nothing recomputed (a chunked
head that forms its logits again in the backward EXECUTES 4/3 of the
head's count), the embedding gather excluded.
"""

from benchmark.flops.dense_decoder import (
    flash_attention_bytes,
    flash_attention_flops,
)


def parts(config, traffic):
    """Forward and backward FLOPs of one sample by part."""
    d, seq = config["hidden_size"], traffic["seq_len"]
    heads, width = config["num_attention_heads"], config["head_dim"]
    applications = config["total_ut_steps"] * config["num_hidden_layers"]
    # per token and block application: q, k, v, out (4 d H D) and the
    # SwiGLU's three matrices
    projections = 2.0 * seq * (
        4 * d * heads * width + 3 * d * config["intermediate_size"])
    return {
        "projections": 3.0 * applications * projections,
        "attention": 3.0 * applications * flash_attention_flops(
            seq, heads, width, backward=False),
        "heads": 3.0 * config["total_ut_steps"] * 2.0 * seq * d
        * config["vocab_size"],
        "gates": 3.0 * config["total_ut_steps"] * 2.0 * seq * d,
    }


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    return sum(parts(config, traffic).values())


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward: ``flash`` over ``T x layers`` block
    applications (what ``flash_roofline`` reads)."""
    heads, width = config["num_attention_heads"], config["head_dim"]
    seq = traffic["seq_len"]
    applications = config["total_ut_steps"] * config["num_hidden_layers"]
    flops = sum(
        flash_attention_flops(seq, heads, width, b) for b in (0, 1))
    nbytes = sum(
        flash_attention_bytes(seq, heads, width, b) for b in (0, 1))
    return {"flash": (flops * applications, nbytes * applications)}
