"""Operations and bytes one next-token training step of a decoder-only
mixture-of-experts language model needs whose attention runs over keys
a learned indexer picks (Keye-VL-2.0's ``KeyeVL2`` language
``config.json``; DeepSeek Sparse Attention, the DeepSeek-V3.2-Exp
report): grouped-query attention with a head width of its own, an
indexer of ``sa_config.indexer_num_heads`` heads of
``sa_config.indexer_head_dim`` over one key a position that keeps
``sa_config.topk`` keys a query, every layer an expert layer of which
THIS CHIP holds ``num_experts`` of ``published.num_experts`` experts, no
shared expert. A configuration names this count by the file's name
(``"flops": "dsa_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Matrix multiplications only, 2 FLOPs a multiply-add. Per position and
layer: the query and output projections (d x H D each), key and value
(d x Hkv D each), the indexer's three (d x J Di, d x Di, d x J), the
router over ALL experts (d x E) and this chip's share of the position's
k routed experts (k x held / E experts of 3 d w on average). Per layer:
the indexer's scores over the CAUSAL HALF of the pairs, S (S + 1) / 2,
at J heads of Di (a selection must score every pair it chooses among),
and attention's two score-sized products over the KEPT entries alone,
``sum_t min(topk, t + 1)`` a head, at H query heads: a program that
walks every causal tile runs more than it is charged for, and ``mfu``
says so. The output head over the held vocabulary. Backward = 2 x
forward, nothing recomputed, the embedding gather excluded. NOTHING for
the selection (a top-k needs no FLOPs), the mask, the KL term's
elementwise work, the norms, the sort, the gathers or the scatter.
"""


def kept_scores(config, traffic):
    """Score entries a head keeps: ``sum_t min(topk, t + 1)``."""
    seq, topk = traffic["seq_len"], config["sa_config"]["topk"]
    full = min(seq, topk)
    return full * (full + 1) / 2.0 + float(seq - full) * topk


def causal_scores(traffic):
    """(query, key) pairs of the causal prefix: S (S + 1) / 2."""
    seq = traffic["seq_len"]
    return seq * (seq + 1) / 2.0


def projection_flops(config):
    """Forward FLOPs of one position's four attention projections."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    width, kv = config["head_dim"], config["num_key_value_heads"]
    return 2.0 * (2 * d * heads * width + 2 * d * kv * width)


def indexer_projection_flops(config):
    """Forward FLOPs of one position's three indexer projections."""
    sa = config["sa_config"]
    heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return 2.0 * config["hidden_size"] * (heads * width + width + heads)


def indexer_score_flops(config, traffic):
    """Forward FLOPs of a layer's scores over the causal half."""
    sa = config["sa_config"]
    return 2.0 * causal_scores(traffic) * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"])


def held_share(config):
    """The share of a layer's experts this chip holds."""
    return config["num_experts"] / config["published"]["num_experts"]


def expert_flops_per_position(config):
    """Forward FLOPs of one position's routed experts HERE, on
    average."""
    return 2.0 * config["num_experts_per_tok"] * held_share(config) * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def parts(config, traffic):
    """Forward and backward FLOPs of one sample by part."""
    d, seq = config["hidden_size"], traffic["seq_len"]
    layers = config["num_hidden_layers"]
    lanes = config["num_attention_heads"] * config["head_dim"]
    return {
        "attention": 3.0 * layers * 2 * 2.0 * kept_scores(
            config, traffic) * lanes,
        "indexer_scores": 3.0 * layers * indexer_score_flops(
            config, traffic),
        "indexer_projections": 3.0 * layers * seq
        * indexer_projection_flops(config),
        "projections_and_router": 3.0 * layers * seq * (
            projection_flops(config)
            + 2.0 * d * config["published"]["num_experts"]),
        "held_experts": 3.0 * layers * seq
        * expert_flops_per_position(config),
        "head": 3.0 * 2.0 * seq * d * config["vocab_size"],
    }


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    return sum(parts(config, traffic).values())


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer.

    ``flash``: the 7 score-sized matmuls (2 forward, 5 backward) over
    the KEPT entries, at H query heads: the work the selection leaves.
    Bytes: forward reads q and writes o at H heads and reads k, v at the
    Hkv heads they have; backward reads q, o, do and writes dq at H
    heads, reads k, v and writes dk, dv at Hkv: 2 bytes an element; the
    kept set itself, a bit a causal pair, once forward and once
    backward. ``indexer_scores``: the scores' products over the causal
    half, forward and backward (3 x forward); bytes: qI, kI and w read
    and their cotangents written, 2 and 4 bytes an element.
    ``moe_experts``: as ``bd_moe_decoder.kernels`` counts them, over
    the rows this chip's experts get on average and the ``num_experts``
    kernels it holds."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    seq, layers = traffic["seq_len"], config["num_hidden_layers"]
    width, sa = config["head_dim"], config["sa_config"]
    idx_heads, idx_width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    flash_flops = 7 * 2.0 * kept_scores(config, traffic) * heads * width
    flash_bytes = 2.0 * seq * width * (
        (2 * heads + 2 * kv) + (4 * heads + 4 * kv)
    ) + 2 * causal_scores(traffic) / 8.0
    score_flops = 3.0 * indexer_score_flops(config, traffic)
    score_bytes = 2.0 * seq * (
        2.0 * (idx_heads * idx_width + idx_width) + 4.0 * idx_heads)
    rows = seq * config["num_experts_per_tok"] * held_share(config)
    expert_flops = 3.0 * seq * expert_flops_per_position(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w)
        + config["num_experts"] * d * w / traffic["minibatch"]
    )
    return {
        "flash": (flash_flops * layers, flash_bytes * layers),
        "indexer_scores": (score_flops * layers, score_bytes * layers),
        "moe_experts": (expert_flops * layers, expert_bytes * layers),
    }
