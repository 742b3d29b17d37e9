"""Operations and bytes a decoder-only language model of Mellum2's
block needs (``mellum``'s ``config.json``) when every layer's experts
are spread over the chips that train it: softmax attention of two kinds
mixed by ``layer_types``, a ``full_attention`` layer over the causal
prefix and a ``sliding_attention`` layer over the ``sliding_window``
keys that end at the query, both with ``num_attention_heads`` query
heads over ``num_key_value_heads`` kv heads of ``head_dim``; every MLP
``sparse``: a router over ``num_experts`` experts and the token's
``num_experts_per_tok`` SwiGLU experts of ``moe_intermediate_size``,
ALL of them (nothing is held back: the group of
``expert_parallel.ranks`` chips has every expert, and the cell's
samples are the group's), no shared expert; an untied head over the
whole vocabulary. Only the first ``num_hidden_layers`` entries of the
per-layer lists count. A configuration names this count by the file's
name (``"flops": "ep_window_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Matrix multiplications only, 2 FLOPs a multiply-add. Per token and
layer: the query projection (d x H D), key and value (d x Hkv D each),
the output projection (H D x d), the router (d x E), k experts of 3 d
w. Per layer: attention's two score-sized products over the entries its
mask KEEPS at H heads: S (S + 1) / 2 a head in a full layer, S W - W (W
- 1) / 2 in a sliding one (W <= S; a query sees itself and the W - 1
keys before it). The output head.

Backward = 2 x forward, nothing recomputed, the embedding gather
excluded. NOTHING for the rotary, the norms, the sort, the gathers, and
no FLOPs for the exchange: its cost is bytes over the chips' links
(``exchange_bytes``).
"""

FULL, SLIDING = "full_attention", "sliding_attention"
# the passes that carry rows between ranks: the dispatch and the
# combine, forward and backward (a rematerialised forward's are time
# and no needed bytes, as its FLOPs are)
EXCHANGE_PASSES = 4
ROW_ITEMSIZE = 2  # the rows travel in bfloat16


def layers_of(config):
    """The kinds of the layers that are built; every one is sparse."""
    count = config["num_hidden_layers"]
    if set(config["mlp_layer_types"][:count]) != {"sparse"}:
        raise ValueError(
            "mlp_layer_types=%r" % (config["mlp_layer_types"][:count],))
    return list(config["layer_types"][:count])


def kept_scores(kind, seq, window):
    """Score entries one head keeps under the layer's mask."""
    if kind == FULL:
        return seq * (seq + 1) / 2.0
    if kind != SLIDING:
        raise ValueError("layer_types names %r" % (kind,))
    w = min(window, seq)
    return float(seq) * w - w * (w - 1) / 2.0


def projection_flops(config):
    """Forward FLOPs of one token's four projections."""
    d, width = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * (2 * d * heads * width + 2 * d * kv * width)


def expert_flops_per_token(config):
    """Forward FLOPs of one token's routed experts, all of them."""
    return 2.0 * config["num_experts_per_tok"] * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def router_flops_per_token(config):
    return 2.0 * config["hidden_size"] * config["num_experts"]


def attention_flops(config, kind, seq):
    """Forward FLOPs of one layer's two score-sized products."""
    return 2 * 2.0 * kept_scores(kind, seq, config["sliding_window"]) * (
        config["num_attention_heads"] * config["head_dim"])


def parts(config, traffic):
    """Forward and backward FLOPs of one sample by part."""
    seq = traffic["seq_len"]
    out = {"flash_full": 0.0, "flash_window": 0.0, "projections": 0.0,
           "router": 0.0, "experts": 0.0}
    for kind in layers_of(config):
        name = "flash_full" if kind == FULL else "flash_window"
        out[name] += 3.0 * attention_flops(config, kind, seq)
        out["projections"] += 3.0 * seq * projection_flops(config)
        out["router"] += 3.0 * seq * router_flops_per_token(config)
        out["experts"] += 3.0 * seq * expert_flops_per_token(config)
    out["head"] = 3.0 * 2.0 * seq * config["hidden_size"] * config[
        "vocab_size"]
    return out


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    return sum(parts(config, traffic).values())


def flash_need(config, traffic, kinds):
    """(FLOPs, bytes) the flash kernels of the layers of ``kinds`` need
    for one sample's forward and backward: the 7 score-sized matmuls (2
    forward, 5 backward) over each layer's own kept entries. Bytes:
    forward reads q and writes o at H heads and reads k, v at the Hkv
    heads they have; backward reads q, o, do and writes dq at H heads,
    reads k, v and writes dk, dv at Hkv: 2 bytes an element."""
    seq, width = traffic["seq_len"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    flops = nbytes = 0.0
    for kind in layers_of(config):
        if kind not in kinds:
            continue
        flops += 7 * 2.0 * kept_scores(
            kind, seq, config["sliding_window"]) * heads * width
        nbytes += 2.0 * seq * width * ((2 * heads + 2 * kv)
                                       + (4 * heads + 4 * kv))
    return flops, nbytes


def exchange_bytes(config, sent_pairs):
    """Bytes ONE rank sends over the chips' links in a step whose
    ``moe_routing`` event counted ``sent_pairs`` (the pairs a rank sent
    to other ranks, the mean over the ranks, summed over the layers):
    each is a row of ``hidden_size`` bfloat16 values that travels in
    ``EXCHANGE_PASSES`` passes. From the counted pairs, not their
    expectation."""
    return (float(sent_pairs) * config["hidden_size"] * ROW_ITEMSIZE
            * EXCHANGE_PASSES)


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer. ``flash``: the
    flash kernels of both kinds of layer (``flash_need``);
    ``flash_window``: those of the sliding layers alone, the band's;
    ``moe_experts``: as ``moe_decoder.kernels`` counts them, over all
    the sample's pairs and, a sample, its share of one read of every
    expert's kernels; ``exchange``: no FLOPs and the bytes a rank sends
    for one sample under a UNIFORM router, where ``ranks - 1`` of
    ``ranks`` pairs leave their rank (a run's own are
    ``exchange_bytes`` of its counted pairs)."""
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    seq, layers = traffic["seq_len"], len(layers_of(config))
    ranks = config["expert_parallel"]["ranks"]
    rows = seq * config["num_experts_per_tok"]
    expert_flops = 3.0 * seq * expert_flops_per_token(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w)
        + config["num_experts"] * d * w / traffic["minibatch"]
    )
    return {
        "flash": flash_need(config, traffic, (FULL, SLIDING)),
        "flash_window": flash_need(config, traffic, (SLIDING,)),
        "moe_experts": (expert_flops * layers, expert_bytes * layers),
        "exchange": (0.0, exchange_bytes(
            config, layers * rows * (ranks - 1) / ranks)),
    }
