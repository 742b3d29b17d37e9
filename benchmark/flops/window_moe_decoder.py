"""Operations and bytes a decoder-only language model of Laguna-XS.2's
block needs (``laguna``'s ``config.json``): softmax attention of two
kinds mixed by ``layer_types``, a ``full_attention`` layer over the
causal prefix and a ``sliding_attention`` layer over the
``sliding_window`` keys that end at the query, each kind with its own
count of query heads (``num_attention_heads_per_layer``) over
``num_key_value_heads`` kv heads of ``head_dim``, a query and a gate a
head; the MLPs by ``mlp_layer_types``: ``dense`` a SwiGLU of
``intermediate_size``, ``sparse`` an expert layer of which THIS CHIP
holds ``num_experts`` of ``published.num_experts`` experts, plus one
shared expert. Only the first ``num_hidden_layers`` entries of the
per-layer lists count. A configuration names this count by the file's
name (``"flops": "window_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Matrix multiplications only, 2 FLOPs a multiply-add. Per token and
layer of H_t heads: the query-and-gate projection (d x 2 H_t D), key
and value (d x Hkv D each), the output projection (H_t D x d). Per
layer: attention's two score-sized products over the entries its mask
KEEPS at H_t heads: S (S + 1) / 2 a head in a full layer, S W - W (W -
1) / 2 in a sliding one (W <= S; a query sees itself and the W - 1 keys
before it). A dense MLP: 3 d f. A sparse one: the router over ALL
experts (d x E), the shared expert (3 d w_s) and this chip's share of
the token's k routed experts: k x held / E experts of 3 d w on average
(what the traffic really sends is ``held_pairs``; the share is its
expectation under a uniform router). The output head over the held
vocabulary.

Backward = 2 x forward, nothing recomputed, the embedding gather
excluded. NOTHING for the rotary, the norms, the gates, the sort, the
gathers or the scatter.
"""

FULL, SLIDING = "full_attention", "sliding_attention"


def layers_of(config):
    """[(kind, query heads, mlp kind)] of the layers that are built."""
    count = config["num_hidden_layers"]
    return list(zip(
        config["layer_types"][:count],
        config["num_attention_heads_per_layer"][:count],
        config["mlp_layer_types"][:count]))


def kept_scores(kind, seq, window):
    """Score entries one head keeps under the layer's mask."""
    if kind == FULL:
        return seq * (seq + 1) / 2.0
    if kind != SLIDING:
        raise ValueError("layer_types names %r" % (kind,))
    w = min(window, seq)
    return float(seq) * w - w * (w - 1) / 2.0


def projection_flops(config, heads):
    """Forward FLOPs of one token's four projections in a layer of
    ``heads`` query heads (the query's is twice as wide: the gate)."""
    d, width = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    return 2.0 * (d * heads * 2 * width + 2 * d * kv * width
                  + heads * width * d)


def held_share(config):
    """The share of a layer's experts this chip holds."""
    return config["num_experts"] / config["published"]["num_experts"]


def expert_flops_per_token(config):
    """Forward FLOPs of one token's routed experts HERE, on average."""
    return 2.0 * config["num_experts_per_tok"] * held_share(config) * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def mlp_flops_per_token(config, mlp):
    d = config["hidden_size"]
    if mlp == "dense":
        return 2.0 * 3 * d * config["intermediate_size"]
    if mlp != "sparse":
        raise ValueError("mlp_layer_types names %r" % (mlp,))
    return (
        2.0 * d * config["published"]["num_experts"]
        + 2.0 * 3 * d * config["shared_expert_intermediate_size"]
        + expert_flops_per_token(config))


def attention_flops(config, kind, heads, seq):
    """Forward FLOPs of one layer's two score-sized products."""
    return 2 * 2.0 * kept_scores(kind, seq, config["sliding_window"]) * (
        heads * config["head_dim"])


def parts(config, traffic):
    """Forward and backward FLOPs of one sample by part."""
    seq = traffic["seq_len"]
    out = {"flash_full": 0.0, "flash_window": 0.0, "projections": 0.0,
           "dense_mlp": 0.0, "router_and_shared": 0.0, "held_experts": 0.0}
    for kind, heads, mlp in layers_of(config):
        name = "flash_full" if kind == FULL else "flash_window"
        out[name] += 3.0 * attention_flops(config, kind, heads, seq)
        out["projections"] += 3.0 * seq * projection_flops(config, heads)
        if mlp == "dense":
            out["dense_mlp"] += 3.0 * seq * mlp_flops_per_token(config, mlp)
        else:
            routed = expert_flops_per_token(config)
            out["held_experts"] += 3.0 * seq * routed
            out["router_and_shared"] += 3.0 * seq * (
                mlp_flops_per_token(config, mlp) - routed)
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
    forward, 5 backward) over each layer's own kept entries at its own
    heads. Bytes: forward reads q and writes o at H_t heads and reads
    k, v at the Hkv heads they have; backward reads q, o, do and writes
    dq at H_t heads, reads k, v and writes dk, dv at Hkv: 2 bytes an
    element (a band reads each key block for the few query blocks that
    see it, which the count, of one read each, leaves out)."""
    seq, width = traffic["seq_len"], config["head_dim"]
    kv = config["num_key_value_heads"]
    flops = nbytes = 0.0
    for kind, heads, _ in layers_of(config):
        if kind not in kinds:
            continue
        flops += 7 * 2.0 * kept_scores(
            kind, seq, config["sliding_window"]) * heads * width
        nbytes += 2.0 * seq * width * ((2 * heads + 2 * kv)
                                       + (4 * heads + 4 * kv))
    return flops, nbytes


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer. ``flash``: the
    flash kernels of both kinds of layer (``flash_need``);
    ``flash_window``: those of the sliding layers alone, the band's;
    ``moe_experts``: as ``moe_decoder.kernels`` counts them, over the
    rows this chip's experts get on average and the ``num_experts``
    kernels it holds, in the sparse layers."""
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    seq = traffic["seq_len"]
    sparse = sum(1 for _, _, mlp in layers_of(config) if mlp == "sparse")
    rows = seq * config["num_experts_per_tok"] * held_share(config)
    expert_flops = 3.0 * seq * expert_flops_per_token(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w)
        + config["num_experts"] * d * w / traffic["minibatch"]
    )
    return {
        "flash": flash_need(config, traffic, (FULL, SLIDING)),
        "flash_window": flash_need(config, traffic, (SLIDING,)),
        "moe_experts": (expert_flops * sparse, expert_bytes * sparse),
    }
