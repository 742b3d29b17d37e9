"""Operations and bytes a decoder-only language model of LFM2's expert
block needs (``lfm2_moe``'s ``config.json``): the mixers by
``layer_types``, a ``conv`` layer the double-gated short convolution
(one projection d -> 3 d, ``conv_L_cache`` depthwise taps between two
gates, one projection d -> d), a ``full_attention`` layer softmax
attention over the causal prefix at ``num_attention_heads`` query heads
over ``num_key_value_heads`` kv heads of ``head_dim``; the first
``num_dense_layers`` layers a dense SwiGLU MLP of ``intermediate_size``,
every other an expert layer of which THIS CHIP holds ``num_experts`` of
``published.num_experts`` experts of ``moe_intermediate_size``, no
shared expert. Only the first ``num_hidden_layers`` entries of
``layer_types`` count. A configuration names this count by the file's
name (``"flops": "conv_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
``per_sample`` counts matrix multiplications only, 2 FLOPs a
multiply-add. Per token and ``conv`` layer: 2 (3 d^2 + d^2). Per token
and attention layer: the four projections (d x H D, two of d x Hkv D, H
D x d); per attention layer the two score-sized products over the S (S
+ 1) / 2 entries a head's causal mask keeps. A dense MLP: 3 d f. An
expert layer: the router over ALL experts (d x E) and this chip's share
of the token's k routed experts: k x held / E experts of 3 d w on
average (what the traffic really sends is ``held_pairs``; the share is
its expectation under a uniform router). The output head over the held
vocabulary, whether or not it shares the embedding's matrix.

Backward = 2 x forward, nothing recomputed, the embedding gather
excluded. NOTHING for the convolution's gates and taps (2 + 2 K - 1
vector operations a channel and token, no contraction: ``kernels``
gives their bytes), the rotary, the norms, the sort, the gathers or the
scatter.
"""

CONV, FULL = "conv", "full_attention"


def layers_of(config):
    """[(mixer kind, whether the MLP is dense)] of the built layers."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for kind in kinds:
        if kind not in (CONV, FULL):
            raise ValueError("layer_types names %r" % (kind,))
    return [(kind, i < config["num_dense_layers"])
            for i, kind in enumerate(kinds)]


def count(config, kind=None, dense=None):
    """How many built layers have that mixer and that kind of MLP."""
    return sum(
        1 for mixer, is_dense in layers_of(config)
        if kind in (None, mixer) and dense in (None, is_dense))


def conv_flops_per_token(config):
    """Forward FLOPs of one token's two projections in a conv layer."""
    d = config["hidden_size"]
    return 2.0 * (3 * d * d + d * d)


def projection_flops_per_token(config):
    """Forward FLOPs of one token's four attention projections."""
    d, width = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * (2 * d * heads * width + 2 * d * kv * width)


def kept_scores(seq):
    """Score entries one head keeps under the causal mask."""
    return seq * (seq + 1) / 2.0


def attention_flops(config, seq):
    """Forward FLOPs of one layer's two score-sized products."""
    return 2 * 2.0 * kept_scores(seq) * (
        config["num_attention_heads"] * config["head_dim"])


def held_share(config):
    """The share of a layer's experts this chip holds."""
    return config["num_experts"] / config["published"]["num_experts"]


def expert_flops_per_token(config):
    """Forward FLOPs of one token's routed experts HERE, on average."""
    return 2.0 * config["num_experts_per_tok"] * held_share(config) * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def parts(config, traffic):
    """Forward and backward FLOPs of one sample by part."""
    seq, d = traffic["seq_len"], config["hidden_size"]
    convs, fulls = count(config, CONV), count(config, FULL)
    dense, sparse = count(config, dense=True), count(config, dense=False)
    return {
        "conv_mixers": 3.0 * seq * convs * conv_flops_per_token(config),
        "projections": 3.0 * seq * fulls * projection_flops_per_token(
            config),
        "flash": 3.0 * fulls * attention_flops(config, seq),
        "dense_mlp": 3.0 * seq * dense * 2.0 * 3 * d * config[
            "intermediate_size"],
        "router": 3.0 * seq * sparse * 2.0 * d * config["published"][
            "num_experts"],
        "held_experts": 3.0 * seq * sparse * expert_flops_per_token(config),
        "head": 3.0 * 2.0 * seq * d * config["vocab_size"],
    }


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    return sum(parts(config, traffic).values())


def gate_need(config, traffic):
    """(vector operations, bytes) the gate-convolve-gate needs for one
    sample's forward and backward through every conv layer, between the
    two projections. Bytes, 2 an element, a channel and token: the
    forward reads B, C, X and writes y (4); the backward reads B, C, X
    and dy and writes dB, dC, dX (7; it forms z and the convolution
    again from what it reads, as ``ops/short_conv.py`` does: keeping
    them would be two more writes and two more reads); the taps and
    their gradient are K x d floats. Operations: B X, K multiply-adds
    and C c forward (2 K + 1), about three times that backward; they
    run on the vector unit, so a roofline reads the bytes."""
    seq, d, taps = (traffic["seq_len"], config["hidden_size"],
                    config["conv_L_cache"])
    elements = float(seq) * d * count(config, CONV)
    return 4.0 * (2 * taps + 1) * elements, 2.0 * (4 + 7) * elements


def flash_need(config, traffic):
    """(FLOPs, bytes) the flash kernels need for one sample's forward
    and backward: the 7 score-sized matmuls (2 forward, 5 backward)
    over the kept entries at H heads. Bytes: forward reads q and writes
    o at H heads and reads k, v at the Hkv heads they have; backward
    reads q, o, do and writes dq at H heads, reads k, v and writes dk,
    dv at Hkv: 2 bytes an element."""
    seq, width = traffic["seq_len"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    fulls = count(config, FULL)
    flops = 7 * 2.0 * kept_scores(seq) * heads * width
    nbytes = 2.0 * seq * width * ((2 * heads + 2 * kv)
                                  + (4 * heads + 4 * kv))
    return flops * fulls, nbytes * fulls


def matmul_bytes(seq, rows_in, rows_out, weights, minibatch):
    """Bytes of dense matmuls forward and backward: each of the three
    passes reads or writes the activations on both sides once and the
    weights once a step, 2 bytes an element."""
    return 3 * 2.0 * (seq * (rows_in + rows_out) + weights / minibatch)


def kernels(config, traffic):
    """{kernel: (operations, bytes)} of the family's parts for one
    sample's forward and backward through every layer.
    ``short_conv_gate``: ``gate_need``; ``short_conv_matmuls``: the conv
    mixers' two projections; ``flash``: ``flash_need``; ``dense_mlp``:
    the leading layers' SwiGLU; ``moe_experts``: as
    ``moe_decoder.kernels`` counts them, over the rows this chip's
    experts get on average and the ``num_experts`` kernels it holds;
    ``head``: the output head."""
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    f, seq = config["intermediate_size"], traffic["seq_len"]
    batch = traffic["minibatch"]
    flops = parts(config, traffic)
    convs, dense = count(config, CONV), count(config, dense=True)
    sparse = count(config, dense=False)
    rows = seq * config["num_experts_per_tok"] * held_share(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w) + config["num_experts"] * d * w / batch)
    return {
        "short_conv_gate": gate_need(config, traffic),
        "short_conv_matmuls": (flops["conv_mixers"], matmul_bytes(
            seq * convs, 2 * d, 4 * d, convs * 4 * d * d, batch)),
        "flash": flash_need(config, traffic),
        "dense_mlp": (flops["dense_mlp"], matmul_bytes(
            seq * dense, 2 * d, 3 * f, dense * 3 * d * f, batch)),
        "moe_experts": (flops["held_experts"], expert_bytes * sparse),
        "head": (flops["head"], matmul_bytes(
            seq, d, config["vocab_size"], d * config["vocab_size"], batch)),
    }
