"""Operations and bytes one block-diffusion training step of a
decoder-only mixture-of-experts language model needs (SDAR's
``sdar_moe`` ``config.json``; the objective is BD3-LMs',
arXiv:2503.09573): grouped-query attention with a head width of its
own, every layer an expert layer of which THIS CHIP holds
``num_experts`` of ``published.num_experts`` experts, no shared expert.
A configuration names this count by the file's name (``"flops":
"bd_moe_decoder"``).

One sample is one clean sequence of L = ``seq_len`` tokens. The
objective runs 2 L positions through every layer, the noisy copy and
the clean copy (the noisy blocks read the clean copy's keys and values
at every layer, so both copies' projections and experts are needed
work), under a mask that keeps L^2 + L B of the (2 L)^2 score entries
(B = ``assumed.block_length``: the noisy rows' own blocks L B, the
clean blocks before a noisy row (L^2 - L B) / 2, the clean blocks up to
a clean row's own (L^2 + L B) / 2), and puts the head on the L noisy
positions alone.

Part of the yardstick: a change to the program cannot move these.
Matrix multiplications only, 2 FLOPs a multiply-add. Per position and
layer: the query and output projections (d x H D each), key and value
(d x Hkv D each), the router over ALL experts (d x E) and this chip's
share of the position's k routed experts: k x held / E experts of 3 d w
on average. Per layer: attention's two score-sized products over the
kept entries at H query heads. The output head over the held
vocabulary on L positions. Backward = 2 x forward, nothing recomputed,
the embedding gather excluded. NOTHING for the noise, the assembly of
the two copies, the norms, the sort, the gathers or the scatter.
"""


def kept_scores(config, traffic):
    """Score entries a head keeps under the mask: L^2 + L B."""
    length, block = traffic["seq_len"], config["assumed"]["block_length"]
    return float(length) * length + float(length) * block


def projection_flops(config):
    """Forward FLOPs of one position's four attention projections."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    width, kv = config["head_dim"], config["num_key_value_heads"]
    return 2.0 * (2 * d * heads * width + 2 * d * kv * width)


def held_share(config):
    """The share of a layer's experts this chip holds."""
    return config["num_experts"] / config["published"]["num_experts"]


def expert_flops_per_position(config):
    """Forward FLOPs of one position's routed experts HERE, on
    average."""
    return 2.0 * config["num_experts_per_tok"] * held_share(config) * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def parts(config, traffic):
    """Forward and backward FLOPs of one sample by part: attention under
    the mask, the projections and the router, the held experts, the
    head."""
    d, length = config["hidden_size"], traffic["seq_len"]
    layers, positions = config["num_hidden_layers"], 2 * length
    lanes = config["num_attention_heads"] * config["head_dim"]
    return {
        "attention": 3.0 * layers * 2 * 2.0 * kept_scores(
            config, traffic) * lanes,
        "projections_and_router": 3.0 * layers * positions * (
            projection_flops(config)
            + 2.0 * d * config["published"]["num_experts"]),
        "held_experts": 3.0 * layers * positions
        * expert_flops_per_position(config),
        "head": 3.0 * 2.0 * length * d * config["vocab_size"],
    }


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one clean sequence of ``seq_len`` tokens: 2 x ``seq_len``
    positions through the layers)."""
    return sum(parts(config, traffic).values())


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer.

    ``flash``: the 7 score-sized matmuls (2 forward, 5 backward) over
    the entries the MASK keeps, L^2 + L B a head, at H query heads: a
    count over the causal half of the 2 L positions would read twice
    too high. Bytes, over 2 L positions: forward reads q and writes o
    at H heads and reads k, v at the Hkv heads they have; backward reads
    q, o, do and writes dq at H heads, reads k, v and writes dk, dv at
    Hkv: 2 bytes an element. ``moe_experts``: as
    ``gdn_moe_decoder.kernels`` counts them, over the rows this chip's
    experts get on average and the ``num_experts`` kernels it holds."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    positions, layers = 2 * traffic["seq_len"], config["num_hidden_layers"]
    width = config["head_dim"]
    flash_flops = 7 * 2.0 * kept_scores(config, traffic) * heads * width
    flash_bytes = 2.0 * positions * width * ((2 * heads + 2 * kv)
                                             + (4 * heads + 4 * kv))
    rows = positions * config["num_experts_per_tok"] * held_share(config)
    expert_flops = 3.0 * positions * expert_flops_per_position(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w)
        + config["num_experts"] * d * w / traffic["minibatch"]
    )
    return {
        "flash": (flash_flops * layers, flash_bytes * layers),
        "moe_experts": (expert_flops * layers, expert_bytes * layers),
    }
