"""Operations and bytes a decoder-only language model of Qwen3-Next's
block needs (``qwen3_next``'s ``config.json``): layers in periods of
``full_attention_interval``, all but the last of a period a Gated
DeltaNet mixer, the last gated grouped-query attention; every layer an
expert layer of which THIS CHIP holds ``num_experts`` of
``published.num_experts`` experts, plus one shared expert behind a gate.
A configuration names this count by the file's name (``"flops":
"gdn_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Matrix multiplications only, 2 FLOPs a multiply-add. Per token:

- a linear layer: the two input projections (d x (2 Hk Dk + 2 Hv Dv), d
  x 2 Hv) and the output projection (Hv Dv x d); and the gated delta
  rule in its chunked form at the configuration's chunk C
  (``assumed.gdn_chunk``), per value head and chunk: ``K K^T`` and ``Q
  K^T`` (2 C^2 Dk each, shared by the value heads of one key head), the
  unit-triangular inverse counted as a solve (C^3), ``U`` and ``W`` (2
  C^2 Dv, 2 C^2 Dk), ``W S``, ``Q S`` and ``K^T V'`` (2 C Dk Dv each)
  and ``attn V'`` (2 C^2 Dv). A per-token recurrence would need no
  matmul at all and 32,768 dependent steps; the chunked form is the
  algorithm, so its matmuls are the needed work;
- a full layer: the query-and-gate projection (d x 2 H D), key and
  value (d x Hkv D each), the output projection (H D x d); causal
  attention at half the score matrix over H query heads;
- every layer: the router over ALL experts (d x E), the shared expert
  (3 d w_s) and its gate (d), and this chip's share of the token's k
  routed experts: k x held / E experts of 3 d w on average (what the
  traffic really sends is ``held_pairs``; the share is its
  expectation under a uniform router);
- the output head over the held vocabulary.

Backward = 2 x forward, nothing recomputed, the embedding gather
excluded. NOTHING for the convolution (4 multiply-adds a channel, no
matmul), the norms, the gates, the sort, the gathers or the scatter.
"""


def linear_dims(config):
    return (config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"])


def linear_layers(config):
    """(linear-attention layers, full-attention layers) of the depth."""
    layers, period = (
        config["num_hidden_layers"], config["full_attention_interval"])
    full = sum(1 for i in range(layers) if (i + 1) % period == 0)
    return layers - full, full


def linear_projection_flops(config):
    """Forward FLOPs of one token's three Gated DeltaNet projections."""
    d = config["hidden_size"]
    hk, hv, dk, dv = linear_dims(config)
    return 2.0 * (d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d)


def delta_rule_flops(config, seq):
    """Forward FLOPs of the chunked rule over one sequence, one layer."""
    hk, hv, dk, dv = linear_dims(config)
    c = config["assumed"]["gdn_chunk"]
    chunks = -(-seq // c)
    per_key_head = 2 * 2.0 * c * c * dk  # K K^T, Q K^T
    per_value_head = (
        float(c) ** 3  # the inverse, as a triangular solve
        + 2.0 * c * c * dv + 2.0 * c * c * dk  # U, W
        + 3 * 2.0 * c * dk * dv  # W S, Q S, K^T V'
        + 2.0 * c * c * dv  # attn V'
    )
    return chunks * (hk * per_key_head + hv * per_value_head)


def attention_projection_flops(config):
    d, heads = config["hidden_size"], config["num_attention_heads"]
    width, kv = config["head_dim"], config["num_key_value_heads"]
    return 2.0 * (d * heads * 2 * width + 2 * d * kv * width
                  + heads * width * d)


def held_share(config):
    """The share of a layer's experts this chip holds."""
    return config["num_experts"] / config["published"]["num_experts"]


def expert_flops_per_token(config):
    """Forward FLOPs of one token's routed experts HERE, on average."""
    return 2.0 * config["num_experts_per_tok"] * held_share(config) * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    d, seq = config["hidden_size"], traffic["seq_len"]
    layers = config["num_hidden_layers"]
    linear, full = linear_layers(config)
    expert = (
        2.0 * d * config["published"]["num_experts"]
        + 2.0 * 3 * d * config["shared_expert_intermediate_size"] + 2.0 * d
        + expert_flops_per_token(config)
    )
    per_token = (
        linear * linear_projection_flops(config)
        + full * attention_projection_flops(config)
        + layers * expert
    )
    attn = 2 * float(seq) * seq * (
        config["num_attention_heads"] * config["head_dim"])
    head = 2.0 * seq * d * config["vocab_size"]
    return 3.0 * (
        seq * per_token + linear * delta_rule_flops(config, seq)
        + full * attn + head)


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer.

    ``flash``: the 7 score-sized matmuls over the causal half (2
    forward, 5 backward) at H query heads. Bytes: forward reads q and
    writes o at H heads and reads k, v at the Hkv heads they have;
    backward reads q, o, do and writes dq at H heads, reads k, v and
    writes dk, dv at Hkv: 2 bytes an element (a kernel that copied k
    and v to every query head would read 8 times the kv bytes counted
    here). ``gdn_scan``: the chunked rule, 3 x its forward FLOPs; bytes:
    forward reads q, k (Hk heads), v, the two gates and writes o;
    backward reads those and do and writes their gradients; the states
    between chunks are the algorithm's own and not counted.
    ``moe_experts``: as ``moe_decoder.kernels`` counts them, over the
    rows this chip's experts get on average and the ``num_experts``
    kernels it holds."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    seq, layers = traffic["seq_len"], config["num_hidden_layers"]
    linear, full = linear_layers(config)
    width = config["head_dim"]
    flash_flops = 7 * float(seq) * seq * heads * width
    flash_bytes = 2.0 * seq * width * ((2 * heads + 2 * kv)
                                       + (4 * heads + 4 * kv))
    hk, hv, dk, dv = linear_dims(config)
    scan_bytes = 3 * seq * (
        2.0 * (2 * hk * dk + 2 * hv * dv) + 4.0 * 2 * hv)
    rows = seq * config["num_experts_per_tok"] * held_share(config)
    expert_flops = 3.0 * seq * expert_flops_per_token(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w)
        + config["num_experts"] * d * w / traffic["minibatch"]
    )
    return {
        "flash": (flash_flops * full, flash_bytes * full),
        "gdn_scan": (
            3.0 * delta_rule_flops(config, seq) * linear,
            scan_bytes * linear),
        "moe_experts": (expert_flops * layers, expert_bytes * layers),
    }
