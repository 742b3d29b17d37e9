"""Operations and bytes a decoder-only language model of Kimi Linear's
block needs (``kimi_linear``'s ``config.json``): layer ``i`` (1-indexed)
a Kimi Delta Attention mixer if in ``linear_attn_config.kda_layers``,
latent attention that rotates nothing if in ``full_attn_layers``; the
first ``first_k_dense_replace`` layers a dense SwiGLU of
``intermediate_size``, every other an expert layer of which THIS CHIP
holds ``num_experts`` of ``published.num_experts`` experts, plus
``num_shared_experts`` shared ones. A configuration names this count by
the file's name (``"flops": "kda_mla_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Contractions only, 2 FLOPs a multiply-add. Per token:

- a KDA layer (H heads of D lanes, gates r wide): the projection of
  q | k | v (d x 3 H D), both low-rank gates (d x r and r x H D each),
  beta (d x H) and the output projection (H D x d); and the delta rule
  with a decay a channel in its chunked form at the configuration's
  chunk C (``assumed.kda_chunk``) and sub-blocks of ``SUB`` = 16 rows,
  per head and chunk, with nb = C / SUB:
  ``K K^T`` and ``Q K^T`` under the decay, by sub-block: the nb (nb +
  1) / 2 sub-blocks on and below the diagonal, 2 SUB^2 D each and
  product (a decay a channel sits inside the contraction, so no
  (C, C) product is decayed afterwards: between sub-blocks a matmul of
  decayed operands, on the diagonal a sum over the channels, the same
  multiply-adds); the unit-triangular inverse counted as a solve
  (C^3); ``U`` and ``W`` (2 C^2 D each); ``W S``, ``Q S`` and ``K^T
  V'`` (2 C D^2 each); ``P V'`` (2 C^2 D). At C 64 and D 128: 11.0
  MFLOP a head and chunk, 0.180 TFLOP a layer and 32,768 tokens
  forward. A per-token recurrence would need no matmul at all and
  32,768 dependent steps; the chunked form is the algorithm, so its
  contractions are the needed work;
- a latent layer: the four latent projections without a q latent (q: d
  x H (nope + rope); kv down: d x (rank + rope); kv up: rank x H (nope
  + v); out: H v x d); causal attention at half the score matrix,
  ``q k^T`` at the q / k width (nope + rope) and ``p v`` at v's;
- a dense layer: 3 d x ``intermediate_size``;
- an expert layer: the router over ALL experts (d x E), the shared
  experts (3 d w each) and this chip's share of the token's k routed
  experts: k x held / E experts of 3 d w on average (what the traffic
  really sends is ``held_pairs``; the share is its expectation under a
  uniform router);
- the output head over the held vocabulary.

Backward = 2 x forward, nothing recomputed, the embedding gather
excluded. NOTHING for the convolutions (4 multiply-adds a channel), the
exponentials of the decay, the norms, the gates' activations, the sort,
the gathers or the scatter.
"""

SUB = 16


def kda_dims(config):
    """(heads, head width, the gates' rank, the chunk)."""
    linear, assumed = config["linear_attn_config"], config["assumed"]
    return (linear["num_heads"], linear["head_dim"],
            assumed["kda_gate_rank"], assumed["kda_chunk"])


def layer_counts(config):
    """(KDA layers, latent layers) among the built layers."""
    kda = sum(
        1 for i in range(1, config["num_hidden_layers"] + 1)
        if i in config["linear_attn_config"]["kda_layers"])
    return kda, config["num_hidden_layers"] - kda


def kda_projection_flops(config):
    """Forward FLOPs of one token's KDA matmuls outside the rule."""
    d = config["hidden_size"]
    heads, dim, rank, _ = kda_dims(config)
    inner = heads * dim
    return 2.0 * (
        d * 3 * inner + 2 * (d * rank + rank * inner) + d * heads + inner * d)


def kda_rule_flops(config, seq):
    """Forward FLOPs of the chunked rule over one sequence, one layer."""
    heads, dim, _, c = kda_dims(config)
    sub = min(SUB, c)
    nb = c // sub
    per_head = (
        2 * (nb * (nb + 1) // 2) * 2.0 * sub * sub * dim  # K K^T, Q K^T
        + float(c) ** 3  # the inverse, as a triangular solve
        + 2 * 2.0 * c * c * dim  # U, W
        + 3 * 2.0 * c * dim * dim  # W S, Q S, K^T V'
        + 2.0 * c * c * dim  # P V'
    )
    return -(-seq // c) * heads * per_head


def widths(config):
    """(q / k head width, v head width) of a latent layer."""
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"])


def latent_projection_flops(config):
    """Forward FLOPs of one token's four latent-attention matmuls."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    qk, v = widths(config)
    return 2.0 * (
        d * heads * qk + d * (rank + rope)
        + rank * heads * (config["qk_nope_head_dim"] + v) + heads * v * d)


def held_share(config):
    """The share of a layer's experts this chip holds."""
    return config["num_experts"] / config["published"]["num_experts"]


def expert_flops_per_token(config):
    """Forward FLOPs of one token's routed experts HERE, on average."""
    return 2.0 * config["num_experts_per_token"] * held_share(config) * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    d, seq = config["hidden_size"], traffic["seq_len"]
    layers = config["num_hidden_layers"]
    dense_layers = min(config["first_k_dense_replace"], layers)
    kda, latent = layer_counts(config)
    qk, v = widths(config)
    expert = (
        2.0 * d * config["published"]["num_experts"]
        + 2.0 * config["num_shared_experts"] * 3 * d
        * config["moe_intermediate_size"]
        + expert_flops_per_token(config)
    )
    per_token = (
        kda * kda_projection_flops(config)
        + latent * latent_projection_flops(config)
        + dense_layers * 2.0 * 3 * d * config["intermediate_size"]
        + (layers - dense_layers) * expert
    )
    attn = float(seq) * seq * config["num_attention_heads"] * (qk + v)
    head = 2.0 * seq * d * config["vocab_size"]
    return 3.0 * (
        seq * per_token + kda * kda_rule_flops(config, seq)
        + latent * attn + head)


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer.

    ``flash``: as ``mla_moe_decoder.kernels`` counts it: the 7
    score-sized matmuls over the causal half with their own widths (2
    forward, 5 backward), q, k, v, o, do read and o, dq, dk, dv written,
    2 bytes an element, in the latent layers.

    ``kda_scan``: the chunked rule, 3 x its forward FLOPs. Bytes, from
    the equations and not from what XLA's lines move, so that a later
    kernel is read against the same work: forward reads q, k, v (2
    bytes), g (float32, a number a CHANNEL) and beta (float32, a number
    a head) and writes o; backward reads the same five and do and
    writes dq, dk, dv, dg, dbeta; and the one float32 state a segment
    (``assumed.kda_segment`` chunks) that the backward must be handed,
    written once and read once. The states between a segment's chunks
    and everything a chunk makes of its operands are the algorithm's
    own and not counted.

    ``moe_experts``: as ``gdn_moe_decoder.kernels`` counts them, over
    the rows this chip's experts get on average and the ``num_experts``
    kernels it holds."""
    heads = config["num_attention_heads"]
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    seq, layers = traffic["seq_len"], config["num_hidden_layers"]
    expert_layers = layers - min(config["first_k_dense_replace"], layers)
    kda, latent = layer_counts(config)
    qk, v = widths(config)
    flash_flops = float(seq) * seq * heads * ((qk + v) + (3 * qk + 2 * v))
    flash_bytes = float(seq) * heads * 2 * (
        (2 * qk + 2 * v) + (4 * qk + 4 * v))
    k_heads, dim, _, chunk = kda_dims(config)
    operands = 3 * dim * 2.0 + dim * 4.0 + 4.0  # q, k, v; g; beta: a token
    segments = -(-seq // (chunk * config["assumed"]["kda_segment"]))
    scan_bytes = seq * k_heads * (
        (operands + dim * 2.0)  # forward: the five read, o written
        + (operands + dim * 2.0) + operands  # backward: and do; the five's
    ) + 2 * segments * k_heads * dim * dim * 4.0
    rows = seq * config["num_experts_per_token"] * held_share(config)
    expert_flops = 3.0 * seq * expert_flops_per_token(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w) + config["num_experts"] * d * w / traffic["minibatch"])
    return {
        "flash": (flash_flops * latent, flash_bytes * latent),
        "kda_scan": (
            3.0 * kda_rule_flops(config, seq) * kda, scan_bytes * kda),
        "moe_experts": (
            expert_flops * expert_layers, expert_bytes * expert_layers),
    }
