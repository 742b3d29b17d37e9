"""Operations and bytes a decoder-only language model of
granite-4.0-h's dense block needs (``granitemoehybrid``'s
``config.json``): layer ``i`` a Mamba-2 state-space mixer or
grouped-query softmax attention as ``layer_types[i]`` says, every layer
a dense SwiGLU of ``shared_intermediate_size`` (``num_local_experts``
0), the head tied to the embedding. A configuration names this count by
the file's name (``"flops": "ssm_dense_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Contractions only, 2 FLOPs a multiply-add. Per token:

- a Mamba-2 layer (H heads of P lanes over a state of N, G groups): the
  input projection d x (2 H P + 2 G N + H) and the output projection H
  P x d; and the selective scan in its chunked form at the published
  chunk Q (``mamba_chunk_size``), per chunk: ``M = C B^T`` ONCE A GROUP
  over the Q (Q + 1) / 2 pairs on and below the diagonal (2 N each);
  the masked product ``(M . decay) (dt x)`` a HEAD over the same pairs
  (2 P each); the chunk's state ``sum_j (decayed dt_j x_j) B_j^T`` (2 Q
  P N a head) and the entering state's part of the output ``S C_i`` (2
  Q P N a head). At Q 256, H 64 x P 64, N 128, G 1: 3.19 MFLOP a token
  forward, 26.1 GFLOP a layer and 8,192 tokens. A per-token recurrence
  would need no matmul at all and 8,192 dependent steps; the chunked
  form is the algorithm, so its contractions are the needed work;
- the attention layer: q and o (d x H D each), k and v (d x Hkv D
  each); causal attention over the kept half of the score matrix,
  ``q k^T`` and ``p v`` at the head's width D = d / H;
- the MLP: 3 d x ``shared_intermediate_size``;
- the output head over the held vocabulary, once (the embedding's
  gather is no contraction).

Backward = 2 x forward, nothing recomputed. NOTHING for the
convolution (4 multiply-adds a channel), its bias, the softplus, the
exponentials of the decay, the decay mask's product with ``M``, the
skip, the gate, the norms or the multipliers.
"""

KINDS = ("mamba", "attention")


def count(config, kind):
    """The built layers of ``kind`` (the first ``num_hidden_layers`` of
    ``layer_types``)."""
    built = config["layer_types"][:config["num_hidden_layers"]]
    if set(built) - set(KINDS):
        raise ValueError("layer_types=%r" % (built,))
    return sum(1 for k in built if k == kind)


def mamba_dims(config):
    """(heads, head width, state, groups, chunk)."""
    return (config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_n_groups"],
            config["mamba_chunk_size"])


def mamba_projection_flops(config):
    """Forward FLOPs of one token's two Mamba-2 projections."""
    d = config["hidden_size"]
    heads, dim, state, groups, _ = mamba_dims(config)
    inner = heads * dim
    return 2.0 * (d * (2 * inner + 2 * groups * state + heads) + inner * d)


def scan_flops(config, seq):
    """Forward FLOPs of the chunked scan over one sequence, one layer.
    A chunk the sequence does not fill is a whole chunk."""
    heads, dim, state, groups, q = mamba_dims(config)
    kept = q * (q + 1) / 2.0
    per_chunk = (
        groups * kept * 2.0 * state  # M, once a group
        + heads * kept * 2.0 * dim  # the masked product, a head
        + heads * 2 * 2.0 * q * dim * state  # the states in and out
    )
    return -(-seq // q) * per_chunk


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def attention_projection_flops(config):
    """Forward FLOPs of one token's four attention projections."""
    d, width = config["hidden_size"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * d * width * (2 * heads + 2 * kv)


def kept_scores(seq):
    """Score entries one head keeps under the causal mask."""
    return seq * (seq + 1) / 2.0


def attention_flops(config, seq):
    """Forward FLOPs of one layer's two score-sized products."""
    return 2 * 2.0 * kept_scores(seq) * (
        config["num_attention_heads"] * head_dim(config))


def parts(config, traffic):
    """Forward FLOPs of one sample by part."""
    d, seq = config["hidden_size"], traffic["seq_len"]
    mamba, full = count(config, "mamba"), count(config, "attention")
    return {
        "mamba_projections": mamba * seq * mamba_projection_flops(config),
        "ssd_scan": mamba * scan_flops(config, seq),
        "attention_projections": (
            full * seq * attention_projection_flops(config)),
        "attention": full * attention_flops(config, seq),
        "dense_mlp": (
            (mamba + full) * seq * 2.0 * 3 * d
            * config["shared_intermediate_size"]),
        "head": 2.0 * seq * d * config["vocab_size"],
    }


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    return 3.0 * sum(parts(config, traffic).values())


def flash_need(config, traffic):
    """(FLOPs, bytes) the flash kernels need for one sample's forward
    and backward, as ``conv_moe_decoder.flash_need`` counts them: the 7
    score-sized matmuls (2 forward, 5 backward) over the kept entries at
    H heads. Bytes: forward reads q and writes o at H heads and reads k,
    v at the Hkv heads they have; backward reads q, o, do and writes dq
    at H heads, reads k, v and writes dk, dv at Hkv: 2 bytes an
    element."""
    seq, width = traffic["seq_len"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    fulls = count(config, "attention")
    flops = 7 * 2.0 * kept_scores(seq) * heads * width
    nbytes = 2.0 * seq * width * ((2 * heads + 2 * kv)
                                  + (4 * heads + 4 * kv))
    return flops * fulls, nbytes * fulls


def scan_need(config, traffic):
    """(FLOPs, bytes) the selective scan needs for one sample's forward
    and backward through every Mamba-2 layer: 3 x its forward FLOPs.
    Bytes, from the equations and not from what XLA's lines move, so
    that a later kernel is read against the same work: forward reads x
    (2 bytes a lane), dt (float32, a number a head: the log decay is
    ``dt A`` with ``A`` a number a head), B and C (2 bytes, a group's N
    each) and writes y; backward reads the same four and dy and writes
    dx, ddt, dB, dC; and the one float32 state a segment
    (``assumed.scan_segment`` chunks) that the backward must be handed,
    written once and read once. The states between a segment's chunks,
    the decay masks and ``M`` are the algorithm's own and not counted."""
    seq = traffic["seq_len"]
    heads, dim, state, groups, chunk = mamba_dims(config)
    operands = heads * dim * 2.0 + heads * 4.0 + 2 * groups * state * 2.0
    lanes = heads * dim * 2.0  # y, or dy
    segments = -(-seq // (chunk * config["assumed"]["scan_segment"]))
    nbytes = seq * (
        (operands + lanes)  # forward: the four read, y written
        + (operands + lanes) + operands  # backward: and dy; the four's
    ) + 2 * segments * heads * dim * state * 4.0
    mamba = count(config, "mamba")
    return 3.0 * scan_flops(config, seq) * mamba, nbytes * mamba


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named parts need for one
    sample's forward and backward through every layer: ``flash``
    (``flash_need``: the one attention layer of a period) and
    ``ssd_scan`` (``scan_need``)."""
    return {
        "flash": flash_need(config, traffic),
        "ssd_scan": scan_need(config, traffic),
    }
