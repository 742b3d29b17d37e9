"""Operations and bytes a decoder-only language model of DeepSeek-V3's
block needs (Moonlight-16B-A3B's ``config.json``): multi-head latent
attention without a q latent in every block, the first
``first_k_dense_replace`` blocks with a dense SwiGLU MLP of
``intermediate_size``, the others with ``n_routed_experts`` SwiGLU
experts of ``moe_intermediate_size`` (``num_experts_per_tok`` a token)
plus ``n_shared_experts`` shared ones. A configuration names this count
by the file's name (``"flops": "mla_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Matrix multiplications only, 2 FLOPs a multiply-add. Per token and
block the five latent projections (q: d x H (nope + rope); kv down: d x
(rank + rope); kv up: rank x H (nope + v); out: H v x d); per token and
dense block 3 d x ``intermediate_size``; per token and expert block the
router (d E) and k + n_shared experts of 3 d w each; causal attention at
half the score matrix, ``q k^T`` at the q / k width (nope + rope = 192)
and ``p v`` at v's (128); the output head. Backward = 2 x forward,
nothing recomputed, the embedding gather excluded. NOTHING for
dispatch, combine, sorting, the partial rotary, the broadcast of the
shared key head or the concatenations: they need no FLOPs, so what the
program spends on them lowers ``mfu``, as it should.
"""


def widths(config):
    """(q / k head width, v head width)."""
    return (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        config["v_head_dim"],
    )


def latent_projection_flops(config):
    """Forward FLOPs of one token's five latent-attention matmuls."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    qk, v = widths(config)
    return 2.0 * (
        d * heads * qk
        + d * (rank + rope)
        + rank * heads * (config["qk_nope_head_dim"] + v)
        + heads * v * d
    )


def expert_flops_per_token(config, shared=False):
    """Forward FLOPs of one token's k routed SwiGLU experts in one
    layer (``shared``: of its shared experts)."""
    count = (
        config["n_shared_experts"] if shared
        else config["num_experts_per_tok"]
    )
    return 2.0 * count * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def attention_units(seq, heads):
    """One score-sized matmul over the causal half, per lane of width:
    S^2 H (x the width contracted or produced = its FLOPs)."""
    return float(seq) * seq * heads


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    d = config["hidden_size"]
    layers = config["num_hidden_layers"]
    dense_layers = min(config["first_k_dense_replace"], layers)
    seq = traffic["seq_len"]
    qk, v = widths(config)
    dense = 2.0 * 3 * d * config["intermediate_size"]
    expert = (
        2.0 * d * config["n_routed_experts"]
        + expert_flops_per_token(config)
        + expert_flops_per_token(config, shared=True)
    )
    per_token = (
        layers * latent_projection_flops(config)
        + dense_layers * dense
        + (layers - dense_layers) * expert
    )
    attn = attention_units(seq, config["num_attention_heads"]) * (qk + v)
    head = 2.0 * seq * d * config["vocab_size"]
    return 3.0 * (seq * per_token + attn * layers + head)


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer.

    ``flash``: the 7 score-sized matmuls with their own widths, on
    NEEDED lanes (192, not the 256 a padded layout would compute): 2
    forward (``q k^T`` at the q / k width, ``p v`` at v's) and 5
    backward (the scores again and dq, dk at the q / k width; ``dp = do
    v^T`` and dv at v's). Bytes: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv, each at its
    width, 2 bytes an element (the per-row log-sum-exp is under 1% and
    left out). ``moe_experts``: the routed experts' grouped matmuls, as
    ``moe_decoder.kernels`` counts them: nine products an expert layer,
    each 2 x rows x d x w over ``rows = seq_len x k``, their two
    activation operands once and the stack of E kernels once A STEP."""
    heads = config["num_attention_heads"]
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    seq, layers = traffic["seq_len"], config["num_hidden_layers"]
    expert_layers = layers - min(config["first_k_dense_replace"], layers)
    qk, v = widths(config)
    flash_flops = attention_units(seq, heads) * (
        (qk + v) + (3 * qk + 2 * v))
    flash_bytes = float(seq) * heads * 2 * (
        (2 * qk + 2 * v) + (4 * qk + 4 * v))
    rows = seq * config["num_experts_per_tok"]
    expert_flops = 3.0 * seq * expert_flops_per_token(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w)
        + config["n_routed_experts"] * d * w / traffic["minibatch"]
    )
    return {
        "flash": (flash_flops * layers, flash_bytes * layers),
        "moe_experts": (
            expert_flops * expert_layers, expert_bytes * expert_layers),
    }
