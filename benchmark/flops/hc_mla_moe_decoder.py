"""Operations and bytes a decoder-only language model of Xing4.0's
block needs (``xing4_0``'s ``config.json``): DeepSeek-V3's block (latent
attention WITH a q latent in every block, the first
``first_k_dense_replace`` blocks a dense SwiGLU MLP of
``intermediate_size``, the others an expert layer of which THIS CHIP
holds ``n_routed_experts`` of ``published.n_routed_experts`` experts,
plus ``n_shared_experts`` shared ones) on a residual path of ``hc_mult``
streams mixed around every sublayer by hyper-connections, and
``num_nextn_predict_layers`` multi-token-prediction modules (a
projection of two widths to one, one more expert block, a second pass
of the output head). A configuration names this count by the file's
name (``"flops": "hc_mla_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Multiply-adds of contractions only, 2 FLOPs each. Per token:

- a block's latent attention: q down (d x q_rank), q up (q_rank x H
  (nope + rope)), kv down (d x (rank + rope)), kv up (rank x H (nope +
  v)), out (H v x d); causal attention at half the score matrix, ``q
  k^T`` at the q / k width (nope + rope) and ``p v`` at v's;
- a dense block's MLP: 3 d x ``intermediate_size``;
- an expert block: the router over ALL experts (d x E), the shared
  experts (3 d w each) and this chip's share of the token's k routed
  experts: k x held / E experts of 3 d w on average (what the traffic
  really sends is ``held_pairs``; the share is its expectation under a
  uniform router);
- each sublayer's hyper-connection (two a block): the coefficients'
  projection (n d x n (n + 2)) and the two mixes, ``H_pre X`` (n d),
  ``H_res X`` (n^2 d) and ``H_post^T y`` (n d): 48 d multiply-adds a
  sublayer at n = 4, 0.03% of a block's; they are contractions the
  architecture defines, so they count, and what the program spends
  MOVING the n streams around them lowers ``mfu``, as it should;
- the prediction module: 2 d x d, one expert block, the head again;
- the output head over the held vocabulary.

Backward = 2 x forward, nothing recomputed, the embedding gathers
excluded. NOTHING for the norms, the sigmoids, the Sinkhorn iterations
(elementwise over n^2 values a token), the sort, the gathers, the
scatter, the partial rotary or the concatenations.
"""


def widths(config):
    """(q / k head width, v head width)."""
    return (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        config["v_head_dim"],
    )


def latent_projection_flops(config):
    """Forward FLOPs of one token's six latent-attention matmuls."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    q_rank = config["q_lora_rank"]
    qk, v = widths(config)
    return 2.0 * (
        d * q_rank
        + q_rank * heads * qk
        + d * (rank + rope)
        + rank * heads * (config["qk_nope_head_dim"] + v)
        + heads * v * d
    )


def hyper_connection_flops(config):
    """Forward FLOPs of one token's hyper-connection around ONE
    sublayer: the coefficients' projection and the three mixes."""
    n, d = config["hc_mult"], config["hidden_size"]
    return 2.0 * (n * d * n * (n + 2) + (n + n * n + n) * d)


def held_share(config):
    """The share of a layer's routed experts this chip holds."""
    return (config["n_routed_experts"]
            / config["published"]["n_routed_experts"])


def expert_flops_per_token(config, shared=False):
    """Forward FLOPs of one token's routed SwiGLU experts HERE, on
    average, in one layer (``shared``: of its shared experts)."""
    count = (
        config["n_shared_experts"] if shared
        else config["num_experts_per_tok"] * held_share(config)
    )
    return 2.0 * count * 3 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def blocks(config):
    """(dense blocks, expert blocks of the main model, prediction
    modules: each one more expert block)."""
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    return dense, layers - dense, config["num_nextn_predict_layers"]


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    d, seq = config["hidden_size"], traffic["seq_len"]
    dense, expert, modules = blocks(config)
    qk, v = widths(config)
    every = latent_projection_flops(config) + 2 * hyper_connection_flops(
        config)
    dense_mlp = 2.0 * 3 * d * config["intermediate_size"]
    expert_mlp = (
        2.0 * d * config["published"]["n_routed_experts"]
        + expert_flops_per_token(config)
        + expert_flops_per_token(config, shared=True)
    )
    per_token = (
        (dense + expert + modules) * every
        + dense * dense_mlp
        + (expert + modules) * expert_mlp
        + modules * 2.0 * 2 * d * d
    )
    attn = float(seq) * seq * config["num_attention_heads"] * (qk + v)
    head = 2.0 * seq * d * config["vocab_size"]
    return 3.0 * (
        seq * per_token + attn * (dense + expert + modules)
        + head * (1 + modules))


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every block, the prediction
    module's among them.

    ``flash``: as ``mla_moe_decoder.kernels`` counts it: the 7
    score-sized matmuls with their own widths, on NEEDED lanes (192 and
    128); forward reads q, k, v and writes o, backward reads q, k, v,
    o, do and writes dq, dk, dv, 2 bytes an element. ``moe_experts``:
    as ``gdn_moe_decoder.kernels`` counts a held share: nine products
    an expert layer over the rows this chip's experts get on average
    and the ``n_routed_experts`` kernels it holds. ``mhc_mix``: the two
    mixes of a sublayer's hyper-connection, ``u = H_pre X`` and ``X' =
    H_res X + H_post^T y``: the bytes NO fusion can avoid: a sublayer's
    forward reads X and writes X' (u and y are the sublayer's own, and
    a kernel that made u on the way to the norm and took y from the
    output projection's epilogue would move neither); its backward
    reads X and dX' and writes dX; n d lanes of 2 bytes each, five
    times a sublayer. The coefficients (n (n + 2) floats a token) are
    under 1% and left out. Bytes bound it: 48 d multiply-adds a token
    against 40 d bytes."""
    heads = config["num_attention_heads"]
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    n, seq = config["hc_mult"], traffic["seq_len"]
    dense, expert, modules = blocks(config)
    layers = dense + expert + modules
    qk, v = widths(config)
    flash_flops = float(seq) * seq * heads * ((qk + v) + (3 * qk + 2 * v))
    flash_bytes = float(seq) * heads * 2 * (
        (2 * qk + 2 * v) + (4 * qk + 4 * v))
    rows = seq * config["num_experts_per_tok"] * held_share(config)
    expert_flops = 3.0 * seq * expert_flops_per_token(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w)
        + config["n_routed_experts"] * d * w / traffic["minibatch"]
    )
    mix_flops = 3.0 * seq * 2.0 * (n + n * n + n) * d
    mix_bytes = 5 * 2.0 * seq * n * d
    return {
        "flash": (flash_flops * layers, flash_bytes * layers),
        "moe_experts": (
            expert_flops * (expert + modules),
            expert_bytes * (expert + modules)),
        "mhc_mix": (mix_flops * 2 * layers, mix_bytes * 2 * layers),
    }
