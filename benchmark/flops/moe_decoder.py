"""Operations and bytes a decoder-only language model with a
mixture-of-experts MLP in every block needs, computed from the
configuration's shapes (an OLMoE ``config.json``: ``num_experts``
SwiGLU experts of width ``intermediate_size``, ``num_experts_per_tok``
of them a token). A configuration names this count by the file's name
(``"flops": "moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Matrix multiplications only: per token and layer the four attention
projections (4 d^2), the router (d E) and the token's k experts (k x 3
d w: gate, up, down), each 2 FLOPs a multiply-add; causal attention at
half the score matrix; the output head. Backward = 2 x forward, nothing
recomputed, the embedding gather excluded. NOTHING for dispatch,
combine or sorting: ordering tokens by expert needs no FLOPs, so a
dispatch that spends some (a one-hot einsum) lowers ``mfu``, as it
should.
"""

from benchmark.flops.dense_decoder import (
    flash_attention_bytes,
    flash_attention_flops,
)


def expert_flops_per_token(config):
    """Forward FLOPs of one token's k SwiGLU experts in one layer."""
    return 2.0 * config["num_experts_per_tok"] * 3 * (
        config["hidden_size"] * config["intermediate_size"])


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    d = config["hidden_size"]
    layers = config["num_hidden_layers"]
    seq = traffic["seq_len"]
    per_token = (
        2.0 * (4 * d * d + d * config["num_experts"])
        + expert_flops_per_token(config)
    )
    attn = flash_attention_flops(
        seq, config["num_attention_heads"],
        d // config["num_attention_heads"], backward=False,
    )
    head = 2.0 * seq * d * config["vocab_size"]
    return 3.0 * ((seq * per_token + attn) * layers + head)


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer.

    ``flash``: as the dense decoder's. ``moe_experts``: the grouped
    matmuls. Nine products a layer (gate, up and down, each forward,
    for the input's gradient and for the kernel's), every one 2 x rows
    x d x w FLOPs over ``rows = seq_len x k`` dispatched rows. Each
    reads or writes its two activation operands once (rows x (d + w)
    elements of 2 bytes) and its stack of E kernels once A STEP: the
    kernels' bytes are shared over the minibatch."""
    heads = config["num_attention_heads"]
    d, w = config["hidden_size"], config["intermediate_size"]
    seq, layers = traffic["seq_len"], config["num_hidden_layers"]
    flash_flops = sum(
        flash_attention_flops(seq, heads, d // heads, b) for b in (0, 1))
    flash_bytes = sum(
        flash_attention_bytes(seq, heads, d // heads, b) for b in (0, 1))
    rows = seq * config["num_experts_per_tok"]
    expert_flops = 3.0 * seq * expert_flops_per_token(config)
    expert_bytes = 9 * 2.0 * (
        rows * (d + w)
        + config["num_experts"] * d * w / traffic["minibatch"]
    )
    return {
        "flash": (flash_flops * layers, flash_bytes * layers),
        "moe_experts": (expert_flops * layers, expert_bytes * layers),
    }
