"""Operations and bytes a decoder-only language model needs, computed
from the configuration's shapes. A configuration names its count by
this file's name (``"flops": "dense_decoder"``); the harness finds
``flops/<name>.py`` under the manifest's paths, so another family
brings a file of its own and edits none. Such a file has
``per_sample(config, traffic)`` and, where the family has kernels
whose roofline is reported, ``kernels(config, traffic)``.

Part of the yardstick: a change to the program cannot move these. The
dense count is ``scripts/bench_transformer_mfu.py:model_train_flops``
(copied; the original is listed in PERF.md for deletion): matrix
multiplications only, causal attention counted at half the score
matrix, backward = 2 x forward, nothing recomputed, the embedding
gather excluded.
"""


def per_sample(config, traffic):
    """FLOPs the forward and backward passes of a decoder-only LM
    require for ONE sample (one sequence of ``seq_len`` tokens)."""
    d = config["hidden_size"]
    layers = config["num_hidden_layers"]
    inter = config["intermediate_size"]
    vocab = config["vocab_size"]
    seq = traffic["seq_len"]
    # per token and layer: q, k, v, out (4 d^2) + mlp up and down
    proj = 2 * seq * (4 * d * d + 2 * d * inter) * layers
    attn = flash_attention_flops(
        seq, config["num_attention_heads"],
        d // config["num_attention_heads"], backward=False,
    ) * layers
    head = 2 * seq * d * vocab
    return 3.0 * (proj + attn + head)


def flash_attention_flops(seq, heads, head_dim, backward):
    """Causal attention over one sequence, in FLOPs. One "unit" is one
    (S x S x D) matmul over the causal half: S^2 D. Forward needs two
    (QK^T, PV). Backward needs five: the scores again (they are never
    stored), dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q. A
    backward split into a dq and a dkv kernel that each rebuild the
    scores executes more than it needs, and its share shows that."""
    unit = float(seq) * seq * head_dim * heads
    return (5 if backward else 2) * unit


def flash_attention_bytes(seq, heads, head_dim, backward, itemsize=2):
    """HBM bytes the kernels must move for one sequence: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq,
    dk, dv (the per-row log-sum-exp is under 1% and left out)."""
    tensor = float(seq) * heads * head_dim * itemsize
    return (8 if backward else 4) * tensor


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named kernels need for one
    sample's forward and backward through every layer; ``flash`` is
    what ``flash_roofline`` reads."""
    heads = config["num_attention_heads"]
    head_dim = config["hidden_size"] // heads
    seq, layers = traffic["seq_len"], config["num_hidden_layers"]
    flops = sum(
        flash_attention_flops(seq, heads, head_dim, b) for b in (0, 1)
    )
    nbytes = sum(
        flash_attention_bytes(seq, heads, head_dim, b) for b in (0, 1)
    )
    return {"flash": (flops * layers, nbytes * layers)}
