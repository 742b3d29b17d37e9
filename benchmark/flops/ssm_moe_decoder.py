"""Operations and bytes a decoder-only language model of Nemotron-H's
stack needs (``nemotron_h``'s ``config.json``): every layer ONE
sublayer, a Mamba-2 state-space mixer (``M``), an expert layer (``E``)
or grouped-query softmax attention (``*``) as the layer's letter in
``hybrid_override_pattern`` says; an untied head. A configuration names
this count by the file's name (``"flops": "ssm_moe_decoder"``).

Part of the yardstick: a change to the program cannot move these.
Contractions only, 2 FLOPs a multiply-add. Per token:

- an ``M`` layer: the two projections and the chunked selective scan at
  the published chunk, as ``ssm_dense_decoder.py`` counts them (its
  functions, read through granite's key names: ``granite_keys``);
- the ``*`` layer: q and o (d x H D each), k and v (d x Hkv D each)
  with D = ``head_dim`` (H D is 4096, wider than d 2688); causal
  attention over the kept half of the score matrix at H heads;
- an ``E`` layer: the router d x ``published.n_routed_experts``; the
  shared expert, TWO matrices of d x
  ``moe_shared_expert_intermediate_size``; and the routed experts HERE:
  of a token's ``num_experts_per_tok`` choices the share ``held /
  published`` falls on this chip's experts on average, each TWO
  matrices of d x ``moe_intermediate_size`` (``relu(x W_up)^2 W_down``:
  no gate), at the NEEDED width 1856 and not the 1920 the kernels' tiles
  cover;
- the output head over the held vocabulary, once.

Backward = 2 x forward, nothing recomputed. NOTHING for the
convolution, the softplus, the decay's exponentials, the ReLU and its
square, the sort, the gathers, the norms.
"""

from benchmark.flops import ssm_dense_decoder as ssm

LETTERS = {"M": "mamba", "E": "experts", "*": "attention"}


def count(config, kind):
    """The built layers of ``kind`` (the first ``num_hidden_layers``
    letters of ``hybrid_override_pattern``)."""
    built = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    if set(built) - set(LETTERS) or len(built) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern=%r" % (built,))
    return sum(1 for letter in built if LETTERS[letter] == kind)


def granite_keys(config):
    """This family's Mamba-2 sizes under the names
    ``ssm_dense_decoder.py`` reads."""
    return {
        "hidden_size": config["hidden_size"],
        "mamba_n_heads": config["mamba_num_heads"],
        "mamba_d_head": config["mamba_head_dim"],
        "mamba_d_state": config["ssm_state_size"],
        "mamba_n_groups": config["n_groups"],
        "mamba_chunk_size": config["chunk_size"],
        "assumed": config["assumed"],
    }


def attention_projection_flops(config):
    """Forward FLOPs of one token's four attention projections."""
    d, width = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * d * width * (2 * heads + 2 * kv)


def attention_flops(config, seq):
    """Forward FLOPs of one layer's two score-sized products."""
    return 2 * 2.0 * ssm.kept_scores(seq) * (
        config["num_attention_heads"] * config["head_dim"])


def held_share(config):
    """The share of a layer's experts this chip holds."""
    return config["n_routed_experts"] / config["published"]["n_routed_experts"]


def held_expert_flops_per_token(config):
    """Forward FLOPs of one token's routed experts HERE, on average:
    two matrices an expert."""
    return 2.0 * config["num_experts_per_tok"] * held_share(config) * 2 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def parts(config, traffic):
    """Forward FLOPs of one sample by part."""
    d, seq = config["hidden_size"], traffic["seq_len"]
    mamba, experts, full = (
        count(config, kind) for kind in ("mamba", "experts", "attention"))
    granite = granite_keys(config)
    return {
        "mamba_projections": (
            mamba * seq * ssm.mamba_projection_flops(granite)),
        "ssd_scan": mamba * ssm.scan_flops(granite, seq),
        "attention_projections": (
            full * seq * attention_projection_flops(config)),
        "attention": full * attention_flops(config, seq),
        "router": (
            experts * seq * 2.0 * d
            * config["published"]["n_routed_experts"]),
        "shared_expert": (
            experts * seq * 2.0 * 2 * d
            * config["moe_shared_expert_intermediate_size"]),
        "held_experts": experts * seq * held_expert_flops_per_token(config),
        "head": 2.0 * seq * d * config["vocab_size"],
    }


def per_sample(config, traffic):
    """FLOPs the forward and backward passes require for ONE sample
    (one sequence of ``seq_len`` tokens)."""
    return 3.0 * sum(parts(config, traffic).values())


def flash_need(config, traffic):
    """(FLOPs, bytes) the flash kernels need for one sample's forward
    and backward, as ``ssm_dense_decoder.flash_need`` counts them, at
    ``head_dim`` lanes a head."""
    seq, width = traffic["seq_len"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    fulls = count(config, "attention")
    flops = 7 * 2.0 * ssm.kept_scores(seq) * heads * width
    nbytes = 2.0 * seq * width * ((2 * heads + 2 * kv)
                                  + (4 * heads + 4 * kv))
    return flops * fulls, nbytes * fulls


def scan_need(config, traffic):
    """(FLOPs, bytes) the selective scan needs, as
    ``ssm_dense_decoder.scan_need`` counts them from the equations, in
    this stack's ``M`` layers."""
    granite = dict(
        granite_keys(config), num_hidden_layers=count(config, "mamba"),
        layer_types=["mamba"] * count(config, "mamba"))
    return ssm.scan_need(granite, traffic)


def expert_need(config, traffic):
    """(FLOPs, bytes) the held experts' grouped matmuls need for one
    sample's forward and backward through every ``E`` layer: 3 x the
    forward FLOPs of the rows this chip's experts get on average at the
    needed width; bytes, each of the 2 projections' 3 calls reading its
    rows (d + w lanes a row, 2 bytes) and the held kernels once."""
    d, w = config["hidden_size"], config["moe_intermediate_size"]
    seq, layers = traffic["seq_len"], count(config, "experts")
    rows = seq * config["num_experts_per_tok"] * held_share(config)
    flops = 3.0 * seq * held_expert_flops_per_token(config)
    nbytes = 6 * 2.0 * (
        rows * (d + w)
        + config["n_routed_experts"] * d * w / traffic["minibatch"])
    return flops * layers, nbytes * layers


def kernels(config, traffic):
    """{kernel: (FLOPs, bytes)} the family's named parts need for one
    sample's forward and backward through every layer: ``flash``,
    ``ssd_scan`` (bytes-bound, as granite's) and ``relu2_gmm`` (the held
    experts' grouped matmuls)."""
    return {
        "flash": flash_need(config, traffic),
        "ssd_scan": scan_need(config, traffic),
        "relu2_gmm": expert_need(config, traffic),
    }
