"""``flops/gdn_moe_decoder.py`` against counts made by hand for one
small shape, and at the published sizes of the
``qwen3-next-80b-a3b-1chip`` cut."""

import os

import pytest

from benchmark.flops import gdn_moe_decoder
from tests.benchmark_harness import _common as common

CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 4, "full_attention_interval": 4,
    "linear_num_key_heads": 1, "linear_num_value_heads": 2,
    "linear_key_head_dim": 4, "linear_value_head_dim": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
    "num_experts": 2, "published": {"num_experts": 8},
    "num_experts_per_tok": 4, "moe_intermediate_size": 4,
    "shared_expert_intermediate_size": 4, "vocab_size": 100,
    "assumed": {"gdn_chunk": 8},
}
TRAFFIC = {"seq_len": 16, "minibatch": 2}
QWEN = os.path.join(
    common.REPO, "benchmark", "configs", "qwen3-next-80b-a3b-1chip",
    "config.json")


def test_per_sample_by_hand():
    # forward, one sequence of 16 tokens, 2 FLOPs a multiply-add.
    # a linear layer's projections: qkvz 8 x (2*1*4 + 2*2*4) = 192
    # weights, ba 8 x 4 = 32, out 2*4 x 8 = 64 -> 288 -> 2*16*288
    linear = 9_216
    assert gdn_moe_decoder.linear_projection_flops(CONFIG) * 16 == linear
    # the chunked rule, 2 chunks of 8: a key head's K K^T and Q K^T
    # 2 * (2*8*8*4) = 1,024; a value head's inverse 8^3 = 512, U and W
    # 2 * (2*8*8*4) = 1,024, W S, Q S, K^T V' 3 * (2*8*4*4) = 768,
    # attn V' 2*8*8*4 = 512 -> 2,816; a chunk 1,024 + 2 * 2,816
    rule = 2 * (1_024 + 2 * 2_816)
    assert gdn_moe_decoder.delta_rule_flops(CONFIG, 16) == rule == 13_312
    # the full layer's projections: query and gate 8 x 4 x 8 = 256, key
    # and value 2 * (8 x 2 x 4) = 128, out 4*4 x 8 = 128 -> 512
    full = 2 * 16 * 512
    assert gdn_moe_decoder.attention_projection_flops(CONFIG) * 16 == full
    # causal attention at half the score matrix, 4 heads of 4: QK^T and
    # PV, 2 * (16 * 16 * 4 * 4)
    attention = 8_192
    # an expert layer: the router over ALL 8 experts 64 weights, the
    # shared expert 3*8*4 = 96 and its gate 8, and 4 choices x 2 / 8 held
    # = 1 routed expert of 96 on average -> 264 weights
    expert = 2 * 16 * 264
    assert gdn_moe_decoder.held_share(CONFIG) == 0.25
    head = 2 * 16 * 8 * 100
    forward = (3 * (linear + rule) + full + attention + 4 * expert + head)
    assert forward == 151_552
    # backward = 2 x forward; nothing recomputed; nothing for the conv,
    # the norms, the gates, the sort, the gathers or the scatter
    assert gdn_moe_decoder.per_sample(CONFIG, TRAFFIC) == 3 * forward
    # two periods: six linear layers and two full ones
    assert gdn_moe_decoder.linear_layers(
        dict(CONFIG, num_hidden_layers=8)) == (6, 2)
    eight = gdn_moe_decoder.per_sample(
        dict(CONFIG, num_hidden_layers=8), TRAFFIC)
    assert eight - 3 * forward == 3 * (forward - head)
    # holding all the experts is the whole layer's k experts a token
    whole = dict(CONFIG, num_experts=8)
    assert gdn_moe_decoder.expert_flops_per_token(whole) == 2 * 4 * 96


def test_kernels_by_hand():
    kernels = gdn_moe_decoder.kernels(CONFIG, TRAFFIC)
    flops, nbytes = kernels["flash"]
    # seven score-sized matmuls over the causal half at 4 query heads of
    # 4: 7 * 16 * 16 * 4 * 4, in the one full layer
    assert flops == 28_672
    # forward reads q, writes o at 4 heads and reads k, v at their 2;
    # backward reads q, o, do, writes dq at 4 and reads k, v, writes dk,
    # dv at 2: (2*4 + 2*2) + (4*4 + 4*2) = 36 head-rows of 4 lanes x 16
    # tokens x 2 bytes
    assert nbytes == 36 * 4 * 16 * 2 == 4_608
    flops, nbytes = kernels["gdn_scan"]
    assert flops == 3 * 3 * 13_312
    # q, k at 1 head of 4, v, o at 2 heads of 4 (2 bytes), the two gates
    # at 2 heads (4 bytes): 16 tokens x (2 * 24 + 4 * 4) = 1,024 forward;
    # backward twice that; three layers
    assert nbytes == 3 * 3 * 1_024
    flops, nbytes = kernels["moe_experts"]
    # rows: 16 tokens x 4 choices x 2 / 8 = 16; nine products of 2 x 16
    # x 8 x 4 in each of the four layers
    assert flops == 4 * 9 * 2 * 16 * 8 * 4
    # their operands once, the 2 held kernels once a step of 2 samples
    assert nbytes == 4 * 9 * 2.0 * (16 * 12 + 2 * 32 / 2)


def test_the_cell_s_count():
    config = common.load(QWEN)
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s32k-b1.json"))
    total = gdn_moe_decoder.per_sample(config, traffic)
    # ISSUE 31 counted 70 TFLOP with flash at its seven kernel matmuls;
    # the repo's convention (backward = 2 x forward) has six
    assert total == pytest.approx(65.70e12, rel=1e-3)
    seq = traffic["seq_len"]
    shares = {
        "linear projections": 3 * 3 * seq
        * gdn_moe_decoder.linear_projection_flops(config),
        "rule": 3 * 3 * gdn_moe_decoder.delta_rule_flops(config, seq),
        "flash": 3 * 2.0 * seq * seq * 16 * 256,
        "full projections": 3 * seq
        * gdn_moe_decoder.attention_projection_flops(config),
        "head": 3 * 2.0 * seq * 2048 * 18992,
    }
    assert shares["linear projections"] == pytest.approx(19.87e12, rel=1e-3)
    assert shares["rule"] == pytest.approx(1.585e12, rel=1e-3)
    assert shares["flash"] == pytest.approx(26.39e12, rel=1e-3)
    assert shares["full projections"] == pytest.approx(5.36e12, rel=1e-2)
    assert shares["head"] == pytest.approx(7.65e12, rel=1e-3)
    experts = total - sum(shares.values())
    assert experts / total == pytest.approx(0.074, abs=0.002)
    kernels = gdn_moe_decoder.kernels(config, traffic)
    # flash: FLOPs bound it (30.8 TFLOP at 197e12 is 156 ms; 1.8 GB at
    # 819e9 is 2 ms); the rule: bytes and FLOPs within 10% of each other
    assert kernels["flash"][0] == pytest.approx(30.79e12, rel=1e-3)
    assert kernels["flash"][0] / 197e12 > 50 * kernels["flash"][1] / 819e9
    assert kernels["gdn_scan"][0] / 197e12 == pytest.approx(8.04e-3, rel=1e-2)
    assert kernels["gdn_scan"][1] / 819e9 == pytest.approx(8.94e-3, rel=1e-2)
