"""``flops/kda_mla_moe_decoder.py`` against counts made by hand for one
small shape, and at the published sizes of the
``kimi-linear-48b-a3b-1chip`` cut."""

import os

import pytest

from benchmark.flops import kda_mla_moe_decoder as count
from tests.benchmark_harness import _common as common
from tests.benchmark_harness.test_kimi_metrics import CONFIG

TRAFFIC = {"seq_len": 64, "minibatch": 2}
KIMI = os.path.join(
    common.REPO, "benchmark", "configs", "kimi-linear-48b-a3b-1chip",
    "config.json")


def test_per_sample_by_hand():
    # forward, one sequence of 64 tokens, 2 FLOPs a multiply-add; d 8, 2
    # KDA heads of 4 lanes (inner 8), gates 4 wide.
    # a KDA layer's projections: q | k | v 8 x 24 = 192 weights, the two
    # low-rank gates 2 x (8 x 4 + 4 x 8) = 128, beta 8 x 2 = 16, out 8 x
    # 8 = 64 -> 400 -> 2 * 64 * 400
    assert count.kda_dims(CONFIG) == (2, 4, 4, 32)
    projections = 51_200
    assert count.kda_projection_flops(CONFIG) * 64 == projections
    # the chunked rule, 2 chunks of 32, sub-blocks of 16 (nb = 2): K K^T
    # and Q K^T over the 3 sub-blocks on and below the diagonal, 2 x 3 x
    # (2*16*16*4) = 12,288; the inverse 32^3 = 32,768; U and W 2 x
    # (2*32*32*4) = 16,384; W S, Q S, K^T V' 3 x (2*32*4*4) = 3,072; P
    # V' 2*32*32*4 = 8,192 -> 72,704 a head and chunk, x 2 heads x 2
    rule = 4 * 72_704
    assert count.kda_rule_flops(CONFIG, 64) == rule == 290_816
    # a chunk the sequence does not fill is a whole chunk
    assert count.kda_rule_flops(CONFIG, 65) == 6 * 72_704
    # the latent layer's projections: q 8 x 2 x 6 = 96, kv down 8 x (6 +
    # 2) = 64, kv up 6 x 2 x (4 + 4) = 96, out 2*4 x 8 = 64 -> 320
    latent = 2 * 64 * 320
    assert count.latent_projection_flops(CONFIG) * 64 == latent
    assert count.widths(CONFIG) == (6, 4)
    # causal attention at half the score matrix, 2 heads: q k^T at 6
    # lanes and p v at 4: 64 * 64 * 2 * 10
    attention = 81_920
    # the dense layer 3 x 8 x 12 = 288 weights; an expert layer: the
    # router over ALL 8 experts 64, the shared expert 3*8*4 = 96, and 4
    # choices x 2 / 8 held = 1 routed expert of 96 on average -> 256
    dense, expert = 2 * 64 * 288, 2 * 64 * 256
    assert count.held_share(CONFIG) == 0.25
    head = 2 * 64 * 8 * 100
    # five layers: KDA, KDA, KDA, latent, KDA; the first one dense
    assert count.layer_counts(CONFIG) == (4, 1)
    forward = (4 * (projections + rule) + latent + attention + dense
               + 4 * expert + head)
    assert forward == 1_761_280
    # backward = 2 x forward; nothing recomputed; nothing for the
    # convolutions, the decays, the norms, the gates, the sort
    assert count.per_sample(CONFIG, TRAFFIC) == 3 * forward
    # the model's own 27 layers would be 20 KDA and 7 latent; here the
    # lists end at 8
    eight = dict(CONFIG, num_hidden_layers=8)
    assert count.layer_counts(eight) == (6, 2)
    more = count.per_sample(eight, TRAFFIC) - 3 * forward
    assert more == 3 * (2 * (projections + rule) + latent + attention
                        + 3 * expert)
    # holding all the experts is the whole layer's k experts a token
    whole = dict(CONFIG, num_experts=8)
    assert count.expert_flops_per_token(whole) == 2 * 4 * 96


def test_kernels_by_hand():
    kernels = count.kernels(CONFIG, TRAFFIC)
    assert set(kernels) == {"flash", "kda_scan", "moe_experts"}
    flops, nbytes = kernels["flash"]
    # seven score-sized matmuls over the causal half at 2 heads: 2
    # forward (6 + 4 lanes) and 5 backward (3 x 6 + 2 x 4), in the one
    # latent layer: 64 * 64 * 2 * 36
    assert flops == 294_912
    # forward reads q, k (6 lanes), v and writes o (4): 20; backward
    # reads q, k, v, o, do and writes dq, dk, dv: 4 x 6 + 4 x 4 = 40: 60
    # lanes x 2 heads x 64 tokens x 2 bytes
    assert nbytes == 60 * 2 * 64 * 2 == 15_360
    flops, nbytes = kernels["kda_scan"]
    assert flops == 3 * 290_816 * 4
    # a token and head: q, k, v 3 x 4 lanes x 2 bytes = 24, g 4 lanes x 4
    # = 16, beta 4 -> 44; o or do 8. Forward 44 + 8, backward 44 + 8 read
    # and 44 written: 148 x 64 tokens x 2 heads = 18,944; and one float32
    # 4 x 4 state a segment of 1 chunk (2 segments), written and read, a
    # head: 2 x 2 x 2 x 64 = 512; four KDA layers
    assert nbytes == 4 * (18_944 + 512) == 77_824
    flops, nbytes = kernels["moe_experts"]
    # 64 rows on average (64 tokens x 4 choices x 2 / 8), nine products
    # of 2 x 64 x 8 x 4 in each of the four expert layers
    assert flops == 4 * 9 * 2 * 64 * 8 * 4
    assert nbytes == 4 * 9 * 2.0 * (64 * (8 + 4) + 2 * 8 * 4 / 2)


def test_the_cell_s_count():
    config = common.load(KIMI)
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s32k-b1.json"))
    assert count.layer_counts(config) == (4, 1)
    total = count.per_sample(config, traffic)
    # ISSUE 58's count by hand: ~101 TFLOP a step
    assert total == pytest.approx(101.1e12, rel=0.005)
    kernels = count.kernels(config, traffic)
    rule = kernels["kda_scan"][0]
    # the rule is 2% of the FLOPs; by time it is another matter
    assert 0.02 < rule / total < 0.025
    assert kernels["flash"][0] / total == pytest.approx(0.39, abs=0.01)
    # FLOPs bound the rule's roofline on a v5e (197 TFLOP/s, 819 GB/s)
    assert rule / 197e12 < kernels["kda_scan"][1] / 819e9 * 2
    assert kernels["kda_scan"][1] == pytest.approx(18.4e9, rel=0.01)
