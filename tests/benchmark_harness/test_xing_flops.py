"""``flops/hc_mla_moe_decoder.py`` against counts made by hand for one
small shape, and at the published sizes of the ``xing4.0-29b-a4b-1chip``
cut."""

import os

import pytest

from benchmark.flops import hc_mla_moe_decoder as count
from tests.benchmark_harness import _common as common

CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4,
    "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4,
    "intermediate_size": 12, "moe_intermediate_size": 4,
    "n_routed_experts": 2, "published": {"n_routed_experts": 8},
    "num_experts_per_tok": 4, "n_shared_experts": 1, "hc_mult": 4,
    "num_nextn_predict_layers": 1, "vocab_size": 100,
}
TRAFFIC = {"seq_len": 16, "minibatch": 2}


def entry(section, name):
    (found,) = [e for e in common.load(common.MANIFEST)[section]
                if e["name"] == name]
    return found


def test_per_sample_by_hand():
    # forward, one sequence of 16 tokens, 2 FLOPs a multiply-add.
    # latent attention's six kernels: q down 8 x 6, q up 6 x 2 x 6, kv
    # down 8 x 6, kv up 4 x 2 x 8, out 2 x 4 x 8: 48 + 72 + 48 + 64 + 64
    assert count.latent_projection_flops(CONFIG) == 2 * 296
    # a sublayer's hyper-connection: the (4 x 8) x 24 projection and the
    # mixes' 4 + 16 + 4 multiply-adds a lane
    assert count.hyper_connection_flops(CONFIG) == 2 * (32 * 24 + 24 * 8)
    every = 2 * 296 + 2 * 1920
    # the dense MLP 3 x 8 x 12; an expert block: the router over ALL 8
    # experts, the shared expert, 4 choices x 2 / 8 held = 1 routed one
    assert count.held_share(CONFIG) == 0.25
    assert count.expert_flops_per_token(CONFIG) == 2 * 96
    assert count.expert_flops_per_token(CONFIG, shared=True) == 2 * 96
    expert = 2 * 64 + 2 * 96 + 2 * 96
    # one dense and two expert blocks, and the module: 2 x 8 x 8 more
    # and one more expert block
    assert count.blocks(CONFIG) == (1, 2, 1)
    per_token = 4 * every + 2 * 288 + 3 * expert + 2 * 128
    # q k^T at 6 lanes and p v at 4 over half of 16 x 16, 2 heads
    attention = 16 * 16 * 2 * (6 + 4)
    head = 2 * 16 * 8 * 100
    assert count.per_sample(CONFIG, TRAFFIC) == 3 * (
        16 * per_token + 4 * attention + 2 * head) == 1_179_648


def test_kernels_by_hand():
    kernels = count.kernels(CONFIG, TRAFFIC)
    # flash: 2 forward and 5 backward score-sized products on needed
    # lanes, four blocks (the module's among them)
    assert kernels["flash"][0] == 4 * 16 * 16 * 2 * (10 + 26)
    assert kernels["flash"][1] == 4 * 16 * 2 * 2 * (20 + 40)
    # the experts: three expert blocks, 16 held rows each
    assert kernels["moe_experts"][0] == 3 * 3 * 16 * 192
    assert kernels["moe_experts"][1] == 3 * 9 * 2 * (16 * 12 + 2 * 32 / 2)
    # the mixes: eight sublayers; a sublayer's forward reads X and
    # writes X', its backward reads X and dX' and writes dX: five
    # passes over 4 x 8 lanes of 2 bytes a token
    assert kernels["mhc_mix"][1] == 8 * 5 * 16 * 32 * 2
    assert kernels["mhc_mix"][0] == 8 * 3 * 16 * 2 * 24 * 8
    # bytes bound it: 48 d multiply-adds against 40 d bytes a token
    flops, moved = kernels["mhc_mix"]
    assert flops / 197e12 < moved / 819e9


def test_the_cell_s_count_at_the_published_sizes():
    config = common.load(os.path.join(
        common.REPO, entry("configs", "xing4.0-29b-a4b-1chip")["file"]))
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s4k-b1.json"))
    assert config["flops"] == "hc_mla_moe_decoder"
    # ISSUE 37's count of the latent kernels: 28.41 M a layer
    assert count.latent_projection_flops(config) == 2 * 28_409_856
    # 2 x 14,336 x 24 coefficient weights a layer (0.69 M) + the mixes
    assert count.hyper_connection_flops(config) == 2 * (
        14336 * 24 + 24 * 3584)
    per_sample = count.per_sample(config, traffic)
    assert per_sample == pytest.approx(15.419e12, rel=1e-3)
    kernels = count.kernels(config, traffic)
    # the mixes have to move 7.05 GB a sample: 8.6 ms at the HBM peak
    assert kernels["mhc_mix"][1] == 12 * 5 * 4096 * 14336 * 2
    assert kernels["mhc_mix"][1] / 819e9 == pytest.approx(8.6e-3, rel=0.01)
    # attention (causal, 192 / 128 lanes, six blocks) is a fifth of the
    # step's required FLOPs at 4,096 tokens
    attention = 3 * 6 * 4096 * 4096 * 32 * 320
    assert 0.15 < attention / per_sample < 0.25
    # without the module (a block of six and a head pass of two) the
    # step needs a quarter less
    without = count.per_sample(
        dict(config, num_nextn_predict_layers=0), traffic)
    assert 0.72 < without / per_sample < 0.80
