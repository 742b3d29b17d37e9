"""``flops/mla_moe_decoder.py`` against counts made by hand for one
small shape, and at the published sizes of the
``moonlight-16b-a3b-1chip`` cut."""

import os

import pytest

from benchmark.flops import mla_moe_decoder
from tests.benchmark_harness import _common as common

CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 20, "moe_intermediate_size": 4, "vocab_size": 100,
    "num_attention_heads": 2, "kv_lora_rank": 6, "qk_nope_head_dim": 4,
    "qk_rope_head_dim": 2, "v_head_dim": 4, "n_routed_experts": 6,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
}
TRAFFIC = {"seq_len": 16, "minibatch": 4}
MOONLIGHT = os.path.join(
    common.REPO, "benchmark", "configs", "moonlight-16b-a3b-1chip",
    "config.json")


def test_per_sample_by_hand():
    # forward, one sequence of 16 tokens, 2 FLOPs a multiply-add.
    # latent attention, a layer: q 8 x 2 x 6 = 96 weights; kv down
    # 8 x (6 + 2) = 64; kv up 6 x 2 x (4 + 4) = 96; out 2 x 4 x 8 = 64
    # -> 320 weights -> 2 * 16 * 320 = 10,240
    latent = 10_240
    assert mla_moe_decoder.latent_projection_flops(CONFIG) * 16 == latent
    # the dense layer: gate, up, down = 3 * 8 * 20 = 480 -> 15,360
    dense = 15_360
    # an expert layer: the router 8 * 6 = 48; two routed experts and one
    # shared of 3 * 8 * 4 = 96 each = 288 -> 336 weights -> 10,752
    expert = 10_752
    # attention, a layer, at half the score matrix: QK^T at 6 lanes,
    # PV at 4: 16 * 16 * 2 heads * (6 + 4) = 5,120
    attention = 5_120
    head = 2 * 16 * 8 * 100
    forward = 3 * (latent + attention) + dense + 2 * expert + head
    assert forward == 108_544
    # backward = 2 x forward; nothing recomputed; nothing for dispatch,
    # the rotary, the broadcast key head or the concatenations
    assert mla_moe_decoder.per_sample(CONFIG, TRAFFIC) == 3 * forward
    # one more leading dense layer swaps an expert layer for a dense one
    two = dict(CONFIG, first_k_dense_replace=2)
    assert mla_moe_decoder.per_sample(two, TRAFFIC) - 3 * forward == (
        3 * (dense - expert))
    # the cut's one dense layer counts once however few layers are kept
    one = dict(CONFIG, num_hidden_layers=1)
    assert mla_moe_decoder.per_sample(one, TRAFFIC) == 3 * (
        latent + attention + dense + head)


def test_kernels_by_hand():
    kernels = mla_moe_decoder.kernels(CONFIG, TRAFFIC)
    flops, nbytes = kernels["flash"]
    # seven score-sized matmuls a layer over the causal half, each
    # 16 * 16 * 2 heads * its width: forward 6 and 4; backward the
    # scores 6, dp 4, dv 4, dq 6, dk 6
    assert flops == 3 * 512 * ((6 + 4) + (6 + 4 + 4 + 6 + 6))
    # forward reads q, k (6 wide), v (4) and writes o (4); backward
    # reads q, k, v, o, do and writes dq, dk, dv: 16 tokens x 2 heads x
    # 2 bytes
    assert nbytes == 3 * 16 * 2 * 2 * ((6 + 6 + 4 + 4) + (4 * 6 + 4 * 4))
    flops, nbytes = kernels["moe_experts"]
    # TWO expert layers of three: 32 dispatched rows, nine products of
    # 2 * 32 * 8 * 4
    assert flops == 2 * 9 * 2 * 32 * 8 * 4
    assert nbytes == 2 * 9 * 2 * (32 * 12 + 6 * 32 / 4)
    assert flops == 2 * 3 * 16 * mla_moe_decoder.expert_flops_per_token(
        CONFIG)


def test_equal_widths_count_what_the_dense_decoder_counts():
    """With q / k and v of one width the flash entry is
    ``dense_decoder``'s: the two files are one yardstick."""
    from benchmark.flops import dense_decoder

    equal = dict(CONFIG, qk_nope_head_dim=2, qk_rope_head_dim=2,
                 v_head_dim=4)
    dense = {"hidden_size": 8, "num_attention_heads": 2,
             "num_hidden_layers": 3}
    assert mla_moe_decoder.kernels(equal, TRAFFIC)["flash"] == (
        dense_decoder.kernels(dense, TRAFFIC)["flash"])


def test_published_sizes_match_the_issue():
    config = common.load(MOONLIGHT)
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s8k-b2.json"))
    assert (traffic["seq_len"], traffic["minibatch"]) == (8192, 2)
    per_sample = mla_moe_decoder.per_sample(config, traffic)
    # ISSUE 29: about 458 MFLOP a token forward at 10,240 rows of the
    # vocabulary (22.5 TFLOP a step); a row more is 2 x 2048 FLOPs
    sixteenth = dict(config, vocab_size=10240)
    small = mla_moe_decoder.per_sample(sixteenth, traffic)
    assert small / 3 / 8192 / 1e6 == pytest.approx(458, abs=0.5)
    assert 2 * small / 1e12 == pytest.approx(22.51, abs=0.01)
    assert per_sample - small == 3 * 8192 * 2.0 * 2048 * (
        config["vocab_size"] - 10240)
    # the cut that was taken: a quarter of the rows, 583.8 MFLOP a
    # token, 28.7 TFLOP a step
    assert config["vocab_size"] == 40960
    token = per_sample / 3 / 8192
    assert token / 1e6 == pytest.approx(583.8, abs=0.1)
    assert 2 * per_sample / 1e12 == pytest.approx(28.69, abs=0.01)
    # the shares the cell's ``why`` gives
    latent = 2 * mla_moe_decoder.latent_projection_flops(config)
    flash = 2 * 8192 * 16 * (192 + 128)
    experts = (mla_moe_decoder.expert_flops_per_token(config)
               + mla_moe_decoder.expert_flops_per_token(config, shared=True))
    dense = 2.0 * 3 * 2048 * 11264
    head = 2.0 * 2048 * 40960
    for part, share in ((latent, 0.094), (flash, 0.144), (experts, 0.237),
                        (dense, 0.237), (head, 0.287)):
        assert part / token == pytest.approx(share, abs=0.002)
    # parameters a layer, as the issue counts them: latent attention
    # 13.8 M, the dense MLP 69.2 M, routed 553.6 M, shared 17.3 M
    assert latent / 2 / 2 == pytest.approx(13.76e6, rel=1e-3)
    assert 64 * 3 * 2048 * 1408 == pytest.approx(553.6e6, rel=1e-3)
    flops, nbytes = mla_moe_decoder.kernels(config, traffic)["flash"]
    # FLOPs bound the flash kernels on a v5e at 8192 x 192 / 128
    peaks = common.load(os.path.join(
        common.REPO, "benchmark", "lib", "peaks.json"))["TPU v5 lite"]
    assert flops / peaks["bf16_flops_per_s"] > 5 * (
        nbytes / peaks["hbm_bytes_per_s"])
    assert flops == 2 * 8192.0 * 8192 * 16 * (4 * 192 + 3 * 128)
