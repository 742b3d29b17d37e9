"""``flops/moe_decoder.py`` against counts made by hand for one small
shape, and at the published sizes of the ``olmoe-1b-7b-1chip`` cut."""

import os

import pytest

from benchmark.flops import dense_decoder, moe_decoder
from tests.benchmark_harness import _common as common

CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 3, "intermediate_size": 4,
    "vocab_size": 100, "num_attention_heads": 2, "num_experts": 6,
    "num_experts_per_tok": 2,
}
TRAFFIC = {"seq_len": 16, "minibatch": 4}
OLMOE = os.path.join(
    common.REPO, "benchmark", "configs", "olmoe-1b-7b-1chip", "config.json")


def test_moe_decoder_by_hand():
    # forward, one sequence of 16 tokens, 2 FLOPs a multiply-add, a
    # layer: q, k, v, out = 4 * 8*8 = 256 weights; the router 8*6 = 48;
    # two experts of gate, up, down = 2 * 3 * 8*4 = 192 -> 496 weights
    # -> 2 * 16 * 496 = 15,872
    # attention: QK^T and PV at half the score matrix -> 4,096
    # head: 2 * 16 * 8 * 100 = 25,600
    forward = 3 * (15_872 + 4_096) + 25_600
    assert forward == 85_504
    # backward = 2 x forward; nothing recomputed; nothing for dispatch,
    # combine or sorting; no embedding gather
    assert moe_decoder.per_sample(CONFIG, TRAFFIC) == 3 * forward
    # four times the experts, the same two a token: the router's row only
    more = dict(CONFIG, num_experts=24)
    assert moe_decoder.per_sample(more, TRAFFIC) - 3 * forward == (
        3 * 3 * 2 * 16 * 8 * 18)


def test_kernels_by_hand():
    kernels = moe_decoder.kernels(CONFIG, TRAFFIC)
    assert kernels["flash"] == dense_decoder.kernels(CONFIG, TRAFFIC)["flash"]
    flops, nbytes = kernels["moe_experts"]
    # a layer: 32 dispatched rows, nine products of 2 * 32 * 8 * 4
    assert flops == 3 * 9 * 2 * 32 * 8 * 4
    # each product: its two activation operands, 32 x (8 + 4) elements
    # of 2 bytes, and a stack of 6 kernels of 8 x 4 once a step of 4
    assert nbytes == 3 * 9 * 2 * (32 * 12 + 6 * 32 / 4)
    # the experts' share of the count is the same three products
    assert flops == 3 * 3 * 16 * moe_decoder.expert_flops_per_token(CONFIG)


def test_published_sizes_match_the_issue():
    config = common.load(OLMOE)
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s4k-b8.json"))
    per_sample = moe_decoder.per_sample(config, traffic)
    # ISSUE 25: 0.608 GFLOP a token at this cut, 19.9 TFLOP a step
    assert per_sample / 4096 / 1e9 == pytest.approx(0.6083, abs=2e-4)
    assert 8 * per_sample / 1e12 == pytest.approx(19.93, abs=0.01)
    # the shares the cut was chosen for: experts half, the head a quarter
    experts = 3 * 4096 * moe_decoder.expert_flops_per_token(config)
    head = 3 * 2.0 * 4096 * 2048 * config["vocab_size"]
    assert experts / per_sample == pytest.approx(0.496, abs=0.002)
    assert head / per_sample == pytest.approx(0.254, abs=0.002)
    # with the whole vocabulary and one layer the head would be 58%
    whole = dict(config, vocab_size=50304)
    assert 4 * head / moe_decoder.per_sample(whole, traffic) == (
        pytest.approx(0.58, abs=0.01))
    flops, nbytes = moe_decoder.kernels(config, traffic)["moe_experts"]
    assert flops == experts
    # FLOPs bound the grouped matmuls on a v5e: 6.3 ms against 2.6 ms
    peaks = common.load(os.path.join(
        common.REPO, "benchmark", "lib", "peaks.json"))["TPU v5 lite"]
    assert flops / peaks["bf16_flops_per_s"] == pytest.approx(6.28e-3, rel=0.01)
    assert nbytes / peaks["hbm_bytes_per_s"] == pytest.approx(2.58e-3, rel=0.01)
