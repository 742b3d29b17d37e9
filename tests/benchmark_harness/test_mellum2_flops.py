"""``flops/ep_window_moe_decoder.py`` against counts made by hand for
one small shape and at the published sizes of the
``mellum2-12b-a2.5b-ep4`` cut."""

import os

import pytest

from benchmark.flops import ep_window_moe_decoder as count
from tests.benchmark_harness import _common as common

FULL, SLIDING = "full_attention", "sliding_attention"
CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 2, "head_dim": 4,
    "num_key_value_heads": 1, "num_attention_heads": 2,
    "layer_types": [SLIDING, FULL, SLIDING],
    "mlp_layer_types": ["sparse"] * 3,
    "sliding_window": 4, "moe_intermediate_size": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "vocab_size": 100,
    "expert_parallel": {"ranks": 4},
}
TRAFFIC = {"seq_len": 16, "minibatch": 4}


def test_per_sample_by_hand():
    assert count.layers_of(CONFIG) == [SLIDING, FULL]
    # forward, one token, 2 FLOPs a multiply-add: query 8 x 2 x 4, key
    # and value 8 x 4 each, out 2 x 4 x 8; the router over 8 experts;
    # 2 experts of 3 x 8 x 4: ALL of a token's choices count
    assert count.projection_flops(CONFIG) == 2 * (64 + 32 + 32 + 64)
    assert count.router_flops_per_token(CONFIG) == 2 * 64
    assert count.expert_flops_per_token(CONFIG) == 2 * 2 * 96
    # attention's two products over the kept entries at 2 heads of 4:
    # 136 a head in the full layer, 16 x 4 - 6 = 58 in the sliding one
    assert count.kept_scores(SLIDING, 16, 4) == 58
    assert count.kept_scores(SLIDING, 8192, 1024) == sum(
        min(i + 1, 1024) for i in range(8192))
    assert count.attention_flops(CONFIG, FULL, 16) == 4 * 136 * 8
    parts = count.parts(CONFIG, TRAFFIC)
    assert parts == {
        "flash_full": 3 * 4 * 136 * 8, "flash_window": 3 * 4 * 58 * 8,
        "projections": 3 * 16 * 2 * 384, "router": 3 * 16 * 2 * 128,
        "experts": 3 * 16 * 2 * 384, "head": 3 * 2 * 16 * 8 * 100}
    assert count.per_sample(CONFIG, TRAFFIC) == sum(parts.values())
    with pytest.raises(ValueError, match="mlp_layer_types"):
        count.layers_of(dict(CONFIG, mlp_layer_types=["dense"] * 3))
    with pytest.raises(ValueError, match="layer_types names"):
        count.kept_scores("chunked_attention", 16, 4)


def test_kernels_and_the_exchange_s_bytes_by_hand():
    kernels = count.kernels(CONFIG, TRAFFIC)
    assert kernels["flash_window"] == (
        7 * 2 * 58 * 2 * 4, 2 * 16 * 4 * (6 * 2 + 6 * 1))
    assert kernels["flash"][0] == kernels["flash_window"][0] + (
        7 * 2 * 136 * 2 * 4)
    # all 32 pairs of a sample in each of two layers, and a sample's
    # quarter of one read of the 8 experts' kernels
    assert kernels["moe_experts"] == (
        2 * 3 * 16 * 384, 2 * 9 * 2 * (32 * 12 + 8 * 32 / 4))
    # a counted pair is a row of 8 bfloat16 values in four passes
    assert count.exchange_bytes(CONFIG, 10) == 10 * 8 * 2 * 4
    assert count.exchange_bytes(CONFIG, 0) == 0
    # under a uniform router three of four pairs leave their rank
    assert kernels["exchange"] == (
        0.0, count.exchange_bytes(CONFIG, 2 * 32 * 3 / 4))


def test_the_cell_s_count_at_the_published_sizes():
    manifest = common.load(common.MANIFEST)
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == "mellum2-12b-a2.5b-ep4"]
    config = common.load(os.path.join(common.REPO, entry["file"]))
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s8k-b4.json"))
    assert config["flops"] == "ep_window_moe_decoder"
    assert count.layers_of(config) == [SLIDING] * 3 + [FULL]
    # ISSUE 45's parameter counts a layer: attention 21.23 M, the
    # router 0.15 M, an expert 6.19 M
    assert count.projection_flops(config) == 2 * 21_233_664
    assert count.router_flops_per_token(config) == 2 * 147_456
    assert count.expert_flops_per_token(config) == 2 * 8 * 6_193_152
    parts = count.parts(config, traffic)
    per_sample = count.per_sample(config, traffic)
    assert per_sample == sum(parts.values())
    # ~27.9 TFLOP a sample, which is a chip a step: the head 40%, the
    # experts 35%, the projections 15%, flash 10%
    assert per_sample == pytest.approx(27.9e12, rel=2e-3)
    share = {name: value / per_sample for name, value in parts.items()}
    assert 0.39 < share["head"] < 0.41
    assert 0.34 < share["experts"] < 0.36
    assert 0.14 < share["projections"] < 0.16
    assert 0.09 < share["flash_full"] + share["flash_window"] < 0.11
    kernels = count.kernels(config, traffic)
    # the exchange under a uniform router: 4 layers x 65,536 pairs x
    # 3 / 4 x 2304 x 2 bytes x 4 passes = 3.6 GB a rank a step, 18 ms
    # at the links' 200 GB/s
    assert kernels["exchange"][1] == 4 * 65536 * 0.75 * 2304 * 2 * 4
    assert kernels["exchange"][1] / 200e9 == pytest.approx(18.1e-3, rel=0.01)
    flops, moved = kernels["flash_window"]
    assert flops == 3 * 14 * count.kept_scores(SLIDING, 8192, 1024) * 4096
