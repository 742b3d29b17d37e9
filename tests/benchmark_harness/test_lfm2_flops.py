"""``benchmark/flops/conv_moe_decoder.py`` (PR 49): LFM2-8B-A1B's count
by hand at the configuration's own sizes, ISSUE 49's arithmetic a token,
the parts' sum, what a cut changes and what it may not."""

import os

import pytest

from benchmark.flops import conv_moe_decoder as F
from tests.benchmark_harness import _common as common

CONFIG = os.path.join(
    common.REPO, "benchmark", "configs", "lfm2-8b-a1b-1chip", "config.json")
TRAFFIC = {"seq_len": 32768, "minibatch": 1}


@pytest.fixture(scope="module")
def config():
    return common.load(CONFIG)


def six_layers(config):
    """ISSUE 49's cut: conv conv full conv conv conv."""
    return dict(config, num_hidden_layers=6)


def test_a_token_s_forward_flops_by_hand(config):
    d = 2048
    assert F.conv_flops_per_token(config) == 2 * (d * 3 * d + d * d)
    assert F.conv_flops_per_token(config) == pytest.approx(33.55e6, rel=1e-3)
    assert F.projection_flops_per_token(config) == 2 * (
        d * 32 * 64 + 2 * d * 8 * 64 + 32 * 64 * d)
    assert F.held_share(config) == 0.25
    assert F.expert_flops_per_token(config) == 2 * 4 * 0.25 * 3 * d * 1792
    # ISSUE 49's table at six layers, M FLOPs a token forward
    parts = F.parts(six_layers(config), TRAFFIC)
    a_token = {k: v / 3 / 32768 / 1e6 for k, v in parts.items()}
    want = {"conv_mixers": 167.8, "projections": 21.0, "flash": 134.2,
            "dense_mlp": 176.2, "held_experts": 88.1, "head": 67.1,
            "router": 0.5}
    assert a_token == pytest.approx(want, abs=0.06)
    assert sum(a_token.values()) == pytest.approx(654.9, abs=0.1)
    assert F.per_sample(six_layers(config), TRAFFIC) == pytest.approx(
        64.4e12, rel=2e-3)


def test_the_cell_s_count(config):
    """Eight layers: six conv and two attention, two dense and six
    expert layers."""
    assert [F.count(config, kind) for kind in (F.CONV, F.FULL)] == [6, 2]
    assert [F.count(config, dense=d) for d in (True, False)] == [2, 6]
    assert F.count(config, F.CONV, dense=True) == 2
    parts = F.parts(config, TRAFFIC)
    total = F.per_sample(config, TRAFFIC)
    assert total == sum(parts.values())
    assert total == pytest.approx(87.3e12, rel=2e-3)
    share = {k: v / total for k, v in parts.items()}
    assert share["flash"] == pytest.approx(0.302, abs=0.002)
    assert share["conv_mixers"] == pytest.approx(0.227, abs=0.002)
    assert share["dense_mlp"] == pytest.approx(0.198, abs=0.002)
    # twice the tokens: four times the flash, twice everything else
    double = F.parts(config, {"seq_len": 65536, "minibatch": 1})
    assert double["flash"] == pytest.approx(4 * parts["flash"], rel=1e-4)
    assert double["conv_mixers"] == 2 * parts["conv_mixers"]


def test_the_gate_s_bytes_and_the_kernels(config):
    kernels = F.kernels(config, TRAFFIC)
    assert set(kernels) == {
        "short_conv_gate", "short_conv_matmuls", "flash", "dense_mlp",
        "moe_experts", "head"}
    ops, moved = kernels["short_conv_gate"]
    # 4 elements forward and 7 backward a channel and token, 2 bytes
    assert moved == 2 * 11 * 32768 * 2048 * 6
    assert moved / 32768 / 6 == 22 * 2048  # 16 KB forward, 28 backward
    assert ops == 4 * 7 * 32768 * 2048 * 6
    # bytes bound it by far: 10.8 ms at the HBM's peak
    assert moved / 819e9 == pytest.approx(10.8e-3, rel=0.01)
    assert ops / 197e12 < 0.01 * moved / 819e9
    flops, moved = kernels["flash"]
    kept = 32768 * 32769 / 2
    assert flops == 2 * 7 * 2 * kept * 32 * 64
    assert moved == 2 * (2.0 * 32768 * 64 * (6 * 32 + 6 * 8))
    # FLOPs bound the flash kernels: 2.5 ms of bytes under 156 ms
    assert flops / 197e12 > 50 * moved / 819e9
    assert kernels["short_conv_matmuls"][0] == F.parts(
        config, TRAFFIC)["conv_mixers"]
    assert kernels["dense_mlp"][0] == F.parts(config, TRAFFIC)["dense_mlp"]
    assert kernels["moe_experts"][0] == F.parts(
        config, TRAFFIC)["held_experts"]
    assert kernels["head"][0] == F.parts(config, TRAFFIC)["head"]


def test_what_the_count_refuses_and_ignores(config):
    with pytest.raises(ValueError, match="layer_types names"):
        F.per_sample(dict(config, layer_types=["mamba"] * 8), TRAFFIC)
    # the entries past num_hidden_layers count for nothing
    longer = dict(config, layer_types=config["layer_types"][:8] + ["x"])
    assert F.per_sample(longer, TRAFFIC) == F.per_sample(config, TRAFFIC)
    # whether the head shares the embedding's matrix moves nothing
    untied = dict(config, assumed=dict(
        config["assumed"], tie_word_embeddings=False))
    assert F.per_sample(untied, TRAFFIC) == F.per_sample(config, TRAFFIC)
    # a chip that held every expert would run four times the experts
    whole = dict(config, num_experts=32)
    assert F.parts(whole, TRAFFIC)["held_experts"] == 4 * F.parts(
        config, TRAFFIC)["held_experts"]
