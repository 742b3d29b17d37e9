"""``benchmark/flops/dsa_moe_decoder.py`` (PR 51): Keye-VL-2.0's count
by hand at the configuration's own widths, ISSUE 51's arithmetic a
token, the parts' sum, what a cut changes and what it may not."""

import os

import pytest

from benchmark.flops import bd_moe_decoder, dsa_moe_decoder as F
from tests.benchmark_harness import _common as common

CONFIG = os.path.join(
    common.REPO, "benchmark", "configs", "keye-vl-2.0-30b-a3b-1chip",
    "config.json")
TRAFFIC = {"seq_len": 32768, "minibatch": 1}


@pytest.fixture(scope="module")
def config():
    return common.load(CONFIG)


def six_layers(config):
    """ISSUE 51's arithmetic is at six layers."""
    return dict(config, num_hidden_layers=6)


def test_a_token_s_forward_flops_by_hand(config):
    d, seq = 2048, 32768
    assert F.projection_flops(config) == 2 * (
        2 * d * 32 * 128 + 2 * d * 4 * 128)
    assert F.projection_flops(config) == pytest.approx(37.7e6, rel=2e-3)
    assert F.indexer_projection_flops(config) == 2 * d * (
        16 * 64 + 64 + 16)
    assert F.indexer_projection_flops(config) == pytest.approx(
        4.5e6, rel=2e-2)
    assert F.held_share(config) == 0.125
    assert F.expert_flops_per_position(config) == (
        2 * 8 * 0.125 * 3 * d * 768)
    # a query keeps min(2048, t + 1) keys: 1,984 on average
    kept = F.kept_scores(config, TRAFFIC)
    assert kept == sum(min(2048, t + 1) for t in range(seq)) == 65012736
    assert kept / seq == pytest.approx(1984.03, abs=0.01)
    assert F.causal_scores(TRAFFIC) == seq * (seq + 1) / 2
    # ISSUE 51's table, M FLOPs a token and layer forward
    parts = F.parts(six_layers(config), TRAFFIC)
    a_token = {k: v / 3 / seq / 6 / 1e6 for k, v in parts.items()}
    assert a_token["attention"] == pytest.approx(32.5, abs=0.05)
    assert a_token["indexer_scores"] == pytest.approx(33.6, abs=0.05)
    assert a_token["indexer_projections"] == pytest.approx(4.5, abs=0.05)
    assert a_token["projections_and_router"] + a_token[
        "held_experts"] == pytest.approx(37.7 + 10.0, abs=0.1)
    assert a_token["head"] * 6 == pytest.approx(77.8, abs=0.05)
    assert sum(a_token.values()) - a_token["head"] == pytest.approx(
        118.3, abs=0.2)
    assert F.per_sample(six_layers(config), TRAFFIC) == pytest.approx(
        77.4e12, rel=1e-3)
    assert F.per_sample(six_layers(config), TRAFFIC) / seq == pytest.approx(
        2.36e9, rel=2e-3)


def test_the_cell_s_count(config):
    parts = F.parts(config, TRAFFIC)
    total = F.per_sample(config, TRAFFIC)
    assert total == sum(parts.values())
    layers = config["num_hidden_layers"]
    six = F.per_sample(six_layers(config), TRAFFIC)
    assert total == pytest.approx(
        parts["head"] + (six - parts["head"]) * layers / 6, rel=1e-9)
    share = {k: v / total for k, v in parts.items()}
    # the mechanism is half the required work
    assert 0.48 < share["attention"] + share["indexer_scores"] < 0.56
    assert share["attention"] == pytest.approx(
        share["indexer_scores"], rel=0.05)
    # dense causal attention would be eight times the kept entries'
    dense = 4.0 * F.causal_scores(TRAFFIC) * 32 * 128
    assert dense / (parts["attention"] / 3 / layers) == pytest.approx(
        8.26, abs=0.01)
    # twice the tokens: the scores four times, the kept entries hardly
    # more than twice, everything else twice
    double = F.parts(config, {"seq_len": 65536, "minibatch": 1})
    assert double["indexer_scores"] == pytest.approx(
        4 * parts["indexer_scores"], rel=1e-4)
    assert double["attention"] == pytest.approx(
        2.0322 * parts["attention"], rel=1e-3)
    assert double["held_experts"] == 2 * parts["held_experts"]
    # a sequence no longer than topk keeps the whole causal prefix
    short = {"seq_len": 2048, "minibatch": 1}
    assert F.kept_scores(config, short) == F.causal_scores(short)


def test_the_kernels(config):
    kernels = F.kernels(config, TRAFFIC)
    assert set(kernels) == {"flash", "indexer_scores", "moe_experts"}
    layers, seq = config["num_hidden_layers"], 32768
    flops, moved = kernels["flash"]
    assert flops == layers * 7 * 2.0 * 65012736 * 32 * 128
    assert flops == pytest.approx(
        7 / 6 * F.parts(config, TRAFFIC)["attention"])
    # the kept entries' products bound it: 19 ms a layer of FLOPs over
    # 2 ms of bytes
    assert flops / layers / 197e12 == pytest.approx(18.9e-3, rel=0.01)
    assert flops / 197e12 > 5 * moved / 819e9
    flops, moved = kernels["indexer_scores"]
    assert flops == layers * 3 * 2.0 * (seq * (seq + 1) / 2) * 16 * 64
    assert flops == F.parts(config, TRAFFIC)["indexer_scores"]
    assert flops / layers / 197e12 == pytest.approx(16.7e-3, rel=0.01)
    assert flops / 197e12 > 50 * moved / 819e9
    # the held experts as SDAR's count has them, at this length
    sdar = dict(config, assumed=dict(config["assumed"], block_length=4))
    assert kernels["moe_experts"] == bd_moe_decoder.kernels(
        sdar, {"seq_len": seq // 2, "minibatch": 1})["moe_experts"]


def test_what_a_cut_changes_and_what_it_may_not(config):
    parts = F.parts(config, TRAFFIC)
    # a chip that held every expert would run eight times the experts
    whole = dict(config, num_experts=128)
    assert F.parts(whole, TRAFFIC)["held_experts"] == 8 * parts[
        "held_experts"]
    # the router scores ALL experts whatever this chip holds
    assert F.parts(whole, TRAFFIC)["projections_and_router"] == parts[
        "projections_and_router"]
    # the head follows the rows held, the layers the depth
    full = dict(config, vocab_size=151936)
    assert F.parts(full, TRAFFIC)["head"] == 8 * parts["head"]
    deeper = dict(config, num_hidden_layers=48)
    assert F.parts(deeper, TRAFFIC)["attention"] == pytest.approx(
        48 / config["num_hidden_layers"] * parts["attention"])
    # half the keys kept: the attention nearly halves, the scores stay
    half = dict(config, sa_config=dict(config["sa_config"], topk=1024))
    assert F.parts(half, TRAFFIC)["indexer_scores"] == parts[
        "indexer_scores"]
    assert F.parts(half, TRAFFIC)["attention"] == pytest.approx(
        0.508 * parts["attention"], rel=1e-2)
