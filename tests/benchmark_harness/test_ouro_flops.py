"""``benchmark/flops/looped_dense_decoder.py`` (PR 55): Ouro's count by
hand at the published widths, ISSUE 55's arithmetic at eight layers and
its three shares, the flash kernels' ``T x layers`` applications, and
one pass against ``dense_decoder.py``'s count."""

import os

import pytest

from benchmark.flops import dense_decoder, looped_dense_decoder as F
from tests.benchmark_harness import _common as common

CONFIG = os.path.join(
    common.REPO, "benchmark", "configs", "ouro-2.6b-1chip", "config.json")
TRAFFIC = {"seq_len": 16384, "minibatch": 1}


@pytest.fixture(scope="module")
def config():
    return common.load(CONFIG)


def eight_layers(config):
    """ISSUE 55's arithmetic is at eight layers."""
    return dict(config, num_hidden_layers=8)


def test_a_step_by_hand_at_eight_layers(config):
    d, seq, vocab, passes = 2048, 16384, 49152, 4
    matrices = 4 * d * d + 3 * d * 5632
    assert matrices == 51_380_224
    parts = F.parts(eight_layers(config), TRAFFIC)
    forward = {name: value / 3 for name, value in parts.items()}
    assert forward["projections"] == 2 * matrices * seq * 32
    assert forward["projections"] == pytest.approx(53.9e12, rel=1e-3)
    assert forward["attention"] == 2 * seq ** 2 * d * 32
    assert forward["attention"] == pytest.approx(35.2e12, rel=1e-3)
    assert forward["heads"] == 2 * seq * d * vocab * passes
    assert forward["heads"] == pytest.approx(13.2e12, rel=1e-3)
    assert forward["gates"] == 2 * seq * d * passes
    total = F.per_sample(eight_layers(config), TRAFFIC)
    assert total == sum(parts.values())
    # ISSUE 55: 306.9 TFLOP a step (its 51.38 M a layer counts the norms'
    # scales, which multiply nothing)
    assert total == pytest.approx(306.9e12, rel=1e-3)
    shares = {name: value / total for name, value in parts.items()}
    assert shares["projections"] == pytest.approx(0.527, abs=0.001)
    assert shares["attention"] == pytest.approx(0.344, abs=0.001)
    assert shares["heads"] == pytest.approx(0.129, abs=0.001)
    assert shares["gates"] < 1e-5


def test_the_cell_s_own_count(config):
    """At the depth the configuration has: the blocks' parts scale with
    it, the heads' do not, so their share is above eight layers'."""
    layers = config["num_hidden_layers"]
    parts, at_eight = F.parts(config, TRAFFIC), F.parts(
        eight_layers(config), TRAFFIC)
    for name in ("projections", "attention"):
        assert parts[name] == pytest.approx(at_eight[name] * layers / 8)
    assert parts["heads"] == at_eight["heads"]
    total = F.per_sample(config, TRAFFIC)
    assert parts["heads"] / total >= 0.129
    # 6 N D would count a block's parameter once a token
    six_n_d = 6 * TRAFFIC["seq_len"] * (
        layers * 51_380_224 + 2 * 2048 * 49152)
    assert total > 2.5 * six_n_d


def test_flash_is_counted_over_every_application(config):
    (flops, moved) = F.kernels(config, TRAFFIC)["flash"]
    applications = 4 * config["num_hidden_layers"]
    one_flops = sum(dense_decoder.flash_attention_flops(
        16384, 16, 128, b) for b in (0, 1))
    one_bytes = sum(dense_decoder.flash_attention_bytes(
        16384, 16, 128, b) for b in (0, 1))
    assert flops == applications * one_flops
    assert moved == applications * one_bytes
    assert F.kernels(eight_layers(config), TRAFFIC)["flash"][0] == (
        32 * one_flops)
    # forward 2 and backward 5 units of S^2 D over the causal half
    assert one_flops == 7 * 16384.0 ** 2 * 2048
    # FLOPs bound it at head 128 and 16,384 positions
    assert flops / 197e12 > moved / 819e9
    assert set(F.kernels(config, TRAFFIC)) == {"flash"}
    # attention's part of the step is the kernels' 2 + 1 x 2 units
    assert F.parts(config, TRAFFIC)["attention"] == pytest.approx(
        flops * 6 / 7)


def test_one_pass_is_the_dense_count_but_for_the_third_matrix(config):
    """``T = 1``: ``dense_decoder``'s count at the same sizes (whose MLP
    has two matrices), plus the SwiGLU's third and the gate."""
    once = dict(config, total_ut_steps=1)
    d, seq, layers = 2048, 16384, config["num_hidden_layers"]
    dense = dense_decoder.per_sample(once, TRAFFIC)
    third = 3.0 * layers * 2 * seq * d * config["intermediate_size"]
    assert F.per_sample(once, TRAFFIC) == pytest.approx(
        dense + third + 3.0 * 2 * seq * d)
    assert F.per_sample(config, TRAFFIC) == pytest.approx(
        4 * F.per_sample(once, TRAFFIC))


def test_the_harness_finds_the_count_by_the_configuration_s_name(config):
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    assert config["flops"] == "looped_dense_decoder"
    module = files.module("flops", config["flops"])
    assert module.per_sample(config, TRAFFIC) == F.per_sample(
        config, TRAFFIC)
    with open(files.find("flops", "looped_dense_decoder.py")) as f:
        source = f.read()
    assert "from benchmark.flops.dense_decoder import" in source
