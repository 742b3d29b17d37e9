"""``flops/bd_moe_decoder.py`` against counts made by hand for one small
shape, and at the published sizes of the ``sdar-30b-a3b-1chip`` cut."""

import os

import pytest

from benchmark.flops import bd_moe_decoder
from tests.benchmark_harness import _common as common

CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 4, "num_experts": 2,
    "published": {"num_experts": 8}, "num_experts_per_tok": 4,
    "moe_intermediate_size": 4, "vocab_size": 100,
    "assumed": {"block_length": 4},
}
TRAFFIC = {"seq_len": 16, "minibatch": 2}


def entry(section, name):
    (found,) = [e for e in common.load(common.MANIFEST)[section]
                if e["name"] == name]
    return found


def test_per_sample_by_hand():
    # forward, one clean sequence of 16 tokens: 32 positions through
    # the layers, 2 FLOPs a multiply-add.
    # the mask keeps L^2 + L B = 256 + 64 = 320 score entries a head
    assert bd_moe_decoder.kept_scores(CONFIG, TRAFFIC) == 320
    # QK^T and PV over them at 4 heads of 4: 2 * (2 * 320 * 16)
    attention = 20_480
    # a position's projections: query and out 2 * (8 x 16) = 256
    # weights, key and value 2 * (8 x 2 x 4) = 128 -> 384
    assert bd_moe_decoder.projection_flops(CONFIG) == 768
    # the router over ALL 8 experts: 64 weights
    projections = 2 * 32 * (384 + 64)
    # 4 choices x 2 / 8 held = 1 routed expert of 3 * 8 * 4 = 96 weights
    assert bd_moe_decoder.held_share(CONFIG) == 0.25
    assert bd_moe_decoder.expert_flops_per_position(CONFIG) == 192
    experts = 32 * 192
    # the head on the L noisy positions alone
    head = 2 * 16 * 8 * 100
    parts = bd_moe_decoder.parts(CONFIG, TRAFFIC)
    assert parts == {
        "attention": 3 * 2 * attention,
        "projections_and_router": 3 * 2 * projections,
        "held_experts": 3 * 2 * experts, "head": 3 * head}
    # backward = 2 x forward; nothing recomputed; nothing for the noise,
    # the assembly, the norms, the sort, the gathers or the scatter
    assert bd_moe_decoder.per_sample(CONFIG, TRAFFIC) == 3 * (
        2 * (attention + projections + experts) + head) == 408_576


def test_kernels_by_hand():
    kernels = bd_moe_decoder.kernels(CONFIG, TRAFFIC)
    # flash: seven score-sized products over the KEPT entries, 2 layers
    assert kernels["flash"][0] == 2 * 7 * 2 * 320 * 16
    # a count over the causal half of the 32 positions would read more
    assert kernels["flash"][0] < 2 * 7 * 32 * 32 * 16
    # bytes over 32 positions: forward q, o at 4 heads and k, v at 2;
    # backward q, o, do, dq at 4 and k, v, dk, dv at 2; 2 bytes each
    assert kernels["flash"][1] == 2 * 2 * 32 * 4 * ((8 + 4) + (16 + 8))
    # the experts: 32 positions x 4 x 2 / 8 = 32 rows; nine products of
    # 2 x rows x 8 x 4; the two kernels' bytes shared by the minibatch
    assert kernels["moe_experts"][0] == 2 * 9 * 2 * 32 * 8 * 4
    assert kernels["moe_experts"][1] == 2 * 9 * 2 * (
        32 * 12 + 2 * 8 * 4 / 2)
    assert set(kernels) == {"flash", "moe_experts"}


def test_the_cell_s_count():
    """ISSUE 35's arithmetic at six layers: 35.8 TFLOP a sample, 55% of
    it attention under the mask."""
    config = common.load(os.path.join(
        common.REPO, entry("configs", "sdar-30b-a3b-1chip")["file"]))
    from benchmark.run import Files

    traffic = common.load(Files(common.MANIFEST).find(
        "traffic", entry("workloads", "sdar30b-bd-s8k")["traffic"] + ".json"))
    parts = bd_moe_decoder.parts(config, traffic)
    layers = config["num_hidden_layers"]
    assert parts["attention"] / layers == pytest.approx(3.30e12, rel=2e-3)
    assert parts["projections_and_router"] / layers == pytest.approx(
        11.29e12 / 6, rel=2e-3)
    assert parts["held_experts"] / layers == pytest.approx(
        2.78e12 / 6, rel=2e-3)
    assert parts["head"] == pytest.approx(1.91e12, rel=2e-3)
    total = bd_moe_decoder.per_sample(config, traffic)
    assert total == pytest.approx(sum(parts.values()))
    if layers == 6:
        assert total == pytest.approx(35.78e12, rel=1e-3)
        assert parts["attention"] / total == pytest.approx(0.553, abs=2e-3)
    flash_flops, flash_bytes = bd_moe_decoder.kernels(
        config, traffic)["flash"]
    # FLOPs bound the kernels at 197 TFLOP/s and 819 GB/s
    assert flash_flops / 197e12 > 10 * flash_bytes / 819e9
    assert flash_flops == pytest.approx(parts["attention"] * 7 / 6)
