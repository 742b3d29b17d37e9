"""``flops/ssm_dense_decoder.py`` against counts made by hand for one
small shape, and at the published sizes of the
``granite-4.0-h-micro-1chip`` cut."""

import os

import pytest

from benchmark.flops import ssm_dense_decoder as count
from tests.benchmark_harness import _common as common
from tests.benchmark_harness.test_granite_metrics import CONFIG

TRAFFIC = {"seq_len": 64, "minibatch": 2}
GRANITE = os.path.join(
    common.REPO, "benchmark", "configs", "granite-4.0-h-micro-1chip",
    "config.json")


def test_per_sample_by_hand():
    # forward, one sequence of 64 tokens, 2 FLOPs a multiply-add; d 8, 4
    # Mamba heads of 4 lanes (inner 16) over a state of 8, 2 groups.
    # the first four of the six listed layers are built: mamba, mamba,
    # attention, mamba
    assert count.count(CONFIG, "mamba") == 3
    assert count.count(CONFIG, "attention") == 1
    assert count.mamba_dims(CONFIG) == (4, 4, 8, 2, 32)
    # a Mamba layer's projections: in 8 x (2 x 16 + 2 x 2 x 8 + 4) = 8 x
    # 68 = 544 weights, out 16 x 8 = 128 -> 672 -> 2 * 64 * 672
    projections = 86_016
    assert count.mamba_projection_flops(CONFIG) * 64 == projections
    # the chunked scan, 2 chunks of 32: 32 x 33 / 2 = 528 pairs on and
    # below the diagonal; M once a group 2 x 528 x 2 x 8 = 16,896; the
    # masked product a head 4 x 528 x 2 x 4 = 16,896; the states in and
    # out 4 x 2 x (2 x 32 x 4 x 8) = 16,384 -> 50,176 a chunk
    scan = 2 * 50_176
    assert count.scan_flops(CONFIG, 64) == scan == 100_352
    # a chunk the sequence does not fill is a whole chunk
    assert count.scan_flops(CONFIG, 65) == 3 * 50_176
    # the attention layer: heads of 8 / 2 = 4 lanes; q and o 2 x 8 x 8,
    # k and v 2 x 8 x 4 -> 192 weights
    assert count.head_dim(CONFIG) == 4
    attention_projections = 2 * 64 * 192
    assert count.attention_projection_flops(CONFIG) * 64 == (
        attention_projections)
    # causal attention over the kept pairs, 64 x 65 / 2 = 2,080, two
    # products of 2 heads x 4 lanes: 2 x 2 x 2,080 x 8
    attention = 66_560
    assert count.attention_flops(CONFIG, 64) == attention
    # the MLP 3 x 8 x 12 = 288 weights, in all four layers; the tied
    # head once
    mlp, head = 2 * 64 * 288, 2 * 64 * 8 * 100
    parts = count.parts(CONFIG, TRAFFIC)
    assert parts == {
        "mamba_projections": 3 * projections, "ssd_scan": 3 * scan,
        "attention_projections": attention_projections,
        "attention": attention, "dense_mlp": 4 * mlp, "head": head}
    forward = (3 * (projections + scan) + attention_projections + attention
               + 4 * mlp + head)
    assert forward == 900_096
    # backward = 2 x forward; nothing recomputed; nothing for the
    # convolution, the decays, the norms, the gate, the multipliers
    assert count.per_sample(CONFIG, TRAFFIC) == 3 * forward
    with pytest.raises(ValueError, match="layer_types"):
        count.count(dict(CONFIG, layer_types=["conv"] * 4), "mamba")


def test_kernels_by_hand():
    need = count.kernels(CONFIG, TRAFFIC)
    assert set(need) == {"flash", "ssd_scan"}
    # flash: 7 score-sized matmuls over the kept pairs at 2 heads of 4
    # lanes; q, o (2 heads), k, v (1 head) forward, q, o, do, dq and k,
    # v, dk, dv backward, 2 bytes an element
    assert need["flash"] == (
        7 * 2 * 2_080 * 2 * 4, 2 * 64 * 4 * ((2 * 2 + 2) + (4 * 2 + 4)))
    # the scan: 3 x its forward FLOPs in the three Mamba layers; a
    # token's operands x (16 lanes x 2 bytes), dt (4 heads x 4), B and C
    # (2 groups x 8 x 2 bytes each) = 112 bytes, y or dy 32: forward 144,
    # backward 144 + 112 = 256; a float32 state (4 x 4 x 8) a segment of
    # one chunk, written and read: 2 x 2 x 512
    flops, nbytes = need["ssd_scan"]
    assert flops == 3 * 3 * 100_352
    assert nbytes == 3 * (64 * (144 + 256) + 2 * 2 * 512)


def test_the_cut_at_its_published_sizes():
    config = common.load(GRANITE)
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s8k-b1.json"))
    assert count.count(config, "mamba") == 9
    assert count.count(config, "attention") == 1
    # 3.18 MFLOP a token and Mamba layer forward in the scan
    assert count.scan_flops(config, 8192) / 8192 == pytest.approx(
        3.18e6, rel=2e-3)
    parts = count.parts(config, traffic)
    total = count.per_sample(config, traffic)
    assert total == pytest.approx(39.47e12, rel=1e-3)
    share = {name: 3 * value / total for name, value in parts.items()}
    # the mixers' projections 29%, the MLPs 63%, the scan's needed work
    # under 2%, the head 3%
    assert share["mamba_projections"] == pytest.approx(0.289, abs=2e-3)
    assert share["dense_mlp"] == pytest.approx(0.627, abs=2e-3)
    assert share["ssd_scan"] == pytest.approx(0.0178, abs=1e-3)
    assert share["head"] == pytest.approx(0.032, abs=1e-3)
    # the scan is bound by its bytes on a v5e: 3.34 GB a sample over 819
    # GB/s against 0.70 TFLOP over 197 TFLOP/s
    flops, nbytes = count.kernels(config, traffic)["ssd_scan"]
    assert nbytes / 819e9 > flops / 197e12
    assert nbytes == pytest.approx(3.34e9, rel=2e-3)
