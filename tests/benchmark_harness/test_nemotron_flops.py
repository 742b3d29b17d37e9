"""``flops/ssm_moe_decoder.py`` against counts made by hand for one
small shape, and at the published sizes of the
``nemotron-3-nano-30b-a3b-1chip`` cut."""

import os

import pytest

from benchmark.flops import ssm_moe_decoder as count
from tests.benchmark_harness import _common as common

TRAFFIC = {"seq_len": 64, "minibatch": 2}
NEMOTRON = os.path.join(
    common.REPO, "benchmark", "configs", "nemotron-3-nano-30b-a3b-1chip",
    "config.json")
CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 5,
    "hybrid_override_pattern": "MEM*EME",
    "mamba_num_heads": 4, "mamba_head_dim": 4, "ssm_state_size": 8,
    "n_groups": 2, "chunk_size": 32,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 6,
    "n_routed_experts": 2, "published": {"n_routed_experts": 8},
    "num_experts_per_tok": 2, "moe_intermediate_size": 10,
    "moe_shared_expert_intermediate_size": 20, "vocab_size": 100,
    "assumed": {"scan_segment": 1},
}


def test_per_sample_by_hand():
    # forward, one sequence of 64 tokens, 2 FLOPs a multiply-add; the
    # first five letters are built: M, E, M, *, E
    assert [count.count(CONFIG, kind) for kind in (
        "mamba", "experts", "attention")] == [2, 2, 1]
    # an M layer as ``test_granite_flops.py`` counts it at these sizes:
    # in 8 x (2 x 16 + 2 x 2 x 8 + 4) = 544 weights, out 128 -> 672
    projections, scan = 2 * 64 * 672, 100_352
    # the * layer: heads of 6 lanes (q wider than d: 12 over 8); q and o
    # 2 x 8 x 12, k and v 2 x 8 x 6 -> 288 weights; two score-sized
    # products over 2,080 kept pairs at 2 heads x 6 lanes
    attention_projections = 2 * 64 * 288
    attention = 2 * 2 * 2_080 * 12
    # an E layer: the router 8 x 8 over ALL the experts; the shared
    # expert TWO matrices of 8 x 20; the routed experts here, 2 choices
    # x 2 / 8 held x TWO matrices of 8 x 10
    router, shared = 2 * 64 * 64, 2 * 64 * 2 * 160
    held = 2 * 64 * 2 * 0.25 * 2 * 80
    head = 2 * 64 * 8 * 100
    assert count.held_share(CONFIG) == 0.25
    assert count.parts(CONFIG, TRAFFIC) == {
        "mamba_projections": 2 * projections, "ssd_scan": 2 * scan,
        "attention_projections": attention_projections,
        "attention": attention, "router": 2 * router,
        "shared_expert": 2 * shared, "held_experts": 2 * held,
        "head": head}
    forward = (2 * (projections + scan) + attention_projections + attention
               + 2 * (router + shared + held) + head)
    assert count.per_sample(CONFIG, TRAFFIC) == 3 * forward
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        count.count(dict(CONFIG, hybrid_override_pattern="M-M*E"), "mamba")


def test_kernels_by_hand():
    need = count.kernels(CONFIG, TRAFFIC)
    assert set(need) == {"flash", "ssd_scan", "relu2_gmm"}
    # flash at 2 / 1 heads of 6 lanes
    assert need["flash"] == (
        7 * 2 * 2_080 * 2 * 6, 2 * 64 * 6 * ((2 * 2 + 2) + (4 * 2 + 4)))
    # the scan in the two M layers, as granite's count has it: 400
    # bytes a token, a float32 state (4 x 4 x 8) a segment of one chunk
    assert need["ssd_scan"] == (
        2 * 3 * 100_352, 2 * (64 * (144 + 256) + 2 * 2 * 512))
    # the held experts' grouped matmuls in the two E layers: 3 x the
    # forward FLOPs; 6 calls each reading its rows (64 x 2 x 0.25 = 32
    # rows of 8 + 10 lanes) and the 2 held kernels of 8 x 10 (a step of
    # 2 samples reads them once), 2 bytes
    flops, nbytes = need["relu2_gmm"]
    assert flops == 2 * 3 * 2 * 64 * 2 * 0.25 * 2 * 80
    assert nbytes == 2 * 6 * 2 * (32 * 18 + 2 * 80 / 2)


def test_the_cut_at_its_published_sizes():
    config = common.load(NEMOTRON)
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s8k-b1.json"))
    assert [count.count(config, kind) for kind in (
        "mamba", "experts", "attention")] == [4, 4, 1]
    parts = count.parts(config, traffic)
    total = count.per_sample(config, traffic)
    assert total == pytest.approx(17.57e12, rel=1e-3)
    share = {name: 3 * value / total for name, value in parts.items()}
    # the mixers' projections 43%, the shared expert 22%, the held
    # experts 4% (1/16 of a model's routed work), attention 16% with
    # its projections, the head 12%, the scan's needed work 1.5%
    assert share["mamba_projections"] == pytest.approx(0.433, abs=2e-3)
    assert share["shared_expert"] == pytest.approx(0.223, abs=2e-3)
    assert share["held_experts"] == pytest.approx(0.042, abs=1e-3)
    assert share["attention"] + share["attention_projections"] == (
        pytest.approx(0.159, abs=2e-3))
    assert share["head"] == pytest.approx(0.123, abs=1e-3)
    assert share["ssd_scan"] == pytest.approx(0.0154, abs=1e-3)
    need = count.kernels(config, traffic)
    # the scan is bound by its bytes on a v5e, the held experts' matmuls
    # by their FLOPs at the NEEDED width: 1856, not the tiles' 1920
    flops, nbytes = need["ssd_scan"]
    assert nbytes / 819e9 > flops / 197e12
    flops, nbytes = need["relu2_gmm"]
    assert flops / 197e12 > nbytes / 819e9
    assert flops == 4 * 3 * 2 * 3072 * 2 * 2688 * 1856
