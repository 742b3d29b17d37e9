"""``flops/window_moe_decoder.py`` against counts made by hand for one
small shape, a closed form, and at the published sizes of the
``laguna-xs.2-1chip`` cut."""

import os

import pytest

from benchmark.flops import window_moe_decoder as count
from tests.benchmark_harness import _common as common

FULL, SLIDING = "full_attention", "sliding_attention"
CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 3, "head_dim": 4,
    "num_key_value_heads": 1, "num_attention_heads": 2,
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING],
    "num_attention_heads_per_layer": [2, 3, 3, 3],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "sliding_window": 4, "intermediate_size": 12,
    "moe_intermediate_size": 4, "shared_expert_intermediate_size": 4,
    "num_experts": 2, "published": {"num_experts": 8},
    "num_experts_per_tok": 4, "vocab_size": 100,
}
TRAFFIC = {"seq_len": 16, "minibatch": 2}


def entry(section, name):
    (found,) = [e for e in common.load(common.MANIFEST)[section]
                if e["name"] == name]
    return found


def test_the_band_s_kept_entries():
    """S W - W (W - 1) / 2 against the enumeration, the window under,
    at and over the sequence; the causal half with its diagonal."""
    for seq, window in ((16, 4), (16, 1), (16, 16), (16, 40), (64, 24)):
        kept = sum(1 for q in range(seq) for k in range(seq)
                   if k <= q and q - k < window)
        assert count.kept_scores(SLIDING, seq, window) == kept
    assert count.kept_scores(FULL, 16, 4) == 16 * 17 / 2
    assert count.kept_scores(SLIDING, 32768, 512) == (
        32768 * 512 - 512 * 511 / 2)
    with pytest.raises(ValueError, match="layer_types names"):
        count.kept_scores("chunked_attention", 16, 4)


def test_per_sample_by_hand():
    # only the first three layers count: full + dense, two sliding + sparse
    assert count.layers_of(CONFIG) == [
        (FULL, 2, "dense"), (SLIDING, 3, "sparse"), (SLIDING, 3, "sparse")]
    # forward, one token, 2 FLOPs a multiply-add. A layer of H heads:
    # query and gate 8 x H x 8, key and value 8 x 4 each, out H x 4 x 8
    assert count.projection_flops(CONFIG, 2) == 2 * (128 + 64 + 64)
    assert count.projection_flops(CONFIG, 3) == 2 * (192 + 64 + 96)
    # the dense MLP 3 x 8 x 12; a sparse one: the router over ALL 8
    # experts, the shared expert, 4 choices x 2 / 8 held = 1 routed one
    assert count.held_share(CONFIG) == 0.25
    assert count.expert_flops_per_token(CONFIG) == 2 * 96
    assert count.mlp_flops_per_token(CONFIG, "dense") == 2 * 288
    assert count.mlp_flops_per_token(CONFIG, "sparse") == 2 * (64 + 96 + 96)
    # attention's two products over the kept entries: 136 a head in the
    # full layer, 16 x 4 - 6 = 58 in a sliding one
    assert count.attention_flops(CONFIG, FULL, 2, 16) == 4 * 136 * 2 * 4
    assert count.attention_flops(CONFIG, SLIDING, 3, 16) == 4 * 58 * 3 * 4
    parts = count.parts(CONFIG, TRAFFIC)
    assert parts["flash_full"] == 3 * 4352
    assert parts["flash_window"] == 3 * 2 * 2784
    assert parts["projections"] == 3 * 16 * (512 + 2 * 704)
    assert parts["dense_mlp"] == 3 * 16 * 576
    assert parts["held_experts"] == 3 * 16 * 2 * 192
    assert parts["router_and_shared"] == 3 * 16 * 2 * 320
    assert parts["head"] == 3 * 2 * 16 * 8 * 100
    assert count.per_sample(CONFIG, TRAFFIC) == sum(parts.values()) == (
        13056 + 16704 + 92160 + 27648 + 18432 + 30720 + 76800)
    with pytest.raises(ValueError, match="mlp_layer_types names"):
        count.mlp_flops_per_token(CONFIG, "hash")


def test_kernels_by_hand():
    kernels = count.kernels(CONFIG, TRAFFIC)
    # the 7 score-sized matmuls at each layer's own kept entries and
    # heads: 136 x 2 in the full layer, 58 x 3 in each sliding one
    assert kernels["flash_window"][0] == 2 * 7 * 2 * 58 * 3 * 4
    assert kernels["flash"][0] == kernels["flash_window"][0] + (
        7 * 2 * 136 * 2 * 4)
    # bytes: q, o (forward), q, o, do, dq (backward) at H heads; k, v
    # and k, v, dk, dv at the one kv head; 2 bytes an element
    assert kernels["flash_window"][1] == 2 * 2 * 16 * 4 * (6 * 3 + 6 * 1)
    assert kernels["flash"][1] == kernels["flash_window"][1] + (
        2 * 16 * 4 * (6 * 2 + 6 * 1))
    # the experts: two sparse layers, 16 held rows each
    assert kernels["moe_experts"][0] == 2 * 3 * 16 * 192
    assert kernels["moe_experts"][1] == 2 * 9 * 2 * (16 * 12 + 2 * 32 / 2)


def test_the_cell_s_count_at_the_published_sizes():
    config = common.load(os.path.join(
        common.REPO, entry("configs", "laguna-xs.2-1chip")["file"]))
    traffic = common.load(os.path.join(
        common.REPO, "benchmark", "traffic", "s32k-b1.json"))
    assert config["flops"] == "window_moe_decoder"
    assert count.layers_of(config) == [
        (FULL, 48, "dense"), (SLIDING, 64, "sparse"),
        (SLIDING, 64, "sparse"), (SLIDING, 64, "sparse"),
        (FULL, 48, "sparse")]
    # ISSUE 42's parameter counts a layer (W_qg, W_k, W_v, W_o)
    assert count.projection_flops(config, 48) == 2 * 41_943_040
    assert count.projection_flops(config, 64) == 2 * 54_525_952
    parts = count.parts(config, traffic)
    seq = 32768
    # the closed form: 6 S (S + 1) H D for the two full layers, 79
    # TFLOP; 3 layers x 12 x kept x 64 x 128 for the band, 4.9 TFLOP
    assert parts["flash_full"] == 2 * 6 * seq * (seq + 1) * 48 * 128
    assert parts["flash_full"] == pytest.approx(79.2e12, rel=2e-3)
    kept = seq * 512 - 512 * 511 / 2
    assert parts["flash_window"] == 3 * 12 * kept * 64 * 128
    assert parts["flash_window"] == pytest.approx(4.91e12, rel=2e-3)
    # were the band ignored the three layers would need 32 times that
    ignored = 3 * 6 * seq * (seq + 1) * 64 * 128
    assert 31 < ignored / parts["flash_window"] < 33
    per_sample = count.per_sample(config, traffic)
    assert per_sample == sum(parts.values())
    share = config["num_experts"] / 256
    rest = 6 * seq * (
        2 * 41_943_040 + 3 * 54_525_952 + 3 * 2048 * 8192
        + 4 * (2048 * 256 + 3 * 2048 * 512 + 8 * share * 3 * 2048 * 512)
        + 2048 * 12544)
    assert per_sample == pytest.approx(
        parts["flash_full"] + parts["flash_window"] + rest, rel=1e-9)
    # the full layers' flash is about half of the step, the band 3%
    assert 0.45 < parts["flash_full"] / per_sample < 0.55
    assert 0.025 < parts["flash_window"] / per_sample < 0.035
    kernels = count.kernels(config, traffic)
    # bytes bound the band's kernels: 3 layers x 2 x S x 128 x (6 x 64
    # + 6 x 8) bytes, 13 ms at the HBM's peak against 29 at the MXU's
    flops, moved = kernels["flash_window"]
    assert moved == 3 * 2 * seq * 128 * 432
    assert flops == 3 * 14 * kept * 64 * 128
    assert flops / 197e12 > moved / 819e9
    assert flops / 197e12 == pytest.approx(29.1e-3, rel=0.01)
