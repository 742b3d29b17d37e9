"""``flops/dense_decoder.py`` against counts made by hand for one small
shape, and the preset's second family's count."""

from benchmark.flops import dense_decoder as flops

CONFIG = {
    "hidden_size": 8, "num_hidden_layers": 3, "intermediate_size": 32,
    "vocab_size": 100, "num_attention_heads": 2,
}
TRAFFIC = {"seq_len": 16}


def test_dense_decoder_by_hand():
    # forward, one sequence of 16 tokens, 2 FLOPs a multiply-add:
    # a layer's projections: q, k, v, out = 4 * 8*8 weights, mlp
    # 2 * 8*32 -> 768 weights -> 2 * 16 * 768 = 24,576
    # a layer's attention: QK^T and PV, 2 * (2 * 16*16*8), halved for
    # the causal mask -> 4,096
    # head: 2 * 16 * 8 * 100 = 25,600
    forward = 3 * (24_576 + 4_096) + 25_600
    assert forward == 111_616
    # backward = 2 x forward; nothing recomputed; no embedding gather
    assert flops.per_sample(CONFIG, TRAFFIC) == 3 * forward


def test_flash_kernels_by_hand():
    # one unit = S^2 * D * H over the causal half = 16*16*4*2 = 2,048
    assert flops.flash_attention_flops(16, 2, 4, backward=False) == 4_096
    assert flops.flash_attention_flops(16, 2, 4, backward=True) == 10_240
    # q, k, v, o (4 tensors of 16*2*4 bf16 = 256 B); backward 8
    assert flops.flash_attention_bytes(16, 2, 4, backward=False) == 1_024
    assert flops.flash_attention_bytes(16, 2, 4, backward=True) == 2_048
    need_flops, need_bytes = flops.kernels(CONFIG, TRAFFIC)["flash"]
    assert need_flops == 3 * (4_096 + 10_240)
    assert need_bytes == 3 * (1_024 + 2_048)
    # the forward count is the attention term of the dense count
    assert flops.flash_attention_flops(16, 2, 4, False) == 4_096


def test_published_config_matches_the_issue():
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(
        here, "..", "..", "benchmark", "configs", "pythia-1b",
        "config.json")
    with open(path) as f:
        config = json.load(f)
    per_token = flops.per_sample(config, {"seq_len": 2048}) / 2048
    # ISSUE 22: 5.9 GFLOP a token at 16 layers and 2k
    assert abs(per_token / 1e9 - 5.85) < 0.01


def test_a_second_family_s_count_is_a_file_found_by_name():
    from benchmark.run import Files
    from tests.benchmark_harness import _common as common

    files = Files(common.PRESET)
    config = common.load(files.find(
        "configs", "tiny-deepfm", "config.json"))
    count = files.module("flops", config["flops"])
    # tower 312 -> 64 -> 32 -> 1 and the FM term, by hand:
    # 2 * (312*64 + 64*32 + 32*1) = 44,096; FM 3 * 39 * 8 = 936
    assert count.per_sample(config, {}) == 3 * (44_096 + 936)
    assert not hasattr(count, "kernels")
