"""The ``kimi-linear-48b-a3b-1chip`` configuration against the published
values of ITS source, moonshotai/Kimi-Linear-48B-A3B-Instruct's
``config.json`` (the model-configs catalog's row): every width as
published, the three reducible keys under their ceilings and over the
guide's floors, and the cell's files, found BY NAME (a later
``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "kimi-linear-48b-a3b-1chip"
CELL = "kimi-linear48b-s32k"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")
# the catalog row's ``config``, whole
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}
# the keys a configuration may reduce, with the source's value as the
# ceiling; every other key of the row is a width or a rule of the block
REDUCIBLE = {"num_hidden_layers": 27, "num_experts": 256,
             "vocab_size": 163840}
NEW_METRICS = ("kda_time_share", "kda_scan_share", "kda_scan_roofline",
               "nope_mla_time_share")


def entry():
    (found,) = [c for c in common.load(common.MANIFEST)["configs"]
                if c["name"] == NAME]
    return found


def body():
    return common.load(os.path.join(common.REPO, entry()["file"]))


@pytest.mark.parametrize(
    "key", sorted(set(CATALOG) - set(REDUCIBLE)))
def test_every_published_value_is_the_file_s(key):
    assert body()[key] == CATALOG[key]
    assert key not in entry()["reduced"]


def test_the_cuts_are_under_their_ceilings_and_over_the_floors():
    config, listed = body(), entry()
    assert config["source"] == listed["source"] == SOURCE
    assert listed["reduced"] == config["reduced"] == list(REDUCIBLE)
    assert config["published"] == REDUCIBLE
    differ = sorted(k for k, v in CATALOG.items() if config[k] != v)
    assert differ == sorted(REDUCIBLE)
    # the leading dense layer and the four that follow: a whole period
    # (KDA, KDA, KDA, latent) and one more KDA layer
    assert config["num_hidden_layers"] == 5
    linear = config["linear_attn_config"]
    kinds = ["kda" if i in linear["kda_layers"] else "full"
             for i in range(1, 6)]
    assert kinds == ["kda", "kda", "kda", "full", "kda"]
    assert 4 in linear["full_attn_layers"]
    # the guide's floors: 8 routed experts, an eighth of the vocabulary
    assert config["num_experts"] == 8
    assert config["held_experts"] == [0, 8]
    assert config["vocab_size"] * 8 == 163840
    rows = config["expert_rows"]
    assert rows["held_rows"] % 512 == 0  # the grouped matmul's row tiles
    expected = 32768 * 8 * 8 // 256
    assert rows["expected_held_pairs"] == expected == 8192
    assert expected < rows["busiest_step_held_pairs"] < rows["held_rows"]


def test_the_file_states_what_was_assumed_and_where_it_departs():
    config = body()
    assumed = config["assumed"]
    assert assumed["kda_gate_rank"] == config["linear_attn_config"][
        "head_dim"] == 128
    assert assumed["kda_chunk"] == 64
    assert assumed["aux_loss_alpha"] == assumed["bias_update_speed"] == 0.001
    assert assumed["embedding_init_std"] == 1.0
    for key in ("kda_gate_rank_source", "kda_chunk_source",
                "kda_segment_source", "A_log_dt_bias", "kda_regime",
                "aux_loss_alpha_source", "bias_update_speed_source",
                "routing", "optimizer", "parameter_dtype",
                "config_json_recalled"):
        assert assumed[key], key
    assert "(1, 16)" in assumed["A_log_dt_bias"]
    departs = " ".join(d["what"] for d in config["departs"])
    assert all(d["what"] and d["effect"] for d in config["departs"])
    for word in ("q | k | v", "chunks of 64", "sub-blocks of 8", "router",
                 "experts 0-7", "dropout", "absorbed"):
        assert word in departs, word
    deployment = config["deployment"]
    assert "32 chips share each layer" in deployment["share"]
    assert "eight ways" in deployment["share"]
    assert "786 GB" in deployment["slice"]
    # what the rule tried, with the compiler's verdicts
    tried = deployment["tried"]
    assert [(t["remat_policy"], t["kda_segment"]) for t in tried] == [
        ("flash", 128), ("full", 128), ("flash", 64)]
    assert all(t["compiler"] for t in tried)
    (chosen,) = [t for t in tried if t.get("chosen")]
    assert chosen["kda_segment"] == assumed["kda_segment"] == 64
    assert chosen["compiler_bytes"] < 15.75 * 2**30
    assert config["flops"] == "kda_mla_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    for part in ("A_log", "dt_bias", "f_down", "conv_kernel", "kv_down",
                 "router", "w_gate"):
        assert any(part in leaf for leaf in leaves), part


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s32k-b1", 1)
    # the quarter rule: four-chip cells are at most a quarter
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert all(len(e["why"]) <= 200 for e in (cell, entry()))
    for word in ("1/32", "4 layers of 5", "20 of 27", "guards"):
        assert word in cell["why"], word
    workload = common.load(files.find("workloads", CELL + ".json"))
    warm = common.load(files.find("workloads", "qwen3next80b-s32k.json"))
    assert (workload["mesh"], workload["log_every"],
            workload["steps_per_task"], workload["warmup_steps"]) == (
                "", 1, warm["steps_per_task"], warm["warmup_steps"])
    assert workload["last_positions"] == 512
    assert workload["reference_remat"] is True
    assert workload["model_params"]["remat_policy"] == "flash"
    assert workload["trace_steps"] == 2
    # the four new metrics, this cell's only
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        metric = by_name[name]
        assert metric["workloads"] == [CELL], name
        assert metric["moves"] == "samples_per_s"
        assert files.find("metrics", name + ".py")
    assert files.find("flops", body()["flops"] + ".py")
    assert files.find("lib", "kda_trace.py")
    for part in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[part])), part
