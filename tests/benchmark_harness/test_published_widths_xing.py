"""The ``xing4.0-29b-a4b-1chip`` configuration against the published
values of ITS source, XingChen-AGI/Xing4.0-29B-A4B's ``config.json``
(the model-configs catalog's row): every width as published, the four
reducible keys under their ceilings and at or over the guide's floors,
what was assumed, and the cell's files, found BY NAME (a later
``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "xing4.0-29b-a4b-1chip"
CELL = "xing4-29b-s4k"
SOURCE = ("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
          "config.json")
# the catalog row's ``config``, whole
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}
# the keys a configuration may reduce, with the source's value as the
# ceiling; every other key of the row is a width or a rule of the block
REDUCIBLE = {"num_hidden_layers": 40, "first_k_dense_replace": 2,
             "n_routed_experts": 64, "vocab_size": 131072}
NEW_METRICS = ("mhc_time_share", "mhc_mix_roofline", "mtp_time_share")


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


def test_the_cuts_are_under_their_ceilings_and_at_the_floors():
    config, listed = body(), entry()
    assert config["source"] == listed["source"] == SOURCE
    assert sorted(listed["reduced"]) == sorted(config["reduced"]) == sorted(
        REDUCIBLE)
    assert config["published"] == REDUCIBLE
    differ = sorted(k for k, v in CATALOG.items() if config[k] != v)
    assert differ == sorted(REDUCIBLE)
    # the guide's floors: the leading dense layers once, four of the
    # layers that follow them, 8 routed experts, an eighth of the
    # vocabulary; the deployment's eighth of the experts
    assert config["first_k_dense_replace"] == 1
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 4
    assert config["n_routed_experts"] * 8 == 64
    assert config["held_experts"] == [0, config["n_routed_experts"]]
    assert config["vocab_size"] * 8 == 131072
    # the rule kept the prediction module: it is no cut
    assert config["num_nextn_predict_layers"] == 1
    rows = config["expert_rows"]
    assert rows["held_rows"] % 512 == 0  # the grouped matmul's row tiles
    expected = 4096 * 4 * config["n_routed_experts"] // 64
    assert rows["expected_held_pairs"] == expected == 2048
    assert expected < rows["busiest_step_held_pairs"] < rows["held_rows"]


def test_the_file_states_what_was_assumed_and_where_it_departs():
    config = body()
    assumed = config["assumed"]
    assert assumed["mtp_loss_weight"] == 0.1
    assert assumed["bias_update_speed"] == 0.001
    assert assumed["embedding_init_std"] == 1.0
    assert assumed["lr_warmup_steps"] == 2000
    # every item ISSUE 37 names, each with its reason
    for key in ("mtp_loss_weight_source", "mtp_hidden", "bias_update_speed_source",
                "sequence_balance_loss", "hc_initial_values",
                "hc_norm_weight", "hc_sinkhorn_order", "hc_expand_and_reduce",
                "optimizer", "parameter_dtype", "config_json_recalled"):
        assert assumed[key], key
    assert "rows before columns" in assumed["hc_sinkhorn_order"]
    assert "copied" in assumed["hc_expand_and_reduce"]
    assert "sum" in assumed["hc_expand_and_reduce"]
    assert "no weight" in assumed["hc_norm_weight"]
    departs = " ".join(d["what"] for d in config["departs"])
    assert all(d["what"] and d["effect"] for d in config["departs"])
    for word in ("halves", "one block of six", "router", "experts 0-7",
                 "bfloat16", "dropout"):
        assert word in departs, word
    deployment = config["deployment"]
    assert "8 chips share each layer" in deployment["share"]
    assert "464 GB" in deployment["slice"]
    # what the rule tried, with the compiler's bytes for every rung
    tried = deployment["tried"]
    (chosen,) = [t for t in tried if t.get("chosen")]
    assert chosen["num_nextn_predict_layers"] == 1
    assert all(t["compiler_bytes"] and t["parameters"] for t in tried)
    assert chosen["compiler_bytes"] < 15.75 * 2**30
    with_module = [t for t in tried if t["num_nextn_predict_layers"] == 1]
    assert {t["remat_policy"] for t in with_module} == {
        "none", "dots", "flash", "full"}
    # the first policy of the rule's order that fits
    order = ["none", "dots", "flash", "full"]
    fits = [t for t in with_module if t["compiler_bytes"] < 15.75 * 2**30]
    assert chosen["remat_policy"] == min(
        (t["remat_policy"] for t in fits), key=order.index)
    assert chosen["parameters"] == 913_473_348
    assert config["flops"] == "hc_mla_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    for part in ("p_res", "q_down", "mtp_proj", "router", "w_gate",
                 "wte/embedding"):
        assert any(part in leaf for leaf in leaves), part
    layers = config["num_hidden_layers"]
    assert all(int(leaf.split("/")[0].split("_")[1]) < layers
               for leaf in leaves if leaf.startswith("block_"))


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s4k-b1", 1)
    # the quarter rule: four-chip cells are at most a quarter
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert all(len(e["why"]) <= 200 for e in (cell, entry()))
    assert "2,048 under 8 data shards" in cell["why"]
    traffic = common.load(files.find("traffic", "s4k-b1.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["records"], traffic["zipf_a"]) == (
                "zipf_tokens", 4096, 1, 256, 1.2)
    workload = common.load(files.find("workloads", CELL + ".json"))
    assert (workload["mesh"], workload["log_every"],
            workload["steps_per_task"], workload["warmup_steps"],
            workload["last_positions"]) == ("", 8, 8, 16, 512)
    (chosen,) = [t for t in body()["deployment"]["tried"] if t.get("chosen")]
    assert workload["model_params"]["remat_policy"] == chosen["remat_policy"]
    assert workload["trace_steps"] >= 2
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert files.find("metrics", name + ".py")
    assert files.find("flops", "hc_mla_moe_decoder.py")
    for name in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[name]))
