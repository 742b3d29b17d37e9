"""The ``moonlight-16b-a3b-1chip`` configuration against the published
values of ITS source, moonshotai/Moonlight-16B-A3B's ``config.json``
(the model-configs catalog's row): every width as published, the two
reducible keys under their ceilings, and the cell's files.
``test_published_widths.py``'s table is keyed by source and lives in a
file only a ``benchmark`` issue may edit, so its parametrised case for
this configuration is red until that issue adds this table there
(PERF.md Section 7)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "moonlight-16b-a3b-1chip"
SOURCE = ("https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
          "config.json")
# the catalog row's ``config``, whole
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
}
# the keys a configuration may reduce, with the source's value as the
# ceiling; every other key of the row is a width or a rule of the block
REDUCIBLE = {"num_hidden_layers": 27, "vocab_size": 163840}
# the vocabulary shares ISSUE 29 allows: a four-, eight- or sixteen-way
# split of wte and lm_head
VOCABULARY_SHARES = (40960, 20480, 10240)


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


def test_the_cuts_are_depth_and_vocabulary_under_their_ceilings():
    config, listed = body(), entry()
    assert config["source"] == listed["source"] == SOURCE
    assert sorted(listed["reduced"]) == sorted(config["reduced"]) == sorted(
        REDUCIBLE)
    assert config["published"] == REDUCIBLE
    differ = sorted(k for k, v in CATALOG.items() if config[k] != v)
    assert differ == sorted(REDUCIBLE)
    # one whole period: the leading dense layer once and one expert layer
    assert config["num_hidden_layers"] == 2 < 27
    assert config["first_k_dense_replace"] == 1
    assert config["vocab_size"] in VOCABULARY_SHARES
    assert 163840 % config["vocab_size"] == 0


def test_the_file_states_what_was_assumed_and_where_it_departs():
    config = body()
    assumed = config["assumed"]
    assert assumed["aux_loss_alpha"] == 0.001
    assert assumed["bias_update_speed"] == 0.001
    for key in ("aux_loss_alpha_source", "bias_update_speed_source",
                "optimizer", "parameter_dtype", "config_json_recalled"):
        assert assumed[key]
    assert "Muon" in assumed["optimizer"]
    departs = " ".join(d["what"] for d in config["departs"])
    assert all(d["what"] and d["effect"] for d in config["departs"])
    for word in ("rotary", "router", "dropout"):
        assert word in departs, word
    deployment = config["deployment"]
    assert isinstance(deployment, dict)
    # every vocabulary share tried, with the compiler's bytes
    tried = {t["vocab_size"]: t for t in deployment["vocabulary_tried"]}
    assert set(tried) == set(VOCABULARY_SHARES)
    assert all(t["compiler_bytes"] for t in tried.values())
    assert deployment["slice"] and deployment["share"]
    assert config["flops"] == "mla_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    assert set(config["check_leaves"]) >= {
        "wte/embedding", "block_0/attn/kv_down/kernel",
        "block_0/mlp_gate/kernel", "block_1/attn/kv_up/kernel",
        "block_1/moe_mlp/router/kernel", "block_1/moe_mlp/w_gate",
        "block_1/moe_mlp/shared_gate/kernel"}


def test_the_new_cell_s_files():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"]
               if w["name"] == "moonlight16b-s8k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8k-b2", 1)
    assert manifest["workloads"][-1] == cell
    assert manifest["configs"][-1]["name"] == NAME
    # the quarter rule: one four-chip cell of five
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(manifest["workloads"]) == 5 and len(four) == 1
    assert all(len(e["why"]) <= 200 for e in (cell, entry()))
    traffic = common.load(files.find("traffic", "s8k-b2.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["records"], traffic["zipf_a"]) == (
                "zipf_tokens", 8192, 2, 256, 1.2)
    workload = common.load(files.find("workloads", "moonlight16b-s8k.json"))
    assert (workload["mesh"], workload["log_every"],
            workload["steps_per_task"], workload["warmup_steps"],
            workload["last_positions"]) == ("", 8, 8, 16, 512)
    assert workload["model_params"]["remat_policy"] in (
        "none", "dots", "flash", "full")
    # the three new metrics, appended at the end, this cell's only
    new = manifest["per_layer"][-3:]
    assert [m["name"] for m in new] == [
        "mla_time_share", "mla_assemble_share", "shared_expert_time_share"]
    for metric in new:
        assert metric["workloads"] == ["moonlight16b-s8k"]
        assert (metric["unit"], metric["better"], metric["source"],
                metric["moves"]) == (
                    "%", "lower", "device_trace", "samples_per_s")
        assert files.find("metrics", metric["name"] + ".py")
    # what the cell reports in a traced run: every metric without a
    # list that moves an end-to-end metric of the cell, and its own
    reported = {m["name"] for m in files.metrics_for(
        "per_layer", "moonlight16b-s8k")}
    assert reported >= {
        "flash_time_share", "flash_roofline", "peak_hbm_gb",
        "mla_time_share", "mla_assemble_share", "shared_expert_time_share"}
    assert not reported & {
        "moe_time_share", "collective_time_share", "loop_host_ms"}
