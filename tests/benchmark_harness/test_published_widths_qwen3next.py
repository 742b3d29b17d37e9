"""The ``qwen3-next-80b-a3b-1chip`` configuration against the published
values of ITS source, Qwen/Qwen3-Next-80B-A3B-Instruct's ``config.json``
(the model-configs catalog's row): every width as published, the three
reducible keys under their ceilings and over the guide's floors, and the
cell's files, found BY NAME (a later ``model_config`` PR appends after
them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "qwen3-next-80b-a3b-1chip"
CELL = "qwen3next80b-s32k"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/"
          "main/config.json")
# the catalog row's ``config``, whole
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
# the keys a configuration may reduce, with the source's value as the
# ceiling; every other key of the row is a width or a rule of the block
REDUCIBLE = {"num_hidden_layers": 48, "num_experts": 512,
             "vocab_size": 151936}
NEW_METRICS = ("gdn_time_share", "gdn_scan_share", "gdn_scan_roofline",
               "expert_share_time_share", "held_pairs_over_share")


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
    assert sorted(listed["reduced"]) == sorted(config["reduced"]) == sorted(
        REDUCIBLE)
    assert config["published"] == REDUCIBLE
    differ = sorted(k for k, v in CATALOG.items() if config[k] != v)
    assert differ == sorted(REDUCIBLE)
    # one whole period: three linear layers and a full one, no leading
    # dense layer
    assert config["num_hidden_layers"] == config[
        "full_attention_interval"] == 4
    # the guide's floors: at least 8 routed experts, an eighth of the
    # vocabulary; the rule's two shares
    assert config["num_experts"] in (32, 16) and config["num_experts"] >= 8
    assert config["held_experts"] == [0, config["num_experts"]]
    assert config["vocab_size"] * 8 == 151936
    rows = config["expert_rows"]
    assert rows["held_rows"] % 512 == 0  # the grouped matmul's row tiles
    expected = 32768 * 10 * config["num_experts"] // 512
    assert rows["expected_held_pairs"] == expected
    assert expected < rows["busiest_step_held_pairs"] < rows["held_rows"]


def test_the_file_states_what_was_assumed_and_where_it_departs():
    config = body()
    assumed = config["assumed"]
    assert assumed["router_aux_loss_coef"] == 0.001
    assert assumed["gdn_chunk"] == 64
    assert assumed["embedding_init_std"] == 1.0
    for key in ("router_aux_loss_coef_source", "gdn_chunk_source",
                "A_log_dt_bias", "optimizer", "parameter_dtype",
                "config_json_recalled"):
        assert assumed[key]
    assert "MTP" in assumed["config_json_recalled"]
    departs = " ".join(d["what"] for d in config["departs"])
    assert all(d["what"] and d["effect"] for d in config["departs"])
    for word in ("in_proj_qkvz", "chunks of 64", "router", "experts 0-31",
                 "dropout"):
        assert word in departs, word
    deployment = config["deployment"]
    assert "16 chips share each layer" in deployment["share"]
    assert "1.28 TB" in deployment["slice"]
    # what the rule tried, with the compiler's bytes
    tried = deployment["tried"]
    assert {(t["num_experts"], t["remat_policy"]) for t in tried} >= {
        (32, "none"), (32, "dots"), (32, "flash")}
    assert all(t["compiler_bytes"] for t in tried)
    (chosen,) = [t for t in tried if t.get("chosen")]
    assert (chosen["num_experts"], chosen["remat_policy"]) == (32, "flash")
    assert chosen["compiler_bytes"] < 15.75 * 2**30
    assert config["flops"] == "gdn_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    assert any("A_log" in leaf for leaf in leaves)
    assert any("router" in leaf for leaf in leaves)
    assert any(leaf.startswith("block_3/attn/") for leaf in leaves)


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
    traffic = common.load(files.find("traffic", "s32k-b1.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["records"], traffic["zipf_a"]) == (
                "zipf_tokens", 32768, 1, 128, 1.2)
    workload = common.load(files.find("workloads", CELL + ".json"))
    warm = common.load(files.find("workloads", "pythia1b-s16k.json"))
    assert (workload["mesh"], workload["log_every"],
            workload["steps_per_task"], workload["warmup_steps"]) == (
                "", 2, warm["steps_per_task"], warm["warmup_steps"])
    assert workload["last_positions"]
    assert workload["model_params"]["remat_policy"] == "flash"
    # two traced steps, the fewest that hold a gap between step
    # programs: stopping the profiler costs ~3 s a traced step here, and
    # at the default 6 nothing of the 20 s window is left after
    # ``trace.done`` for stall_share, input_wait_ms, slow_steps_in_window
    assert workload["trace_steps"] == 2
    # the five new metrics, this cell's only
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        metric = by_name[name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "samples_per_s"
        assert files.find("metrics", name + ".py")
    assert by_name["gdn_scan_roofline"]["better"] == "higher"
    assert by_name["held_pairs_over_share"]["source"] == "program_counter"
    assert len({by_name[n]["layer"] for n in NEW_METRICS[:3]}) == 1
    assert by_name["expert_share_time_share"]["layer"] == by_name[
        "moe_time_share"]["layer"]
    # what the cell reports in a traced run: every metric without a
    # list that moves an end-to-end metric of the cell, and its own
    reported = {m["name"] for m in files.metrics_for("per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | {
        "flash_time_share", "flash_roofline", "peak_hbm_gb"}
    assert not reported & {
        "moe_time_share", "mla_time_share", "collective_time_share",
        "loop_host_ms"}
    # nothing older lists the new cell, and nothing older was moved
    older = [m for m in manifest["per_layer"] if m["name"] not in NEW_METRICS]
    assert not any(CELL in m.get("workloads", []) for m in older)
