"""The ``sdar-30b-a3b-1chip`` configuration against the published values
of ITS source, JetLM/SDAR-30B-A3B-Chat's ``config.json`` (the
model-configs catalog's row): every width as published, the three
reducible keys under their ceilings and over the guide's floors, what
was assumed of the objective, and the cell's files, found BY NAME (a
later ``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "sdar-30b-a3b-1chip"
CELL = "sdar30b-bd-s8k"
SOURCE = ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
          "config.json")
# the catalog row's ``config``, whole
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
# the keys a configuration may reduce, with the source's value as the
# ceiling; every other key of the row is a width or a rule of the block
REDUCIBLE = {"num_hidden_layers": 48, "num_experts": 128,
             "vocab_size": 151936}
NEW_METRICS = ("bd_flash_fill", "bd_overhead_share")


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
    # the period is 1: the guide's floor is four layers; the rule's
    # three depths
    assert config["num_hidden_layers"] in (6, 5, 4)
    # the guide's floors: at least 8 routed experts, an eighth of the
    # vocabulary; the deployment's eighth of the experts
    assert config["num_experts"] * 8 == 128 and config["num_experts"] >= 8
    assert config["held_experts"] == [0, config["num_experts"]]
    assert config["vocab_size"] * 8 == 151936
    rows = config["expert_rows"]
    assert rows["held_rows"] % 512 == 0  # the grouped matmul's row tiles
    # both copies' positions reach the router
    expected = 2 * 8192 * 8 * config["num_experts"] // 128
    assert rows["expected_held_pairs"] == expected == 16384
    assert expected < rows["busiest_step_held_pairs"] < rows["held_rows"]


def test_the_file_states_what_was_assumed_and_where_it_departs():
    config = body()
    assumed = config["assumed"]
    # what the catalog's row does not give (not_given: block length,
    # noise schedule), each with its source
    assert assumed["block_length"] == 4
    assert assumed["noise_schedule"] == "linear"
    assert assumed["t_min"] == 0.001
    assert assumed["mask_token_id"] == config["vocab_size"] - 1
    assert assumed["router_aux_loss_coef"] == 0.001
    assert assumed["embedding_init_std"] == 1.0
    assert assumed["lr_warmup_steps"] == 2000
    for key in ("block_length_source", "noise_schedule_source",
                "t_min_source", "mask_token_id_source", "targets",
                "sequence", "router_aux_loss_coef_source", "optimizer",
                "parameter_dtype", "config_json_recalled"):
        assert assumed[key], key
    assert "not given by the catalog's row" in assumed["block_length_source"]
    assert "not given by the catalog's row" in assumed[
        "noise_schedule_source"]
    assert "no shift" in assumed["targets"]
    assert "8,192 of the 32,768" in assumed["sequence"]
    departs = " ".join(d["what"] for d in config["departs"])
    assert all(d["what"] and d["effect"] for d in config["departs"])
    for word in ("kv head h // 8", "computed inside the flash kernels",
                 "router", "experts 0-15", "noisy copy's L positions",
                 "dropout"):
        assert word in departs, word
    deployment = config["deployment"]
    assert "8 chips share each layer" in deployment["share"]
    assert "488 GB" in deployment["slice"]
    # what the rule tried, with the compiler's bytes
    tried = deployment["tried"]
    (chosen,) = [t for t in tried if t.get("chosen")]
    assert chosen["num_hidden_layers"] == config["num_hidden_layers"]
    assert all(t["compiler_bytes"] and t["parameters"] for t in tried)
    assert chosen["compiler_bytes"] < 15.75 * 2**30
    # the first policy of the rule's order that fits the largest depth
    order = ["none", "dots", "flash", "full"]
    same_depth = [t for t in tried
                  if t["num_hidden_layers"] == chosen["num_hidden_layers"]]
    assert chosen["remat_policy"] == min(
        (t["remat_policy"] for t in same_depth), key=order.index)
    assert config["flops"] == "bd_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    assert any("router" in leaf for leaf in leaves)
    assert any("w_gate" in leaf for leaf in leaves)
    assert any("q_norm" in leaf for leaf in leaves)
    assert "wte/embedding" in leaves
    layers = config["num_hidden_layers"]
    assert all(int(leaf.split("/")[0].split("_")[1]) < layers
               for leaf in leaves if leaf.startswith("block_"))


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "bd-s8k-b1", 1)
    # the quarter rule: four-chip cells are at most a quarter
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert all(len(e["why"]) <= 200 for e in (cell, entry()))
    assert "1/8" in cell["why"]  # the expert rows' eighth
    traffic = common.load(files.find("traffic", "bd-s8k-b1.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["records"], traffic["zipf_a"]) == (
                "zipf_tokens", 8192, 1, 256, 1.2)
    workload = common.load(files.find("workloads", CELL + ".json"))
    like = common.load(files.find("workloads", "qwen3next80b-s32k.json"))
    assert (workload["mesh"], workload["log_every"],
            workload["steps_per_task"], workload["warmup_steps"],
            workload["last_positions"]) == (
                "", 2, like["steps_per_task"], like["warmup_steps"], 512)
    (chosen,) = [t for t in body()["deployment"]["tried"] if t.get("chosen")]
    assert workload["model_params"]["remat_policy"] == chosen["remat_policy"]
    assert workload["trace_steps"] >= 2
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert files.find("metrics", name + ".py")
    # nothing older lists the new cell, and nothing older was moved
    older = [m for m in manifest["per_layer"] if m["name"] not in NEW_METRICS]
    assert not any(CELL in m.get("workloads", []) for m in older)
    assert files.find("flops", "bd_moe_decoder.py")
    for name in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[name]))
