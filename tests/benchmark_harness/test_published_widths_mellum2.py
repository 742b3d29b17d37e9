"""The ``mellum2-12b-a2.5b-ep4`` configuration against the published
values of ITS source, JetBrains/Mellum2-12B-A2.5B-Instruct's
``config.json`` (the model-configs catalog's row): every width as
published, depth the only cut and at the guide's floor, what was
assumed, the deployment on a four-chip host, and the cell's files,
found BY NAME (a later ``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "mellum2-12b-a2.5b-ep4"
CELL = "mellum2-ep4-s8k"
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's ``config``, whole
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
# the one key this configuration reduces, with the source's value
REDUCIBLE = {"num_hidden_layers": 28}
NEW_METRICS = ("ep_exchange_time_share", "ep_exchange_exposed_share",
               "ep_exchange_ici_share", "ep_rank_load_max_over_mean")


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


def test_the_row_is_the_catalog_s():
    """Where the guide's catalog is installed, ``CATALOG`` above is its
    row's ``config``, key for key."""
    import json

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct"]
    assert row["config"] == CATALOG and row["source_url"] == SOURCE


def test_depth_is_the_only_cut_and_a_whole_period():
    config, listed = body(), entry()
    assert config["source"] == listed["source"] == SOURCE
    assert listed["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == REDUCIBLE
    assert sorted(k for k, v in CATALOG.items() if config[k] != v) == [
        "num_hidden_layers"]
    layers = config["num_hidden_layers"]
    assert layers == 4
    assert config["layer_types"][:layers] == PERIOD
    assert config["mlp_layer_types"][:layers] == ["sparse"] * 4
    # nothing is held: all 64 experts, every head, the whole vocabulary
    assert "held_experts" not in config and "expert_rows" not in config
    assert all(len(e[key]) <= 200 for e in (listed,)
               for key in ("why", "source"))


def test_the_file_states_the_deployment_and_what_was_assumed():
    config = body()
    spread = config["expert_parallel"]
    assert (spread["ranks"], spread["held_experts_a_rank"],
            spread["mesh"]) == (4, 16, "ep=4")
    assert spread["ranks"] * spread["held_experts_a_rank"] == config[
        "num_experts"]
    assert "ragged_all_to_all" in spread["exchange"]
    rows = spread["received_rows"]
    # the grouped matmul's row tiles; over the mean, under all pairs
    assert rows % 512 == 0
    assert spread["expected_received_pairs"] == 8192 * 8 == 65536
    assert (spread["expected_received_pairs"]
            < spread["busiest_received_pairs"] < rows <= 4 * 65536)
    assert spread["why"]
    deployment = config["deployment"]
    assert "16 of 64 experts a chip" in deployment["share"]
    assert "each chip a data shard" in deployment["share"]
    assert "six further hosts as pipeline stages" in deployment["share"]
    assert "REPLICATED" in deployment["replicated_or_sharded"]
    # the rule: the FIRST policy of the four that compiles
    order = ["none", "dots", "flash", "full"]
    tried = deployment["tried"]
    (chosen,) = [t for t in tried if t.get("chosen")]
    limit = 15.75 * 2**30
    assert [t["remat_policy"] for t in tried] == order[:len(tried)]
    assert tried.index(chosen) == len(tried) - 1
    assert all(t["compiler"] for t in tried)
    assert all(t["compiler_bytes"] > limit for t in tried[:-1])
    assert chosen["compiler_bytes"] < limit
    assert {t["parameters"] for t in tried} == {2_123_976_960}
    assumed = config["assumed"]
    assert assumed["router_aux_loss_coef"] == 0.001
    assert assumed["embedding_init_std"] == 1.0
    assert (assumed["learning_rate"], assumed["weight_decay"],
            assumed["lr_warmup_steps"]) == (0.0003, 0.01, 2000)
    for key in ("router_aux_loss_coef_source", "routing", "head_norm",
                "mtp", "intermediate_size", "sliding_window",
                "attention_factor", "yarn_dim", "optimizer",
                "parameter_dtype", "config_json_recalled"):
        assert assumed[key], key
    assert "none is built" in assumed["mtp"]
    assert "no layer uses" in assumed["intermediate_size"]
    assert "i - j < 1024" in assumed["sliding_window"]
    assert "no capacity factor" in assumed["routing"]
    assert all(d["what"] and d["effect"] for d in config["departs"])
    departs = " ".join(d["what"] for d in config["departs"])
    for word in ("HALVES", "router", "received_rows", "4 heads in memory",
                 "ZeRO over ep", "dropout"):
        assert word in departs, word
    assert config["flops"] == "ep_window_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    # a window layer's and the full layer's W_q and W_k, a router, two
    # whole expert tensors (three quarters of each on ranks other than
    # 0), the embedding and the head
    for leaf in ("block_0/attn/query/kernel", "block_0/attn/key/kernel",
                 "block_3/attn/query/kernel", "block_3/attn/key/kernel",
                 "wte/embedding", "lm_head/kernel"):
        assert leaf in leaves, leaf
    assert any("router" in leaf for leaf in leaves)
    assert any(leaf.endswith("w_gate") for leaf in leaves)
    assert any(leaf.endswith("w_down") for leaf in leaves)


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8k-b4", 4)
    # the quarter rule: four-chip cells are at most a quarter
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert len(cell["why"]) <= 200
    assert "its share" in cell["why"] and "guard" in cell["why"]
    traffic = common.load(files.find("traffic", "s8k-b4.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["zipf_a"]) == ("zipf_tokens", 8192, 4, 1.2)
    workload = common.load(files.find("workloads", CELL + ".json"))
    assert (workload["mesh"], workload["last_positions"]) == ("ep=4", 512)
    (chosen,) = [t for t in body()["deployment"]["tried"] if t.get("chosen")]
    assert workload["model_params"]["remat_policy"] == chosen["remat_policy"]
    assert workload["trace_steps"] >= 2
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert files.find("metrics", name + ".py")
    assert files.find("flops", "ep_window_moe_decoder.py")
    assert files.find("lib", "ep_trace.py")
    for name in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[name]))
    # what was there is there: the new entries are members, wherever a
    # later PR appends
    names = {w["name"] for w in manifest["workloads"]}
    assert {"pythia1b-fsdp4-s2k", "olmoe1b7b-s4k", "laguna-xs2-s32k",
            CELL} <= names
