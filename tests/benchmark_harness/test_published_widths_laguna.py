"""The ``laguna-xs.2-1chip`` configuration against the published values
of ITS source, poolside/Laguna-XS.2's ``config.json`` (the
model-configs catalog's row): every width as published, the three
reducible keys under their ceilings and at or over the guide's floors,
what was assumed, and the cell's files, found BY NAME (a later
``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "laguna-xs.2-1chip"
CELL = "laguna-xs2-s32k"
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# the catalog row's ``config``, whole
CATALOG = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
# the keys a configuration may reduce, with the source's value as the
# ceiling; every other key of the row is a width or a rule of the block
REDUCIBLE = {"num_hidden_layers": 40, "num_experts": 256,
             "vocab_size": 100352}
NEW_METRICS = ("window_attn_time_share", "window_flash_roofline",
               "window_flash_fill")


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
    (row,) = [r for r in rows if r["name"] == "Laguna-XS.2"]
    assert row["config"] == CATALOG and row["source_url"] == SOURCE


def test_the_cuts_are_under_their_ceilings_and_at_the_floors():
    config, listed = body(), entry()
    assert config["source"] == listed["source"] == SOURCE
    assert sorted(listed["reduced"]) == sorted(config["reduced"]) == sorted(
        REDUCIBLE)
    assert config["published"] == REDUCIBLE
    differ = sorted(k for k, v in CATALOG.items() if config[k] != v)
    assert differ == sorted(REDUCIBLE)
    # the guide's floors: the leading dense layer once and one whole
    # period of four after it, in the published 3 : 1; at least 8
    # routed experts; an eighth of the vocabulary
    layers = config["num_hidden_layers"]
    assert layers == 5
    assert config["mlp_layer_types"][:layers] == ["dense"] + ["sparse"] * 4
    assert config["layer_types"][1:layers] == PERIOD[1:] + PERIOD[:1]
    assert config["num_attention_heads_per_layer"][:layers] == [
        48, 64, 64, 64, 48]
    assert config["vocab_size"] * 8 == 100352
    # the held share: experts 0 .. n-1 under 256 / n-way expert
    # parallelism, n one of the rule's three
    held = config["num_experts"]
    assert held in (32, 16, 8) and 256 % held == 0
    assert config["held_experts"] == [0, held]
    ways = 256 // held
    assert held * ways == config["published"]["num_experts"] == 256
    assert "%d chips share each layer" % ways in config["deployment"]["share"]
    rows = config["expert_rows"]
    assert rows["held_rows"] % 512 == 0  # the grouped matmul's row tiles
    expected = 32768 * 8 * held // 256
    assert rows["expected_held_pairs"] == expected == 1024 * held
    assert expected < rows["busiest_step_held_pairs"] < rows["held_rows"]
    assert rows["margin"] and rows["why"]


def test_the_file_states_what_was_assumed_and_where_it_departs():
    config = body()
    assumed = config["assumed"]
    assert assumed["bias_update_speed"] == 0.001
    assert assumed["embedding_init_std"] == 1.0
    assert assumed["lr_warmup_steps"] == 2000
    assert (assumed["learning_rate"], assumed["weight_decay"]) == (
        0.0003, 0.01)
    # every item ISSUE 42 names, each with its argument
    for key in ("gating", "router", "bias_update_speed_source", "head_norm",
                "sliding_window", "attention_factor", "yarn_dim",
                "optimizer", "parameter_dtype", "config_json_recalled"):
        assert assumed[key], key
    assert "elementwise" in assumed["gating"].lower()
    assert "12.6 M" in assumed["gating"] and "16.8 M" in assumed["gating"]
    assert "sigmoid scores" in assumed["router"]
    assert "OUTPUT" in assumed["router"]
    assert "56 / 72" in assumed["head_norm"]
    assert "i - j < 512" in assumed["sliding_window"]
    departs = " ".join(d["what"] for d in config["departs"])
    assert all(d["what"] and d["effect"] for d in config["departs"])
    for word in ("HALVES", "router", "experts 0 to n-1", "balancing bias",
                 "2 of this cut's 5 layers and 1 of 4", "8 heads in memory",
                 "whole (q-block, k-block) grid", "dropout"):
        assert word in departs, word
    deployment = config["deployment"]
    assert "534 GB" in deployment["slice"]
    assert "pipeline stages" in deployment["share"]
    # what the rule tried, with the compiler's count for every rung
    tried = deployment["tried"]
    (chosen,) = [t for t in tried if t.get("chosen")]
    assert chosen["num_experts"] == config["num_experts"]
    assert all(t["parameters"] and t["compiler"] for t in tried)
    assert {t["parameters"] for t in tried if t["num_experts"] == 32} == {
        766_531_584}
    assert {t["parameters"] for t in tried if t["num_experts"] == 16} <= {
        565_204_992}
    limit = 15.75 * 2**30
    assert chosen["compiler_bytes"] < limit
    order = ["none", "dots", "flash", "full"]
    # the LARGEST share that fits under some policy, and of its
    # policies the first that fits
    fits = [t for t in tried if t.get("compiler_bytes", limit) < limit
            and not t.get("chip_refused")]
    assert chosen["num_experts"] == max(t["num_experts"] for t in fits)
    assert chosen["remat_policy"] == min(
        (t["remat_policy"] for t in fits
         if t["num_experts"] == chosen["num_experts"]), key=order.index)
    larger = [t for t in tried if t["num_experts"] > chosen["num_experts"]]
    assert {t["remat_policy"] for t in tried
            if t["num_experts"] == chosen["num_experts"]} >= set(
                order[:order.index(chosen["remat_policy"]) + 1])
    assert all(t not in fits for t in larger)
    assert config["flops"] == "window_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    assert len(leaves) >= 8
    # W_qg and W_k of a window layer and of a full layer, layer 0's
    # W_qg, a router, a held expert, the embedding
    for leaf in ("block_2/attn/query/kernel", "block_2/attn/key/kernel",
                 "block_4/attn/query/kernel", "block_4/attn/key/kernel",
                 "block_0/attn/query/kernel", "wte/embedding"):
        assert leaf in leaves, leaf
    assert any("router" in leaf for leaf in leaves)
    assert any("w_gate" in leaf for leaf in leaves)
    assert all(int(leaf.split("/")[0].split("_")[1]) < 5
               for leaf in leaves if leaf.startswith("block_"))


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
    assert "2 of 5" in cell["why"] and "1 of 4" in cell["why"]
    assert "1,024 rows" in cell["why"]
    traffic = common.load(files.find("traffic", "s32k-b1.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["records"], traffic["zipf_a"]) == (
                "zipf_tokens", 32768, 1, 128, 1.2)
    workload = common.load(files.find("workloads", CELL + ".json"))
    assert (workload["mesh"], workload["log_every"],
            workload["steps_per_task"], workload["warmup_steps"],
            workload["last_positions"], workload["reference_remat"]) == (
                "", 2, 2, 4, 512, True)
    (chosen,) = [t for t in body()["deployment"]["tried"] if t.get("chosen")]
    assert workload["model_params"]["remat_policy"] == chosen["remat_policy"]
    assert workload["trace_steps"] >= 2
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert files.find("metrics", name + ".py")
    assert files.find("flops", "window_moe_decoder.py")
    for name in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[name]))
    # the eight cells before it are there
    assert len(manifest["workloads"]) >= 9
