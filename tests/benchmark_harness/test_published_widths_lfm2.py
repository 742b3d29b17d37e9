"""The ``lfm2-8b-a1b-1chip`` configuration against the published values
of ITS source, LiquidAI/LFM2-8B-A1B's ``config.json`` (the model-configs
catalog's row): every width as published, the three cuts the guide's
Section 4 allows and each at or over its floor, what was assumed, the
deployment this chip is a share of, and the cell's files, found BY NAME
(a later ``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "lfm2-8b-a1b-1chip"
CELL = "lfm2-8b-s32k"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
C, A = "conv", "full_attention"
LAYERS = [C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, A,
          C, C]
# the catalog row's ``config``, whole
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": LAYERS,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}
# the keys this configuration reduces, with the source's values
REDUCIBLE = {"num_hidden_layers": 24, "num_experts": 32,
             "vocab_size": 65536}
NEW_METRICS = ("short_conv_time_share", "short_conv_gate_roofline",
               "dense_mlp_time_share")


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
    (row,) = [r for r in rows if r["name"] == "LFM2-8B-A1B"]
    assert row["config"] == CATALOG and row["source_url"] == SOURCE
    assert row["head_dim"] is None  # hence ``assumed.head_dim``


def test_the_three_cuts_and_their_floors():
    config, listed = body(), entry()
    assert config["source"] == listed["source"] == SOURCE
    assert listed["reduced"] == config["reduced"] == list(REDUCIBLE)
    assert config["published"] == REDUCIBLE
    assert sorted(k for k, v in CATALOG.items() if config[k] != v) == sorted(
        REDUCIBLE)
    # no width among them
    assert not [k for k in REDUCIBLE
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    layers = config["num_hidden_layers"]
    built = config["layer_types"][:layers]
    # both leading dense layers, then at least four expert layers with a
    # whole period (full, conv, conv, conv) among them
    assert layers - config["num_dense_layers"] >= 4
    assert built[:2] == [C, C] and built[2:6] == [A, C, C, C]
    assert built == LAYERS[:layers]
    assert config["num_experts"] >= 8
    assert config["held_experts"] == [0, config["num_experts"]]
    assert config["vocab_size"] * 8 >= REDUCIBLE["vocab_size"]
    assert config["head_dim"] * config["num_attention_heads"] == config[
        "hidden_size"] and config["head_dim"] == 64
    assert all(len(listed[key]) <= 200 for key in ("why", "source"))


def test_the_file_states_the_deployment_and_what_was_assumed():
    config = body()
    rows = config["expert_rows"]
    # the grouped matmul's row tiles; over the busiest run, under all pairs
    assert rows["held_rows"] % 512 == 0
    assert rows["expected_held_pairs"] == 32768 * 4 * 8 // 32 == 32768
    assert (rows["expected_held_pairs"] * 0.9
            < rows["busiest_step_held_pairs"] < rows["held_rows"]
            <= 32768 * 4)
    assert rows["margin"] and rows["why"]
    deployment = config["deployment"]
    assert "4 chips share each layer" in deployment["share"]
    assert "8 of 32 routed experts a chip" in deployment["share"]
    assert "pipeline stages" in deployment["share"]
    # the rule: the FIRST policy of the four that compiles, at the
    # largest depth it names
    order = ["none", "dots", "flash", "full"]
    tried = deployment["tried"]
    (chosen,) = [t for t in tried if t.get("chosen")]
    limit = 15.75 * 2**30
    assert [t["remat_policy"] for t in tried] == order
    assert all(t["compiler"] for t in tried)
    assert all(t["compiler_bytes"] > limit
               for t in tried[:tried.index(chosen)])
    assert chosen["compiler_bytes"] < limit
    assert chosen["num_hidden_layers"] == config["num_hidden_layers"] == 8
    assert {t["parameters"] for t in tried} == {772_217_088}
    assert chosen["chip"] and chosen["chip"] != "TBD"
    assumed = config["assumed"]
    assert assumed["tie_word_embeddings"] is True
    assert "8.34 B" in assumed["tie_word_embeddings_source"]
    assert assumed["embedding_init_std"] == 0.02
    assert assumed["bias_update_speed"] == 0.001
    assert (assumed["learning_rate"], assumed["weight_decay"],
            assumed["lr_warmup_steps"]) == (0.0003, 0.01, 2000)
    for key in ("head_dim", "embedding_init_std_source", "sequence",
                "router", "bias_update_speed_source", "held_experts", "ids",
                "optimizer", "parameter_dtype", "config_json_recalled"):
        assert assumed[key], key
    assert "2048 / 32" in assumed["head_dim"]
    assert "32,768 of the 128,000" in assumed["sequence"]
    assert all(d["what"] and d["effect"] for d in config["departs"])
    departs = " ".join(d["what"] for d in config["departs"])
    for word in ("HALVES", "router", "experts 0 to 7", "B | C | X",
                 "plain jax.numpy", "8 heads in memory", "64-wide",
                 "dropout"):
        assert word in departs, word
    assert config["flops"] == "conv_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    for leaf in ("block_0/attn/in_proj/kernel", "block_0/attn/conv_kernel",
                 "block_1/mlp_gate/kernel", "block_2/attn/key/kernel",
                 "block_2/attn/q_norm/scale", "wte/embedding"):
        assert leaf in leaves, leaf
    assert any("router" in leaf for leaf in leaves)
    assert any(leaf.endswith("w_gate") for leaf in leaves)


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s32k-b1", 1)
    assert len(cell["why"]) <= 200
    assert "head 64" in cell["why"] and "2 of 8 layers" in cell["why"]
    traffic = common.load(files.find("traffic", "s32k-b1.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["zipf_a"]) == ("zipf_tokens", 32768, 1, 1.2)
    workload = common.load(files.find("workloads", CELL + ".json"))
    assert (workload["mesh"], workload["last_positions"]) == ("", 512)
    (chosen,) = [t for t in body()["deployment"]["tried"] if t.get("chosen")]
    assert workload["model_params"]["remat_policy"] == chosen["remat_policy"]
    assert workload["trace_steps"] >= 2
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert files.find("metrics", name + ".py")
    assert files.find("flops", "conv_moe_decoder.py")
    assert files.find("lib", "conv_trace.py")
    for name in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[name]))
    # what was there is there: the new entries are members, wherever a
    # later PR appends
    names = {w["name"] for w in manifest["workloads"]}
    assert {"pythia1b-fsdp4-s2k", "olmoe1b7b-s4k", "laguna-xs2-s32k",
            "mellum2-ep4-s8k", CELL} <= names
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
