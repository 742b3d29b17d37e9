"""The ``keye-vl-2.0-30b-a3b-1chip`` configuration against the published
values of ITS source, Kwai-Keye/Keye-VL-2.0-30B-A3B's ``config.json``
(the model-configs catalog's row): every width as published, the three
cuts the guide's Section 4 allows and each at or over its floor, what
was assumed (each item with its source and the reading not taken), the
deployment this chip is a share of, and the cell's files, found BY NAME
(a later ``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "keye-vl-2.0-30b-a3b-1chip"
CELL = "keye-vl2-30b-s32k"
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")
# the catalog row's ``config``, whole
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
# the keys this configuration reduces, with the source's values
REDUCIBLE = {"num_hidden_layers": 48, "num_experts": 128,
             "vocab_size": 151936}
NEW_METRICS = ("indexer_time_share", "indexer_select_share",
               "indexer_score_roofline", "sparse_attn_fill")


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
    (row,) = [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"]
    assert row["config"] == CATALOG and row["source_url"] == SOURCE
    assert "learned sparse attention" in row["mechanisms"]


def test_the_three_cuts_and_their_floors():
    config, listed = body(), entry()
    assert config["source"] == listed["source"] == SOURCE
    assert listed["reduced"] == config["reduced"] == list(REDUCIBLE)
    assert config["published"] == REDUCIBLE
    assert sorted(k for k, v in CATALOG.items() if config[k] != v) == sorted(
        REDUCIBLE)
    # no width among them, and none changed inside a nested group
    assert not [k for k in REDUCIBLE
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert config["sa_config"] == CATALOG["sa_config"]
    assert config["rope_scaling"] == CATALOG["rope_scaling"]
    assert config["num_hidden_layers"] >= 4
    assert config["num_experts"] >= 16
    assert config["held_experts"] == [0, config["num_experts"]]
    assert config["vocab_size"] >= 18992
    assert config["vocab_size"] * 8 == REDUCIBLE["vocab_size"]
    assert config["num_experts"] * 8 == REDUCIBLE["num_experts"]
    assert all(len(listed[key]) <= 200 for key in ("why", "source"))
    assert "indexer" in listed["why"] and "2,048" in listed["why"]


ASSUMED_WITH_A_READING_NOT_TAKEN = (
    "indexer_query_input", "indexer_key_norm", "indexer_rotary",
    "q_chunk_size")


def test_the_file_states_the_deployment_and_what_was_assumed():
    config = body()
    rows = config["expert_rows"]
    # the grouped matmul's row tiles; over the busiest run, under all pairs
    assert rows["held_rows"] % 512 == 0
    assert rows["expected_held_pairs"] == 32768 * 8 * 16 // 128 == 32768
    assert (rows["expected_held_pairs"] * 0.9
            < rows["busiest_step_held_pairs"] < rows["held_rows"]
            <= 32768 * 8)
    assert rows["margin"] and rows["why"]
    deployment = config["deployment"]
    assert "8 chips share each layer" in deployment["share"]
    assert "16 of 128 experts a chip" in deployment["share"]
    assert "pipeline stages" in deployment["share"]
    assert "six steps or more" in deployment["rule"]
    # the rule: the LARGEST depth whose FIRST policy that compiles also
    # runs six steps in the window
    order = ["none", "dots", "flash", "full"]
    tried = deployment["tried"]
    (chosen,) = [t for t in tried if t.get("chosen")]
    limit = 15.75 * 2**30
    depths = sorted({t["num_hidden_layers"] for t in tried}, reverse=True)
    assert depths[0] == 6 and chosen["num_hidden_layers"] == depths[-1]
    assert chosen["num_hidden_layers"] == config["num_hidden_layers"]
    for depth in depths:
        at = [t for t in tried if t["num_hidden_layers"] == depth]
        assert [t["remat_policy"] for t in at] == order[:len(at)]
        assert all(t["compiler_bytes"] > limit for t in at[:-1])
        assert at[-1]["compiler_bytes"] < limit
        assert at[-1]["chip"] and at[-1]["chip"] != "TBD"
        assert all(t["compiler"] for t in at)
    # a deeper cut that fits was left for the steps it runs in a window
    assert "NOT chosen" in [t for t in tried
                            if t["num_hidden_layers"] == 6][-1]["chip"]
    assert {t["parameters"] for t in tried} == {659_190_016, 562_290_560}
    assumed = config["assumed"]
    for key in ASSUMED_WITH_A_READING_NOT_TAKEN:
        assert "ot taken" in assumed[key], key
    assert "V3.2-Exp" in assumed["indexer_query_input"]
    assert "LayerNorm" in assumed["indexer_key_norm"]
    assert "heads^-1/2 x head_dim^-1/2" in assumed["indexer_weight_scale"]
    assert "Hadamard" in assumed["indexer_quantisation"]
    assert "SHARED by a chunk of 512 queries" in assumed["q_chunk_size"]
    assert "ties to the lower position" in assumed["selection"]
    assert "sparse training stage" in assumed["training_stage"]
    assert "warm-up stage" in assumed["training_stage"]
    assert assumed["indexer_loss_coef"] == 1.0
    assert assumed["router_aux_loss_coef"] == 0.001
    assert assumed["embedding_init_std"] == 1.0
    assert (assumed["learning_rate"], assumed["weight_decay"],
            assumed["lr_warmup_steps"]) == (0.0003, 0.01, 2000)
    for key in ("head_norm", "sequence", "routing", "optimizer",
                "parameter_dtype", "config_json_recalled",
                "indexer_loss_coef_source", "embedding_init_std_source"):
        assert assumed[key], key
    assert "32,768 of the 262,144" in assumed["sequence"]
    assert "num_local_experts stays as published" in assumed[
        "config_json_recalled"]
    assert all(d["what"] and d["effect"] for d in config["departs"])
    departs = " ".join(d["what"] for d in config["departs"])
    for word in ("vision tower", "mrope_section", "int8 mask",
                 "four times a layer", "dsa_indexer_loss",
                 "4 heads in memory", "router", "experts 0-15 of 128",
                 "dropout"):
        assert word in departs, word
    assert config["flops"] == "dsa_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    for leaf in ("wte/embedding", "block_0/attn/query/kernel",
                 "block_0/attn/key/kernel", "block_0/attn/q_norm/scale"):
        assert leaf in leaves, leaf
    # one layer's three indexer matrices, which only L_I reaches
    for name in ("indexer_q", "indexer_k", "indexer_w"):
        assert any(leaf.endswith(name + "/kernel") for leaf in leaves), name
    assert any("router" in leaf for leaf in leaves)
    assert any(leaf.endswith("w_gate") for leaf in leaves)
    assert all(int(leaf.split("/")[0][6:]) < config["num_hidden_layers"]
               for leaf in leaves if leaf.startswith("block_"))


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s32k-b1", 1)
    assert len(cell["why"]) <= 200
    assert "2,048 picked keys" in cell["why"] and "top-k" in cell["why"]
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
    assert files.find("flops", "dsa_moe_decoder.py")
    assert files.find("lib", "dsa_trace.py")
    for name in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[name]))
    # what was there is there: the new entries are members, wherever a
    # later PR appends
    names = {w["name"] for w in manifest["workloads"]}
    assert {"pythia1b-fsdp4-s2k", "olmoe1b7b-s4k", "sdar30b-bd-s8k",
            "lfm2-8b-s32k", CELL} <= names
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
