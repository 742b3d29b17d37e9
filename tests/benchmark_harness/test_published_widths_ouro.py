"""The ``ouro-2.6b-1chip`` configuration against the published values of
ITS source, ByteDance/Ouro-2.6B's ``config.json`` (the model-configs
catalog's row): every key as published but the depth, the one cut the
guide's Section 4 allows here and its floor, what was assumed (each
item with its source), the deployment this chip is a share of, the
depth rule's readings, and the cell's files, found BY NAME (a later
``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "ouro-2.6b-1chip"
CELL = "ouro2.6b-s16k"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
# the catalog row's ``config``, whole
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
REDUCIBLE = {"num_hidden_layers": 48}
NEW_METRICS = ("exit_head_time_share", "looped_outside_blocks_share")
LIMIT = 15.75 * 2 ** 30  # what the compiler gives a program on a v5e


def entry():
    (found,) = [c for c in common.load(common.MANIFEST)["configs"]
                if c["name"] == NAME]
    return found


def body():
    return common.load(os.path.join(common.REPO, entry()["file"]))


@pytest.mark.parametrize("key", sorted(set(CATALOG) - set(REDUCIBLE)))
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
    (row,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
    assert row["config"] == CATALOG and row["source_url"] == SOURCE
    assert "layers run several times" in row["mechanisms"]


def test_the_one_cut_and_its_floor():
    config, listed = body(), entry()
    assert config["source"] == listed["source"] == SOURCE
    assert listed["reduced"] == config["reduced"] == list(REDUCIBLE)
    assert config["published"] == REDUCIBLE
    assert sorted(k for k, v in CATALOG.items() if config[k] != v) == sorted(
        REDUCIBLE)
    assert 4 <= config["num_hidden_layers"] <= 9
    # the loop and the vocabulary are untouched
    assert config["total_ut_steps"] == 4
    assert config["early_exit_threshold"] == 1
    assert config["vocab_size"] == 49152
    assert len(config["layer_types"]) == config["max_window_layers"] == 48
    assert all(len(listed[key]) <= 200 for key in ("why", "source"))
    assert "4 times" in listed["why"] and "49,152" in listed["why"]
    assert "%d of 48 layers" % config["num_hidden_layers"] in listed["why"]


def test_the_depth_is_the_rule_s():
    """The LARGEST depth of 9..4 whose step compiles under the chip's
    memory under ``flash`` and runs the window: every depth above the
    chosen one was tried and refused, with the compiler's bytes."""
    config = body()
    deployment = config["deployment"]
    assert "pipeline stages" in deployment["share"]
    assert "vocabulary whole" in deployment["share"]
    assert "LARGEST" in deployment["rule"]
    tried = deployment["tried"]
    (chosen,) = [t for t in tried if t.get("chosen")]
    assert chosen["num_hidden_layers"] == config["num_hidden_layers"]
    assert chosen["compiler_bytes"] < LIMIT
    assert chosen["chip"] and "ran" in chosen["chip"]
    deeper = [t for t in tried
              if t["num_hidden_layers"] > chosen["num_hidden_layers"]]
    assert deeper and all(t["compiler_bytes"] > LIMIT for t in deeper)
    assert {t["num_hidden_layers"] for t in deeper} >= {
        chosen["num_hidden_layers"] + 1}
    assert all(t["remat_policy"] == "flash" and t["compiler"]
               for t in tried)
    # the state is 16 bytes a parameter of ONE tree of that depth
    for t in tried:
        assert t["parameters"] == (
            2 * 49152 * 2048 + 2048 + 2049
            + t["num_hidden_layers"] * (51_380_224 + 4 * 2048))


def test_the_file_states_what_was_assumed():
    config = body()
    assumed = config["assumed"]
    assert assumed["beta"] == 0.05 and "entropy" in assumed["beta_source"]
    for key in ("block_norms", "final_norm", "gate", "remainder"):
        assert "recalled, no network here" in assumed[key], key
    assert "input_layernorm_2" in assumed["block_norms"]
    assert "EVERY pass" in assumed["final_norm"]
    assert "WITH bias" in assumed["gate"]
    assert "sum to 1" in assumed["remainder"]
    assert "Stage II" in assumed["training_stage"]
    assert "16k" in assumed["training_lengths"]
    assert "16,384 of the 65,536" in assumed["sequence"]
    assert (assumed["learning_rate"], assumed["weight_decay"],
            assumed["lr_warmup_steps"]) == (0.0003, 0.01, 2000)
    assert assumed["embedding_init_std"] == 1.0
    for key in ("optimizer", "parameter_dtype", "config_json_recalled",
                "embedding_init_std_source", "inference"):
        assert assumed[key], key
    assert "16 bytes a parameter" in assumed["parameter_dtype"]
    assert "2510.25741" in config["paper"]
    assert all(d["what"] and d["effect"] for d in config["departs"])
    departs = " ".join(d["what"] for d in config["departs"])
    for word in ("lambda_T", "chunk", "bfloat16", "one after the other"):
        assert word in departs, word
    assert config["flops"] == "looped_dense_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    for leaf in ("wte/embedding", "lm_head/kernel",
                 "early_exit_gate/kernel", "early_exit_gate/bias"):
        assert leaf in leaves, leaf
    assert any(leaf.endswith("attn/query/kernel") for leaf in leaves)
    assert any(leaf.endswith("mlp_down/kernel") for leaf in leaves)
    assert any(leaf.endswith("_out/scale") for leaf in leaves)
    assert all(int(leaf.split("/")[0][6:]) < config["num_hidden_layers"]
               for leaf in leaves if leaf.startswith("block_"))


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s16k-b1", 1)
    assert len(cell["why"]) <= 200
    assert "%d block applications" % (4 * body()["num_hidden_layers"]) in (
        cell["why"])
    traffic = common.load(files.find("traffic", "s16k-b1.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["records"], traffic["zipf_a"]) == (
        "zipf_tokens", 16384, 1, 128, 1.2)
    workload = common.load(files.find("workloads", CELL + ".json"))
    assert workload == {
        "mesh": "", "model_params": {"remat_policy": "flash"},
        "log_every": 1, "steps_per_task": 2, "warmup_steps": 4,
        "last_positions": 512, "reference_remat": True, "trace_steps": 2}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert files.find("metrics", name + ".py")
    assert files.find("flops", "looped_dense_decoder.py")
    assert files.find("lib", "looped_trace.py")
    for name in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[name]))
    # what was there is there: the new entries are members, wherever a
    # later PR appends
    names = {w["name"] for w in manifest["workloads"]}
    assert {"pythia1b-s16k", "pythia1b-fsdp4-s2k", "olmoe1b7b-s4k",
            "keye-vl2-30b-s32k", CELL} <= names
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
