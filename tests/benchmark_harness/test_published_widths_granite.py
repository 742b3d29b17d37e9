"""The ``granite-4.0-h-micro-1chip`` configuration against the published
values of ITS source, ibm-granite/granite-4.0-h-micro's ``config.json``
(the model-configs catalog's row): every width as published, the two
reducible keys under their ceilings and over the guide's floors, and the
cell's files, found BY NAME (a later ``model_config`` PR appends after
them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "granite-4.0-h-micro-1chip"
CELL = "granite4h-micro-s8k"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/"
          "blob/main/config.json")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the catalog row's ``config``, whole
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": PERIOD + (["mamba"] * 5 + ["attention"]
                             + ["mamba"] * 4) * 3,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
# the keys a configuration may reduce, with the source's value as the
# ceiling; every other key of the row is a width or a rule of the block
REDUCIBLE = {"num_hidden_layers": 40, "vocab_size": 100352}
NEW_METRICS = ("mamba_time_share", "ssd_scan_share", "ssd_scan_roofline",
               "mamba_bytes_share")


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
    # a whole period: the first ten of the published forty, nine Mamba-2
    # layers to one attention layer as the model's 36 to 4
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"][:10] == PERIOD
    assert len(config["layer_types"]) == 40
    assert config["layer_types"].count("attention") == 4
    # the guide's floor: an eighth of the vocabulary
    assert config["vocab_size"] * 8 == 100352
    # the widths the issue names, by their keys
    assert config["mamba_n_heads"] * config["mamba_d_head"] == (
        config["mamba_expand"] * config["hidden_size"]) == 4096
    assert config["hidden_size"] // config["num_attention_heads"] == 64
    assert config["attention_multiplier"] == 1 / 64


def test_the_file_states_what_was_assumed_and_where_it_departs():
    config = body()
    assumed = config["assumed"]
    assert assumed["scan_segment"] == 8
    assert assumed["embedding_init_std"] == 0.1
    assert (assumed["learning_rate"], assumed["weight_decay"],
            assumed["lr_warmup_steps"]) == (0.0003, 0.01, 2000)
    for key in ("scan_segment_source", "A_log_dt_bias_D", "conv_init",
                "mamba_regime", "embedding_init_std_source", "optimizer",
                "lr_warmup_steps_source", "sequence", "parameter_dtype",
                "config_json_recalled"):
        assert assumed[key], key
    assert "(1, 16)" in assumed["A_log_dt_bias_D"]
    assert "(0.001, 0.1)" in assumed["A_log_dt_bias_D"]
    departs = " ".join(d["what"] for d in config["departs"])
    assert all(d["what"] and d["effect"] for d in config["departs"])
    for word in ("mlp_gate and mlp_up", "z | x | B | C | dt",
                 "chunks of mamba_chunk_size = 256", "bfloat16 compute",
                 "remat", "no cache"):
        assert word in departs, word
    deployment = config["deployment"]
    assert "four pipeline stages" in deployment["share"]
    assert "eight ways" in deployment["share"]
    assert "51 GB" in deployment["slice"]
    # what the rule tried, with the compiler's verdicts
    tried = deployment["tried"]
    assert tried and all(t["compiler"] for t in tried)
    (chosen,) = [t for t in tried if t.get("chosen")]
    assert chosen["remat_policy"] == "flash"
    assert chosen["scan_segment"] == assumed["scan_segment"]
    assert chosen["compiler_bytes"] < 15.75 * 2**30
    assert deployment["chosen"]
    assert config["flops"] == "ssm_dense_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    for part in ("wte/embedding", "in_proj", "conv_kernel", "conv_bias",
                 "A_log", "dt_bias", "/D", "out_norm_scale", "key/kernel",
                 "mlp_down"):
        assert any(part in leaf for leaf in leaves), part


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8k-b1", 1)
    # the quarter rule: four-chip cells are at most a quarter
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert all(len(e["why"]) <= 200 for e in (cell, entry()))
    for word in ("9 Mamba-2 layers of 10", "36 of 40", "16,384 does not fit",
                 "lfm2-8b-s32k"):
        assert word in cell["why"], word
    workload = common.load(files.find("workloads", CELL + ".json"))
    warm = common.load(files.find("workloads", "kimi-linear48b-s32k.json"))
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
    assert files.find("lib", "ssm_trace.py")
    for part in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[part])), part
