"""The ``nemotron-3-nano-30b-a3b-1chip`` configuration against the
published values of ITS source, nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-
BF16's ``config.json`` (the model-configs catalog's row): every width as
published, the three reducible keys under their ceilings and over the
guide's floors, and the cell's files, found BY NAME (a later
``model_config`` PR appends after them)."""

import os

import pytest

from tests.benchmark_harness import _common as common

NAME = "nemotron-3-nano-30b-a3b-1chip"
CELL = "nemotron3-nano-s8k"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
          "blob/main/config.json")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the catalog row's ``config``, whole
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}
# the keys a configuration may reduce, with the source's value as the
# ceiling; every other key of the row is a width or a rule of the stack
REDUCIBLE = {"num_hidden_layers": 52, "n_routed_experts": 128,
             "vocab_size": 131072}
NEW_METRICS = ("relu2_moe_time_share", "relu2_shared_time_share",
               "relu2_gmm_roofline", "mamba_g8_time_share",
               "ssd_g8_scan_roofline", "relu2_active_share")


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
    # the first nine letters of the published 52: 4 M, 4 E, 1 attention,
    # and a whole repeating unit (EMEMEM*, seven) inside them
    assert len(PATTERN) == 52 and config["num_hidden_layers"] == 9
    built = config["hybrid_override_pattern"][:9]
    assert built == "MEMEM*EME" and "EMEMEM*" in PATTERN[1:9] + PATTERN[9:]
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (
        23, 23, 6)
    assert (built.count("M"), built.count("E"), built.count("*")) == (4, 4, 1)
    # the guide's floors: 8 routed experts held, an eighth of the
    # vocabulary
    assert config["n_routed_experts"] == 8 == config["held_experts"][1]
    assert config["held_experts"][0] == 0
    assert config["vocab_size"] * 8 == 131072
    # the widths the issue names, by their keys
    assert config["mamba_num_heads"] * config["mamba_head_dim"] == 4096
    assert config["num_attention_heads"] * config["head_dim"] == 4096 > (
        config["hidden_size"])
    assert config["num_attention_heads"] // config[
        "num_key_value_heads"] == 16
    assert config["moe_shared_expert_intermediate_size"] == 2 * config[
        "moe_intermediate_size"]
    assert config["moe_intermediate_size"] % 128 == 64  # 14.5 lane tiles


def test_the_file_states_what_was_assumed_and_where_it_departs():
    config = body()
    assumed = config["assumed"]
    assert assumed["attention_rotary"] is False
    assert assumed["router_float32"] is True
    assert (assumed["aux_loss_alpha"], assumed["bias_update_speed"]) == (
        0.0001, 0.001)
    assert assumed["scan_segment"] == 8
    assert assumed["embedding_init_std"] == 1.0
    assert (assumed["learning_rate"], assumed["weight_decay"],
            assumed["lr_warmup_steps"]) == (0.0003, 0.01, 2000)
    for key in ("attention_rotary_source", "aux_loss_alpha_source",
                "bias_update_speed_source", "routing",
                "router_float32_source", "time_step_limit",
                "scan_segment_source", "A_log_dt_bias_D",
                "embedding_init_std_source", "optimizer",
                "lr_warmup_steps_source", "sequence", "parameter_dtype",
                "config_json_recalled"):
        assert assumed[key], key
    # each assumption names the reading NOT taken
    for key in ("attention_rotary_source", "aux_loss_alpha_source",
                "router_float32_source"):
        assert "not taken" in assumed[key].lower(), key
    assert "(0, inf)" in assumed["time_step_limit"]
    departs = " ".join(d["what"] for d in config["departs"])
    assert all(d["what"] and d["effect"] for d in config["departs"])
    for word in ("Block(only=", "z | x | B | C | dt",
                 "chunks of chunk_size = 128", "(512, 896, 640)",
                 "bfloat16 compute", "remat", "8 of 128 experts",
                 "no cache"):
        assert word in departs, word
    deployment = config["deployment"]
    assert "16 chips share each layer" in deployment["share"]
    assert "eight ways" in deployment["share"]
    assert "MEMEM*EME" in deployment["share"]
    assert "506 GB" in deployment["slice"]
    # what the rule tried, with the compiler's verdicts
    tried = deployment["tried"]
    assert tried and all(t["compiler"] for t in tried)
    (chosen,) = [t for t in tried if t.get("chosen")]
    assert chosen["remat_policy"] == "flash"
    assert chosen["scan_segment"] == assumed["scan_segment"]
    assert chosen["compiler_bytes"] < 15.75 * 2**30
    assert deployment["chosen"]
    rows = config["expert_rows"]
    assert rows["held_rows"] % 512 == 0
    assert rows["expected_held_pairs"] == 8192 * 6 * 8 // 128
    assert rows["held_rows"] >= 1.5 * rows["busiest_step_held_pairs"]
    assert config["flops"] == "ssm_moe_decoder"
    assert config["expect"] == {"attention": "pallas"}
    assert config["compute_dtype"] == "bfloat16"
    leaves = config["check_leaves"]
    for part in ("wte/embedding", "in_proj", "A_log", "dt_bias", "conv_bias",
                 "out_norm_scale", "router", "w_up", "w_down", "shared_up",
                 "shared_down", "key/kernel"):
        assert any(part in leaf for leaf in leaves), part


def test_the_new_cell_s_files_by_name():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8k-b1", 1)
    assert all(len(e["why"]) <= 200 for e in (cell, entry()))
    for word in ("384 rows", "1/16", "row tile"):
        assert word in cell["why"], word
    workload = common.load(files.find("workloads", CELL + ".json"))
    # granite's, so the two Mamba-2 cells differ in the model alone
    assert workload == common.load(
        files.find("workloads", "granite4h-micro-s8k.json"))
    assert workload["model_params"]["remat_policy"] == "flash"
    # the six new metrics, this cell's only
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        metric = by_name[name]
        assert metric["workloads"] == [CELL], name
        assert metric["moves"] == "samples_per_s"
        assert files.find("metrics", name + ".py")
    assert files.find("flops", body()["flops"] + ".py")
    for part in ("zoo", "reference", "check"):
        assert os.path.exists(os.path.join(common.REPO, body()[part])), part
