"""Every configuration of ``BENCHMARK.json`` against the published
values of ITS source. ``test_manifest.py::test_published_widths_are_
the_source_s`` holds every configuration to pythia-1b's table, which a
second architecture cannot meet (PERF.md Section 7 has the edit it
wants: the table keyed by ``source``); this file is that table, keyed
so, one case a configuration."""

import os

import pytest

from tests.benchmark_harness import _common as common

PYTHIA = "https://huggingface.co/EleutherAI/pythia-1b/blob/main/config.json"
OLMOE = ("https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/"
         "main/config.json")
# what the source publishes and no cut may change; the keys a
# configuration may reduce, with the source's value as the ceiling
PUBLISHED = {
    PYTHIA: {
        "widths": {
            "hidden_size": 2048, "intermediate_size": 8192,
            "num_attention_heads": 8, "vocab_size": 50304,
            "max_position_embeddings": 2048, "rotary_pct": 0.25,
            "use_parallel_residual": True, "tie_word_embeddings": False,
        },
        "reducible": {"num_hidden_layers": 16},
    },
    OLMOE: {
        "widths": {
            "hidden_size": 2048, "intermediate_size": 1024,
            "num_attention_heads": 16, "num_key_value_heads": 16,
            "num_experts": 64, "num_experts_per_tok": 8,
            "max_position_embeddings": 4096, "rope_theta": 10000,
            "rms_norm_eps": 1e-05, "norm_topk_prob": False,
            "hidden_act": "silu", "attention_bias": False,
            "tie_word_embeddings": False,
        },
        "reducible": {"num_hidden_layers": 16, "vocab_size": 50304},
    },
}


def configurations():
    return [c["name"] for c in common.load(common.MANIFEST)["configs"]]


@pytest.mark.parametrize("name", configurations())
def test_published_widths_by_source(name):
    (entry,) = [c for c in common.load(common.MANIFEST)["configs"]
                if c["name"] == name]
    body = common.load(os.path.join(common.REPO, entry["file"]))
    assert body["source"] == entry["source"]
    assert entry["source"] in PUBLISHED, (
        "a configuration of a new source brings its published table")
    published = PUBLISHED[entry["source"]]
    for key, value in published["widths"].items():
        assert body[key] == value, (name, key)
        assert key not in entry["reduced"], (name, key)
    for key, ceiling in published["reducible"].items():
        if key in entry["reduced"]:
            assert 0 < body[key] < ceiling, (name, key)
        else:
            assert body[key] == ceiling, (name, key)
    assert set(entry["reduced"]) <= set(published["reducible"])
    assert len(body["departs"]) >= 3
    assert all(d["what"] and d["effect"] for d in body["departs"])
    assert body["deployment"] if entry["reduced"] else True


def test_the_catalog_s_row_is_the_olmoe_file_but_for_the_cuts():
    """The file holds every number of the catalog row's ``config``
    under the same key; only the keys in ``reduced`` differ, and the
    file says what they were."""
    catalog = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304,
    }
    body = common.load(os.path.join(
        common.REPO, "benchmark", "configs", "olmoe-1b-7b-1chip",
        "config.json"))
    differ = sorted(k for k, v in catalog.items() if body[k] != v)
    assert differ == sorted(body["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert body["published"] == {k: catalog[k] for k in differ}
    # the cut: one layer (the pattern's period), a quarter of the rows
    assert body["num_hidden_layers"] == 1
    assert body["vocab_size"] * 4 == catalog["vocab_size"]
    assert body["assumed"]["loss_weights"] == {
        "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001}
    assert body["flops"] == "moe_decoder"
    assert body["expect"] == {"attention": "pallas"}
    assert set(body["check_leaves"]) == {
        "wte/embedding", "block_0/moe_mlp/router/kernel",
        "block_0/moe_mlp/w_gate", "block_0/attn/query/kernel"}


def test_the_new_cell_s_files():
    from benchmark.run import Files

    files = Files(common.MANIFEST)
    manifest = common.load(common.MANIFEST)
    (cell,) = [w for w in manifest["workloads"]
               if w["name"] == "olmoe1b7b-s4k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b-1chip", "s4k-b8", 1)
    assert manifest["workloads"][-1] == cell
    assert manifest["configs"][-1]["name"] == "olmoe-1b-7b-1chip"
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(manifest["workloads"]) == 4 and len(four) == 1
    traffic = common.load(files.find("traffic", "s4k-b8.json"))
    assert (traffic["generator"], traffic["seq_len"], traffic["minibatch"],
            traffic["records"], traffic["zipf_a"]) == (
                "zipf_tokens", 4096, 8, 512, 1.2)
    body = common.load(files.find("workloads", "olmoe1b7b-s4k.json"))
    assert (body["mesh"], body["log_every"], body["steps_per_task"],
            body["warmup_steps"]) == ("", 8, 8, 16)
    assert body["model_params"]["remat_policy"] in (
        "none", "dots", "flash", "full")
