"""Kwai-Keye/Keye-VL-2.0-30B-A3B's language model (``model_type``
``KeyeVL2``) as a model-zoo module for ``worker.main``: the
``qwen3_moe`` block with a learned sparse-attention indexer in every
layer (``sa_config``; DeepSeek Sparse Attention as the DeepSeek-V3.2-Exp
report publishes it), trained on text by next-token prediction in the
report's sparse training stage: the model under its language loss, the
indexer under its own KL term.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm: grouped-query attention (``head_dim`` of its own,
``num_key_value_heads`` kv heads read uncopied by the kernels, an
RMSNorm over the lanes of every query and key head, rotary over the
whole head at ``rope_theta``); the indexer of ``sa_config``
(``indexer_num_heads`` query heads of ``indexer_head_dim`` over ONE key
a position, ``topk`` keys a query:
``models/transformer.py:IndexerDims``, ``ops/sparse_attention.py``);
every layer an expert layer that routes over all
``published.num_experts`` experts (float32 softmax, top
``num_experts_per_tok``, normalised, no shared expert) and holds
``held_experts`` of them in a row buffer of ``expert_rows.held_rows``
rows. What the row of the catalog does not settle is ``assumed``'s,
each with its source; what the block cannot express is refused, not
imitated; where it departs from the published block is listed under
``departs``. The vision tower waits (``departs``): the cell trains
text, where the three position streams of ``mrope_section`` are equal.
The optimizer is the repo zoo's AdamW under a linear warm-up
(``assumed``). A cell sets ``remat_policy`` through the worker's
``--model_params``.

``callbacks()`` (``benchmark/lib/probe.py``) is the benchmark's only
hook inside the worker process: peak device memory and, in a traced
run, the profiler.
"""

import json
import os

import optax

from elasticdl_tpu.models.moe_transformer import (  # noqa: F401, I001
    MoeTransformerLM,
    batch_spec,
    dataset_fn,
    loss,
    sharding_rules,
)
from elasticdl_tpu.models.transformer import IndexerDims
from elasticdl_tpu.train.optimizers import create_optimizer

from benchmark.lib.probe import callbacks  # noqa: F401

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_ENV = "EDLBENCH_CONFIG"


def load_config():
    path = os.environ.get(CONFIG_ENV) or os.path.join(_HERE, "config.json")
    with open(path) as f:
        return json.load(f)


def model_from_config(config, mesh=None, remat_policy="none",
                      attention_impl="auto"):
    """The MoeTransformerLM of a ``KeyeVL2`` language ``config.json``.
    Sizes are read, never defaulted; what the block cannot express is
    an error."""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("use_sliding_window", False),
                      ("sliding_window", None), ("attention_bias", False),
                      ("norm_topk_prob", True)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    rope = config["rope_scaling"]
    if rope["rope_type"] != "default" or sum(rope["mrope_section"]) * 2 != (
            config["head_dim"]):
        raise ValueError(
            "rope_scaling=%r: on text the three streams of mrope_section "
            "are one position and fill half a head; no other rope_type "
            "is built" % (rope,))
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError(
            "sa_config.indexer_num_kv_heads=%r: the indexer's heads share "
            "one key a position" % (sa["indexer_num_kv_heads"],))
    first, count = config["held_experts"]
    if count != config["num_experts"]:
        raise ValueError(
            "num_experts is the count this chip holds: %d, held_experts "
            "says %d" % (config["num_experts"], count))
    if config["num_local_experts"] != config["published"]["num_experts"]:
        raise ValueError(
            "num_local_experts stays as published: %d and %d"
            % (config["num_local_experts"],
               config["published"]["num_experts"]))
    assumed = config["assumed"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        head_norm="rmsnorm",
        rope_theta=float(config["rope_theta"]),
        indexer=IndexerDims(
            heads=sa["indexer_num_heads"],
            head_dim=sa["indexer_head_dim"],
            topk=sa["topk"]),
        indexer_loss_coef=assumed["indexer_loss_coef"],
        num_experts=config["published"]["num_experts"],
        held_experts=(first, count),
        held_rows=config["expert_rows"]["held_rows"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        expert_act="swiglu",
        moe_every=1,
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        scoring="softmax",
        normalize_gates=config["norm_topk_prob"],
        embed_init_std=assumed["embedding_init_std"],
        dispatch_impl="sorted",
        aux_loss_weight=assumed["router_aux_loss_coef"],
        z_loss_weight=0.0,
        attention_impl=attention_impl,
        mesh=mesh,
        remat=remat_policy != "none",
        remat_policy="full" if remat_policy == "none" else remat_policy,
    )


def optimizer():
    assumed = load_config()["assumed"]
    return create_optimizer(
        "AdamW",
        learning_rate=optax.linear_schedule(
            0.0, assumed["learning_rate"], assumed["lr_warmup_steps"]),
        weight_decay=assumed["weight_decay"])


def custom_model(mesh=None, remat_policy="none"):
    return model_from_config(
        load_config(), mesh=mesh, remat_policy=remat_policy)
