"""JetLM/SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``) as a model-zoo
module for ``worker.main``, trained as SDAR trains it: by block
diffusion, not by next-token prediction.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm: grouped-query attention (``head_dim`` of its own,
``num_key_value_heads`` kv heads read uncopied by the flash kernel, an
RMSNorm over the lanes of every query and key head, rotary over the
whole head); every layer an expert layer that routes over all
``published.num_experts`` experts (float32 softmax, top
``num_experts_per_tok``, normalised, no shared expert) and holds
``held_experts`` of them in a row buffer of ``expert_rows.held_rows``
rows. The objective is ``assumed``'s: blocks of ``block_length``
tokens, a noise level a block from U(``t_min``, 1), the linear
schedule, ``[MASK]`` the id ``mask_token_id``; a step runs the noisy
and the clean copy of a sequence under one block-structured mask
(``ops/block_diffusion.py``, ``ops/flash_attention.py:BlockDiffusion``)
and the zoo's ``loss`` weights the masked positions by 1 / t. What the
block cannot express is refused, not imitated. Where it departs from
the published block is listed under ``departs`` in the config file.
The optimizer is the repo zoo's AdamW under a linear warm-up
(``assumed``; Moonlight's configuration argues for both it and the
unit-variance embedding). A cell sets ``remat_policy`` through the
worker's ``--model_params``.

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
    """The MoeTransformerLM of an ``sdar_moe`` ``config.json``. Sizes
    are read, never defaulted; what the block cannot express is an
    error."""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("rope_scaling", None), ("use_sliding_window", False),
                      ("sliding_window", None), ("attention_bias", False),
                      ("norm_topk_prob", True)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    first, count = config["held_experts"]
    if count != config["num_experts"]:
        raise ValueError(
            "num_experts is the count this chip holds: %d, held_experts "
            "says %d" % (config["num_experts"], count))
    assumed = config["assumed"]
    if assumed["noise_schedule"] != "linear":
        raise ValueError(
            "noise_schedule=%r: ops/block_diffusion.py has the linear "
            "schedule only" % (assumed["noise_schedule"],))
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        head_norm="rmsnorm",
        rope_theta=float(config["rope_theta"]),
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
        objective="block_diffusion",
        bd_block=assumed["block_length"],
        bd_mask_id=assumed["mask_token_id"],
        bd_t_min=assumed["t_min"],
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
