"""XingChen-AGI/Xing4.0-29B-A4B (``model_type`` ``xing4_0``) as a
model-zoo module for ``worker.main``.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm: DeepSeek-V3's latent attention WITH a q latent
(``q_lora_rank``, its own norm) under YaRN (``rope_scaling``: the
blended frequency table and the ``mscale``-squared softmax scale); the
first ``first_k_dense_replace`` blocks dense SwiGLU of
``intermediate_size``, the others an expert layer that routes over all
``published.n_routed_experts`` experts (sigmoid scores, selection by
score + balancing bias, top ``num_experts_per_tok``, gates normalised
and scaled by ``routed_scaling_factor``, ``n_shared_experts`` shared)
and holds ``held_experts`` of them in a row buffer of
``expert_rows.held_rows`` rows; a residual path of ``hc_mult`` streams
mixed around every sublayer by manifold-constrained hyper-connections
(``hc_sinkhorn_iters``, ``hc_eps``, the clamp of ``H~_res``); and
``num_nextn_predict_layers`` multi-token-prediction modules (0 or 1)
whose loss the zoo's ``loss`` adds at ``assumed.mtp_loss_weight``. What
the block cannot express is refused, not imitated: expert groups,
another rope scaling than YaRN, a sequence-wise balance loss. Where it
departs from the published block is listed under ``departs`` in the
config file. The optimizer is the repo zoo's AdamW under a linear
warm-up (``assumed``; Moonlight's configuration argues for both it and
the unit-variance embedding). A cell sets ``remat_policy`` through the
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
from elasticdl_tpu.models.transformer import (
    HyperDims,
    LatentDims,
    YarnScaling,
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
    """The MoeTransformerLM of a ``xing4_0`` ``config.json``. Sizes are
    read, never defaulted; what the block cannot express is an error."""
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False), ("n_group", 1),
                      ("topk_group", 1), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    if config.get("seq_aux"):
        raise ValueError("a sequence-wise balance loss is not built here")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("grouped-query attention is not built here")
    scaling = config["rope_scaling"]
    if scaling["type"] != "yarn":
        raise ValueError(
            "rope_scaling type %r: YaRN only" % (scaling["type"],))
    first, count = config["held_experts"]
    if count != config["n_routed_experts"]:
        raise ValueError(
            "n_routed_experts is the count this chip holds: %d, "
            "held_experts says %d" % (config["n_routed_experts"], count))
    assumed = config["assumed"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        latent=LatentDims(
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            q_lora_rank=config["q_lora_rank"],
        ),
        rope_theta=float(config["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(scaling["factor"]),
            original_max_position_embeddings=scaling[
                "original_max_position_embeddings"],
            beta_fast=float(scaling["beta_fast"]),
            beta_slow=float(scaling["beta_slow"]),
            mscale=float(scaling["mscale"]),
            mscale_all_dim=float(scaling["mscale_all_dim"]),
        ),
        hc=HyperDims(
            streams=config["hc_mult"],
            sinkhorn_iters=config["hc_sinkhorn_iters"],
            eps=config["hc_eps"],
            res_clamp=(float(config["mhc_h_res_clamp_min"]),
                       float(config["mhc_h_res_clamp_max"])),
        ),
        mtp_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=assumed["mtp_loss_weight"],
        first_k_dense=config["first_k_dense_replace"],
        dense_act="swiglu",
        dense_dim=config["intermediate_size"],
        num_experts=config["published"]["n_routed_experts"],
        held_experts=(first, count),
        held_rows=config["expert_rows"]["held_rows"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        expert_act="swiglu",
        shared_experts=config["n_shared_experts"],
        moe_every=1,
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        scoring="sigmoid",
        normalize_gates=config["norm_topk_prob"],
        gate_scale=float(config["routed_scaling_factor"]),
        bias_update_speed=assumed["bias_update_speed"],
        embed_init_std=assumed["embedding_init_std"],
        seq_aux=False,
        dispatch_impl="sorted",
        aux_loss_weight=0.0,
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
