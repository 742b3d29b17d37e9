"""moonshotai/Kimi-Linear-48B-A3B-Instruct (``model_type``
``kimi_linear``) as a model-zoo module for ``worker.main``.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm; layer ``i`` (1-indexed) a Kimi Delta Attention mixer
(``KimiDeltaAttention``: ``linear_attn_config``'s heads, head width and
taps, both low-rank gates ``assumed.kda_gate_rank`` wide) if it is in
``linear_attn_config.kda_layers``, latent attention
(``LatentAttention``: no q latent, a kv latent of ``kv_lora_rank`` with
its own norm, and with ``mla_use_nope`` NOTHING rotated) if in
``full_attn_layers``; the first ``first_k_dense_replace`` blocks dense
SwiGLU of ``intermediate_size``, the others an expert layer that routes
over all ``published.num_experts`` experts (sigmoid scores, selection by
score + balancing bias, top ``num_experts_per_token``, gates
renormalised and scaled by ``routed_scaling_factor``), holds
``held_experts`` of them in a row buffer of ``expert_rows.held_rows``
rows, and adds ``num_shared_experts`` shared ones. What the block cannot
express is refused, not imitated: a q latent, expert groups, a rope
scaling, a prediction module. Where it departs from the published block
is listed under ``departs`` in the config file. The loss is the zoo's:
cross-entropy plus the model's ``aux_loss`` (``aux_loss_alpha`` x the
sequence-wise balance loss, from the file's ``assumed``). The optimizer
is the repo zoo's AdamW under a linear warm-up (``assumed``; Moonlight's
configuration argues for both it and the unit-variance embedding). A
cell sets ``remat_policy`` through the worker's ``--model_params``.

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
from elasticdl_tpu.models.transformer import KdaDims, LatentDims
from elasticdl_tpu.train.optimizers import create_optimizer

from benchmark.lib.probe import callbacks  # noqa: F401

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_ENV = "EDLBENCH_CONFIG"


def load_config():
    path = os.environ.get(CONFIG_ENV) or os.path.join(_HERE, "config.json")
    with open(path) as f:
        return json.load(f)


def layer_kinds(config):
    """The built layers' kinds, the first ``num_hidden_layers`` of the
    published lists (1-indexed there): ``kda`` or ``full``."""
    linear = config["linear_attn_config"]
    kinds = []
    for i in range(1, config["num_hidden_layers"] + 1):
        if (i in linear["kda_layers"]) == (i in linear["full_attn_layers"]):
            raise ValueError(
                "layer %d is in one of kda_layers and full_attn_layers" % i)
        kinds.append("kda" if i in linear["kda_layers"] else "full")
    return tuple(kinds)


def model_from_config(config, mesh=None, remat_policy="none",
                      attention_impl="auto"):
    """The MoeTransformerLM of a Kimi Linear style ``config.json``.
    Sizes are read, never defaulted; what the block cannot express is an
    error."""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("q_lora_rank", None), ("num_expert_group", 1),
                      ("topk_group", 1), ("rope_scaling", None),
                      ("moe_router_activation_func", "sigmoid"),
                      ("moe_layer_freq", 1),
                      ("num_nextn_predict_layers", 0)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("grouped-query attention is not built here")
    first, count = config["held_experts"]
    if count != config["num_experts"]:
        raise ValueError(
            "num_experts is the count this chip holds: %d, held_experts "
            "says %d" % (config["num_experts"], count))
    assumed, linear = config["assumed"], config["linear_attn_config"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        layer_kinds=layer_kinds(config),
        kda=KdaDims(
            num_heads=linear["num_heads"],
            head_dim=linear["head_dim"],
            conv_kernel_dim=linear["short_conv_kernel_size"],
            gate_rank=assumed["kda_gate_rank"],
            chunk=assumed["kda_chunk"],
            segment=assumed["kda_segment"],
        ),
        latent=LatentDims(
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rotary=not config["mla_use_nope"],
        ),
        rope_theta=float(config["rope_theta"]),
        first_k_dense=config["first_k_dense_replace"],
        dense_act="swiglu",
        dense_dim=config["intermediate_size"],
        num_experts=config["published"]["num_experts"],
        held_experts=(first, count),
        held_rows=config["expert_rows"]["held_rows"],
        top_k=config["num_experts_per_token"],
        expert_dim=config["moe_intermediate_size"],
        expert_act="swiglu",
        shared_experts=config["num_shared_experts"],
        moe_every=1,
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        scoring="sigmoid",
        normalize_gates=config["moe_renormalize"],
        gate_scale=config["routed_scaling_factor"],
        bias_update_speed=assumed["bias_update_speed"],
        embed_init_std=assumed["embedding_init_std"],
        seq_aux=True,
        dispatch_impl="sorted",
        aux_loss_weight=assumed["aux_loss_alpha"],
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
