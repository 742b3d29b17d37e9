"""moonshotai/Moonlight-16B-A3B (``model_type`` ``deepseek_v3``) as a
model-zoo module for ``worker.main``.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm, latent attention in every block (``LatentAttention``: no q
latent, a kv latent of ``kv_lora_rank`` with its own norm, rotary on
``qk_rope_head_dim`` lanes of a key head all heads share), the first
``first_k_dense_replace`` blocks dense SwiGLU of ``intermediate_size``,
the others ``n_routed_experts`` SwiGLU experts of
``moe_intermediate_size`` with ``n_shared_experts`` shared ones, sigmoid
scores, selection by score + balancing bias, gates normalised and scaled
by ``routed_scaling_factor``, the sorted dropless dispatch. What the
block cannot express is refused, not imitated: a q latent, expert
groups, a rope scaling. Where it departs from the published block is
listed under ``departs`` in the config file. The loss is the zoo's:
cross-entropy plus the model's ``aux_loss`` (``aux_loss_alpha`` x the
sequence-wise balance loss, from the file's ``assumed``). The optimizer
is the repo zoo's AdamW under the learning-rate warm-up every
pre-training run has, linear from 0 over ``lr_warmup_steps`` (the
file's ``assumed``): at the full rate from the first step the routers
of a seeded model collapse onto one set of experts within 8 steps
(PERF.md Section 6), which no run in training does. A cell sets
``remat_policy`` through the worker's ``--model_params``.

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
from elasticdl_tpu.models.transformer import LatentDims
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
    """The MoeTransformerLM of a Moonlight / DeepSeek-V3 style
    ``config.json``. Sizes are read, never defaulted; what the block
    cannot express is an error."""
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False), ("q_lora_rank", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                      ("num_nextn_predict_layers", 0), ("seq_aux", True)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    if config.get("rope_scaling") is not None:
        raise ValueError("a rope_scaling is not built here")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("grouped-query attention is not built here")
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
        ),
        rope_theta=float(config["rope_theta"]),
        first_k_dense=config["first_k_dense_replace"],
        dense_act="swiglu",
        dense_dim=config["intermediate_size"],
        num_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        expert_act="swiglu",
        shared_experts=config["n_shared_experts"],
        moe_every=1,
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        scoring="sigmoid",
        normalize_gates=config["norm_topk_prob"],
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
