"""ibm-granite/granite-4.0-h-micro (``model_type``
``granitemoehybrid``) as a model-zoo module for ``worker.main``.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm, every layer dense (``num_local_experts`` 0: SwiGLU of
``shared_intermediate_size``); layer ``i`` a Mamba-2 mixer
(``Mamba2Mixer``: ``mamba_n_heads`` heads of ``mamba_d_head`` over a
state of ``mamba_d_state``, ``mamba_n_groups`` groups, a convolution of
``mamba_d_conv`` taps with its bias, chunks of ``mamba_chunk_size``) or
grouped-query softmax attention that rotates nothing
(``position_embedding_type`` ``nope``) as ``layer_types[i]`` says; the
family's four multipliers (``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier``, ``logits_scaling``)
and the head tied to the embedding. What the block cannot express is
refused, not imitated: experts, a bias on a projection, rotated
attention, another activation or norm. Where it departs from the
published block is listed under ``departs`` in the config file. The
loss is the zoo's cross-entropy. The optimizer is the repo zoo's AdamW
under a linear warm-up (``assumed``). A cell sets ``remat_policy``
through the worker's ``--model_params``.

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
from elasticdl_tpu.models.transformer import Mamba2Dims, MixerKind
from elasticdl_tpu.train.optimizers import create_optimizer

from benchmark.lib.probe import callbacks  # noqa: F401

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_ENV = "EDLBENCH_CONFIG"
KINDS = {"mamba": "mamba", "attention": "full"}


def load_config():
    path = os.environ.get(CONFIG_ENV) or os.path.join(_HERE, "config.json")
    with open(path) as f:
        return json.load(f)


def layer_kinds(config):
    """The built layers' kinds, the first ``num_hidden_layers`` of the
    published ``layer_types``: ``mamba`` or ``full``."""
    built = config["layer_types"][:config["num_hidden_layers"]]
    if len(built) != config["num_hidden_layers"] or set(built) - set(KINDS):
        raise ValueError(
            "layer_types=%r: %d layers, each 'mamba' or 'attention'"
            % (built, config["num_hidden_layers"]))
    return tuple(KINDS[kind] for kind in built)


def model_from_config(config, mesh=None, remat_policy="none",
                      attention_impl="auto"):
    """The MoeTransformerLM of a ``granitemoehybrid`` ``config.json``.
    Sizes are read, never defaulted; what the block cannot express is an
    error."""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", True),
                      ("attention_bias", False), ("mamba_proj_bias", False),
                      ("num_local_experts", 0), ("num_experts_per_tok", 0),
                      ("normalization_function", "rmsnorm"),
                      ("position_embedding_type", "nope"),
                      ("rope_scaling", None),
                      ("mamba_expand", config["mamba_n_heads"]
                       * config["mamba_d_head"] // config["hidden_size"]),
                      ("shared_intermediate_size",
                       config["intermediate_size"])):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    layers, assumed = config["num_hidden_layers"], config["assumed"]
    heads = config["num_attention_heads"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=layers,
        num_heads=heads,
        embed_dim=config["hidden_size"],
        layer_kinds=layer_kinds(config),
        # the softmax kind's own entry puts its operations under the
        # scopes ``attn_full/...``; nothing of it rotates
        kind_fields={"full": MixerKind(
            heads, rope_theta=float(config["rope_theta"]))},
        mamba=Mamba2Dims(
            num_heads=config["mamba_n_heads"],
            head_dim=config["mamba_d_head"],
            state=config["mamba_d_state"],
            groups=config["mamba_n_groups"],
            conv_kernel=config["mamba_d_conv"],
            chunk=config["mamba_chunk_size"],
            segment=assumed["scan_segment"],
            conv_bias=config["mamba_conv_bias"],
        ),
        num_kv_heads=config["num_key_value_heads"],
        rotary=False,
        attention_scale=config["attention_multiplier"],
        embedding_scale=config["embedding_multiplier"],
        residual_scale=config["residual_multiplier"],
        logits_divisor=config["logits_scaling"],
        tie_embeddings=True,
        first_k_dense=layers,
        dense_act="swiglu",
        dense_dim=config["shared_intermediate_size"],
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        embed_init_std=assumed["embedding_init_std"],
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
