"""LiquidAI/LFM2-8B-A1B (``model_type`` ``lfm2_moe``, 8.3B-A1.5B) as a
model-zoo module for ``worker.main``.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm (``norm_eps``). The mixers follow ``layer_types``, which has no
period (``conv conv full_attention conv conv conv full_attention
...``), so the model is given the built layers' kinds as the list
itself: a ``conv`` layer is the gated short convolution of
``conv_L_cache`` taps over the model's width (``models/transformer.py:
ShortConv``), a ``full_attention`` layer softmax attention over the
causal prefix with ``num_attention_heads`` query heads over
``num_key_value_heads`` kv heads of ``head_dim``, an RMSNorm over the
lanes of every q and k head, rotary over the whole head at
``rope_theta``. The first ``num_dense_layers`` layers have a SwiGLU MLP
of ``intermediate_size``; every other one an expert layer that routes
over all ``published.num_experts`` experts (sigmoid scores in float32,
selection by score + ``expert_bias``, top ``num_experts_per_tok``, the
unbiased scores normalised over the chosen, times
``routed_scaling_factor``, no shared expert) and holds ``held_experts``
of them in a row buffer of ``expert_rows.held_rows`` rows. The output
head is the token embedding (``assumed.tie_word_embeddings``). Only the
first ``num_hidden_layers`` entries of ``layer_types`` are built. What
the block cannot express is refused, not imitated. Where it departs
from the published block, and what the config does not settle, is
listed under ``departs`` and ``assumed`` in the config file. The loss is
the zoo's cross-entropy (no balance loss: ``aux_loss_weight`` 0; the
balancing bias does that work). The optimizer is the repo zoo's AdamW
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
from elasticdl_tpu.models.transformer import ShortConvDims
from elasticdl_tpu.train.optimizers import create_optimizer

from benchmark.lib.probe import callbacks  # noqa: F401

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_ENV = "EDLBENCH_CONFIG"
# the config's names of the two kinds of layer, and the model's
KINDS = {"conv": "conv", "full_attention": "full"}


def load_config():
    path = os.environ.get(CONFIG_ENV) or os.path.join(_HERE, "config.json")
    with open(path) as f:
        return json.load(f)


def layer_kinds(config):
    """The built layers' kinds, ``conv`` or ``full``, one a layer."""
    return tuple(
        KINDS[name]
        for name in config["layer_types"][:config["num_hidden_layers"]])


def model_from_config(config, mesh=None, remat_policy="none",
                      attention_impl="auto"):
    """The MoeTransformerLM of an ``lfm2_moe`` ``config.json``. Sizes
    are read, never defaulted; what the block cannot express is an
    error."""
    for key, want in (("conv_bias", False), ("norm_topk_prob", True),
                      ("use_expert_bias", True)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    if config["head_dim"] * config["num_attention_heads"] != config[
            "hidden_size"]:
        raise ValueError(
            "head_dim is hidden_size / num_attention_heads in lfm2_moe: "
            "%d x %d is not %d" % (
                config["head_dim"], config["num_attention_heads"],
                config["hidden_size"]))
    first, count = config["held_experts"]
    if count != config["num_experts"]:
        raise ValueError(
            "num_experts is the count this chip holds: %d, held_experts "
            "says %d" % (config["num_experts"], count))
    assumed = config["assumed"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        layer_kinds=layer_kinds(config),
        conv=ShortConvDims(taps=config["conv_L_cache"]),
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        head_norm="rmsnorm",
        rope_theta=float(config["rope_theta"]),
        first_k_dense=config["num_dense_layers"],
        dense_act="swiglu",
        dense_dim=config["intermediate_size"],
        num_experts=config["published"]["num_experts"],
        held_experts=(first, count),
        held_rows=config["expert_rows"]["held_rows"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        expert_act="swiglu",
        moe_every=1,
        norm="rmsnorm",
        norm_eps=config["norm_eps"],
        scoring="sigmoid",
        normalize_gates=True,
        gate_scale=float(config["routed_scaling_factor"]),
        bias_update_speed=assumed["bias_update_speed"],
        embed_init_std=assumed["embedding_init_std"],
        tie_embeddings=assumed["tie_word_embeddings"],
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
