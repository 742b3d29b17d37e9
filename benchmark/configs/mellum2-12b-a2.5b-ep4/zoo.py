"""JetBrains/Mellum2-12B-A2.5B-Instruct (``model_type`` ``mellum``) as a
model-zoo module for ``worker.main``, trained as its deployment runs
it: every layer's 64 experts spread over the four chips of a host.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm. The mixers follow ``layer_types``: a ``full_attention`` layer
sees the causal prefix under the YaRN table of
``rope_parameters.full_attention``, a ``sliding_attention`` layer the
``sliding_window`` keys that end at the query (the flash kernels' band
layout) under the plain table; both have ``num_attention_heads`` query
heads over ``num_key_value_heads`` kv heads of ``head_dim`` and rotate
the whole head. Every MLP is ``sparse``: a softmax router over all
``num_experts`` experts, top ``num_experts_per_tok``, gates normalised
(``norm_topk_prob``), SwiGLU experts of ``moe_intermediate_size``, no
shared expert, dropless (``dispatch_impl="sorted"``). On the mesh the
cell names (``ep=4``) an expert layer holds ``num_experts / 4`` experts
a rank and exchanges its rows over ``ep`` (``ops/moe.py``), receiving
into ``expert_parallel.received_rows`` rows; on one device (the tests)
it holds them all and exchanges nothing. Only the first
``num_hidden_layers`` entries of the two per-layer lists are built, and
they have to be whole periods of one pattern. What the block cannot
express is refused, not imitated. Where it departs from the published
block, and what the config does not settle, is listed under ``departs``
and ``assumed`` in the config file. The loss is the zoo's cross-entropy
plus ``assumed.router_aux_loss_coef`` x the balance loss. The optimizer
is the repo zoo's AdamW under a linear warm-up (``assumed``). A cell
sets ``remat_policy`` through the worker's ``--model_params``.

``callbacks()`` (``benchmark/lib/probe.py``) is the benchmark's only
hook inside the worker process: peak device memory and, in a traced
run, the profiler.
"""

import json
import math
import os

import optax

from elasticdl_tpu.models.moe_transformer import (  # noqa: F401, I001
    MoeTransformerLM,
    batch_spec,
    dataset_fn,
    loss,
    sharding_rules,
)
from elasticdl_tpu.models.transformer import MixerKind, YarnScaling
from elasticdl_tpu.train.optimizers import create_optimizer

from benchmark.lib.probe import callbacks  # noqa: F401

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_ENV = "EDLBENCH_CONFIG"
# the config's names of the two kinds of layer, and the model's
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def load_config():
    path = os.environ.get(CONFIG_ENV) or os.path.join(_HERE, "config.json")
    with open(path) as f:
        return json.load(f)


def layer_kinds(config):
    """The built layers' kinds, ``full`` or ``window``, one a layer."""
    return tuple(
        KINDS[name]
        for name in config["layer_types"][:config["num_hidden_layers"]])


def mixer_kind(config, name):
    """The ``MixerKind`` of the config's kind ``name``: the model's
    heads, its rotary table from ``rope_parameters``, its window from
    ``sliding_window``."""
    rope = config["rope_parameters"][name]
    scaling = None
    if rope["rope_type"] == "yarn":
        # ``attention_factor`` multiplies cos and sin; YarnScaling says
        # an amplitude as 0.1 mscale ln(factor) + 1
        mscale = (rope["attention_factor"] - 1.0) / (
            0.1 * math.log(rope["factor"]))
        scaling = YarnScaling(
            factor=float(rope["factor"]),
            original_max_position_embeddings=rope[
                "original_max_position_embeddings"],
            beta_fast=float(rope["beta_fast"]),
            beta_slow=float(rope["beta_slow"]),
            mscale=mscale,
            mscale_all_dim=0.0,
        )
    elif rope["rope_type"] != "default":
        raise ValueError(
            "rope_type %r: 'default' or 'yarn'" % (rope["rope_type"],))
    return MixerKind(
        num_heads=config["num_attention_heads"],
        rope_theta=float(rope["rope_theta"]),
        rotary_dim=None,
        rope_scaling=scaling,
        window=(config["sliding_window"]
                if name == "sliding_attention" else None),
    )


def model_from_config(config, mesh=None, remat_policy="none",
                      attention_impl="auto"):
    """The MoeTransformerLM of a ``mellum`` ``config.json``. Sizes are
    read, never defaulted; what the block cannot express is an error."""
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("use_sliding_window", True)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    layers = config["num_hidden_layers"]
    if set(config["mlp_layer_types"][:layers]) != {"sparse"}:
        raise ValueError(
            "mlp_layer_types=%r: every built layer is sparse"
            % (config["mlp_layer_types"][:layers],))
    kinds = layer_kinds(config)
    assumed = config["assumed"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=layers,
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        layer_kinds=kinds,
        kind_fields={
            KINDS[name]: mixer_kind(config, name)
            for name in KINDS if KINDS[name] in kinds},
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        num_experts=config["num_experts"],
        exchange_rows=config["expert_parallel"]["received_rows"],
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
