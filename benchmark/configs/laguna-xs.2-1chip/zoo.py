"""poolside/Laguna-XS.2 (``model_type`` ``laguna``, 33.4B-A3B) as a
model-zoo module for ``worker.main``.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm. The mixers follow ``layer_types``: a ``full_attention`` layer
sees the causal prefix, a ``sliding_attention`` layer the
``sliding_window`` keys that end at the query (the flash kernels' band
layout), and what a KIND has of its own comes from the config's keys by
kind (``MixerKind``): ``num_attention_heads_per_layer`` (48 / 64 query
heads over ``num_key_value_heads`` kv heads of ``head_dim``), and
``rope_parameters``' base, share of the head that rotates
(``partial_rotary_factor``) and, for a ``yarn`` table, the blended
frequencies and ``attention_factor`` on cos and sin. ``gating``: an
elementwise sigmoid output gate from the layer's input. The MLPs follow
``mlp_layer_types``: the leading ``dense`` layers a SwiGLU of
``intermediate_size``, the ``sparse`` ones an expert layer that routes
over all ``published.num_experts`` experts (sigmoid scores, selection
by score + balancing bias, top ``num_experts_per_tok``, gates
normalised and scaled by ``moe_routed_scaling_factor``, one shared
expert) and holds ``held_experts`` of them in a row buffer of
``expert_rows.held_rows`` rows. Only the first ``num_hidden_layers``
entries of the three per-layer lists are built, and they have to be
whole periods of one pattern. What the block cannot express is refused,
not imitated. Where it departs from the published block, and what the
config does not settle, is listed under ``departs`` and ``assumed`` in
the config file. The loss is the zoo's cross-entropy (no balance loss:
``aux_loss_weight`` 0). The optimizer is the repo zoo's AdamW under a
linear warm-up (``assumed``). A cell sets ``remat_policy`` through the
worker's ``--model_params``.

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


def first_dense(config):
    """How many leading layers are dense; the others must be sparse."""
    mlps = config["mlp_layer_types"][:config["num_hidden_layers"]]
    dense = mlps.index("sparse") if "sparse" in mlps else len(mlps)
    if set(mlps[:dense]) - {"dense"} or set(mlps[dense:]) - {"sparse"}:
        raise ValueError(
            "mlp_layer_types=%r: leading dense layers, then sparse ones"
            % (mlps,))
    return dense


def mixer_kind(config, name):
    """The ``MixerKind`` of the config's kind ``name``: its heads from
    ``num_attention_heads_per_layer`` (one count a kind, or the layers
    are not of kinds), its rotary table from ``rope_parameters``, its
    window from ``sliding_window``."""
    layers = config["num_hidden_layers"]
    heads = {
        count for kind, count in zip(
            config["layer_types"][:layers],
            config["num_attention_heads_per_layer"][:layers])
        if kind == name}
    if len(heads) != 1:
        raise ValueError(
            "%s layers have %r query heads: one count a kind"
            % (name, sorted(heads)))
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
    lanes = int(config["head_dim"] * rope["partial_rotary_factor"])
    return MixerKind(
        num_heads=heads.pop(),
        rope_theta=float(rope["rope_theta"]),
        rotary_dim=None if lanes == config["head_dim"] else lanes,
        rope_scaling=scaling,
        window=(config["sliding_window"]
                if name == "sliding_attention" else None),
    )


def model_from_config(config, mesh=None, remat_policy="none",
                      attention_impl="auto"):
    """The MoeTransformerLM of a ``laguna`` ``config.json``. Sizes are
    read, never defaulted; what the block cannot express is an error."""
    for key, want in (("attention_bias", False),
                      ("tie_word_embeddings", False), ("gating", True),
                      ("moe_apply_router_weight_on_input", False)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    if config["shared_expert_intermediate_size"] % config[
            "moe_intermediate_size"]:
        raise ValueError(
            "the shared expert is a whole number of routed experts wide")
    first, count = config["held_experts"]
    if count != config["num_experts"]:
        raise ValueError(
            "num_experts is the count this chip holds: %d, held_experts "
            "says %d" % (config["num_experts"], count))
    kinds = layer_kinds(config)
    assumed = config["assumed"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        # the model's own head count is no layer's: every kind has its
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        layer_kinds=kinds,
        kind_fields={
            KINDS[name]: mixer_kind(config, name)
            for name in KINDS if KINDS[name] in kinds},
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        output_gate="sigmoid",
        first_k_dense=first_dense(config),
        dense_act="swiglu",
        dense_dim=config["intermediate_size"],
        num_experts=config["published"]["num_experts"],
        held_experts=(first, count),
        held_rows=config["expert_rows"]["held_rows"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        expert_act="swiglu",
        shared_experts=(config["shared_expert_intermediate_size"]
                        // config["moe_intermediate_size"]),
        moe_every=1,
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        scoring="sigmoid",
        normalize_gates=True,
        gate_scale=float(config["moe_routed_scaling_factor"]),
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
