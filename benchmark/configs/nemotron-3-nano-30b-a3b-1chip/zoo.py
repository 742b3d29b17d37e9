"""nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (``model_type``
``nemotron_h``) as a model-zoo module for ``worker.main``.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The model is the repo's own ``MoeTransformerLM`` with
RMSNorm as a stack of layers of ONE sublayer each: layer ``i`` is what
the ``i``-th letter of ``hybrid_override_pattern`` says, ``M`` a
Mamba-2 mixer (``Mamba2Mixer``: ``mamba_num_heads`` heads of
``mamba_head_dim`` over a state of ``ssm_state_size``, ``n_groups``
groups, a convolution of ``conv_kernel`` taps with its bias, chunks of
``chunk_size``), ``E`` the expert layer alone (sigmoid scores over the
published ``n_routed_experts`` with a balancing bias, top
``num_experts_per_tok``, gates renormalised and scaled by
``routed_scaling_factor``; this chip's ``held_experts`` and the shared
expert, every body ``relu(x W_up)^2 W_down``), ``*`` grouped-query
softmax attention that rotates nothing; an untied head. What the stack
cannot express is refused, not imitated: a dense MLP layer (``-``),
expert groups, a bias on a projection, another activation, a tied head.
Where it departs from the published block is listed under ``departs``
in the config file. The loss is the zoo's cross-entropy plus the
model's weighted balance loss. The optimizer is the repo zoo's AdamW
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
from elasticdl_tpu.models.transformer import Mamba2Dims
from elasticdl_tpu.train.optimizers import create_optimizer

from benchmark.lib.probe import callbacks  # noqa: F401

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_ENV = "EDLBENCH_CONFIG"
KINDS = {"M": "mamba", "E": "experts", "*": "full"}


def load_config():
    path = os.environ.get(CONFIG_ENV) or os.path.join(_HERE, "config.json")
    with open(path) as f:
        return json.load(f)


def layer_kinds(config):
    """The built layers' kinds, the first ``num_hidden_layers`` letters
    of the published ``hybrid_override_pattern``: ``mamba``,
    ``experts`` or ``full``."""
    built = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    if len(built) != config["num_hidden_layers"] or set(built) - set(KINDS):
        raise ValueError(
            "hybrid_override_pattern=%r: %d layers, each 'M', 'E' or '*' "
            "(a dense MLP layer, '-', is not built)"
            % (built, config["num_hidden_layers"]))
    return tuple(KINDS[letter] for letter in built)


def model_from_config(config, mesh=None, remat_policy="none",
                      attention_impl="auto"):
    """The MoeTransformerLM of a ``nemotron_h`` ``config.json``. Sizes
    are read, never defaulted; what the stack cannot express is an
    error."""
    for key, want in (("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("mamba_proj_bias", False), ("use_bias", False),
                      ("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True), ("sliding_window", None),
                      ("norm_eps", config["layer_norm_epsilon"]),
                      ("intermediate_size", config["moe_intermediate_size"]),
                      ("n_shared_experts", 1)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    # the shared expert is ONE MLP of ``shared_experts x expert_dim``
    # lanes (``MoeMlp._shared``): 2 x 1856 = 3712
    shared, rest = divmod(
        config["moe_shared_expert_intermediate_size"],
        config["moe_intermediate_size"])
    if rest:
        raise ValueError(
            "moe_shared_expert_intermediate_size=%d is no multiple of "
            "moe_intermediate_size=%d: the shared expert's width is "
            "shared_experts x expert_dim here"
            % (config["moe_shared_expert_intermediate_size"],
               config["moe_intermediate_size"]))
    first, count = config["held_experts"]
    if count != config["n_routed_experts"]:
        raise ValueError(
            "n_routed_experts is the count this chip holds: %d, "
            "held_experts says %d" % (config["n_routed_experts"], count))
    assumed = config["assumed"]
    if assumed["attention_rotary"]:
        raise ValueError("assumed.attention_rotary: nothing rotates here")
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        layer_kinds=layer_kinds(config),
        mamba=Mamba2Dims(
            num_heads=config["mamba_num_heads"],
            head_dim=config["mamba_head_dim"],
            state=config["ssm_state_size"],
            groups=config["n_groups"],
            conv_kernel=config["conv_kernel"],
            chunk=config["chunk_size"],
            segment=assumed["scan_segment"],
            conv_bias=config["use_conv_bias"],
        ),
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        rotary=False,
        rope_theta=float(config["rope_theta"]),
        # the kinds say which layers hold experts
        first_k_dense=0,
        moe_every=1,
        num_experts=config["published"]["n_routed_experts"],
        held_experts=(first, count),
        held_rows=config["expert_rows"]["held_rows"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        expert_act="relu2",
        shared_experts=shared,
        norm="rmsnorm",
        norm_eps=config["layer_norm_epsilon"],
        scoring="sigmoid",
        normalize_gates=config["norm_topk_prob"],
        gate_scale=config["routed_scaling_factor"],
        bias_update_speed=assumed["bias_update_speed"],
        router_float32=assumed["router_float32"],
        embed_init_std=assumed["embedding_init_std"],
        seq_aux=False,
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
