"""allenai/OLMoE-1B-7B as a model-zoo module for ``worker.main``.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
RMSNorm, QK-norm, rotary on the whole head, SwiGLU experts of the
stated width in every block, the sorted dropless dispatch and gates
left unnormalised where the source says so; where it still departs from
the published block is listed under ``departs`` in the config file.
The loss is the zoo's: cross-entropy plus the model's ``aux_loss``
(``router_aux_loss_coef`` x load balancing + ``router_z_loss_coef`` x
router z-loss, both read from the file's ``assumed``). A cell sets
``remat_policy`` through the worker's ``--model_params``.

``callbacks()`` (``benchmark/lib/probe.py``) is the benchmark's only
hook inside the worker process: peak device memory and, in a traced
run, the profiler.
"""

import json
import os

from elasticdl_tpu.models.moe_transformer import (  # noqa: F401, I001
    MoeTransformerLM,
    batch_spec,
    dataset_fn,
    loss,
    optimizer,
    sharding_rules,
)

from benchmark.lib.probe import callbacks  # noqa: F401

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_ENV = "EDLBENCH_CONFIG"


def load_config():
    path = os.environ.get(CONFIG_ENV) or os.path.join(_HERE, "config.json")
    with open(path) as f:
        return json.load(f)


def model_from_config(config, mesh=None, remat_policy="none",
                      attention_impl="auto"):
    """The MoeTransformerLM of an OLMoE ``config.json``. Sizes are
    read, never defaulted; what the block cannot express is an error."""
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False), ("clip_qkv", None),
                      ("rope_scaling", None), ("rope_theta", 10000)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("grouped-query attention is not built here")
    weights = config["assumed"]["loss_weights"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["intermediate_size"],
        expert_act="swiglu",
        moe_every=1,
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        qk_norm=True,
        normalize_gates=config["norm_topk_prob"],
        dispatch_impl="sorted",
        aux_loss_weight=weights["router_aux_loss_coef"],
        z_loss_weight=weights["router_z_loss_coef"],
        attention_impl=attention_impl,
        mesh=mesh,
        remat=remat_policy != "none",
        remat_policy="full" if remat_policy == "none" else remat_policy,
    )


def custom_model(mesh=None, remat_policy="none"):
    return model_from_config(
        load_config(), mesh=mesh, remat_policy=remat_policy)
