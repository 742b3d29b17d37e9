"""ByteDance/Ouro-2.6B (``model_type`` ``ouro``; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741) as a model-zoo
module for ``worker.main``: a looped language model. ``num_hidden_layers``
sandwich-normed dense blocks run ``total_ut_steps`` times over ONE set
of parameters, the final norm ends every pass, one gate gives every
position a distribution over the passes' exits, and the model is
trained by its Stage I objective: the expected cross-entropy over the
exits less ``beta`` x that distribution's entropy.

Every size comes from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default): no width is
defaulted here. The block is the repo's own ``MoeTransformerLM`` with
every layer dense (``first_k_dense = num_hidden_layers``): RMSNorm
before AND after each sublayer (``sandwich``), 16 heads of ``head_dim``
with rotary over the whole head at ``rope_theta``, SwiGLU of
``intermediate_size``, no bias, a head untied from the embedding; the
loop is ``LoopedDims(total_ut_steps, assumed.beta)``. What the row of
the catalog does not settle is ``assumed``'s, each with its source;
what the block cannot express is refused, not imitated; where the
program departs from the published implementation is listed under
``departs``. The paper's Stage II (the gate alone, against a frozen
model) is another job and not built. The optimizer is the repo zoo's
AdamW under a linear warm-up (``assumed``). A cell sets
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
from elasticdl_tpu.models.transformer import LoopedDims
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
    """The MoeTransformerLM of an ``ouro`` ``config.json``. Sizes are
    read, never defaulted; what the block cannot express is an error."""
    layers = config["num_hidden_layers"]
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("rope_scaling", None), ("sliding_window", None),
                      ("use_sliding_window", False),
                      ("num_key_value_heads", config["num_attention_heads"]),
                      ("early_exit_threshold", 1)):
        if config[key] != want:
            raise ValueError(
                "%s=%r: this zoo builds %r only" % (key, config[key], want))
    if set(config["layer_types"][:layers]) != {"full_attention"}:
        raise ValueError(
            "layer_types=%r: every layer built is full_attention"
            % (config["layer_types"][:layers],))
    assumed = config["assumed"]
    return MoeTransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=layers,
        num_heads=config["num_attention_heads"],
        embed_dim=config["hidden_size"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        first_k_dense=layers,
        dense_act="swiglu",
        dense_dim=config["intermediate_size"],
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        sandwich=True,
        looped=LoopedDims(config["total_ut_steps"], assumed["beta"]),
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
