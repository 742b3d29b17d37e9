"""EleutherAI/pythia-1b as a model-zoo module for ``worker.main``.

The sizes come from the ``config.json`` the harness names in
``EDLBENCH_CONFIG`` (this directory's by default), so the one-chip
configuration that only cuts depth shares this file. The block is the
repo's own ``TransformerLM`` (pre-LayerNorm, GELU, rotary, multi-head,
untied head); where it departs from GPT-NeoX is listed under
``departs`` in the config file. Depth has one source, the config
file; a cell sets ``remat_policy`` through the worker's
``--model_params``.

``callbacks()`` (``benchmark/lib/probe.py``) is the benchmark's only
hook inside the worker process: peak device memory and, in a traced
run, the profiler.
"""

import json
import os

from elasticdl_tpu.models.transformer import (  # noqa: F401, I001
    TransformerLM,
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
    """The TransformerLM of a GPT-NeoX ``config.json``. Widths are
    read, never defaulted; a ratio that is not whole is an error."""
    hidden = config["hidden_size"]
    ratio, rest = divmod(config["intermediate_size"], hidden)
    if rest:
        raise ValueError(
            "intermediate_size %d is not a multiple of hidden_size %d"
            % (config["intermediate_size"], hidden)
        )
    return TransformerLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        embed_dim=hidden,
        mlp_ratio=ratio,
        attention_impl=attention_impl,
        mesh=mesh,
        remat=remat_policy != "none",
        remat_policy="full" if remat_policy == "none" else remat_policy,
    )


def custom_model(mesh=None, remat_policy="none"):
    return model_from_config(
        load_config(), mesh=mesh, remat_policy=remat_policy)
