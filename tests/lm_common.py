"""Shared by the ``test_<name>_lm.py`` files that hold a configuration's
block against its plain reference (``test_kimi_lm.py``,
``test_granite_lm.py``; the next ``model_config`` PR's takes its model
and its reference from here too): the benchmark's own check run once a
file, a program a side, at the fewest layers that hold one layer of
every kind the file names; and the digest of a model's parameter tree,
which the older models' leaf-for-leaf tests read."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.refcheck import compare, load_by_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRESET = os.path.join(REPO, "tests", "benchmark_harness", "preset")


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reference_check(family, config, workload, name, seed=3):
    """``check.py`` of the configuration directory ``family`` on
    ``config`` (a preset's, or a cut of it) under the preset workload
    ``workload``: ((errors by name, whether they pass), the system's
    outputs, the variables). Three programs: ``init``, the system's
    value and gradients, the reference's; a module-scoped fixture calls
    this once and every test of the file asserts on what it returned.
    Its cost is the two sides' traces and compiles, which grow by the
    layer: a ten-layer preset read 37 s where its two-layer cut reads a
    quarter of that."""
    spec = {"config": config,
            "cell": read_json(PRESET, "workloads", workload),
            "zoo": os.path.join(family, "zoo.py"),
            "reference": os.path.join(family, "reference.py")}
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(
            0, config["vocab_size"], size=(128,)), jnp.int32)
    parts = load_by_path(
        name + "_check_for_lm", os.path.join(family, "check.py")).build(
            spec, tokens)
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(seed), tokens)
    system = jax.jit(parts["system"])(variables, tokens)
    plain = jax.jit(parts["reference"])(variables, tokens)
    return compare(system, plain, parts["tolerance"]), system, variables


def tree_digest(preset):
    """(leaves, sha256 of the sorted (path, shape, dtype) of every
    leaf) of the preset configuration ``preset``'s parameter tree, from
    shapes alone, and the model."""
    config = read_json(PRESET, "configs", preset, "config.json")
    zoo = load_by_path(
        "zoo_tree_" + preset.replace("-", "_"),
        os.path.join(REPO, config["zoo"]))
    model = zoo.model_from_config(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)))
    leaves = sorted(
        ("/".join(str(getattr(p, "key", p)) for p in path),
         tuple(leaf.shape), str(leaf.dtype))
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes))
    return (len(leaves), hashlib.sha256(
        repr(leaves).encode()).hexdigest()), model
