"""Parameter sharding rules: regex path -> PartitionSpec.

This is the GSPMD replacement for everything the reference does with
explicit parameter placement (dense params hashed across PS pods,
worker/ps_client.py:77-89): instead of routing tensors to servers, we
annotate how each parameter array is laid out over mesh axes and let XLA
insert the collectives.

Rules are ordered (first match wins), keyed on the '/'-joined parameter
path. A model module can export ``sharding_rules()`` to override; the
defaults below implement:

- replicated everything (pure DP) when the mesh has no fsdp/tp extent
- ZeRO-style fsdp sharding of the largest dimension when fsdp > 1
"""

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory

logger = _logger_factory("elasticdl_tpu.parallel.sharding")


class ShardingRules:
    def __init__(self, rules=None, default_spec=P()):
        # rules: [(regex, PartitionSpec)]
        self._rules = [(re.compile(r), spec) for r, spec in (rules or [])]
        self._default = default_spec

    def spec_for(self, path: str, shape=None):
        for pattern, spec in self._rules:
            if pattern.search(path):
                return spec
        return self._default


def constrain(x, mesh, spec):
    """Pin the layout of an ACTIVATION: ``x`` with
    ``with_sharding_constraint(NamedSharding(mesh, spec))``.

    The rules above say where parameters live; nothing but this says
    where an activation lives, and GSPMD otherwise propagates the
    weights' layout into it: under ``fsdp`` that replicated the batch
    and all-reduced a full-batch activation after every contraction
    (tensor parallelism over an axis named fsdp; PERF.md, PR 24). With
    the batch pinned to the data axes the partitioner moves the
    weights instead (ZeRO-3: all-gather before use, reduce-scatter of
    the gradients).

    The identity where there is nothing to say: no mesh, one device,
    or inside a region that is already manual over the mesh's axes
    (``pipeline_transformer`` runs ``transformer.Block`` in one; a
    constraint there names axes that are of type Manual and is
    refused; the test ``ops/attention.py:_shard_over_mesh`` makes).
    """
    if jax_compat.nothing_to_partition(mesh):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _tree_paths(tree, prefix=""):
    if isinstance(tree, dict):
        for key, value in tree.items():
            yield from _tree_paths(value, prefix + str(key) + "/")
    else:
        yield prefix.rstrip("/"), tree


def _rebuild(tree, flat, prefix=""):
    if isinstance(tree, dict):
        return {
            key: _rebuild(value, flat, prefix + str(key) + "/")
            for key, value in tree.items()
        }
    return flat[prefix.rstrip("/")]


def fsdp_auto_spec(shape, mesh, axis="fsdp", min_size=2**14):
    """ZeRO-style: shard the largest divisible dim over the fsdp axis;
    small params stay replicated (sharding them costs more in gathers
    than it saves in HBM)."""
    if axis not in mesh.shape or mesh.shape[axis] == 1:
        return P()
    if int(np.prod(shape)) < min_size:
        return P()
    axis_size = mesh.shape[axis]
    dims = sorted(
        range(len(shape)), key=lambda d: shape[d], reverse=True
    )
    for dim in dims:
        if shape[dim] % axis_size == 0:
            spec = [None] * len(shape)
            spec[dim] = axis
            return P(*spec)
    return P()


def infer_state_shardings(state, mesh, rules: ShardingRules = None):
    """Build a TrainState-shaped tree of NamedShardings.

    params/opt_state follow the rules (or fsdp auto-sharding); step and
    model_state (batch stats etc.) are replicated. Optimizer slot state
    inherits its parameter's spec (ZeRO: momentum/variance shard with the
    weight).
    """
    param_specs = {}
    for path, value in _tree_paths(state.params):
        if rules is not None:
            spec = rules.spec_for(path, value.shape)
        else:
            spec = fsdp_auto_spec(value.shape, mesh)
        param_specs[path] = spec

    def shard_params_like(tree):
        flat = {}
        for path, value in _tree_paths(tree):
            flat[path] = NamedSharding(mesh, param_specs[path])
        return _rebuild(tree, flat)

    def shard_opt_state(opt_state):
        # Optimizer state mirrors the params pytree inside each optax
        # sub-state; leaves with a matching path take the param's spec,
        # everything else (counters, scalars) is replicated.
        param_shapes = {
            path: value.shape for path, value in _tree_paths(state.params)
        }

        def map_leaf_with_path(path_tuple, leaf):
            path = "/".join(
                str(getattr(k, "key", getattr(k, "idx", k)))
                for k in path_tuple
            )
            # find the param path as a '/'-bounded suffix of the
            # opt-state path ('out_proj/kernel' must not match
            # 'proj/kernel')
            for p_path, spec in param_specs.items():
                if (
                    path == p_path or path.endswith("/" + p_path)
                ) and leaf.shape == param_shapes[p_path]:
                    return NamedSharding(mesh, spec)
            return NamedSharding(mesh, P())

        return jax.tree_util.tree_map_with_path(
            map_leaf_with_path, opt_state
        )

    from elasticdl_tpu.train.train_state import TrainState

    return TrainState(
        step=NamedSharding(mesh, P()),
        params=shard_params_like(state.params),
        model_state=jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), state.model_state
        ),
        opt_state=shard_opt_state(state.opt_state),
    )
