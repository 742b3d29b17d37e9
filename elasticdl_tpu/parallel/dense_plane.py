"""The dense data plane: what happens to every dense gradient, stated
explicitly.

The reference framework had two dense strategies — push_gradient to the
PS, or Horovod allreduce (AllReduceTrainer). The TPU rebuild keeps
NEITHER on the hot path: dense parameters and optimizer state live
sharded over the mesh (NamedSharding), gradients are reduced by
compiler-inserted collectives inside the one jitted step, and the PS
serves only sparse embedding rows. This module makes that plane
inspectable: given the parameter tree and the mesh, it derives the
per-parameter reduction plan XLA will lower —

- a parameter sharded over ``fsdp`` (ZeRO) gets its gradient
  **reduce-scattered** over ``fsdp`` (each shard keeps only its slice,
  half the traffic of an all-reduce) and the optimizer applies on the
  shard; the remaining ``dp`` extent all-reduces the scattered slice;
- a replicated parameter (small, or no divisible dim — the
  ``fsdp_auto_spec`` min-size fallback) gets a plain **psum**
  (all-reduce) over the full data extent, and every device applies the
  identical update;
- a ``tp``/``pp``-sharded parameter reduces only over the data axes —
  its model-axis shards are *different* values, not partials.

The byte totals use the standard ring-algorithm costs (payload ``B``
over ``n`` devices: all-reduce ``2B(n-1)/n``, reduce-scatter
``B(n-1)/n``), the same figures `parallel/collectives.py` records for
explicit in-body collectives — so the telemetry field
``collective_bytes_per_step`` means the same thing whichever layer
moved the bytes.

Nothing here touches the step function: the plan is derived from
shapes and shardings at trace time, costs nothing per step, and is
exported through the worker TelemetryBlob into FleetMonitor /statusz
and the postmortem timeline.
"""

from dataclasses import dataclass, field

import numpy as np
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.parallel.mesh import REPLICA_AXES
from elasticdl_tpu.parallel.sharding import _tree_paths, fsdp_auto_spec

logger = _logger_factory("elasticdl_tpu.parallel.dense_plane")

__all__ = ["DenseParamPlan", "DensePlan", "plan_dense_plane"]


@dataclass
class DenseParamPlan:
    path: str
    shape: tuple
    nbytes: int
    spec: object  # PartitionSpec
    mode: str  # "reduce_scatter" | "psum" | "local"
    grad_bytes_per_step: int


@dataclass
class DensePlan:
    """The derived reduction plan for one model on one mesh."""

    mesh_shape: dict
    mesh_axes: tuple
    params: list = field(default_factory=list)

    @property
    def param_bytes(self):
        return sum(p.nbytes for p in self.params)

    @property
    def sharded_param_bytes(self):
        return sum(
            p.nbytes for p in self.params if p.mode == "reduce_scatter"
        )

    @property
    def replicated_param_bytes(self):
        return sum(p.nbytes for p in self.params if p.mode == "psum")

    @property
    def collective_bytes_per_step(self):
        return sum(p.grad_bytes_per_step for p in self.params)

    def counts(self):
        out = {}
        for p in self.params:
            out[p.mode] = out.get(p.mode, 0) + 1
        return out

    def mesh_shape_str(self):
        """Compact non-trivial-axes spelling, e.g. ``dp=2,tp=2`` — the
        wire form for TelemetryBlob.mesh_shape (all-axes-1 single chip
        spells ``dp=1``)."""
        parts = [
            "%s=%d" % (axis, size)
            for axis, size in self.mesh_shape.items()
            if size > 1
        ]
        return ",".join(parts) if parts else "dp=1"

    def summary(self):
        counts = self.counts()
        return {
            "mesh_shape": self.mesh_shape_str(),
            "param_bytes": self.param_bytes,
            "sharded_param_bytes": self.sharded_param_bytes,
            "replicated_param_bytes": self.replicated_param_bytes,
            "collective_bytes_per_step": self.collective_bytes_per_step,
            "reduce_scatter_params": counts.get("reduce_scatter", 0),
            "psum_params": counts.get("psum", 0),
            "local_params": counts.get("local", 0),
        }


def _ring(nbytes, n):
    return nbytes * (n - 1) // n if n > 1 else 0


def plan_dense_plane(params, mesh, rules=None):
    """Derive the :class:`DensePlan` for ``params`` (a real or abstract
    param tree) over ``mesh``, using the same spec resolution as
    ``infer_state_shardings`` — so the plan describes exactly the
    layout the trainer will jit with."""
    shape = dict(mesh.shape)
    plan = DensePlan(mesh_shape=shape, mesh_axes=tuple(mesh.axis_names))
    fsdp = shape.get("fsdp", 1)
    dp = shape.get("dp", 1)
    ep = shape.get("ep", 1)
    for path, leaf in _tree_paths(params):
        if rules is not None:
            spec = rules.spec_for(path, leaf.shape)
        else:
            spec = fsdp_auto_spec(leaf.shape, mesh)
        spec = spec if spec is not None else P()
        spec_axes, zero_axes = set(), set()
        for entry in spec:
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            spec_axes.update(names)
            if "fsdp" in names:
                # what shares a dimension with fsdp stores a slice of
                # the parameter as fsdp does (("fsdp", "ep"): the
                # vocabulary's matrices of an expert model)
                zero_axes.update(names)
        nbytes = int(np.prod(leaf.shape or (1,))) * int(
            np.dtype(leaf.dtype).itemsize
        )
        zero = int(np.prod([shape.get(axis, 1) for axis in zero_axes]))
        # the ranks of an expert group are data shards for whatever is
        # not divided over them (parallel/mesh.py)
        replicas = dp * (1 if "ep" in spec_axes else ep)
        data_extent = replicas * (1 if "fsdp" in spec_axes else fsdp)
        if zero_axes:
            # grad reduce-scatters over fsdp (and what stores with it);
            # each scattered slice then all-reduces over the data
            # extent that is left (if any)
            mode = "reduce_scatter"
            grad_bytes = _ring(nbytes, zero) + 2 * _ring(
                nbytes // max(zero, 1), replicas
            )
        elif spec_axes - set(REPLICA_AXES):
            # tp/pp/sp/ep-sharded: each model shard is a distinct
            # value; only the data extent carries partials to reduce
            shard = nbytes
            for axis in spec_axes - set(REPLICA_AXES):
                shard //= max(shape.get(axis, 1), 1)
            if data_extent > 1:
                mode = "psum"
                grad_bytes = 2 * _ring(shard, data_extent)
            else:
                mode = "local"
                grad_bytes = 0
        elif data_extent > 1:
            # replicated small param: plain all-reduce over all data
            # parallelism, identical optimizer apply everywhere
            mode = "psum"
            grad_bytes = 2 * _ring(nbytes, data_extent)
        else:
            mode = "local"
            grad_bytes = 0
        plan.params.append(
            DenseParamPlan(
                path=path,
                shape=tuple(leaf.shape),
                nbytes=nbytes,
                spec=spec,
                mode=mode,
                grad_bytes_per_step=grad_bytes,
            )
        )
    return plan
