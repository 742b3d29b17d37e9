"""Device mesh construction for the elastic SPMD worker set.

The reference's parallelism topology is worker pods x PS pods connected
by gRPC; its only "mesh" is the Horovod ring. On TPU the topology is a
``jax.sharding.Mesh`` over ICI-connected chips, with six logical axes:

- ``dp``   — pure data parallelism (params replicated)
- ``fsdp`` — data parallelism with parameter/optimizer sharding (ZeRO)
- ``pp``   — pipeline parallelism (stage-sharded layer stacks)
- ``tp``   — tensor parallelism (within-layer sharding)
- ``sp``   — sequence/context parallelism (ring attention)
- ``ep``   — expert parallelism: an expert layer's experts (and their
  optimizer state) are divided over it. Outside the expert layer its
  ranks are data shards like ``dp``'s: the batch divides over ``ep``
  too, everything but the experts is reduced over it as over a data
  axis, and the expert layer's exchange (``ops/moe.py``) carries each
  rank's tokens to the ranks that hold their experts and back

Axis sizes multiply to the device count. Defaults put every device on
``dp`` (the reference's data-parallel-only world); model code opts into
the other axes via sharding rules.
"""

import math
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory

logger = _logger_factory("elasticdl_tpu.parallel.mesh")

AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep")
# The batch is sharded over both flavors of data parallelism and over
# the ranks of an expert group, which see tokens of their own.
REPLICA_AXES = ("dp", "fsdp")
DATA_AXES = REPLICA_AXES + ("ep",)


@dataclass
class MeshConfig:
    dp: int = -1  # -1: absorb remaining devices
    fsdp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    devices: list = field(default_factory=list)

    def resolve(self, num_devices=None):
        # validate sizes HERE (not only in the CLI parser): a
        # programmatically built MeshConfig(fsdp=0) would otherwise
        # surface as a bare ZeroDivisionError / numpy reshape error
        for axis in ("fsdp", "pp", "tp", "sp", "ep"):
            if getattr(self, axis) < 1:
                raise ValueError(
                    "mesh axis %s=%d: sizes must be >= 1"
                    % (axis, getattr(self, axis))
                )
        if self.dp < 1 and self.dp != -1:
            raise ValueError(
                "mesh axis dp=%d: must be >= 1, or -1 to absorb the "
                "remaining devices" % self.dp
            )
        devices = list(self.devices) or list(jax.devices())
        if num_devices is not None:
            devices = devices[:num_devices]
        n = len(devices)
        fixed = self.fsdp * self.pp * self.tp * self.sp * self.ep
        dp = self.dp
        if dp == -1:
            if n % fixed != 0:
                raise ValueError(
                    "%d devices not divisible by fsdp*pp*tp*sp*ep=%d"
                    % (n, fixed)
                )
            dp = n // fixed
        if dp * fixed != n:
            raise ValueError(
                "Mesh %dx%dx%dx%dx%dx%d != %d devices"
                % (dp, self.fsdp, self.pp, self.tp, self.sp, self.ep, n)
            )
        return (dp, self.fsdp, self.pp, self.tp, self.sp, self.ep, devices)


def parse_mesh_spec(spec: str) -> "MeshConfig | None":
    """Parse the CLI mesh string, e.g. ``"dp=4,fsdp=2"``. Unnamed axes
    default (dp absorbs the remaining devices). Empty string -> None."""
    spec = (spec or "").strip()
    if not spec:
        return None
    sizes = {}
    for part in spec.split(","):
        name, _, value = part.partition("=")
        name = name.strip()
        if name not in AXES:
            raise ValueError(
                "unknown mesh axis %r (valid: %s)" % (name, ", ".join(AXES))
            )
        if name in sizes:
            raise ValueError("duplicate mesh axis %r in %r" % (name, spec))
        try:
            sizes[name] = int(value)
        except ValueError:
            raise ValueError(
                "mesh axis %r needs an integer size, e.g. %s=2 (got %r)"
                % (name, name, value)
            ) from None
        # catch bad sizes HERE with the axis name attached: a negative
        # or zero size would otherwise surface much later as a baffling
        # numpy reshape / "not divisible" error inside build_mesh
        # (dp=-1 alone is the documented absorb-the-rest value)
        if sizes[name] < 1 and not (name == "dp" and sizes[name] == -1):
            raise ValueError(
                "mesh axis %s=%d: sizes must be >= 1 (only dp may be -1 "
                "to absorb the remaining devices)" % (name, sizes[name])
            )
    return MeshConfig(**sizes)


def build_mesh(config: MeshConfig = None, num_devices=None) -> Mesh:
    config = config or MeshConfig()
    *shape, devices = config.resolve(num_devices)
    shape = tuple(shape)
    try:
        # Topology-aware placement: on a real TPU slice this assigns mesh
        # neighbors to ICI torus neighbors so GSPMD collectives ride
        # adjacent links instead of hopping across the slice.
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(
            shape, devices=devices
        )
    except Exception as e:
        # Fallback (virtual CPU devices, unusual shapes): enumeration
        # order — correct, just not topology-optimal. Routine on CPU
        # meshes, so log-and-degrade at debug.
        logger.debug("topology-aware device mesh unavailable: %s", e)
        device_array = np.array(devices).reshape(shape)
    return Mesh(device_array, AXES)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch dim sharded over all data axes; feature dims replicated."""
    return NamedSharding(mesh, P(DATA_AXES))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_parallel_size(mesh: Mesh) -> int:
    return int(
        math.prod(mesh.shape[a] for a in DATA_AXES if a in mesh.shape)
    )
