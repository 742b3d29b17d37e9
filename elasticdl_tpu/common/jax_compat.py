"""The shard_map spellings the parallel/ops stack routes through
one place, for the installed runtime (jax 0.9.0, pinned in
pyproject.toml).

No version probing: support for a JAX that is not installed is not
kept. What remains is here because each call site would otherwise
repeat a detail — when ``check_vma`` may be switched off, and the
empty-axes no-op of a vary-cast, how to ask whether a region is
already manual, whether anything is left to partition and whether a
Pallas kernel may run.
"""

import jax

__all__ = ["shard_map", "pvary", "manual_over", "nothing_to_partition",
           "kernels_can_run"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map``. A ``pallas_call`` inside a checked region must
    declare its outputs' vma (ops/flash_attention.py ``_out_struct``);
    ``check_vma=False`` is left for the ring-attention flash fold, which
    the checker still refuses (ops/ring_attention.py)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def pvary(x, axes):
    """Cast ``x`` to device-varying over ``axes`` inside a manual
    region (VMA typing needs the explicit cast to mix literal inits
    with per-device scan state). No axes, no cast."""
    axes = tuple(axes)
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")



def manual_over(mesh):
    """Whether the caller is being traced inside a region that is
    already manual over every axis of ``mesh`` (the pipeline's stage
    body): arrays are one shard there, a second ``shard_map`` over the
    mesh cannot open, and a sharding constraint that names its axes is
    refused (they are of type Manual)."""
    manual = jax.sharding.get_abstract_mesh().manual_axes
    return set(mesh.axis_names) <= set(manual)


def nothing_to_partition(mesh):
    """Whether the arrays of a step over ``mesh`` are whole where the
    caller is being traced: no mesh, one device, or a region already
    manual over the whole mesh. A sharding constraint has nothing to
    say there and a second ``shard_map`` nothing to open."""
    return mesh is None or mesh.size == 1 or manual_over(mesh)


def kernels_can_run(mesh):
    """Whether a ``pallas_call`` may run as it is in a step over
    ``mesh``: on a TPU with nothing to partition. A Mosaic kernel has
    no GSPMD partitioning rule (``ops/attention.py:_shard_over_mesh``),
    so every kernel's chooser asks this before anything of its own."""
    return nothing_to_partition(mesh) and jax.default_backend() == "tpu"


def out_struct(shape, dtype, *operands):
    """The ``out_shape`` of a ``pallas_call`` result that varies over
    every mesh axis any of ``operands`` varies over: inside a
    VMA-checked ``shard_map`` (a region manual over the mesh) a
    ``pallas_call`` must say so itself; anywhere else the set is
    empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
