"""Mesh collective helpers: the one sanctioned spelling of cross-device
reductions outside compiler-inserted GSPMD.

Two reasons every in-body collective routes through here instead of
bare ``jax.lax.psum``/``all_gather`` (enforced by the edlint rule
``perf-bare-collective``):

1. **Correct AD in unchecked manual regions.** With ``check_vma=True``
   jax types a psum's output as invariant over the reduced axes and
   transposes it to a vary-cast. With ``check_vma=False`` — which the
   ring-attention flash fold still needs — ``jax.lax.psum`` still
   binds the pmap-era primitive whose transpose is another psum. That
   convention is wrong for a ``jax.vjp`` taken *inside* a shard_map
   body: the cotangent of a psum output is already replicated over the
   reduced axes, so psumming it again scales gradients by the axis
   size. The 1f1b pipeline schedule takes exactly such an in-body vjp
   of the user's stage function, which is how a Megatron-style
   ``psum(h @ W2, "tp")`` stage once produced 2x gradients for every
   tp-sharded leaf on tp=2. ``mesh_psum`` pins the vary-cast transpose
   in both modes via a custom_vjp.

2. **Byte accounting.** The dense-plane telemetry (collective bytes
   per step) needs to know how much traffic a step puts on the ICI.
   Helpers record ring-algorithm byte estimates into an ambient
   :class:`CollectiveBytes` accumulator at trace time, so a single
   traced step yields the per-step figure without touching the hot
   path at run time.
"""

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from elasticdl_tpu.common import jax_compat

__all__ = [
    "CollectiveBytes",
    "axis_size_product",
    "mesh_all_gather",
    "mesh_pmean",
    "mesh_psum",
    "mesh_reduce_scatter",
    "track_collective_bytes",
]


@dataclass
class CollectiveBytes:
    """Trace-time estimate of bytes a step moves over the interconnect.

    Ring-algorithm costs per participating device, with ``n`` the
    number of devices in the collective and ``B`` the payload bytes:
    all-reduce ``2B(n-1)/n``, reduce-scatter and all-gather each
    ``B(n-1)/n``. These are the standard bandwidth-optimal figures and
    match what XLA's ring implementations move on ICI.
    """

    all_reduce: int = 0
    reduce_scatter: int = 0
    all_gather: int = 0
    calls: int = 0
    by_kind: dict = field(default_factory=dict)

    @property
    def total(self):
        return self.all_reduce + self.reduce_scatter + self.all_gather

    def record(self, kind, payload_bytes, axis_size):
        if axis_size <= 1:
            return
        ring = payload_bytes * (axis_size - 1) // axis_size
        if kind == "all_reduce":
            self.all_reduce += 2 * ring
        elif kind == "reduce_scatter":
            self.reduce_scatter += ring
        elif kind == "all_gather":
            self.all_gather += ring
        else:
            raise ValueError("unknown collective kind %r" % (kind,))
        self.calls += 1
        self.by_kind[kind] = self.by_kind.get(kind, 0) + 1


_ambient = threading.local()


@contextmanager
def track_collective_bytes(acc: CollectiveBytes = None):
    """Accumulate collective byte estimates from helpers traced inside
    the ``with`` block. Yields the accumulator. Reentrant: nested
    blocks each see only their own calls plus inner blocks'."""
    acc = acc if acc is not None else CollectiveBytes()
    stack = getattr(_ambient, "stack", None)
    if stack is None:
        stack = _ambient.stack = []
    stack.append(acc)
    try:
        yield acc
    finally:
        stack.pop()


def _record(kind, x, axis_size):
    stack = getattr(_ambient, "stack", None)
    if not stack:
        return
    payload = 0
    for leaf in jax.tree_util.tree_leaves(x):
        aval = jax.core.get_aval(leaf)
        payload += int(aval.size) * int(
            jnp.dtype(getattr(aval, "dtype", jnp.float32)).itemsize
        )
    for acc in stack:
        acc.record(kind, payload, axis_size)


def _normalize_axes(axes):
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def axis_size_product(axes, mesh=None):
    """Product of the named axis sizes: from ``mesh`` when given, else
    from the enclosing manual region (``jax.lax.axis_size``). An axis
    neither resolves is an error — a silent 1 would turn ``mesh_pmean``
    into a sum."""
    n = 1
    for axis in _normalize_axes(axes):
        if mesh is not None and axis in mesh.shape:
            n *= int(mesh.shape[axis])
            continue
        try:
            n *= int(jax.lax.axis_size(axis))
        except NameError:
            raise ValueError(
                "mesh axis %r is bound by neither mesh=%s nor an "
                "enclosing shard_map" % (
                    axis,
                    None if mesh is None else dict(mesh.shape),
                )
            ) from None
    return n


def mesh_psum(x, axes, *, mesh=None):
    """All-reduce ``x`` over the named mesh ``axes``; the transpose of
    an all-reduce whose output is replicated over ``axes`` is the
    identity (a vary-cast), NOT another psum. Safe to call from code
    that is differentiated inside a shard_map body — which bare
    ``jax.lax.psum`` is not under ``check_vma=False`` (see module
    docstring)."""
    axes = _normalize_axes(axes)
    if mesh is not None:
        # size-1 axes reduce over nothing; dropping them here makes the
        # helper a true no-op on a collapsed mesh (and callable outside
        # a manual region, where the axis name is unbound)
        axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    if not axes:
        return x
    _record("all_reduce", x, axis_size_product(axes, mesh))

    @jax.custom_vjp
    def _allreduce(v):
        # edlint: disable=perf-bare-collective — this IS the helper
        return jax.lax.psum(v, axes)

    def _fwd(v):
        return _allreduce(v), None

    def _bwd(_, ct):
        return (jax_compat.pvary(ct, axes),)

    _allreduce.defvjp(_fwd, _bwd)
    return _allreduce(x)


def mesh_pmean(x, axes, *, mesh=None):
    """Mean-reduce over the named axes; same AD contract as
    :func:`mesh_psum`."""
    axes = _normalize_axes(axes)
    if not axes:
        return x
    size = axis_size_product(axes, mesh)
    summed = mesh_psum(x, axes, mesh=mesh)
    return jax.tree_util.tree_map(lambda v: v / size, summed)


def mesh_reduce_scatter(x, axis, *, scatter_dimension=0, tiled=True,
                        mesh=None):
    """Reduce-scatter over one named axis: each shard ends holding the
    fully-reduced slice of ``x`` along ``scatter_dimension``. Half the
    traffic of an all-reduce — the dense data plane's grad reduction
    primitive when optimizer state is sharded over the same axis."""
    _record("reduce_scatter", x, axis_size_product((axis,), mesh))
    # edlint: disable=perf-bare-collective — this IS the helper
    return jax.lax.psum_scatter(
        x, axis, scatter_dimension=scatter_dimension, tiled=tiled
    )


def mesh_all_gather(x, axis, *, gather_dimension=0, tiled=True,
                    mesh=None):
    """All-gather over one named axis; the inverse of
    :func:`mesh_reduce_scatter` for re-materializing a sharded value."""
    _record("all_gather", x, axis_size_product((axis,), mesh))
    # edlint: disable=perf-bare-collective — this IS the helper
    return jax.lax.all_gather(
        x, axis, axis=gather_dimension, tiled=tiled
    )
