"""perf-bare-collective: a raw ``jax.lax`` collective outside the
``parallel/`` / ``ops/`` scopes that own cross-device communication.

``parallel/collectives.py`` is the one sanctioned spelling of an
explicit in-body collective everywhere else in the tree, for two
load-bearing reasons:

1. **AD correctness in unchecked manual regions.** Under
   ``check_vma=False`` jax 0.9 still binds the pmap-era
   ``transpose(psum) = psum`` rule, which silently scales
   gradients by the axis size when the collective is differentiated
   INSIDE a shard_map body — exactly what the 1f1b pipeline schedule
   does to every stage function. ``mesh_psum`` pins the modern
   transpose (identity) via a custom_vjp; a bare ``lax.psum`` in a
   model or training scope is a latent 2x-gradient bug that no test
   catches until someone runs that model on tp>1.

2. **Byte accounting.** The dense-plane telemetry
   (``collective_bytes_per_step``) is summed from the helpers' ring-
   cost recorder at trace time. A bare collective moves bytes the
   telemetry never sees, so /statusz under-reports ICI traffic.

What fires: any call whose callee resolves to a ``jax.lax`` /
``lax``-prefixed (or bare-imported) collective —
``psum``, ``pmean``, ``psum_scatter``, ``all_gather``, ``all_to_all``,
``all_reduce`` — in a module outside ``elasticdl_tpu.parallel.`` and
``elasticdl_tpu.ops.``. Those two scopes implement the helpers and the
hand-scheduled kernels; everywhere else routes through
``parallel.collectives.mesh_*``.

A legitimate exception carries
``# edlint: disable=perf-bare-collective`` with the reason on the
suppression line.
"""

import ast

from elasticdl_tpu.analysis.core import (
    Finding,
    attr_chain,
    walk_with_scope,
)

RULE = "perf-bare-collective"

_COLLECTIVE_LEAVES = {
    "psum",
    "pmean",
    "psum_scatter",
    "all_gather",
    "all_to_all",
    "all_reduce",
}

# scopes that OWN communication: the helper module itself, the manual
# pipeline/tensor-parallel schedules, and the hand-written kernels
_ALLOWED_PREFIXES = (
    "elasticdl_tpu.parallel.",
    "elasticdl_tpu.ops.",
)


def _in_scope(module):
    if not module.startswith("elasticdl_tpu."):
        return False
    return not any(module.startswith(p) for p in _ALLOWED_PREFIXES)


def _collective_leaf(func):
    """The collective's name when ``func`` is a raw lax collective
    (``jax.lax.psum``, ``lax.psum``, or a bare ``psum`` from
    ``from jax.lax import psum``), else None. The ``mesh_*`` helpers
    have different leaf names and never match."""
    if isinstance(func, ast.Name):
        return func.id if func.id in _COLLECTIVE_LEAVES else None
    chain = attr_chain(func)
    if chain is None:
        return None
    parts = chain.split(".")
    leaf = parts[-1]
    if leaf not in _COLLECTIVE_LEAVES:
        return None
    # attribute calls must come off a lax module; `store.all_gather`
    # or `self.psum` style methods are not collectives
    return leaf if parts[-2] == "lax" else None


def run(units):
    findings = []
    for unit in units:
        if not _in_scope(unit.module):
            continue
        for node, scope in walk_with_scope(unit.tree):
            if not isinstance(node, ast.Call):
                continue
            leaf = _collective_leaf(node.func)
            if leaf is None:
                continue
            findings.append(
                Finding(
                    rule=RULE,
                    path=unit.path,
                    line=node.lineno,
                    symbol=scope,
                    code="lax.%s()" % leaf,
                    message=(
                        "bare lax.%s outside parallel/+ops/: use "
                        "parallel.collectives.mesh_%s — the helper "
                        "pins the correct psum transpose for vjp "
                        "inside shard_map (under check_vma=False the "
                        "bare spelling silently scales grads by the "
                        "axis size) and records the bytes the dense-plane "
                        "telemetry reports"
                        % (leaf, "psum" if leaf == "all_reduce" else leaf)
                    ),
                )
            )
    return findings
