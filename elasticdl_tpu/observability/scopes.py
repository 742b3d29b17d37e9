"""The program's scopes, by name: one registry from what a
``jax.named_scope`` writes into an operation's ``op_name`` to the
FAMILY of work it is (ISSUE 62). Pure strings: imports neither jax nor
flax, so the benchmark's CPU children and its parent read it too.

Bytes and seconds are told in this one vocabulary:
``observability/device.py:peak_live`` groups what a step holds in HBM
by ``op_scope``, ``device.py:scope_mix`` names at compile the fusions
that hold two families' work, and ``benchmark/lib/step_account.py``
charges every operation of a traced step to ``family``.

A model that opens a new ``jax.named_scope`` registers it in
``FAMILIES`` (or ``CONTAINERS``) below: ``tests/test_scopes.py`` walks
the ASTs of ``models/``, ``ops/`` and ``train/`` and fails on a scope
this table does not know.
"""

import re

_JIT_PART_RE = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPER_RE = re.compile(r"[\w.]+\(")
_INDEX_RE = re.compile(r"_\d+$")

UNNAMED = "unnamed"

# family -> the scopes that are its work. ``name/`` is a group: every
# ``name/<part>`` below it (``mla/q_proj``), the part being the next
# name on the path; any other entry is the scope's whole name, of one
# or two names (``dense_mlp``, ``mtp/proj``).
FAMILIES = {
    # softmax attention of every kind: projections, rotation, the
    # flash kernels, latent attention, the learned indexer
    "attention": ("attn_full/", "attn_window/", "mla/", "dsa/"),
    # what stands in attention's place in a linear / state-space /
    # convolution block
    "mixer": ("gdn/", "kda/", "mamba/", "short_conv/"),
    # a block's second sublayer, dense or experts
    "mlp": ("dense_mlp", "moe/"),
    # the residual stream between sublayers: a block's norms and adds,
    # the hyper-connections
    "residual": ("residual/", "mhc/"),
    # from the tokens in and to the loss out: the embedding, the final
    # norm, the head, the loss, a looped model's exits, the prediction
    # module's own projection and head
    "head_loss": (
        "embed", "final_norm", "head", "loss", "exit/", "mtp/proj",
        "mtp/head", "looped/exit_norm"),
    # ``train/step_fns.py`` around the model: the cast of the
    # parameters, micro-batching, the health scalars, the update
    "step": ("cast_params", "micro_batch", "health", "optimizer"),
    # an objective's own work on the inputs (block diffusion)
    "objective": ("bd/",),
}
# scopes that only hold others: no family of their own, the deepest
# registered scope inside decides
CONTAINERS = ("forward", "looped/pass", "mtp/block")

# Mosaic kernel-name prefix -> scope, for a kernel whose ``op_name``
# lost its scope (the backward of a ``custom_vjp`` runs outside the
# forward's ``named_scope``); the longest prefix wins. ``gated_norm_*``
# (``ops/gated_norm.py``) is deliberately absent: one kernel pair runs
# under three scopes (``mamba/out_norm``, ``gdn/out_norm``,
# ``kda/out_norm``), so its name can say none of them; its backward
# opens the caller's scope inside the VJP and the ``op_name`` decides
KERNELS = {
    "flash_band": "attn_window/flash",
    "flash_sparse": "dsa/attend",
    "flash": "attn_full/flash",
    "rotary_": "attn_full/rotary",
    "dsa_select": "dsa/select",
    "dsa_mask": "dsa/scores",
    "dsa_indexer_loss": "dsa/indexer_loss",
    "gdn_": "gdn/scan",
    "kda_": "kda/scan",
    "ssd": "mamba/scan",
    "short_conv_": "short_conv/gate",
    "gmm": "moe/experts",
    "tgmm": "moe/experts",
    "mhc_pre": "mhc/pre",
    "mhc_post": "mhc/post",
}

_GROUPS = {
    scope[:-1]: family for family, scopes in FAMILIES.items()
    for scope in scopes if scope.endswith("/")
}
_EXACT = {
    scope: family for family, scopes in FAMILIES.items()
    for scope in scopes if not scope.endswith("/")
}
_KERNEL_PREFIXES = sorted(KERNELS, key=len, reverse=True)


def op_scope(op_name):
    """(scope, direction) of an instruction's ``op_name``: the path cut
    to the program's own names (``jit(...)`` parts and the primitive at
    the end dropped, ``jvp(`` / ``transpose(`` unwrapped, a trailing
    index folded so that every block's buffers are one group:
    ``forward/TransformerLM/block_*/attn``), and ``backward`` under a
    ``transpose(``, ``recompute`` under ``checkpoint`` or
    ``rematted_computation``, else ``forward``."""
    if "checkpoint" in op_name or "rematted_computation" in op_name:
        direction = "recompute"
    elif "transpose(" in op_name:
        direction = "backward"
    else:
        direction = "forward"
    # ``transpose(jvp(forward))/M/jit(_take)/gather`` -> forward/M
    path = _WRAPPER_RE.sub("", _JIT_PART_RE.sub("", op_name))
    names = [
        _INDEX_RE.sub("_*", name)
        for name in path.replace(")", "").split("/")[:-1]
        if name and name not in ("checkpoint", "rematted_computation")
    ]
    # the forward's scopes repeat inside a backward that recomputes
    # them (``transpose(jvp(forward))/M/jvp(forward)/M/checkpoint``)
    if names and names[0] in names[1:]:
        names = names[len(names) - 1 - names[::-1].index(names[0]):]
    return "/".join(names) or "unscoped", direction


def family_of(scope):
    """The family of a registered scope (``mla/q_proj`` -> attention);
    None for a container and for a name the registry does not know."""
    if scope in _EXACT:
        return _EXACT[scope]
    if scope in CONTAINERS:
        return None
    return _GROUPS.get(scope.split("/", 1)[0])


def known(scope):
    """Whether the registry knows a ``jax.named_scope`` argument: a
    family's scope, a part of a family's group, or a container."""
    return scope in CONTAINERS or family_of(scope) is not None


def time_direction(op_name):
    """Where an operation's TIME goes: ``recompute`` is the forward run
    again inside a backward (jax names it ``rematted_computation``);
    what else lies under a rematerialised block's ``checkpoint`` is its
    backward proper, which ``op_scope`` counts as ``recompute`` for
    bytes (the buffers of a block's backward live and die together)."""
    if "rematted_computation" in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


def _deepest(names):
    """The deepest registered scope on a path of names, or None. At
    each name from the path's end: the scope it closes with the name
    before it (``mtp/proj``, a group's part: ``mla/q_proj``), then the
    scope it is alone (``dense_mlp``; a group's name at the path's end
    has no part). Containers are passed over."""
    for at in range(len(names) - 1, -1, -1):
        name = names[at]
        if at:
            pair = "%s/%s" % (names[at - 1], name)
            if pair in _EXACT or names[at - 1] in _GROUPS:
                return pair
        if name in _EXACT or name in _GROUPS:
            return name
    return None


def family(op_name, kernel=None):
    """``(family, scope, direction)`` of one operation. The deepest
    registered scope on the ``op_name``'s path decides (an expert layer
    inside ``mtp/block`` is ``mlp``); without one, a Mosaic kernel's
    name does by ``KERNELS``; else ``(unnamed, <op_scope's path>,
    direction)``. ``direction`` is ``time_direction``'s."""
    path, _ = op_scope(op_name or "")
    direction = time_direction(op_name or "")
    scope = _deepest(path.split("/"))
    if scope is None and kernel:
        lowered = kernel.lower()
        for prefix in _KERNEL_PREFIXES:
            if lowered.startswith(prefix):
                scope = KERNELS[prefix]
                break
    if scope is None:
        return UNNAMED, path, direction
    return family_of(scope), scope, direction
