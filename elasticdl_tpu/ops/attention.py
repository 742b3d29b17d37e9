"""Attention dispatch: Pallas flash kernel on TPU, XLA math elsewhere.

``dot_product_attention`` is the op model code calls; the implementation
is picked by backend (or forced via ``impl=``):

- ``"pallas"``  — ops/flash_attention.py blockwise kernel (TPU)
- ``"xla"``     — plain jnp softmax attention (any backend; also the
                  correctness oracle the kernel is tested against)
- ``"auto"``    — pallas on TPU when shapes allow, else xla
"""

import functools
import math

import jax
import jax.numpy as jnp

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.ops import flash_attention as _flash

logger = _logger_factory("elasticdl_tpu.ops.attention")


def xla_attention(q, k, v, causal=False, sm_scale=None, mask=None):
    """Reference O(S^2) attention over (batch, heads, seq, dim); v, and
    so the output, may have a width of its own (the scale is q's); k
    and v may have a head for every ``group`` query heads, and are
    repeated here (the kernel reads them uncopied). ``mask``: a layout
    of ``ops/flash_attention.py`` in ``causal``'s place; the dense mask
    is the layout's ``keep``, the function the kernels' tiles apply."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    layout = _flash.as_layout(causal if mask is None else mask)
    if layout != _flash.FULL:
        seq_q, seq_k = s.shape[-2], s.shape[-1]
        q_pos = jnp.arange(seq_q)[:, None]
        k_pos = jnp.arange(seq_k)[None, :]
        s = jnp.where(layout.keep(q_pos, k_pos), s, _flash.NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _pallas_refusal(q, k, v, block_q, block_k, layout=_flash.CAUSAL):
    """Why the flash kernel cannot take these shapes under this
    layout; "" when it can."""
    seq_q, seq_k = q.shape[2], k.shape[2]
    # None = flash_attention's auto-tuner picks the block; ask it what
    # it would pick so this gate can't drift from the tuner's fallback
    tiles = [
        _flash._blocks(
            seq_q, seq_k, q.shape[-1], q.dtype, block_q, block_k,
            backward=backward, v_dim=v.shape[-1], layout=layout)
        for backward in (False, True)]
    block_q, block_k = tiles[0]
    if seq_q % block_q or seq_k % block_k:
        return "seq (%d, %d) not divisible by blocks (%d, %d)" % (
            seq_q, seq_k, block_q, block_k,
        )
    if seq_q < 8 or seq_k < 128:
        # below one lane tile the kernel buys nothing
        return "seq (%d, %d) below one (8, 128) tile" % (seq_q, seq_k)
    return next(filter(None, (
        layout.refusal(seq_q, seq_k, *pair) for pair in tiles)), "")


def _flash_facts(q, k, v, causal, block_q, block_k):
    """What the flash kernel does with these shapes, for the log line:
    where k and v have fewer heads than q, their count and the group
    (``kv_heads=2 group=8``); where v has a width of its own, both widths and how the q / k one
    is laid on the lanes (``head q/k=192 v=128 layout=whole``:
    ``flash_attention.QK_LAYOUT``); which backward
    (``flash_attention.backward_schedule``; a model's float32 init
    trace may read ``split`` where its bfloat16 step reads ``fused``:
    the dtype is on the line for that; ``fused dq_buffers=1`` where
    the fused kernel holds dq's whole-head output block in one buffer
    to fit its budget, ``flash_attention.fused_dq_buffers``, and the
    bare word wherever it has the pipeline's two); and how many of
    one head's (q-block, k-block) grid steps compute a tile, how many
    of those apply the causal mask, and how many are skipped
    (``flash_attention.causal_pairs``): the forward's, and the
    backward's where ``_blocks`` gives it other blocks. A layout that
    walks runs (``Band``) counts the steps of the grid that runs, the
    backward's on its (k-block, q-block) grid, and says the length of
    the inner axis after them (``run_len=2``)."""
    shapes = (q.shape[2], k.shape[2], q.shape[-1], q.dtype)
    v_dim = v.shape[-1]
    layout = _flash.as_layout(causal)
    # the diagonal's line is what it always was; another layout's says
    # which, and the tiles its counts are of
    other = layout not in (_flash.CAUSAL, _flash.FULL)

    def pairs(backward):
        blocks = _flash._blocks(
            *shapes, block_q, block_k, backward=backward, v_dim=v_dim,
            layout=layout)
        counts = "run=%d masked=%d skipped=%d" % _flash.causal_pairs(
            *shapes[:2], *blocks, causal=layout, k_outer=backward)
        steps = _flash._inner_steps(
            layout, *blocks, shapes[0] // blocks[0], shapes[1] // blocks[1],
            k_outer=backward)
        return counts + (" blocks=%dx%d" % blocks if other else ""), steps

    (forward, steps), (backward, back_steps) = pairs(False), pairs(True)
    run_len = "" if not hasattr(layout, "run") else " run_len=%d%s" % (
        steps, "" if back_steps == steps else " (backward %d)" % back_steps)
    widths = "" if v_dim == q.shape[-1] else (
        "head q/k=%d v=%d layout=%s, " % (
            q.shape[-1], v_dim, _flash.QK_LAYOUT))
    if k.shape[1] != q.shape[1]:
        widths = "kv_heads=%d group=%d, %s" % (
            k.shape[1], q.shape[1] // k.shape[1], widths)
    dq_buffers = _flash.fused_dq_buffers(
        *shapes, block_q, block_k, v_dim, layout=layout)
    schedule = ("split", "fused dq_buffers=1", "fused")[dq_buffers]
    return "%sflash backward=%s, %spairs %s%s%s" % (
        widths,
        schedule,
        "mask=%s " % layout if other else "",
        forward,
        " (backward %s)" % backward if backward != forward else "",
        run_len,
    )


@functools.lru_cache(maxsize=None)
def _log_auto_once(backend, impl, reason, q_shape, q_dtype, flash):
    """One line per distinct resolution (this runs at trace time, once
    per attention layer per trace). A TPU backend that resolves to the
    XLA reference is a warning: the O(S^2) path is running where the
    kernel was expected. ``flash``: ``_flash_facts`` where the kernel
    runs."""
    log = (
        logger.warning if backend == "tpu" and impl == "xla"
        else logger.info
    )
    log(
        "attention impl=auto resolved to %s (backend=%s, q=%s %s%s%s)",
        impl, backend, q_shape, q_dtype,
        ", reason: %s" % reason if reason else "",
        ", %s" % flash if flash else "",
    )


def _shard_over_mesh(kernel, mesh, spec, q):
    """Run ``kernel(q, k, v)`` per shard of ``spec`` (the caller's
    layout of q/k/v and of the output over ``mesh``). A ``pallas_call``
    has no GSPMD partitioning rule: in a jit over more than one device
    jax refuses it ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" — first met on
    four v5e chips, PR 21), so the manual region is what lets the
    kernel run on a mesh at all, and each chip then computes only its
    shard's attention. Inside a region that is already manual over the
    whole mesh (the pipeline's stage body) q/k/v are one shard and the
    kernel runs on them as is: a second shard_map cannot open there."""
    if jax_compat.nothing_to_partition(mesh):
        return kernel
    for dim, axes in enumerate(spec):
        if axes is None:
            continue
        axes = (axes,) if isinstance(axes, str) else axes
        ways = math.prod(mesh.shape[a] for a in axes)
        if q.shape[dim] % ways:
            raise ValueError(
                "flash attention over mesh %s: dim %d of q=%s does not "
                "divide over %s=%d; pick impl='xla' for shapes that do "
                "not" % (dict(mesh.shape), dim, q.shape, axes, ways)
            )
    return jax_compat.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )


def dot_product_attention(
    q,
    k,
    v,
    causal=False,
    sm_scale=None,
    impl="auto",
    block_q=None,
    block_k=None,
    interpret=False,
    mesh=None,
    spec=None,
    note="",
    mask=None,
):
    """q/k/v are (batch, heads, seq, dim); k and v may have a head for
    every ``group`` query heads. ``mask``: a layout of
    ``ops/flash_attention.py`` (``BlockDiffusion(half_len, block)``,
    ``Band(window)``) in the place of the boolean ``causal``; both
    implementations read it, and the resolution's line names it
    (``mask=window(512)``). ``mesh`` and ``spec``: the mesh the
    caller's step is sharded over and the PartitionSpec of q/k/v on it;
    the Pallas kernel then runs inside a shard_map over them. ``note``:
    what the caller wants on the resolution's log line beside the
    kernel's own facts (``gate=sigmoid rotary=64/256``)."""
    layout = _flash.as_layout(causal if mask is None else mask)
    if impl == "auto":
        backend = jax.default_backend()
        reason = (
            _pallas_refusal(q, k, v, block_q, block_k, layout)
            if backend == "tpu"
            else "the Pallas kernel needs a TPU backend"
        )
        impl = "xla" if reason else "pallas"
        _log_auto_once(
            backend, impl, reason, tuple(q.shape), q.dtype.name,
            "" if reason else ", ".join(filter(None, (
                note, _flash_facts(q, k, v, layout, block_q, block_k)))),
        )
    if impl == "pallas":
        kernel = functools.partial(
            _flash.flash_attention, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            mask=mask,
        )
        if mesh is not None:
            kernel = _shard_over_mesh(kernel, mesh, spec, q)
        return kernel(q, k, v)
    if impl == "xla":
        return xla_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, mask=mask)
    raise ValueError("unknown attention impl %r" % (impl,))
