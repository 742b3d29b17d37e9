"""Flash attention as Pallas TPU kernels (forward + backward).

Blockwise online-softmax attention: O(S) memory instead of the O(S^2)
score matrix, scores kept in VMEM, matmuls on the MXU. This is the
single-chip building block; sequence parallelism composes it with ring /
all-to-all collectives (ops/ring_attention.py).

No reference counterpart — the reference's models are CTR/vision Keras
nets with no attention anywhere (SURVEY.md §5 "long-context: absent");
this is a new TPU-first capability.

Work, in score-sized matmuls (one is 2 x seq_q x seq_k x head_dim FLOPs
a head, halved by the causal skip): the forward runs 2 (``q k^T``,
``p v``); the backward needs 5 (the scores again, ``dp``, ``dv``,
``dq``, ``dk``) and ``flash_bwd`` runs exactly those, once a (q-block,
k-block) pair: dk and dv accumulate in float32 scratch across the
q-blocks of one k-block, and dq, whose sum runs across the k-blocks,
in a float32 scratch of one head's whole ``(seq_q, head_dim)`` that
stays in VMEM for that head's sweep. That scratch is 16 MiB at
16,384 x 256, so the call states its own VMEM limit
(``_FUSED_VMEM_BYTES``), and ``backward_schedule`` keeps the older pair
for shapes whose count (``fused_bwd_vmem_bytes``) is over it:
``flash_dq`` + ``flash_dkv``, 3 + 4 matmuls, the scores rebuilt twice,
no state that grows with the sequence. One algorithm under two
schedules, chosen by shape: gradients agree to the last bit in
interpret mode (tests/test_attention_ops.py).

Layout: (batch, heads, seq, head_dim); the kernels flatten batch*heads
into one parallel grid axis and see one head's (seq, head_dim) rows.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Lane width of the m/l scratch rows (min f32 tile is (8, 128)).
_STATS_LANES = 128
# checkpoint_name labels on the forward kernel's outputs; remat policies
# reference these (e.g. models/transformer.py) to save o/lse instead of
# re-running the forward flash pass in backward.
FLASH_OUT_NAME = "flash_out"
FLASH_LSE_NAME = "flash_lse"


def _auto_block(seq, cap):
    """Largest power-of-two block <= cap that divides seq (>= 128 when
    possible so blocks stay MXU-tile aligned)."""
    block = cap
    while block > 128 and seq % block:
        block //= 2
    return block if seq % block == 0 else min(seq, 128)


def _blocks(seq_q, seq_k, block_q, block_k):
    """The blocks a call runs with: ``None`` is the largest power of
    two (up to 512 / 1024) that divides the sequence."""
    if block_q is None:
        block_q = _auto_block(seq_q, 512)
    if block_k is None:
        # Smaller causal k-blocks (512) look 30-40% faster in an
        # ISOLATED kernel fwd+bwd micro-bench (above-diagonal blocks
        # skip compute), but inside the full jitted train step the
        # effect is noise at S<=2k and a 1-2% REGRESSION at S=4-8k —
        # XLA's surrounding schedule absorbs the skip and the extra
        # k-iterations cost loop overhead. Defaults follow the in-model
        # measurement; pass block_k explicitly to retune.
        block_k = _auto_block(seq_k, 1024)
    return min(block_q, seq_q), min(block_k, seq_k)


def _causal_mask(s, q_block, k_block, block_q, block_k):
    q_pos = q_block * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0
    )
    k_pos = k_block * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale,
    causal,
    block_q,
    block_k,
):
    q_block = pl.program_id(1)
    k_block = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(k_block == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: blocks strictly above the diagonal contribute nothing.
    diag_ok = (
        (q_block + 1) * block_q - 1 >= k_block * block_k
        if causal
        else True
    )

    @pl.when(diag_ok)
    def _compute():
        # Matmuls run on inputs in their NATIVE dtype with f32 MXU
        # accumulation: for bf16 inputs bf16xbf16->f32 is bit-identical
        # to upcasting first (bf16 products are exact in f32), while an
        # f32xf32 matmul the MXU must emulate in multiple passes runs
        # several times slower. Softmax statistics stay in f32.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = (
            jax.lax.dot_general(
                q,
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )
        if causal:
            s = _causal_mask(s, q_block, k_block, block_q, block_k)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(k_block == num_k - 1)
    def _finalize():
        l_final = l_ref[:, :1]
        # Fully-masked rows (can't happen causally, but keep the kernel
        # total): emit zeros, lse = -inf.
        safe_l = jnp.where(l_final > 0.0, l_final, 1.0)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (
            m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30))
        )


def _q_specs():
    """(q-ish spec, k-ish spec, lse-ish spec) index maps of the merged
    "(bh, seq, d)" view on a ``(bh, q-block, k-block)`` grid."""
    q_idx = lambda b, i, j: (b, i, 0)
    k_idx = lambda b, i, j: (b, j, 0)
    stat_idx = lambda b, i, j: (b, 0, i)
    return q_idx, k_idx, stat_idx


def _out_struct(shape, dtype, *operands):
    """A pallas_call output that varies over every mesh axis any operand
    varies over: inside a VMA-checked ``shard_map`` (the pipeline's
    manual region) a pallas_call must say so itself; anywhere else the
    set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    bh, seq_q, head_dim = q.shape
    seq_k = k.shape[1]
    num_q = seq_q // block_q
    num_k = seq_k // block_k
    grid = (bh, num_q, num_k)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
    )
    q_idx, k_idx, stat_idx = _q_specs()
    # lse rides in (bh, 1, seq) — the singleton axis makes the block's
    # second-minor dim equal the full array dim, satisfying the TPU
    # (8, 128) tiling rule that a 2-D (1, block_q) block violates
    out_shape = (
        _out_struct(q.shape, q.dtype, q, k, v),
        _out_struct((bh, 1, seq_q), jnp.float32, q, k, v),
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, block_k, head_dim), k_idx),
            pl.BlockSpec((1, block_k, head_dim), k_idx),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, 1, block_q), stat_idx),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the kernel's name in the compiled program and in a device
        # trace (benchmark/metrics/flash_time_share.py finds "flash")
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _p_and_ds(q, k, v, do, lse_ref, delta_ref, q_block, k_block,
              sm_scale, causal, block_q, block_k):
    """``p = exp(s - lse)`` and ``ds = p * (dp - delta) * sm_scale`` of
    one (q-block, k-block) pair, both float32: the two score-sized
    matmuls (``q k^T``, ``do v^T``) every backward kernel starts from.
    Native-dtype matmul inputs, f32 accumulation (see _fwd_kernel)."""
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]
    s = (
        jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )
    if causal:
        s = _causal_mask(s, q_block, k_block, block_q, block_k)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do,
        v,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta) * sm_scale


def _dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    dq_acc_ref,
    *,
    sm_scale,
    causal,
    block_q,
    block_k,
):
    q_block = pl.program_id(1)
    k_block = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(k_block == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    diag_ok = (
        (q_block + 1) * block_q - 1 >= k_block * block_k
        if causal
        else True
    )

    @pl.when(diag_ok)
    def _compute():
        k = k_ref[0]
        _, ds = _p_and_ds(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref, delta_ref,
            q_block, k_block, sm_scale, causal, block_q, block_k,
        )
        dq_acc_ref[:] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    @pl.when(k_block == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    *out_and_scratch,
    sm_scale,
    causal,
    block_q,
    block_k,
    with_dq,
):
    """dk and dv of one k-block, accumulated in float32 over the
    q-blocks (grid ``(bh, k-block, q-block)``).

    ``with_dq`` (the fused backward, ``flash_bwd``): dq is accumulated
    from the same ``ds``. Its accumulator must outlive the k-blocks, so
    it is the whole ``(seq_q, head_dim)`` of this ``bh`` in float32,
    and ``dq_ref`` the whole dq of this ``bh`` (an output block whose
    index depends on ``bh`` alone stays in VMEM until ``bh`` moves on).
    A q-block's rows are zeroed when the first k-block meets them and
    cast to the output, once, when the last one has; in between the
    ``ds @ k`` terms arrive in ascending k, as in ``_dq_kernel``."""
    if with_dq:
        (dk_ref, dv_ref, dq_ref,
         dk_acc_ref, dv_acc_ref, dq_acc_ref) = out_and_scratch
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = out_and_scratch
    k_block = pl.program_id(1)
    q_block = pl.program_id(2)
    num_k = pl.num_programs(1)
    num_q = pl.num_programs(2)
    rows = pl.ds(pl.multiple_of(q_block * block_q, block_q), block_q)

    @pl.when(q_block == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    if with_dq:
        @pl.when(k_block == 0)
        def _init_dq():
            dq_acc_ref[rows, :] = jnp.zeros(
                (block_q, dq_acc_ref.shape[1]), jnp.float32
            )

    diag_ok = (
        (q_block + 1) * block_q - 1 >= k_block * block_k
        if causal
        else True
    )

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        p, ds = _p_and_ds(
            q, k, v_ref[0], do, lse_ref, delta_ref,
            q_block, k_block, sm_scale, causal, block_q, block_k,
        )
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype),
            do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = ds.astype(q.dtype)
        dk_acc_ref[:] += jax.lax.dot_general(
            ds,
            q,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if with_dq:
            dq_acc_ref[rows, :] += jnp.dot(
                ds, k, preferred_element_type=jnp.float32
            )

    @pl.when(q_block == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)

    if with_dq:
        @pl.when(k_block == num_k - 1)
        def _finalize_dq():
            dq_ref[0, rows, :] = dq_acc_ref[rows, :].astype(dq_ref.dtype)


# VMEM the fused backward may hold, and the limit its pallas_call states
# (the default scoped limit, 16 MiB, is the size of its accumulator at
# 16,384 x 256). A v5e or v6e core has 128 MiB, a v7x core 64.
_FUSED_VMEM_BYTES = 64 * 2**20


def fused_bwd_vmem_bytes(seq_q, head_dim, block_q, block_k, itemsize):
    """VMEM of ``flash_bwd`` from its shapes, counted generously: dq of
    one ``bh`` as float32 accumulator and double-buffered output block;
    the float32 dk / dv accumulators; q, do, k, v, dk, dv blocks, two
    buffers each; four score-sized float32 temporaries (s, p, dp, ds).
    The v5e compiler takes 16,384 x 256 (blocks 512 / 1024, bfloat16)
    under a limit of 36 MiB and refuses it under 32; this says 47."""
    dq = seq_q * head_dim * (4 + 2 * itemsize)
    kv = block_k * head_dim * (2 * 4 + 4 * 2 * itemsize)
    q_do = block_q * head_dim * 2 * 2 * itemsize
    scores = 4 * block_q * block_k * 4
    return dq + kv + q_do + scores


def backward_schedule(seq_q, seq_k, head_dim, dtype, block_q=None,
                      block_k=None):
    """Which backward these shapes get: ``"fused"`` (one kernel,
    ``flash_bwd``: the scores rebuilt once) where dq's accumulator fits
    the VMEM budget, ``"split"`` (``flash_dq`` + ``flash_dkv``: rebuilt
    twice, no state that grows with the sequence) above it. ``_bwd``
    decides by this and ``ops/attention.py`` logs it."""
    block_q, block_k = _blocks(seq_q, seq_k, block_q, block_k)
    held = fused_bwd_vmem_bytes(
        seq_q, head_dim, block_q, block_k, jnp.dtype(dtype).itemsize
    )
    return "fused" if held <= _FUSED_VMEM_BYTES else "split"


def _bwd(
    q, k, v, o, lse, do, sm_scale, causal, block_q, block_k, interpret,
):
    bh, seq_q, head_dim = q.shape
    seq_k = k.shape[1]
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )[:, None, :]  # (bh, 1, seq): same tiling-friendly layout as lse
    dq_idx = lambda b, j, i: (b, 0, 0)
    num_q = seq_q // block_q
    num_k = seq_k // block_k
    q_idx, k_idx, stat_idx = _q_specs()
    operands = (q, k, v, do, lse, delta)
    statics = dict(
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k
    )
    fuse = backward_schedule(
        seq_q, seq_k, head_dim, q.dtype, block_q, block_k
    ) == "fused"

    def swapped(idx):
        # the dkv grid iterates (bh, k-block, q-block)
        return lambda b, j, i: idx(b, i, j)

    dq_struct = _out_struct(q.shape, q.dtype, *operands)
    dkv_structs = (
        _out_struct(k.shape, k.dtype, *operands),
        _out_struct(v.shape, v.dtype, *operands),
    )
    dkv_in_specs = [
        pl.BlockSpec((1, block_q, head_dim), swapped(q_idx)),
        pl.BlockSpec((1, block_k, head_dim), swapped(k_idx)),
        pl.BlockSpec((1, block_k, head_dim), swapped(k_idx)),
        pl.BlockSpec((1, block_q, head_dim), swapped(q_idx)),
        pl.BlockSpec((1, 1, block_q), swapped(stat_idx)),
        pl.BlockSpec((1, 1, block_q), swapped(stat_idx)),
    ]
    dkv_out_specs = (
        pl.BlockSpec((1, block_k, head_dim), swapped(k_idx)),
        pl.BlockSpec((1, block_k, head_dim), swapped(k_idx)),
    )
    dkv_scratch = [
        pltpu.VMEM((block_k, head_dim), jnp.float32),
        pltpu.VMEM((block_k, head_dim), jnp.float32),
    ]

    if fuse:
        dk, dv, dq = pl.pallas_call(
            functools.partial(_dkv_kernel, with_dq=True, **statics),
            grid=(bh, num_k, num_q),
            in_specs=dkv_in_specs,
            out_specs=dkv_out_specs + (
                pl.BlockSpec((1, seq_q, head_dim), dq_idx),
            ),
            scratch_shapes=dkv_scratch + [
                pltpu.VMEM((seq_q, head_dim), jnp.float32),
            ],
            out_shape=dkv_structs + (dq_struct,),
            compiler_params=pltpu.CompilerParams(
                # dq gathers over the k-blocks too: only bh is parallel
                dimension_semantics=(
                    "parallel", "arbitrary", "arbitrary"
                ),
                vmem_limit_bytes=_FUSED_VMEM_BYTES,
            ),
            interpret=interpret,
            name="flash_bwd",
        )(*operands)
        return dq, dk, dv

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **statics),
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, block_k, head_dim), k_idx),
            pl.BlockSpec((1, block_k, head_dim), k_idx),
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, 1, block_q), stat_idx),
            pl.BlockSpec((1, 1, block_q), stat_idx),
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim), q_idx),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        out_shape=dq_struct,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_dq",
    )(*operands)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, with_dq=False, **statics),
        grid=(bh, num_k, num_q),
        in_specs=dkv_in_specs,
        out_specs=dkv_out_specs,
        scratch_shapes=dkv_scratch,
        out_shape=dkv_structs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_dkv",
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------
#
# The gradient is attached by an identity-primal custom_vjp ``_attach``
# over explicit (o, lse) values rather than by wrapping the forward
# kernel itself. Rationale: if the forward pallas_call lives inside the
# custom_vjp, its lse output exists only as a hidden residual, so a
# rematerialization policy (jax.checkpoint) can never mark it saveable —
# every rematted transformer block then pays a SECOND forward flash pass
# during backward. Here (o, lse) are ordinary named primal values
# (checkpoint_name "flash_out"/"flash_lse"): a policy that saves them
# lets remat DCE the forward kernel in the backward re-trace, while
# ``_attach``'s own primal is a free identity. Its backward is ``_bwd``:
# 5 score-sized matmuls in one kernel (``flash_bwd``) where dq's
# accumulator fits the VMEM budget, 7 in two (``flash_dq``,
# ``flash_dkv``) where it does not; ``delta = o . do`` is computed
# outside either, from the saved ``o``.


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _attach(q, k, v, o, lse, sm_scale, causal, block_q, block_k,
            interpret):
    return o


def _attach_fwd(q, k, v, o, lse, sm_scale, causal, block_q, block_k,
                interpret):
    return o, (q, k, v, o, lse)


def _attach_bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd(
        q, k, v, o, lse, do, sm_scale, causal, block_q, block_k,
        interpret,
    )
    # o/lse arrive behind stop_gradient; their cotangents are discarded.
    return dq, dk, dv, jnp.zeros_like(o), jnp.zeros_like(lse)


_attach.defvjp(_attach_fwd, _attach_bwd)


def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    # stop_gradient on the kernel inputs keeps AD linearization out of
    # the forward pallas_call (it has no JVP rule and needs none — all
    # gradients flow through _attach's bwd kernels).
    o, lse = _fwd(
        jax.lax.stop_gradient(q),
        jax.lax.stop_gradient(k),
        jax.lax.stop_gradient(v),
        sm_scale,
        causal,
        block_q,
        block_k,
        interpret,
    )
    o = checkpoint_name(o, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return _attach(
        q,
        k,
        v,
        o,
        lse,
        sm_scale,
        causal,
        block_q,
        block_k,
        interpret,
    )


def flash_attention(
    q,
    k,
    v,
    causal=False,
    sm_scale=None,
    block_q=None,
    block_k=None,
    interpret=False,
):
    """Blockwise attention over (batch, heads, seq, head_dim) inputs.

    Sequence lengths must be multiples of the block sizes (the auto
    dispatcher in ops/attention.py falls back to the XLA impl when they
    are not); head_dim should be a multiple of 128 lanes for best MXU
    utilisation but any size compiles.

    block_q/block_k default to the largest power-of-two blocks (up to
    512/1024) dividing the sequence: measured on v5e at S=16k, (512,
    1024) runs 4.6x faster than (128, 128) — bigger k-blocks amortize
    the online-softmax rescale and keep the MXU fed.
    """
    if q.ndim != 4:
        raise ValueError("expected 4-D q/k/v")
    batch, heads, seq_q, head_dim = q.shape
    seq_k = k.shape[2]
    block_q, block_k = _blocks(seq_q, seq_k, block_q, block_k)
    if seq_q % block_q or seq_k % block_k:
        raise ValueError(
            "seq lengths (%d, %d) must be multiples of the block sizes "
            "(%d, %d)" % (seq_q, seq_k, block_q, block_k)
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    merge = lambda t: t.reshape(batch * heads, t.shape[2], head_dim)
    o = _flash(
        merge(q),
        merge(k),
        merge(v),
        sm_scale,
        causal,
        block_q,
        block_k,
        interpret,
    )
    return o.reshape(batch, heads, seq_q, head_dim)
