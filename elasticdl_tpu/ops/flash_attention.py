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
stays in VMEM for that head's sweep, beside dq's output block of the
same rows, which is written back once a head. That scratch is 16 MiB
at 16,384 x 256, so the call states its own VMEM limit
(``_FUSED_VMEM_BYTES``). The output block has the pipeline's two
buffers where the count (``fused_bwd_vmem_bytes``) has room for them
and ONE (``pl.Buffered(1)``; ``fused_dq_buffers``) where only that
keeps the kernel within the limit: 32,768 x 256 and 32,768 x 192 /
128 (PR 61). ``backward_schedule`` keeps the older pair for shapes
over the limit even so (65,536 x 256): ``flash_dq`` + ``flash_dkv``,
3 + 4 matmuls, the scores rebuilt twice, no state that grows with the
sequence. One algorithm under two schedules, chosen by shape:
gradients agree to the last bit in interpret mode
(tests/test_attention_ops.py).

A causal call's grid still has a step for every (q-block, k-block)
pair, and each pair is one of three classes, decided from its two block
indices and the two block sizes (``_causal_pair``), each paying only for
what it needs. A SKIPPED pair (wholly above the diagonal) computes
nothing and fetches nothing: the index maps clamp the moving block index
to the nearest pair that runs, so the pipeline finds the block already
in VMEM and issues no DMA; what is left of it is the grid step itself.
An INTERIOR pair (wholly below) runs the tile without the mask, whose
select would keep every element: the two iotas, the compare and the
select are about 6 of the ~15 vector operations an element of the
forward's tile costs, on a vector unit with no bfloat16 path. A DIAGONAL
pair runs the tile with the mask. At 16,384 with blocks 1024 / 1024 a
head has 136 pairs that run, 16 of them diagonal, and 120 skipped; at
2048 the forward (512 / 1024) has 6, 4 and 2, the backward (512 / 512)
10, 4 and 6 (``causal_pairs``; ``ops/attention.py`` logs the counts;
``_blocks`` chooses the blocks, for each kernel apart, and says what
was read on the chip). A call that is not causal (the ring's
off-diagonal shards) has the identity index maps and one unmasked
body. On a v5e, 8 x 16,384 x 256 bfloat16 at blocks 512 / 1024, the
three classes took the forward from 10.54 to 8.48 ms and ``flash_bwd``
from 17.02 to 16.47, every output bit unchanged (PERF.md, PR 28).

The diagonal is one LAYOUT of the mask among several (``Causal``,
``Full``, ``BlockDiffusion``, ``Band``; ``as_layout`` takes the boolean
``causal`` every caller had). A layout is a small static description
that says, of two positions, whether the query may see the key
(``keep``: the kernels' select on a masked tile and the XLA path's
dense mask are this one function), of a (q-block, k-block) pair and the
two block sizes, its class (``pair``), and how a grid walks a head's
pairs: over the whole rectangle, with the block a skipped step names
(``k_named``, ``q_named``), or over each row's (column's) own run of
blocks (``run``). The kernels, the index maps and ``causal_pairs`` read
nothing else, so a band is a further layout (``Band``), document
boundaries would be another, and neither a further kernel.
``BlockDiffusion(half_len, block)`` is block diffusion's training mask
over ``[noisy copy ; clean copy]`` of a sequence (BD3-LMs,
arXiv:2503.09573): a noisy query sees its own noisy block, both
directions, and the clean blocks before it; a clean query the clean
blocks up to its own; L^2 + L B of the (2 L)^2 score entries, with
tiles that divide L the clean -> noisy quadrant skipped whole.
``Band(window)`` is sliding-window attention: a query sees itself and
the ``window - 1`` keys before it, S W - W (W - 1) / 2 of the S^2
entries, on a grid as long as the band: the inner axis of each of its
four kernels' grids is the longest run of blocks a row (on the k-outer
grids a column) of tiles has, and the inner index an offset into the
outer block's own run (``_grid_step``), so a head walks 128 steps at
32,768 x 512 under 512 x 512 tiles, 127 of them running, where the
rectangle has 4,096 (``Band``; ``_blocks`` picks a band's tiles by its
window).

Layout: (batch, heads, seq, head_dim); the kernels flatten batch*heads
into one parallel grid axis and see one head's (seq, head_dim) rows.
v may have a width of its own (latent attention: q and k of 192, v of
128): q, k, dq and dk are ``head_dim`` wide, v, o, do and dv ``v_dim``,
the scores one (block_q, block_k) tile either way; the blocks, the VMEM
count and the schedule take both widths, and a call of equal widths
traces what it always traced (``QK_LAYOUT``). k and v may have a head
for every ``group`` query heads (grouped-query attention): merged query
head ``b`` reads merged kv head ``b // group`` through the index maps,
nothing is copied, and dk and dv are summed over the group after the
backward kernel (``_bwd`` says what that costs); a call of equal head
counts gets the index maps it always had.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
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


_LANES = 128
# How a q / k width that is not whole lane tiles (latent attention's 192
# = 128 nope + 64 rope) is laid on the 128 lanes: as it is. The block's
# last dimension is the array's, Mosaic pads the last tile itself and
# the contraction runs over 192. Of the three layouts measured alone on
# a v5e (16 and 32 heads x 8192 x 192 / 128, bfloat16, causal; PERF.md
# Section 6, PR 29) this one and "zero-padded to 256 in VMEM" tied
# (forward 3.587 and 3.584 ms, ``flash_bwd`` 7.503 and 7.500 at 16
# heads) and "the score as two contractions, 128 + 64" lost 1.6% and
# 1.0%: the MXU pays two passes for 192 however they are asked for. So
# the kernels have ONE body for every width; ``ops/attention.py`` puts
# the word on the attention line.
QK_LAYOUT = "whole"


def _lanes(width):
    """What VMEM holds of a row of ``width`` elements: whole 128-lane
    tiles (192 -> 256, and since PR 49 64 -> 128: half of every q, k,
    v, o, dq, dk, dv tile of a 64-wide head is padding, so its dq
    accumulator costs what a 128-wide head's does: 32,768 x 64 counts
    52 of the 64 MiB and stays fused, 65,536 x 64 only with dq's
    output block in one buffer, ``fused_dq_buffers``)."""
    return -(-width // _LANES) * _LANES


def _blocks(seq_q, seq_k, head_dim, dtype, block_q, block_k,
            backward=False, v_dim=None, layout=None):
    """The blocks a kernel runs with: ``None`` is the largest power of
    two, up to a cap chosen from the shapes, that divides the sequence.
    The forward and the backward of one call each ask for their own.
    ``head_dim`` is the width of q and k, ``v_dim`` that of v and o
    (``None``: the same).

    The caps are 512 q-rows by 1024 k-rows, with two departures. What
    a block pair costs decides them (module docstring): a skipped pair
    costs its grid step (~0.4 us) and an interior pair no mask, so a
    smaller block buys less skipped work than it pays in steps and in
    rescales of the accumulator, except where the diagonal is most of
    the grid. Read IN THE STEP (the isolated kernel gives the sign, not
    the size), v5e, bfloat16, PR 28 (PERF.md Section 6):

    - 1024 q-rows from 8192 tokens on. 16,384 x 256
      (``pythia1b-s16k``): 1024 / 1024 against 512 / 1024 is +2.0%
      samples/s (``flash_fwd`` 66.3 -> 60.4 ms a step, ``flash_bwd``
      129.1 -> 127.0): 136 pairs run and 120 are skipped a head where
      272 and 240 were. Not where a q-row is over 512 bytes (256 x
      float32: the v5e compiler refuses the forward, its blocks and
      score temporaries pass the 16 MiB scoped VMEM), and not where the
      taller block would push ``flash_bwd`` over its budget, counted
      with the pipeline's two buffers of dq's output block as every
      shape had them before PR 61 (24,576 x 256 keeps 512 q-rows and
      two buffers, 63 MiB: the fused backward is worth more than the
      block, and whether the block is worth more than the second
      buffer, 60 MiB counted, nobody has read on a chip; at 16,384 x
      256 ``fused_bwd_vmem_bytes`` says 56 of 64 MiB). At 4096 x 128
      (``olmoe1b7b-s4k``) 1024 / 1024 read inside the cell's spread:
      left.
    - 512 k-rows for the BACKWARD up to 2048 tokens. 2048 x 256
      (``pythia1b-s2k``): 10 half-size tiles for 8 halves' needed work
      where 512 / 1024 computes 6 whole ones for 4 (a ceiling of 80%
      where that one is 67%); ``flash_bwd`` 11.77 -> 10.59 ms a step,
      +0.54% samples/s. The forward loses by the same blocks (0.86 ->
      0.96 ms a layer alone: twice the rescales of its accumulator),
      so with 512 / 512 in both the step gains half as much (+0.26%);
      at 4096 x 128 they cost the forward 58%.

    Under a ``Band`` (``layout``; every caller that asks passes the
    call's, so the gate, the log line and the kernels get one answer)
    the grid is as long as the band, no tile pays for steps that
    compute nothing, and the choice is what a tile keeps against what a
    step costs. The q-rows are the window rounded up to a power of two
    (no fewer than 256, no more than the rule above gives); the k-rows
    the same in the BACKWARD, and the rule's own in the forward. Read
    on a v5e at 64 heads over 8 kv heads x 32,768 x 128, bfloat16,
    window 512 (``laguna-xs2-s32k``; ``scripts/band_flash.py``, PR 43,
    PERF.md Section 6), a call alone, forward / backward ms, where the
    rectangle's 1024 / 1024 read 29.3 / 54.8:

        1024 x 1024   20.5 / 37.2   fill 25%    512 x 512   22.0 / 23.8   50%
         512 x 1024   18.5 / 30.5        33%    256 x 512   24.6 / 29.6   50%
        1024 x  512   29.1 / 32.6        33%    256 x 256   34.7 / 32.9   67%

    A backward tile costs its area (five MXU products: the call's time
    over its tiles is 2.9 us at 512 x 512, 9.2 at 1024 x 1024, 1.35 at
    256 x 256, ~4.7 ms of XLA beside the kernel included; the kernel
    alone reads 2.36 us a 512 x 512 tile in the step) plus 0.5-0.8 us a
    step, so it wants the fill and stops at the step's cost: 512 x 512. A forward step costs its
    q-rows whatever its width (2.7-2.9 us at 512 rows over 512 or 1024
    keys, 4.8-5.0 at 1024, 1.4-1.5 at 256: not the MXU's time; the
    online softmax's row maxima, sums and rescales scale so, and no
    trace of PR 43 splits a step), so it wants few (row, step)
    visits: k-blocks as long as the rule gives, under q-blocks a
    window long, 1.5 live steps a row for 2. IN THE STEP
    (``--step``, ms a step; the rectangle 2167.8): 1024 / 1024 in both
    2067.3; 512 / 512 in both 2036.1; 256 / 256 2139.0; the forward 1024
    / 1024 over a backward of 512 / 512 2028.3 and 2027.5; 256 / 1024
    over it 2037.2; 512 / 2048 over it 2054.1; the choice, 512 / 1024
    over 512 / 512, 2015.0 twice.

    A 64-wide head (``lfm2-8b-s32k``: 32 heads over 8 kv heads x 32,768
    x 64) keeps the rule's 1024 / 1024 in both kernels, on purpose. Its
    tiles' VMEM is a 128-wide head's (``_lanes``), the compiler takes
    2048-row tiles at this width (it refuses the forward at 2048 x 1024
    x 128: 16.33 of 16 MiB scoped), and none of them wins. A call alone,
    forward / backward ms by the HOST's clock around ten calls on a
    ``TPU v5 lite`` (``scripts/flash_head64.py``, which refuses to time
    on another backend; PR 49, PERF.md Section 6):

        1024 x 1024   72.0 / 133.2      2048 x  512   117.8 / 137.4
         512 x 1024   84.6 / 141.1       512 x 2048    74.3 / 134.0
        1024 x  512  144.1 / 139.5      2048 x 1024    70.6 / refused

    (2048 x 1024 ran 70.6 / 137.3 while ``_lanes`` still counted a
    64-wide row as 64; under the honest count its backward is the split
    pair, and the compiler refuses ``flash_dkv`` there at 22.34 of 16
    MiB scoped; a second run of the sweep read the other five pairs to
    0.1%)

    and the call of equal FLOPs at a 128-wide head (16 heads over 4)
    35.5 / 66.3 at 1024 x 1024: half, tile for tile. A tile's cost is
    the vector unit's work on its (block_q, block_k) scores, which is
    the same at either width, and the two products fill half an MXU
    pass at 64; no choice of tiles changes either. The forward's 2%
    under 2048 x 1024 is 2.8 ms of the cell's 1,335 ms step and would
    need a branch by width that a 128-wide head may not take (the
    compiler refuses its forward) and a backward of other tiles.
    """
    picks_q, picks_k = block_q is None, block_k is None
    if block_k is None:
        short = backward and seq_k <= 2048
        block_k = _auto_block(seq_k, 512 if short else 1024)
    if block_q is None:
        block_q = _auto_block(seq_q, 512)
        itemsize = jnp.dtype(dtype).itemsize
        tall = _auto_block(seq_q, 1024)
        if (
            seq_q >= 8192
            and max(head_dim, v_dim or head_dim) * itemsize <= 512
            and fused_bwd_vmem_bytes(
                seq_q, head_dim, tall, block_k, itemsize, v_dim
            ) <= _FUSED_VMEM_BYTES
        ):
            block_q = tall
    if isinstance(layout, Band):
        side = max(1 << (layout.window - 1).bit_length(), 256)
        if picks_q:
            block_q = _auto_block(seq_q, min(block_q, side))
        if picks_k and backward:
            block_k = _auto_block(seq_k, min(block_k, side))
    return min(block_q, seq_q), min(block_k, seq_k)


def _causal_mask(s, q_block, k_block, block_q, block_k):
    q_pos = q_block * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0
    )
    k_pos = k_block * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _causal_pair(q_block, k_block, block_q, block_k):
    """What the causal diagonal means for the pair (q-block, k-block),
    from the two block indices and the two block sizes alone:
    ``(last_k, first_q, masked)``.

    ``last_k`` is the last k-block that ``q_block`` needs and
    ``first_q`` the first q-block that ``k_block`` needs (neither held
    to the grid: a sequence may end before it). The pair RUNS when
    ``k_block <= last_k``, which is ``q_block >= first_q``; every other
    pair lies wholly above the diagonal. ``masked``: some element of the
    tile has ``q_pos < k_pos``, so a pair that runs needs
    ``_causal_mask``; where it is false the select would keep every
    element. ``Causal``'s class, its clamps and through them the
    kernels' ``pl.when`` predicates, the index maps and ``causal_pairs``
    all read this one function."""
    # non-negative operands: the truncating division is the floor, and
    # one scalar instruction where a traced ``//`` is five
    div = (
        jax.lax.div if isinstance(q_block, jax.Array)
        else lambda a, b: a // b
    )
    last_k = div((q_block + 1) * block_q - 1, block_k)
    first_q = div(k_block * block_k, block_q)
    masked = (k_block + 1) * block_k - 1 > q_block * block_q
    return last_k, first_q, masked


# ---------------------------------------------------------------------------
# Mask layouts
# ---------------------------------------------------------------------------
#
# A layout answers four questions, the first of positions, the others
# of block indices and block sizes (numpy integers or traced scalars
# alike, never data):
#
#   keep(q_pos, k_pos)                        may the query see the key
#   pair(q_block, k_block, block_q, block_k)  (runs, masked): does the
#       tile keep any element; does a tile that runs drop some
#
# and how a grid walks a head's pairs, one of two ways. On the
# RECTANGLE (``Causal``, ``Full``, ``BlockDiffusion``) the inner grid
# axis is every block of the moving side, and the layout says which
# block a step that computes nothing names:
#
#   k_named(q_block, k_block, ...)            on a (q-block, k-block)
#       grid, the k-block step ``k_block`` names: its own if the pair
#       runs, else that of a neighbouring step that runs
#   q_named(q_block, k_block, ..., num_q)     the same of a (k-block,
#       q-block) grid's q-blocks
#
# On a grid of RUNS (``Band``) the inner axis is as long as the longest
# run of moving blocks any outer block has, and its index is an offset
# into the outer block's own run (``_grid_step``); the layout says where
# that run lies:
#
#   run(outer, block_q, block_k, k_outer)     (first, last) k-block of
#       the q-block ``outer``'s row of tiles or, ``k_outer``, q-block
#       of the k-block ``outer``'s column; ``first`` is one of the
#       grid's, ``last`` may lie past its end
#
# and ``refusal(seq_q, seq_k, block_q, block_k)``: why these tiles
# cannot carry it, "" when they can.


def _ops(*values):
    """(array namespace, floor division of non-negative integers) for
    block indices that are traced scalars inside a kernel or an index
    map and numpy arrays in ``causal_pairs`` and the tests."""
    if any(isinstance(v, jax.Array) for v in values):
        return jnp, jax.lax.div
    return np, lambda a, b: a // b


def _clip(xp, x, lo, hi):
    return xp.minimum(xp.maximum(x, lo), hi)


@dataclasses.dataclass(frozen=True)
class Full:
    """Every query sees every key (the ring's off-diagonal shards):
    the identity index maps and one unmasked body."""

    def __str__(self):
        return "full"

    def keep(self, q_pos, k_pos):
        return (q_pos >= 0) & (k_pos >= 0)

    def pair(self, q_block, k_block, block_q, block_k):
        always = (q_block >= 0) & (k_block >= 0)
        return always, ~always

    def k_named(self, q_block, k_block, block_q, block_k):
        return k_block

    def q_named(self, q_block, k_block, block_q, block_k, num_q):
        return q_block

    def refusal(self, seq_q, seq_k, block_q, block_k):
        return ""


@dataclasses.dataclass(frozen=True)
class Causal:
    """Position ``q`` sees the keys up to itself (``_causal_pair``)."""

    def __str__(self):
        return "causal"

    def keep(self, q_pos, k_pos):
        return q_pos >= k_pos

    def pair(self, q_block, k_block, block_q, block_k):
        last_k, _, masked = _causal_pair(q_block, k_block, block_q, block_k)
        return k_block <= last_k, masked

    def k_named(self, q_block, k_block, block_q, block_k):
        last_k, _, _ = _causal_pair(q_block, k_block, block_q, block_k)
        return jnp.minimum(k_block, last_k)

    def q_named(self, q_block, k_block, block_q, block_k, num_q):
        # held to the grid where seq_q ends before the k-block starts
        _, first_q, _ = _causal_pair(q_block, k_block, block_q, block_k)
        return jnp.minimum(jnp.maximum(q_block, first_q), num_q - 1)

    def refusal(self, seq_q, seq_k, block_q, block_k):
        return ""


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """Block diffusion's training mask over ``2 x half_len`` positions,
    the noisy copy of a sequence and then the clean one, in blocks of
    ``block`` tokens. With ``half(p) = p // half_len`` (0 noisy, 1
    clean) and ``blk(p) = (p mod half_len) // block``, q sees k iff

        noisy q, noisy k:   blk(k) == blk(q)   its own block, both ways
        noisy q, clean k:   blk(k) <  blk(q)   the clean blocks before
        clean q, noisy k:   never
        clean q, clean k:   blk(k) <= blk(q)   block-causal

    Every row keeps a key (a noisy token its own block, a clean token
    itself). Tiles divide ``half_len`` (``refusal``), so a tile lies in
    one quadrant and its class follows from the first and the last
    block its rows and its columns touch. A row of tiles runs on up to
    two runs of k-blocks (its own noisy blocks, then the clean ones up
    to its bound), a column on up to two runs of q-blocks; a skipped
    step names the block of the run's nearest end."""

    half_len: int
    block: int

    def __str__(self):
        return "block_diffusion(%d, %d)" % (self.half_len, self.block)

    def _block_of(self, pos, div):
        """The block of a position inside its half; a shift where the
        block length is a power of two (the kernel's vector path)."""
        if self.block & (self.block - 1) == 0:
            return pos >> (self.block.bit_length() - 1)
        return div(pos, self.block)

    def keep(self, q_pos, k_pos):
        xp, div = _ops(q_pos, k_pos)
        q_clean, k_clean = q_pos >= self.half_len, k_pos >= self.half_len
        q_blk = self._block_of(
            q_pos - xp.where(q_clean, self.half_len, 0), div)
        k_blk = self._block_of(
            k_pos - xp.where(k_clean, self.half_len, 0), div)
        # a row's bound on clean blocks and the one noisy block it
        # sees; a key's block in the half it lies in, and in the other
        # a number no row's bound or block meets. Integers until the
        # last two compares: Mosaic selects no booleans
        upto = xp.where(q_clean, q_blk, q_blk - 1)
        own = xp.where(q_clean, -1, q_blk)
        clean_blk = xp.where(k_clean, k_blk, self.half_len)
        noisy_blk = xp.where(k_clean, -2, k_blk)
        return (clean_blk <= upto) | (noisy_blk == own)

    def _tile(self, index, size, xp, div):
        """(in the clean half, first block, last block) of the tile
        ``index`` of ``size`` positions."""
        per_half = self.half_len // size
        clean = index >= per_half
        start = (index - xp.where(clean, per_half, 0)) * size
        return clean, div(start, self.block), div(
            start + size - 1, self.block)

    def pair(self, q_block, k_block, block_q, block_k):
        xp, div = _ops(q_block, k_block)
        q_clean, q0, q1 = self._tile(q_block, block_q, xp, div)
        k_clean, k0, k1 = self._tile(k_block, block_k, xp, div)
        noisy = ~q_clean
        # clean keys: some row's bound reaches the first; every row's
        # the last. Noisy keys: the block ranges meet; are one block
        some = xp.where(
            k_clean, k0 <= xp.where(q_clean, q1, q1 - 1),
            noisy & (k0 <= q1) & (q0 <= k1))
        every = xp.where(
            k_clean, k1 <= xp.where(q_clean, q0, q0 - 1),
            noisy & (q1 <= k0) & (k1 <= q0))
        return some, ~every

    def k_named(self, q_block, k_block, block_q, block_k):
        xp, div = _ops(q_block, k_block)
        q_clean, q0, q1 = self._tile(q_block, block_q, xp, div)
        half = self.half_len // block_k
        # the noisy k-blocks a noisy row meets: those of its own blocks
        own = _clip(
            xp, k_block, div(q0 * self.block, block_k),
            div((q1 + 1) * self.block - 1, block_k))
        # the clean ones: blocks 0 .. upto, none for a row in block 0
        upto = xp.where(q_clean, q1, q1 - 1)
        tiles = div((upto + 1) * self.block + block_k - 1, block_k)
        clean = _clip(xp, k_block, half, half + tiles - 1)
        return xp.where(
            q_clean | ((k_block >= half) & (tiles > 0)), clean, own)

    def q_named(self, q_block, k_block, block_q, block_k, num_q):
        xp, div = _ops(q_block, k_block)
        k_clean, k0, k1 = self._tile(k_block, block_k, xp, div)
        half = self.half_len // block_q
        # noisy keys: the noisy rows of their own blocks
        own = _clip(
            xp, q_block, div(k0 * self.block, block_q),
            div((k1 + 1) * self.block - 1, block_q))
        # clean keys: the noisy rows of a later block (none for the
        # last clean blocks), then the clean rows from their own on
        later = div((k0 + 1) * self.block, block_q)
        noisy = _clip(xp, q_block, later, half - 1)
        clean = xp.maximum(q_block, half + div(k0 * self.block, block_q))
        return xp.where(
            k_clean,
            xp.where((q_block < half) & (later < half), noisy, clean), own)

    def refusal(self, seq_q, seq_k, block_q, block_k):
        if seq_q != 2 * self.half_len or seq_k != 2 * self.half_len:
            return "%s covers %d positions, q and k have (%d, %d)" % (
                self, 2 * self.half_len, seq_q, seq_k)
        if self.half_len % self.block:
            return "%s: the block does not divide the half" % (self,)
        if self.half_len % block_q or self.half_len % block_k:
            return "%s: tiles (%d, %d) do not divide the half" % (
                self, block_q, block_k)
        return ""


@dataclasses.dataclass(frozen=True)
class Band:
    """Sliding-window attention: position ``q`` sees the ``window`` keys
    that end at itself, ``k <= q`` and ``q - k < window`` (the
    ``transformers`` library's convention: the window counts the
    query). ``seq x window - window (window - 1) / 2`` entries a head.

    A row of tiles runs on ONE run of k-blocks, from the block of its
    first row's first key to the diagonal's; a column on the q-blocks
    from the diagonal's to that of its last key's last reader
    (``run``). The grid is as long as the band: its inner axis is the
    longest run a row (a column) has, and step ``inner`` of an outer
    block names block ``first + inner`` of that block's own run
    (``_grid_step``; every kernel of both backward schedules walks so).
    The steps that compute nothing are the slots past a run's end: of
    a run the sequence cuts (row 0 has no block before the diagonal's,
    the last columns no reader after the last q-block) and, where the
    two block sizes differ, of a run shorter than the longest. At
    32,768 under a window of 512, 1024 / 1024 blocks walk 64 steps a
    head for the rectangle's 1,024, 63 of them running; 512 / 512
    blocks 128, 127 running, and keep half of what they compute where
    1024 / 1024 keep a quarter (``_blocks`` chooses, and says what was
    read on the chip)."""

    window: int
    # the word a band's kernels carry in their names (``_kernel_name``)
    kernels = "band"

    def __str__(self):
        return "window(%d)" % self.window

    def keep(self, q_pos, k_pos):
        # ``q_pos - window`` is a column's work, the tile's two compares
        return (k_pos <= q_pos) & (k_pos > q_pos - self.window)

    def run(self, outer, block_q, block_k, k_outer=False):
        """(first, last) k-block of the q-block ``outer``'s row of
        tiles or, ``k_outer``, q-block of the k-block ``outer``'s
        column; a column's ``last`` may lie past the grid's end."""
        xp, div = _ops(outer)
        if k_outer:
            k0 = outer * block_k
            return div(k0, block_q), div(
                k0 + block_k - 1 + self.window - 1, block_q)
        q0 = outer * block_q
        return div(xp.maximum(q0 - (self.window - 1), 0), block_k), div(
            q0 + block_q - 1, block_k)

    def pair(self, q_block, k_block, block_q, block_k):
        q0, k0 = q_block * block_q, k_block * block_k
        q1, k1 = q0 + block_q - 1, k0 + block_k - 1
        # some row's window meets a column; every row's holds them all
        some = (k0 <= q1) & (k1 > q0 - self.window)
        every = (k1 <= q0) & (k0 > q1 - self.window)
        return some, ~every

    def refusal(self, seq_q, seq_k, block_q, block_k):
        if self.window < 1:
            return "%s: a window holds at least the query" % (self,)
        if seq_q != seq_k:
            return (
                "%s over q and k of (%d, %d) positions: a band across "
                "the shards of a sequence is not built" % (
                    self, seq_q, seq_k))
        return ""


FULL, CAUSAL = Full(), Causal()


def as_layout(causal):
    """The layout of a call's ``causal`` argument: a layout, or the
    boolean it always was (True: the diagonal; False: no mask)."""
    if causal is True:
        return CAUSAL
    if causal is False or causal is None:
        return FULL
    return causal


def _mask_tile(layout, s, q_block, k_block, block_q, block_k):
    """The scores ``s`` of one masked tile with what the layout drops
    at ``NEG_INF``, the mask computed from the two block indices. The
    diagonal's is ``_causal_mask`` as it always was; another layout's
    positions are a column and a row, so what ``keep`` computes of one
    side costs a vector, and only its last compare the tile."""
    if layout == CAUSAL:
        return _causal_mask(s, q_block, k_block, block_q, block_k)
    q_pos = q_block * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (s.shape[0], 1), 0)
    k_pos = k_block * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, s.shape[1]), 1)
    return jnp.where(layout.keep(q_pos, k_pos), s, NEG_INF)


def _inner_steps(causal, block_q, block_k, num_q, num_k, k_outer=False):
    """Steps of a grid's inner axis under the layout ``causal``: on
    ``(bh, q-block, k-block)`` or, ``k_outer``, ``(bh, k-block,
    q-block)``. Every block of the moving side on the rectangle; the
    longest run an outer block has, held to the grid, where the layout
    walks runs (a window as long as the sequence: the rectangle's
    side)."""
    layout = as_layout(causal)
    num_outer, num_inner = (num_k, num_q) if k_outer else (num_q, num_k)
    if not hasattr(layout, "run"):
        return num_inner
    first, last = layout.run(
        np.arange(num_outer), block_q, block_k, k_outer)
    return int((np.minimum(last, num_inner - 1) - first + 1).max())


def _grid_step(causal, outer, inner, block_q, block_k, num_inner,
               k_outer=False):
    """``(block, live)`` of step ``inner`` of the outer block ``outer``:
    the moving block the step names, and whether it is one of the outer
    block's run. On the rectangle the step's own index, and ``None``
    (``pair`` alone says which steps run). On a grid of runs block
    ``first + inner`` of the outer block's own run; a slot past the
    run's end (or the grid's) names the run's last block, which the
    step before it fetched, and computes nothing."""
    layout = as_layout(causal)
    if not hasattr(layout, "run"):
        return inner, None
    xp, _ = _ops(outer, inner)
    first, last = layout.run(outer, block_q, block_k, k_outer)
    last = xp.minimum(last, num_inner - 1)
    block = first + inner
    return xp.minimum(block, last), block <= last


def causal_pairs(seq_q, seq_k, block_q, block_k, causal=True,
                 k_outer=False):
    """``(run, masked, skipped)``: of the steps one head's grid walks
    (``k_outer``: the k-outer grid's, whose count differs only where a
    layout walks runs), how many compute a tile, how many of those
    apply the mask, and how many compute and fetch nothing, under the
    layout ``causal`` (``as_layout``). A function of shapes
    (``ops/attention.py`` logs it)."""
    num_q, num_k = seq_q // block_q, seq_k // block_k
    q_block = np.arange(num_q)[:, None]
    k_block = np.arange(num_k)[None, :]
    run, masked = as_layout(causal).pair(q_block, k_block, block_q, block_k)
    run = np.broadcast_to(run, (num_q, num_k))
    steps = (num_k if k_outer else num_q) * _inner_steps(
        causal, block_q, block_k, num_q, num_k, k_outer)
    return (
        int(run.sum()), int((run & masked).sum()), steps - int(run.sum())
    )


def _each_class(causal, q_block, k_block, block_q, block_k, tile,
                live=None):
    """``tile(masked)`` once for the pair's class under the layout
    ``causal``: a masked pair with the select (``masked`` is the layout
    whose mask the tile takes), an interior one without (``None``: a
    body of its own, so the iotas, the compare and the select are not
    in it), nothing for a skipped pair. A call without a mask has the
    one unmasked body. ``live``: on a grid of runs, whether the step
    is one of its run's (``_grid_step``)."""
    layout = as_layout(causal)
    if layout == FULL:
        tile(None)
        return
    run, masked = layout.pair(q_block, k_block, block_q, block_k)
    if live is not None:
        run = jnp.logical_and(live, run)
    pl.when(jnp.logical_and(run, masked))(
        functools.partial(tile, layout))
    pl.when(jnp.logical_and(run, jnp.logical_not(masked)))(
        functools.partial(tile, None))


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
    num_k,
):
    q_block = pl.program_id(1)
    inner = pl.program_id(2)
    steps = pl.num_programs(2)
    k_block, live = _grid_step(
        causal, q_block, inner, block_q, block_k, num_k)

    @pl.when(inner == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _tile(masked):
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
        if masked is not None:
            s = _mask_tile(masked, s, q_block, k_block, block_q, block_k)
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

    _each_class(causal, q_block, k_block, block_q, block_k, _tile, live)

    @pl.when(inner == steps - 1)
    def _finalize():
        l_final = l_ref[:, :1]
        # Fully-masked rows (can't happen causally, but keep the kernel
        # total): emit zeros, lse = -inf.
        safe_l = jnp.where(l_final > 0.0, l_final, 1.0)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (
            m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30))
        )


def _kernel_name(causal, kernel):
    """The name a kernel has in the compiled program and in a device
    trace: ``flash_<kernel>`` (``benchmark/metrics/flash_time_share.py``
    finds "flash"), and ``flash_<word>_<kernel>`` under a layout that
    names its kernels (``Band``: ``flash_band_fwd``), so that a trace
    tells them from the diagonal's."""
    word = getattr(as_layout(causal), "kernels", "")
    return "flash_%s%s" % (word + "_" if word else "", kernel)


def _index_maps(causal, block_q, block_k, num_q, k_outer=False, num_k=None):
    """(q-ish, k-ish, lse-ish) index maps of the merged "(bh, seq, d)"
    view, on a ``(bh, q-block, k-block)`` grid or, ``k_outer``, on
    ``(bh, k-block, q-block)``, under the layout ``causal``.

    A masked grid's steps that compute nothing name the block of a
    neighbouring step that runs, so the pipeline, which copies a block
    only when its index changes, fetches nothing for them. On the
    rectangle the inner axis is clamped by the layout (``k_named``,
    ``q_named``; the diagonal's are ``_causal_pair``'s bounds: k-blocks
    from above by ``last_k``, q-blocks from below by ``first_q``); on a
    grid of runs (``num_k`` is read there alone) it is an offset into
    the outer block's run, ``_grid_step``, the arithmetic the kernels
    do. Only inputs move with the inner axis; outputs follow the outer
    one, which is never clamped. A call without a mask gets the
    identity (``Full``'s)."""
    layout = as_layout(causal)

    def moving(outer, inner):
        if hasattr(layout, "run"):
            return _grid_step(
                layout, outer, inner, block_q, block_k,
                num_q if k_outer else num_k, k_outer)[0]
        if k_outer:
            return layout.q_named(inner, outer, block_q, block_k, num_q)
        return layout.k_named(outer, inner, block_q, block_k)

    def q_block(outer, inner):
        return moving(outer, inner) if k_outer else outer

    def k_block(outer, inner):
        return outer if k_outer else moving(outer, inner)

    q_idx = lambda b, outer, inner: (b, q_block(outer, inner), 0)
    k_idx = lambda b, outer, inner: (b, k_block(outer, inner), 0)
    stat_idx = lambda b, outer, inner: (b, 0, q_block(outer, inner))
    return q_idx, k_idx, stat_idx


def _kv_index_map(k_idx, group):
    """The k-ish index map for arrays with a head for every ``group``
    query heads (grouped-query attention: k and v themselves): merged
    query head ``b`` reads merged head ``b // group``, and nothing is
    copied. At ``group`` 1 it IS ``k_idx``, so a call of equal head
    counts lowers to the module it always lowered to."""
    if group == 1:
        return k_idx

    def kv_idx(b, outer, inner):
        _, block, lane = k_idx(b, outer, inner)
        return jax.lax.div(b, group), block, lane

    return kv_idx


def _out_struct(shape, dtype, *operands):
    """A pallas_call output that varies over every mesh axis any operand
    varies over: inside a VMA-checked ``shard_map`` (the pipeline's
    manual region) a pallas_call must say so itself; anywhere else the
    set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    bh, seq_q, head_dim = q.shape
    seq_k, v_dim = k.shape[1], v.shape[2]
    block_q, block_k = _blocks(
        seq_q, seq_k, head_dim, q.dtype, block_q, block_k, v_dim=v_dim,
        layout=as_layout(causal))
    num_q = seq_q // block_q
    num_k = seq_k // block_k
    grid = (bh, num_q, _inner_steps(causal, block_q, block_k, num_q, num_k))

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        num_k=num_k,
    )
    q_idx, k_idx, stat_idx = _index_maps(
        causal, block_q, block_k, num_q, num_k=num_k)
    kv_idx = _kv_index_map(k_idx, bh // k.shape[0])
    # lse rides in (bh, 1, seq) — the singleton axis makes the block's
    # second-minor dim equal the full array dim, satisfying the TPU
    # (8, 128) tiling rule that a 2-D (1, block_q) block violates
    out_shape = (
        _out_struct((bh, seq_q, v_dim), q.dtype, q, k, v),
        _out_struct((bh, 1, seq_q), jnp.float32, q, k, v),
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, block_k, head_dim), kv_idx),
            pl.BlockSpec((1, block_k, v_dim), kv_idx),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, v_dim), q_idx),
            pl.BlockSpec((1, 1, block_q), stat_idx),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, v_dim), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_kernel_name(causal, "fwd"),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _p_and_ds(q, k, v, do, lse_ref, delta_ref, q_block, k_block,
              sm_scale, masked, block_q, block_k):
    """``p = exp(s - lse)`` and ``ds = p * (dp - delta) * sm_scale`` of
    one (q-block, k-block) pair, both float32: the two score-sized
    matmuls (``q k^T``, ``do v^T``) every backward kernel starts from.
    ``masked``: the layout whose mask the tile takes, None for an
    interior one. Native-dtype matmul inputs, f32 accumulation (see
    _fwd_kernel)."""
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
    if masked is not None:
        s = _mask_tile(masked, s, q_block, k_block, block_q, block_k)
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
    num_k,
):
    q_block = pl.program_id(1)
    inner = pl.program_id(2)
    steps = pl.num_programs(2)
    k_block, live = _grid_step(
        causal, q_block, inner, block_q, block_k, num_k)

    @pl.when(inner == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def _tile(masked):
        k = k_ref[0]
        _, ds = _p_and_ds(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref, delta_ref,
            q_block, k_block, sm_scale, masked, block_q, block_k,
        )
        dq_acc_ref[:] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    _each_class(causal, q_block, k_block, block_q, block_k, _tile, live)

    @pl.when(inner == steps - 1)
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
    num_q,
    num_k,
    with_dq,
):
    """dk and dv of one k-block, accumulated in float32 over the
    q-blocks (grid ``(bh, k-block, q-block)``; under a layout that
    walks runs, the q-blocks of the k-block's run).

    ``with_dq`` (the fused backward, ``flash_bwd``): dq is accumulated
    from the same ``ds``. Its accumulator must outlive the k-blocks, so
    it is the whole ``(seq_q, head_dim)`` of this ``bh`` in float32,
    and ``dq_ref`` the whole dq of this ``bh`` (an output block whose
    index depends on ``bh`` alone stays in VMEM until ``bh`` moves on).
    A q-block's rows are zeroed when the first k-block meets them and
    cast to the output, once, when the last one has: the grid's first
    and last on the rectangle, those of the q-block's own run on a grid
    of runs, where no other k-block has a step for these rows. In
    between the ``ds @ k`` terms arrive in ascending k, as in
    ``_dq_kernel``."""
    if with_dq:
        (dk_ref, dv_ref, dq_ref,
         dk_acc_ref, dv_acc_ref, dq_acc_ref) = out_and_scratch
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = out_and_scratch
    k_block = pl.program_id(1)
    inner = pl.program_id(2)
    grid_k = pl.num_programs(1)
    steps = pl.num_programs(2)
    q_block, live = _grid_step(
        causal, k_block, inner, block_q, block_k, num_q, k_outer=True)
    rows = pl.ds(pl.multiple_of(q_block * block_q, block_q), block_q)
    if with_dq and live is not None:
        first_k, last_k = as_layout(causal).run(q_block, block_q, block_k)
        last_k = jnp.minimum(last_k, num_k - 1)

    @pl.when(inner == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    if with_dq:
        @pl.when(
            k_block == 0 if live is None
            else jnp.logical_and(live, k_block == first_k))
        def _init_dq():
            dq_acc_ref[rows, :] = jnp.zeros(
                (block_q, dq_acc_ref.shape[1]), jnp.float32
            )

    def _tile(masked):
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        p, ds = _p_and_ds(
            q, k, v_ref[0], do, lse_ref, delta_ref,
            q_block, k_block, sm_scale, masked, block_q, block_k,
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

    _each_class(causal, q_block, k_block, block_q, block_k, _tile, live)

    @pl.when(inner == steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)

    if with_dq:
        @pl.when(
            k_block == grid_k - 1 if live is None
            else jnp.logical_and(live, k_block == last_k))
        def _finalize_dq():
            dq_ref[0, rows, :] = dq_acc_ref[rows, :].astype(dq_ref.dtype)


# VMEM the fused backward may hold, and the limit its pallas_call states
# (the default scoped limit, 16 MiB, is the size of its accumulator at
# 16,384 x 256). A v5e or v6e core has 128 MiB, a v7x core 64.
_FUSED_VMEM_BYTES = 64 * 2**20


def fused_bwd_vmem_bytes(seq_q, head_dim, block_q, block_k, itemsize,
                         v_dim=None, dq_buffers=2):
    """VMEM of ``flash_bwd`` from its shapes, counted generously: dq of
    one ``bh`` as float32 accumulator and ``dq_buffers`` buffers of its
    output block (2: the pipeline's pair, what every block gets unless
    it says otherwise; 1: ``pl.Buffered(1)``, ``fused_dq_buffers``);
    the float32 dk / dv accumulators; q, do, k, v, dk, dv blocks, two
    buffers each; four score-sized float32 temporaries (s, p, dp, ds).
    q, k, dq, dk are ``head_dim`` wide, v, do, dv ``v_dim`` (``None``:
    the same), each rounded up to whole 128-lane tiles, which is what
    VMEM holds of a 192-wide row. The v5e compiler takes 16,384 x 256
    (blocks 512 / 1024, bfloat16) under a limit of 36 MiB and refuses
    it under 32; this says 47. With ONE buffer of dq's block (blocks
    512 / 1024, bfloat16, compiled for a described v5e, PR 61) it
    takes 32,768 x 256 under a limit of 59 MiB and refuses it under
    58 where this says 63, and 32,768 x 192 / 128 under 56, not 55,
    where this says 61.25; with two it refuses both under 72."""
    head_dim = _lanes(head_dim)
    v_dim = head_dim if v_dim is None else _lanes(v_dim)
    dq = seq_q * head_dim * (4 + dq_buffers * itemsize)
    kv = block_k * (head_dim + v_dim) * (4 + 2 * 2 * itemsize)
    q_do = block_q * (head_dim + v_dim) * 2 * itemsize
    scores = 4 * block_q * block_k * 4
    return dq + kv + q_do + scores


def fused_dq_buffers(seq_q, seq_k, head_dim, dtype, block_q=None,
                     block_k=None, v_dim=None, layout=None):
    """How many buffers dq's whole-head output block of ``flash_bwd``
    has at these shapes: 2 where the count with the pipeline's pair is
    within ``_FUSED_VMEM_BYTES`` (the program every such shape always
    got), else 1 where the count with one is, else 0: no fused kernel.
    The block's index depends on ``bh`` alone, so it is written back
    once a head and the second buffer hides one write of ``seq_q x
    head_dim`` a head (16 MiB, ~20 us at a v5e's HBM peak, at 32,768 x
    256) behind the next head's first tile; at 32,768 x 256 and 32,768
    x 192 / 128 it is what kept the fused kernel out (79 and 77.25 MiB
    counted with two, 63 and 61.25 with one)."""
    block_q, block_k = _blocks(
        seq_q, seq_k, head_dim, dtype, block_q, block_k, backward=True,
        v_dim=v_dim, layout=layout)
    for dq_buffers in (2, 1):
        held = fused_bwd_vmem_bytes(
            seq_q, head_dim, block_q, block_k, jnp.dtype(dtype).itemsize,
            v_dim, dq_buffers)
        if held <= _FUSED_VMEM_BYTES:
            return dq_buffers
    return 0


def backward_schedule(seq_q, seq_k, head_dim, dtype, block_q=None,
                      block_k=None, v_dim=None, layout=None):
    """Which backward these shapes get: ``"fused"`` (one kernel,
    ``flash_bwd``: the scores rebuilt once) where dq's accumulator and
    its output block, in two buffers or in one (``fused_dq_buffers``),
    fit the VMEM budget, ``"split"`` (``flash_dq`` + ``flash_dkv``:
    rebuilt twice, no state that grows with the sequence) above it.
    ``_bwd`` decides by this and ``ops/attention.py`` logs it."""
    return "fused" if fused_dq_buffers(
        seq_q, seq_k, head_dim, dtype, block_q, block_k, v_dim, layout
    ) else "split"


def _bwd(
    q, k, v, o, lse, do, sm_scale, causal, block_q, block_k, interpret,
):
    bh, seq_q, head_dim = q.shape
    seq_k, v_dim = k.shape[1], v.shape[2]
    block_q, block_k = _blocks(
        seq_q, seq_k, head_dim, q.dtype, block_q, block_k, backward=True,
        v_dim=v_dim, layout=as_layout(causal))
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )[:, None, :]  # (bh, 1, seq): same tiling-friendly layout as lse
    dq_idx = lambda b, j, i: (b, 0, 0)
    num_q = seq_q // block_q
    num_k = seq_k // block_k
    operands = (q, k, v, do, lse, delta)
    statics = dict(
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        num_k=num_k,
    )
    # the inner axis of the (bh, k-block, q-block) grids and of the
    # split pair's (bh, q-block, k-block) one
    q_steps = _inner_steps(
        causal, block_q, block_k, num_q, num_k, k_outer=True)
    k_steps = _inner_steps(causal, block_q, block_k, num_q, num_k)
    dq_buffers = fused_dq_buffers(
        seq_q, seq_k, head_dim, q.dtype, block_q, block_k, v_dim)

    # grouped-query attention: k and v have a head for every ``group``
    # query heads. The kernels read head ``b // group`` through the
    # index maps and write dk and dv of every QUERY head, in float32;
    # ``_sum_groups`` adds each group's and rounds once. After the
    # kernel and not inside it: the grid's ``bh`` axis stays parallel
    # and the bodies are the ones every other call runs; it costs a
    # float32 write and read of (bh, seq_k, head_dim + v_dim), 2.1 GB
    # at 16 heads x 32,768 x 256, 2.6 ms at the HBM's peak beside a
    # backward of hundreds.
    group = bh // k.shape[0]
    # the dkv grid iterates (bh, k-block, q-block)
    q_idx, k_idx, stat_idx = _index_maps(
        causal, block_q, block_k, num_q, k_outer=True, num_k=num_k)
    kv_idx = _kv_index_map(k_idx, group)

    dq_struct = _out_struct(q.shape, q.dtype, *operands)
    dkv_structs = (
        _out_struct(k.shape, k.dtype, *operands),
        _out_struct(v.shape, v.dtype, *operands),
    ) if group == 1 else (
        _out_struct((bh, seq_k, head_dim), jnp.float32, *operands),
        _out_struct((bh, seq_k, v_dim), jnp.float32, *operands),
    )

    def _sum_groups(dk, dv):
        if group == 1:
            return dk, dv
        return tuple(
            d.reshape((-1, group) + d.shape[1:]).sum(axis=1).astype(x.dtype)
            for d, x in ((dk, k), (dv, v)))

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, head_dim), q_idx),
        pl.BlockSpec((1, block_k, head_dim), kv_idx),
        pl.BlockSpec((1, block_k, v_dim), kv_idx),
        pl.BlockSpec((1, block_q, v_dim), q_idx),
        pl.BlockSpec((1, 1, block_q), stat_idx),
        pl.BlockSpec((1, 1, block_q), stat_idx),
    ]
    dkv_out_specs = (
        pl.BlockSpec((1, block_k, head_dim), k_idx),
        pl.BlockSpec((1, block_k, v_dim), k_idx),
    )
    dkv_scratch = [
        pltpu.VMEM((block_k, head_dim), jnp.float32),
        pltpu.VMEM((block_k, v_dim), jnp.float32),
    ]

    if dq_buffers:
        dk, dv, dq = pl.pallas_call(
            functools.partial(
                _dkv_kernel, with_dq=True, num_q=num_q, **statics),
            grid=(bh, num_k, q_steps),
            in_specs=dkv_in_specs,
            out_specs=dkv_out_specs + (
                # written back once a head: a second buffer only where
                # the budget has room for it (``fused_dq_buffers``)
                pl.BlockSpec(
                    (1, seq_q, head_dim), dq_idx,
                    pipeline_mode=(
                        None if dq_buffers == 2 else pl.Buffered(1))),
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
            name=_kernel_name(causal, "bwd"),
        )(*operands)
        return (dq,) + _sum_groups(dk, dv)

    # the split pair's dq grid iterates (bh, q-block, k-block)
    q_idx, k_idx, stat_idx = _index_maps(
        causal, block_q, block_k, num_q, num_k=num_k)
    kv_idx = _kv_index_map(k_idx, group)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **statics),
        grid=(bh, num_q, k_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, block_k, head_dim), kv_idx),
            pl.BlockSpec((1, block_k, v_dim), kv_idx),
            pl.BlockSpec((1, block_q, v_dim), q_idx),
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
        name=_kernel_name(causal, "dq"),
    )(*operands)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, with_dq=False, num_q=num_q, **statics),
        grid=(bh, num_k, q_steps),
        in_specs=dkv_in_specs,
        out_specs=dkv_out_specs,
        scratch_shapes=dkv_scratch,
        out_shape=dkv_structs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_kernel_name(causal, "dkv"),
    )(*operands)
    return (dq,) + _sum_groups(dk, dv)


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
    mask=None,
):
    """Blockwise attention over (batch, heads, seq, head_dim) inputs.
    ``mask``: a layout (``BlockDiffusion(half_len, block)``,
    ``Band(window)``) in the place of the boolean ``causal``; tiles or
    shapes it cannot carry are refused.
    k and v may have fewer heads than q (grouped-query attention: a
    head count that divides q's; query head ``h`` reads kv head ``h //
    group``, through the index maps, no copy; dk and dv are summed over
    the group after the backward kernel, ``_bwd``). ``v`` may have a
    width of its own (latent attention trains with q
    and k of 192 and v of 128): q, k and their gradients are
    ``head_dim`` wide, v, the output and their gradients ``v.shape[-1]``;
    ``sm_scale`` defaults to q's width.

    Sequence lengths must be multiples of the block sizes (the auto
    dispatcher in ops/attention.py falls back to the XLA impl when they
    are not); head_dim should be a multiple of 128 lanes for best MXU
    utilisation but any size compiles.

    block_q/block_k default (``None``) to the largest power-of-two
    blocks dividing the sequence, up to caps ``_blocks`` chooses from
    the shapes for the forward and for the backward apart (512 / 1024;
    1024 / 1024 for long sequences; 512 / 512 for a short one's
    backward; under a ``Band`` by its window): measured on v5e at
    S=16k, (512, 1024) runs 4.6x faster than (128, 128) — bigger
    k-blocks amortize the online-softmax rescale and keep the MXU fed.
    """
    if q.ndim != 4:
        raise ValueError("expected 4-D q/k/v")
    batch, heads, seq_q, head_dim = q.shape
    seq_k, v_dim = k.shape[2], v.shape[3]
    if k.shape[3] != head_dim:
        raise ValueError(
            "q and k must share a width, got %d and %d"
            % (head_dim, k.shape[3]))
    if k.shape[1] != v.shape[1] or heads % k.shape[1]:
        raise ValueError(
            "k and v must share a head count that divides q's, got "
            "%d, %d and %d" % (k.shape[1], v.shape[1], heads))
    layout = as_layout(causal if mask is None else mask)
    # the forward's blocks; the backward's are these or their halves
    fwd_q, fwd_k = _blocks(
        seq_q, seq_k, head_dim, q.dtype, block_q, block_k, v_dim=v_dim,
        layout=layout)
    if seq_q % fwd_q or seq_k % fwd_k:
        raise ValueError(
            "seq lengths (%d, %d) must be multiples of the block sizes "
            "(%d, %d)" % (seq_q, seq_k, fwd_q, fwd_k)
        )
    for backward in (False, True):
        refusal = layout.refusal(seq_q, seq_k, *_blocks(
            seq_q, seq_k, head_dim, q.dtype, block_q, block_k,
            backward=backward, v_dim=v_dim, layout=layout))
        if refusal:
            raise ValueError(refusal)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    # (batch, heads) merged, heads minor: with ``group`` query heads a
    # kv head, merged query head ``b`` reads merged kv head ``b // group``
    merge = lambda t: t.reshape((-1,) + t.shape[2:])
    o = _flash(
        merge(q),
        merge(k),
        merge(v),
        sm_scale,
        # the boolean a call always passed, where it passed one
        causal if mask is None else layout,
        block_q,
        block_k,
        interpret,
    )
    return o.reshape(batch, heads, seq_q, v_dim)
