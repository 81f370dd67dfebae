"""Flash attention for TPU — the Pallas analog of the reference's fused
attention kernels (csrc/transformer/softmax_kernels.cu:595 fused
scale+mask+softmax + cublas strided-batch QK^T/PV matmuls,
csrc/transformer/inference/csrc/softmax.cu).

Instead of materializing the [S, S] score matrix in HBM between three kernel
launches like the CUDA reference, the whole QK^T -> online-softmax -> PV chain
runs in one Pallas kernel, streaming K/V blocks through VMEM with fp32
accumulators (flash-attention style).  The MXU sees two big matmuls per block
pair; HBM traffic is O(S*d) instead of O(S^2).

q and k share a head size and v may have another: the output takes
v's (``d_v`` in the two calls below).

Backward is the FlashAttention-2 scheme: forward saves only the per-row
logsumexp; ONE Pallas kernel (_fa_bwd_kernel, `flash_bwd_dkdv` in a
trace) recomputes P block-wise, each tile once, and makes dk, dv and dq
from it with no [S, S] HBM materialization: key blocks outermost, q
blocks inner, a head's whole dq resident in VMEM as float32 and written
once a head: five products a tile.  (A second kernel that rebuilt every
tile for dq, seven products in all, took half as long again: the call
at [2, 20, 8192, 256] 36.2 ms on the v5e against 24.1 now, 1.91 against
1.29 at [1, 16, 4096, 128], the gradients equal bit for bit: PERF.md
section 6, PR 52.)  The XLA reference path serves CPU and the
bias/fallback cases.

The forward kernel (_fa_kernel) computes a step transposed: scores
k . q^T, [keys, block_q], q rows along the LANES.  A row's running max,
sum and rescaling factor are then [1, block_q] rows that fill their
vector registers, a step's max and sum reduce over the sublanes, and
out = (v^T . p)^T is transposed once a q block.  With the rows on the
sublanes (the kernel until PR 35) every per-row number was a cross-lane
reduction and then a [block_q, 1] column, one lane in 128 at work, and
that was six tenths of the kernel: 0.435 ms a call at [4, 20, 1024, 64],
causal, dropout 0.1, on the v5e against 0.287 now (PERF.md section 6,
PR 35).  What is left follows the score elements (scale, mask, exp, sum,
the dropout planes and select, the cast), not the rows or the steps.
The backward kernel carries no such state and keeps q rows on the
sublanes; the dropout bits are defined in that orientation
(_dropout_keep), and the forward transposes the drawn WORDS.

Inside a tile a causal call bounds its work by the diagonal (the section
"The causal bound inside a tile" below): of the tile's 512-column
sub-tiles, the ones wholly above the diagonal are not computed and only
those it can cross are masked, in the forward and the backward kernel
from the same dropout bits, each tile still being one step; the backward
kernel, whose time follows the products it computes, bounds each group of
256 q rows by its own last row as well.  With the shipped 512 x 1024
blocks at S=1024 the forward kernel computes 3/4 of the S x S square and
masks 2/4, the backward kernel 5/8 and 1/4, where both computed
and masked all of it (causal_sub_tile_shares counts it).  Non-causal
calls, and causal ones whose key block is no multiple of the sub-tile or
a single one, lower to the kernels as they were.
"""

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import (BATCH_AXES, HEAD_AXES, manual_kernel_region,
                       pallas_available, pallas_interpret)

# Finite mask value: keeps running-max finite for fully-masked rows (an -inf
# row max would turn exp(s - m) into NaN).
DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

_LANES = 128  # TPU lane width; softmax stats are carried at this width
# Row statistics (logsumexp, delta) ride as [B,H,S,8] so their blocks satisfy
# Mosaic's last-two-dims tiling rule; lane 0 holds the value.
_STATS_LANES = 8


# --------------------------------------------------------------------------- #
# Reference implementation (also the backward path and the CPU fallback)
# --------------------------------------------------------------------------- #
def mha_reference(q, k, v, causal: bool = False,
                  sm_scale: Optional[float] = None, bias=None,
                  dropout_rate: float = 0.0, dropout_seed=None,
                  window: Optional[int] = None):
    """Plain-XLA multi-head attention: q,k,v [B, H, S, D] -> [B, H, S, D].

    k and v may have fewer heads than q (a divisor): KV head j serves the
    query heads [j * group, (j + 1) * group).  `window` (causal only)
    keeps the last `window` keys of each query, its own included.

    fp32 softmax regardless of input dtype (matches the reference kernels,
    which upcast for the softmax — softmax_kernels.cu attn_softmax).
    dropout_rate > 0 applies PROBABILITY dropout (on the normalized
    softmax, the reference's attn-dropout semantics —
    dropout_kernels.cu:868) keyed by the int32 dropout_seed; the mask
    stream differs from the Pallas kernel's in-kernel PRNG, so the two
    paths agree in distribution, not bit-for-bit."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    group = _kv_group(q.shape[1], k.shape[1])
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        q_len, k_len = s.shape[-2], s.shape[-1]
        idx_q = jax.lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        idx_k = jax.lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        s = jnp.where(idx_k > idx_q, DEFAULT_MASK_VALUE, s)
        if window is not None:
            s = jnp.where(idx_k <= idx_q - window, DEFAULT_MASK_VALUE, s)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        keep = jax.random.bernoulli(
            jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.int32)),
            1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _kv_group(q_heads: int, kv_heads: int) -> int:
    """Query heads per key/value head."""
    if q_heads % kv_heads:
        raise ValueError(f"{q_heads} query heads are no multiple of "
                         f"{kv_heads} key/value heads")
    return q_heads // kv_heads


# --------------------------------------------------------------------------- #
# Pallas kernel
# --------------------------------------------------------------------------- #
def _ld(ref, rows=None):
    """Load the [rows, d] tile from a (1, 1, rows, d) block, or the rows
    `rows` (a static slice) of it."""
    if rows is None:
        return ref[0, 0]
    return ref[0, 0, rows, :]


def _st(ref, val):
    ref[0, 0] = val


def causal_keep_mask(qi_block, ki_block, block_q, block_k):
    """[block_q, block_k] keep mask (col <= row) from ABSOLUTE block
    indices — the one causal-tile mask shared by the dense fwd/bwd kernels
    and the block-sparse kernels (block_sparse_flash.py)."""
    row = qi_block * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    col = ki_block * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return col <= row


# --------------------------------------------------------------------------- #
# The causal bound inside a tile
# --------------------------------------------------------------------------- #
# The shipped blocks are large (512 x 1024: small blocks are
# grid-overhead-bound, see DEFAULT_BLOCK_Q), so at S=1024 one key block
# spans the whole row and skipping whole blocks above the diagonal skips
# nothing.  A causal call therefore bounds each [block_q, block_k] tile's
# work by the diagonal, in sub-tiles of _CAUSAL_SUB_K key columns and in
# both kernels (_walk_tile): the sub-tiles wholly above the
# diagonal are not computed, and of the leading ones that are, only those
# the diagonal can cross pay for the mask's iota, compare and select.  A
# tile is still ONE step (one online-softmax update, one accumulation),
# at a width that is static in each of block_k // _CAUSAL_SUB_K bodies
# picked by program_id: two with the shipped blocks.  In the backward
# kernel, where the blocks are aligned, the step is taken in groups of
# _CAUSAL_SUB_Q q rows, each as wide as its own last row reaches.
#
# Why one step of static width and not a loop over sub-tiles (v5e, PR 31,
# PERF.md section 6): with q rows on the sublanes, as the forward kernel
# had them then, a step's cost was mostly its rows' running max / sum /
# accumulator update and cross-lane reductions, paid per step whatever
# the width (0.275 of the kernel's 0.47 ms at [4, 20, 1024, 64]), so two
# lax.fori_loops over 512-column sub-tiles made the forward kernel 29%
# SLOWER than no bound (three steps a head for two) where this form made
# it 4% faster; the backward kernel carries no such state, follows the
# products it computes, and takes the same form.  PR 35 took that
# per-step cost out of the forward (rows along the lanes: _fa_kernel) and
# kept this form; a loop has not been tried on the new body.
#
# Why 512 columns: the 8-bit dropout layout draws one PRNG word per four
# columns, so a 512-wide sub-tile's words are exactly one 128-lane vreg
# column ([block_q, 128]) and its four byte planes concatenate on vreg
# boundaries: the dropout bits are drawn per 512-column unit whatever is
# computed of it.  (Looped, 256- and 128-column sub-tiles made the
# forward kernel 2.3 and 3.8 times slower than no bound.)
_CAUSAL_SUB_K = 512


def _causal_sub_tile(block_q: int, block_k: int, causal: bool) -> int:
    """Width of the key-column sub-tiles a causal call walks its tiles
    in, or 0 where the tile is walked whole as before: a non-causal call,
    and a causal one whose resolved key block is no multiple of
    _CAUSAL_SUB_K or a single sub-tile."""
    if causal and block_k > _CAUSAL_SUB_K and block_k % _CAUSAL_SUB_K == 0:
        return _CAUSAL_SUB_K
    return 0


def _causal_sub_range(qi, ki, block_q, block_k, sub):
    """(n_full, n_end) for tile (qi, ki): of its block_k // sub sub-tiles,
    [0, n_full) lie wholly under the diagonal (last column <= first row:
    no mask), [n_full, n_end) are crossed by it (masked) and the rest lie
    wholly above (first column > last row: not computed).  Python ints
    for the counter, traced int32 scalars inside the kernels."""
    lo, hi = ((max, min) if isinstance(qi, int)
              else (jnp.maximum, jnp.minimum))
    n_sub = block_k // sub
    off = qi * block_q - ki * block_k        # first row - first column
    n_full = hi(lo(off + 1, 0) // sub, n_sub)
    n_end = hi(lo(off + block_q - 1 + sub, 0) // sub, n_sub)
    return n_full, n_end


def causal_pieces(q_len, k_len, block_q, block_k, causal=True,
                  backward=False):
    """What a call's forward kernel (or, `backward`, its backward
    kernel) computes, as rectangles of the S x S score matrix:
    (row0, row1, col0, col1, mask0) for rows [row0, row1) against key
    columns [col0, col1), the causal mask applied from column mask0 on
    (mask0 == col1: none).  Static, from the lengths and the blocks as
    the kernels fit them."""
    _, block_q, block_k = _resolve_blocks(q_len, k_len, block_q, block_k)
    walk = _causal_walk(q_len, k_len, block_q, block_k, causal, backward)
    sub = walk.get("sub_k", block_k)
    n_sub = block_k // sub
    for qi in range(q_len // block_q):
        for ki in range(k_len // block_k):
            row0, col0 = qi * block_q, ki * block_k
            n_full, n_end = ((n_sub, n_sub) if not causal else
                             _causal_sub_range(qi, ki, block_q, block_k,
                                               sub))
            if n_end == 0:
                continue
            if not walk:    # walked whole: a causal tile is masked whole
                parts = [(None, block_k, 0 if causal else None)]
            elif n_full == n_sub:
                parts = [(None, block_k, None)]
            else:
                parts = _prefix_parts(n_end, block_q, sub, walk["band"],
                                      walk["sub_q"])
            for qrows, width, mask_col in parts:
                r0, r1 = (0, block_q) if qrows is None else (qrows.start,
                                                             qrows.stop)
                yield (row0 + r0, row0 + r1, col0, col0 + width,
                       col0 + (width if mask_col is None else mask_col))


def causal_sub_tile_shares(q_len, k_len, block_q, block_k, causal):
    """{kernel: (computed, masked)}: the share of the S x S products each
    kernel of a call computes, and the share that also pays for the
    causal mask (causal_pieces, by area).  (1, 0) for a non-causal call,
    (1, 1) for a causal one walked whole at one tile, (1/2, 0) in the
    limit of an exact bound.  Static and exact: this is the mechanism's
    counter."""
    def shares(backward):
        computed = masked = 0
        for row0, row1, col0, col1, mask0 in causal_pieces(
                q_len, k_len, block_q, block_k, causal, backward):
            computed += (row1 - row0) * (col1 - col0)
            masked += (row1 - row0) * (col1 - mask0)
        return computed / (q_len * k_len), masked / (q_len * k_len)
    return {"flash_fwd": shares(False), "flash_bwd_dkdv": shares(True)}


# The dropout draw: one PRNG word per FOUR mask positions, compared byte
# by byte against the quantized keep probability (1/256 granularity,
# corrected by the exact inverse scale, _keep_scale), a quarter of the
# PRNG words of a draw per position in each of the kernels that
# regenerate the mask (v5e, round 4: +2.7% on the flagship step).  The
# dispatcher keeps every key block the kernels see a multiple of 128
# columns (_use_pallas: any other length goes to XLA), so a block always
# holds whole words.
def _quantized_threshold(rate: float) -> int:
    """The integer threshold the kernel compares random bytes against —
    the ONE definition shared by mask generation and its inverse scale
    (two copies drifting apart would bias E[output])."""
    return max(1, min(256, round((1.0 - rate) * 256)))


def _keep_scale(rate: float) -> float:
    """Exact inverse keep-probability for the quantized threshold the
    kernel actually compares against — using 1/(1-rate) with the 8-bit
    threshold would bias E[output] by up to ~0.2%."""
    return 256.0 / _quantized_threshold(rate)


def _fmix32(x):
    """murmur3's 32-bit finalizer."""
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    x = (x ^ (x >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _interpret_random_bits(v1, v2, shape):
    """Interpret-mode stand-in for prng_seed(v1, v2) + prng_random_bits:
    the TPU PRNG has no CPU lowering (the Pallas TPU interpreter returns
    zeros for it), so the CPU lane hashes the two seed values and the
    position instead.  Another stream than the chip's, but regenerable
    from the same tile coordinates, which is all the kernels rely on."""
    key = _fmix32(_fmix32(v2.astype(jnp.uint32)) ^ v1.astype(jnp.uint32))
    pos = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
           * np.uint32(shape[1])
           + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    return _fmix32(pos ^ key)


def _dropout_keep(seed_ref, b, h, qi, ki, rate, block_q, block_k,
                  num_k_blocks, interpret=False, keys_first=False):
    """Regenerable per-tile keep mask: the PRNG is reseeded from the step
    seed and the tile's ABSOLUTE coordinates, so the forward kernel and
    the backward kernel (whose grids order (qi, ki) differently)
    reproduce the identical mask — the TPU analog of the reference's
    philox-offset dropout (dropout_kernels.cu:868).

    Mosaic on current TPUs rejects prng_seed with more than 2 values, so
    the coordinates are folded exactly into two: (seed, b, h) -> value 1
    (grid dim 1 is the head axis in both kernels, so num_programs(1)
    is the head count) and (qi, ki, seed) -> value 2 via the static
    k-block count.  The seed rides in BOTH values: with value 1 alone,
    sequential per-step seeds (the natural dropout_seed=step usage) would
    alias step s+1/head h with step s/head h+1 and recycle whole mask
    patterns.  Value 2 mixes the seed with the Knuth multiplicative hash
    (2654435761 == -1640531527 as an int32 bit pattern): int32 multiply
    wraps mod 2^32 (MLIR arith has two's-complement semantics, no UB),
    and under that wrap an odd multiplier is a bijection of the seed, so
    the anti-aliasing argument holds for arbitrary step counts — unlike
    the old seed*40503, whose argument silently broke once the product
    first wrapped (seed ~53k).  A collision now needs seed'-seed ==
    bh-bh' AND tile-tile' == (seed'-seed)*2654435761 mod 2^32 —
    vanishingly unlikely while tile counts stay tiny vs 2^32.  All
    arithmetic stays in plain int32: scalar casts/bitcasts are
    Mosaic-illegal ('tpu.bitcast' needs vector operands — measured on
    v5e, round 4).

    `keys_first` (the forward kernel, whose scores are [keys, q rows]):
    the same decision for every (row, key), as [block_k, block_q].  The
    WORDS are drawn as ever and transposed, a quarter of the tile; their
    four byte planes then stack along the sublanes."""
    v1 = seed_ref[0] + b * pl.num_programs(1) + h
    v2 = qi * num_k_blocks + ki + seed_ref[0] * np.int32(-1640531527)
    if interpret:
        def random_bits(shape):
            return _interpret_random_bits(v1, v2, shape)
    else:
        pltpu.prng_seed(v1, v2)
        random_bits = pltpu.prng_random_bits
    # one 32-bit word per FOUR mask positions: byte j of word w maps to
    # column j*block_k/4 + w (column-GROUP layout — no Mosaic lane
    # interleave needed; each (word, byte) is used exactly once, so
    # positions stay iid uniform bytes).
    assert block_k % 4 == 0, "the dropout draw requires block_k % 4 == 0"
    w = random_bits((block_q, block_k // 4))
    if keys_first:
        # signed words: the planes are 0..255 either way, and the
        # v5e compares int32 natively (uint32: 0.010 ms a call more)
        w = w.astype(jnp.int32).T
        planes = [(w >> (8 * j)) & 0xFF for j in range(3)] + [
            jax.lax.shift_right_logical(w, jnp.int32(24))]
        return (jnp.concatenate(planes, axis=0)
                < np.int32(_quantized_threshold(rate)))
    w = w.astype(jnp.uint32)
    m = jnp.concatenate(
        [(w >> np.uint32(8 * j)) & np.uint32(0xFF) for j in range(4)],
        axis=1)
    return m < np.uint32(_quantized_threshold(rate))


# --------------------------------------------------------------------------- #
# The window: a band under the diagonal
# --------------------------------------------------------------------------- #
# A causal call with `window` = W lets a query see its last W keys, its
# own included: column c of row r is kept where r - W < c <= r.  Only the
# tiles the band crosses are visited at all: the inner grid dimension of
# each kernel counts `steps` blocks from the first one its outer block
# needs (_Band), the operands' index maps fetch those, and a step that
# falls past the last block of the sequence, or on a tile outside the
# band, computes nothing.  Tiles are walked whole and masked whole (no
# sub-tile plan: a band of 512 in blocks of 512 has no sub-tile to skip).
# A call without a window never comes here: its grid, index maps and
# kernel bodies are what they were.
class _Band:
    """The band's geometry for blocks of block_q x block_k over nq x nk
    tiles: the first inner block of an outer block and the inner steps,
    for the kernel that walks key blocks under a q block (`first_k`,
    `steps_k`: the forward) and the one that walks q blocks over a key
    block (`first_q`, `steps_q`: the backward).  The first-block functions take Python ints or
    traced int32 scalars."""

    def __init__(self, window, block_q, block_k, nq, nk):
        self.window, self.nq, self.nk = window, nq, nk
        self.block_q, self.block_k = block_q, block_k
        self.steps_k = max(
            min((qi * block_q + block_q - 1) // block_k, nk - 1)
            - self.first_k(qi) + 1 for qi in range(nq))
        self.steps_q = max(
            min((ki * block_k + block_k + window - 2) // block_q, nq - 1)
            - self.first_q(ki) + 1 for ki in range(nk))

    def first_k(self, qi):
        lo = max if isinstance(qi, int) else jnp.maximum
        return lo(qi * self.block_q - (self.window - 1), 0) // self.block_k

    def first_q(self, ki):
        return (ki * self.block_k) // self.block_q

    def k_block(self, qi, j):
        """Index map: the key block of inner step j under q block qi."""
        return jnp.minimum(self.first_k(qi) + j, self.nk - 1)

    def q_block(self, ki, j):
        return jnp.minimum(self.first_q(ki) + j, self.nq - 1)


def _walk_tile(qi, ki, block_q, block_k, causal, walk, step, band=None):
    """Run a kernel's step on tile (qi, ki): step(kj, n, rows, parts).

    Without a plan (`walk` empty: a non-causal call, or blocks the
    sub-tile does not divide) the tile is one step over its whole key
    block, masked whole if causal, and skipped if wholly above the
    diagonal (the analog of the reference's triangular-launch trick).

    With one (_causal_walk) the tile's work is bounded by the diagonal
    inside it: one step over the n leading sub-tiles of walk["sub_k"] key
    columns that hold a position under the diagonal, at a static width,
    one body per n (two with the shipped blocks); n == 0, the tile wholly
    above, runs none.  kj is the first sub-tile's index along the whole
    key axis (the dropout stream's coordinate), rows the key rows inside
    the block, and parts the step's static pieces (q rows, key columns,
    first masked column; _prefix_parts):
    - one piece, all q rows by n * sub columns.  The diagonal crosses at
      most `band` sub-tiles of any tile, so the ones before the last
      `band` lie wholly under it and go unmasked;
    - or, where the blocks are aligned (sub_q > 0: the q block a multiple
      of `sub`, the key block a multiple of the q block, so a tile on the
      diagonal ends its n sub-tiles exactly at its last row), one piece a
      group of sub_q rows, each as wide as its own last row reaches and
      masked in its own diagonal square alone.
    A tile wholly under the diagonal takes an unmasked body of its own,
    traced only where the call has such a tile (`full`)."""
    if not walk:
        should_compute = True
        if causal:
            should_compute = qi * block_q + block_q - 1 >= ki * block_k
        if band is not None:
            # inside the sequence, and the tile's last column within
            # reach of its first row
            should_compute &= (
                (qi < band.nq) & (ki < band.nk)
                & (ki * block_k + block_k - 1 > qi * block_q - band.window))

        @pl.when(should_compute)
        def _compute():
            step(ki, 1, None, [(None, block_k, 0 if causal else None)])
        return

    sub, band, full, sub_q = (walk[key] for key in
                              ("sub_k", "band", "full", "sub_q"))
    n_sub = block_k // sub
    n_full, n_end = _causal_sub_range(qi, ki, block_q, block_k, sub)
    for n in range(1, n_sub + 1):
        if sub_q and n * sub < block_q:
            continue   # aligned, no tile on the diagonal ends this early
        parts = _prefix_parts(n, block_q, sub, band, sub_q)
        crossed = n_end == n
        if full and n == n_sub:
            crossed &= n_full < n_sub

        @pl.when(crossed)
        def _(n=n, parts=parts):
            step(ki * n_sub, n, None if n == n_sub else slice(0, n * sub),
                 parts)

    if full:
        @pl.when(n_full == n_sub)
        def _():
            step(ki * n_sub, n_sub, None, [(None, block_k, None)])


def _prefix_parts(n, block_q, sub, band, sub_q):
    """The static pieces (q rows, key columns, first masked column) of
    the step over a tile's n leading sub-tiles (_walk_tile)."""
    if sub_q:
        return [(slice(r, r + sub_q), n * sub - block_q + r + sub_q,
                 n * sub - block_q + r)
                for r in range(0, block_q, sub_q)]
    return [(None, n * sub, max(0, n - band) * sub)]


# Rows of a q-row group of the aligned form, in the backward kernel
# (v5e, PR 31, when it was two, alone at [4, 20, 1024, 64], ms a call
# with groups of 512 = none / 256 / 128 rows): dkdv 0.457 / 0.401 /
# 0.425, dq 0.304 / 0.271 / 0.266; at 128 the modules pass twice the non-causal ones' size
# (tests/unit/test_flash_setup_guard.py).  The forward kernel takes none:
# 0.434 / 0.447 / 0.412 on the body of that time (rows on the sublanes,
# its cost per row and step, not per column).  Since PR 35 its rows lie
# along the lanes and its cost follows the score elements; a group of q
# rows is then a lane slice of every [keys, block_q] array, and has not
# been tried.
_CAUSAL_SUB_Q = 256


def _causal_walk(q_len, k_len, block_q, block_k, causal, row_groups):
    """A kernel's plan for the causal bound (_walk_tile), static, from
    the lengths and the resolved blocks: the sub-tile width, the most
    sub-tiles of one tile that the diagonal crosses (`band`), whether
    some tile lies wholly under it (`full`) and, for a kernel that takes
    them (`row_groups`: the backward), the rows of a q-row group
    where the blocks are aligned (`sub_q`, else 0).  {} where the tiles
    are walked whole (_causal_sub_tile)."""
    sub = _causal_sub_tile(block_q, block_k, causal)
    if not sub:
        return {}
    ranges = [_causal_sub_range(qi, ki, block_q, block_k, sub)
              for qi in range(q_len // block_q)
              for ki in range(k_len // block_k)]
    aligned = (block_q % sub == 0 and block_k % block_q == 0
               and block_q % _CAUSAL_SUB_Q == 0)
    return dict(sub_k=sub,
                band=max(n_end - n_full for n_full, n_end in ranges),
                full=any(n_full == block_k // sub for n_full, _ in ranges),
                sub_q=_CAUSAL_SUB_Q if row_groups and aligned else 0)


def _step_keep(seed_ref, b, h, qi, kj, n, rate, block_q, unit, num_units,
               interpret, keys_first=False):
    """Keep mask of a step over the n units of `unit` key columns from
    unit kj on: each unit's own regenerable draw (_dropout_keep), side by
    side, so the mask of a position does not depend on how a kernel
    walks its tiles.  [block_q, n * unit], or `keys_first` its
    transpose."""
    draws = [_dropout_keep(seed_ref, b, h, qi, kj + j if j else kj, rate,
                           block_q, unit, num_units, interpret=interpret,
                           keys_first=keys_first)
             for j in range(n)]
    return draws[0] if n == 1 else jnp.concatenate(
        draws, axis=0 if keys_first else 1)


def _step_mask(x, qi, kj, block_q, unit, qrows, mask_col, fill,
               window=None, keys_first=False):
    """x, the scores or probabilities of q rows `qrows` (None: all) of
    block qi against the key columns from unit kj on, with `fill` above
    the diagonal from column mask_col on (None: the piece lies wholly
    under the diagonal); under a `window`, the whole tile, with `fill`
    left of the band as well.  x is [q rows, keys], or `keys_first`
    [keys, q rows]."""
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    if window is not None:
        row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, x.shape,
                                                      q_axis)
        col = kj * unit + jax.lax.broadcasted_iota(jnp.int32, x.shape,
                                                   k_axis)
        return jnp.where((col <= row) & (col > row - window), x, fill)
    if mask_col is None:
        return x
    if (not keys_first and qrows is None and mask_col == 0
            and x.shape[1] == unit):
        return jnp.where(causal_keep_mask(qi, kj, block_q, unit), x, fill)
    shape = list(x.shape)
    shape[k_axis] -= mask_col
    row = (qi * block_q + (qrows.start if qrows else 0)
           + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis))
    col = kj * unit + mask_col + jax.lax.broadcasted_iota(
        jnp.int32, shape, k_axis)
    tail = jnp.where(col <= row,
                     x[mask_col:] if keys_first else x[:, mask_col:], fill)
    if mask_col == 0:
        return tail
    head = x[:mask_col] if keys_first else x[:, :mask_col]
    return jnp.concatenate([head, tail], axis=k_axis)


def _piece(x, qrows, width):
    """Rows `qrows` (None: all) and the first `width` columns of a
    step-wide array."""
    if qrows is not None:
        x = x[qrows]
    return x if width == x.shape[1] else x[:, :width]


def _at(rows):
    """Index of rows `rows` of a 2-D scratch (None: all of it)."""
    return (Ellipsis,) if rows is None else (rows, slice(None))


def _fa_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
               causal: bool, sm_scale: float, block_q: int, block_k: int,
               num_k_blocks: int, dropout_rate: float,
               interpret: bool = False, walk=None, band=None):
    """The forward kernel, q rows along the lanes: a step's scores are
    k . q^T, [keys, block_q], so the row statistics (max, sum, the
    rescaling of the accumulator) are [1, block_q] rows that fill their
    vector registers, the max and the sum of a step reduce over the
    sublanes (element-wise across registers, one 8-to-1 fold a lane
    tile), and the accumulator is v^T . p, [d, block_q]; out and the
    log-sum-exp are transposed back ONCE a q block.  It takes the q
    block it is given: whole lane tiles or less (64, 8, a short sequence
    whole).  Dropout is _dropout_keep's draw, keys_first.

    Without `scratch` the call's inner grid dimension is one step: there
    is no earlier max or sum to fold in, and the step finishes in place."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = step = pl.program_id(3)
    last_step = num_k_blocks - 1
    window = None
    if band is not None:   # inner step -> key block of the band
        ki, last_step, window = (band.first_k(qi) + step, band.steps_k - 1,
                                 band.window)

    def _finish(m, denom, acc):
        """Write out = acc / denom, [block_q, d], and the log-sum-exp
        m + log denom from the [1, block_q] statistics and the
        [d, block_q] accumulator: the q block's one transpose of each."""
        # logsumexp residual for the backward pass (FlashAttention-2 style)
        lse = m + jnp.log(denom + 1e-37)
        # Fully-masked rows have denom == 0; emit zeros not NaN.
        out_t = acc / jnp.where(denom == 0.0, 1.0, denom)
        _st(o_ref, out_t.T.astype(o_ref.dtype))
        lse_ref[0, 0] = jnp.broadcast_to(lse, (_STATS_LANES, block_q)).T

    if scratch:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(step == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    unit = walk["sub_k"] if walk else block_k

    def _update(kj, n, rows, parts):
        """One online-softmax step over the n units of `unit` keys from
        unit kj on: the whole block (n 1, rows None, kj == ki) or
        sub-tiles of it, key rows `rows` of the block.  In one piece: the
        forward kernel takes no q-row groups."""
        (_, _, mask_col), = parts
        # bf16 operands straight into the MXU; fp32 accumulation via
        # preferred_element_type (upcasting first would force an fp32 matmul).
        q = _ld(q_ref)                               # [bq, d]
        k = _ld(k_ref, rows)                         # [width, d]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [width, bq] fp32
        s = _step_mask(s, qi, kj, block_q, unit, None, mask_col,
                       DEFAULT_MASK_VALUE, window, keys_first=True)

        m_next = jnp.max(s, axis=0, keepdims=True)    # [1, bq]
        if scratch:
            m_prev = m_scr[...]
            m_next = jnp.maximum(m_prev, m_next)
            alpha = jnp.exp(m_prev - m_next)          # [1, bq]
        p = jnp.exp(s - m_next)                       # [width, bq] fp32
        l_next = jnp.sum(p, axis=0, keepdims=True)    # [1, bq]

        if dropout_rate > 0.0:
            # probability dropout: the PV input is masked+rescaled but the
            # normalizer l accumulates the RAW p (softmax normalizes true
            # probabilities; dropout applies to the normalized P, which
            # commutes with the final /l)
            keep = _step_keep(seed_ref, b, h, qi, kj, n, dropout_rate,
                              block_q, unit,
                              num_k_blocks * (block_k // unit),
                              interpret, keys_first=True)
            p = jnp.where(keep, p * _keep_scale(dropout_rate), 0.0)

        v_blk = _ld(v_ref, rows)                     # [width, d]
        pv = jax.lax.dot_general(
            v_blk, p.astype(v_blk.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [d, bq]
        if not scratch:
            _finish(m_next, l_next, pv)
            return
        m_scr[...] = m_next
        l_scr[...] = l_scr[...] * alpha + l_next
        acc_scr[...] = acc_scr[...] * alpha + pv

    _walk_tile(qi, ki, block_q, block_k, causal, walk, _update, band)

    if scratch:
        @pl.when(step == last_step)
        def _finalize():
            _finish(m_scr[...], l_scr[...], acc_scr[...])


def _seed_arg(dropout_seed):
    """int32[1] scalar-prefetch operand (0 when dropout is off)."""
    if dropout_seed is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(dropout_seed, jnp.int32).reshape((1,))


def _fit_block(length: int, target: int, align: int) -> int:
    """Largest divisor of `length` <= `target` that is a multiple of
    `align`; falls back to the largest unaligned divisor (callers judge
    usability).  A short whole length (< align) is its own block."""
    best_unaligned = 1
    b = min(target, length)
    while b >= 1:
        if length % b == 0:
            if b % align == 0 or b == length:
                return b
            if best_unaligned == 1:
                best_unaligned = b
        b -= 1
    return best_unaligned


def _resolve_blocks(q_len, k_len, block_q, block_k):
    """Fit the requested blocks to the sequence lengths.

    Returns (usable, bq, bk): the largest ALIGNED divisors of the lengths
    at most the requested blocks (k lane-aligned, q sublane-aligned), so
    e.g. 1536 fits as 512x768 and 1152 as 384x384.  `usable` requires a
    strictly lane/sublane-aligned tiling: a length with no such divisor
    (primes, 1000, short whole lengths < the 128-lane width) dispatches to
    XLA instead — masked lane reductions on partial tiles are exactly the
    configuration the TPU-path tests cannot cover (interpret-mode tests
    don't exercise lane masking), so the dispatcher never runs them."""
    bq = _fit_block(q_len, block_q, 8)
    bk = _fit_block(k_len, block_k, _LANES)
    usable = bk % _LANES == 0 and bq % 8 == 0
    return usable, bq, bk


def _dims(arr, layout):
    """(batch, heads, seq, d) for either layout."""
    if layout == "bhsd":
        b, h, s, d = arr.shape
    else:  # "bshd": [B, S, heads, d] — head dim indexed in the BlockSpec
        b, s, h, d = arr.shape
    return b, h, s, d


def _tile_spec(rows, d, seq_of, group=1, inner=None):
    """[B, H, S, D] BlockSpec for one [rows, d] tile per (b, h) grid
    cell; `seq_of` picks which grid index walks the sequence dim ('i' or
    'j').  The trailing *_ absorbs the scalar-prefetch ref (the dropout
    seed) that PrefetchScalarGridSpec appends to every index_map.

    `group` > 1: the operand is a key or value array with one head for
    every `group` heads of the grid, found by index and not by a
    repeated copy.  `inner` (a banded call): maps (outer block, inner
    step) to the block fetched (_Band.k_block).  Both with seq_of 'j':
    the forward's key and value tiles.  (The backward call writes its
    own index maps: _flash_bwd_call.)

    (A native [B, S, heads, d] tiling — block (1, rows, 1, d) indexing
    the head dim — is Mosaic-ILLEGAL: the block's last two dims are then
    (1, d) over a (heads, d) axis pair, and 1 is neither a multiple of 8
    nor the full head count.  Measured round 3 on v5e: such specs fail
    Pallas lowering outright, so the "bshd" layout transposes at the
    kernel boundary instead — see flash_attention_pallas.)"""
    if group > 1 or inner is not None:
        def index(b, h, i, j, *_):
            return (b, h // group if group > 1 else h,
                    j if inner is None else inner(i, j), 0)
        return pl.BlockSpec((1, 1, rows, d), index)
    if seq_of == "i":
        return pl.BlockSpec((1, 1, rows, d),
                            lambda b, h, i, j, *_: (b, h, i, 0))
    return pl.BlockSpec((1, 1, rows, d),
                        lambda b, h, i, j, *_: (b, h, j, 0))


def _kernel_name(base, band):
    """A banded call's kernels carry their own names, so that a trace
    tells them from the full causal ones."""
    return base if band is None else base + "_band"


def _check_window(window, causal, dropout_rate):
    if window is None:
        return
    if not causal or window < 1:
        raise ValueError(f"window={window}: a window is the last `window` "
                         "keys of a causal call, at least 1")
    if dropout_rate > 0.0:
        raise ValueError("a windowed call takes no in-kernel dropout: the "
                         "banded grid has no dropout units")


def flash_attention_pallas(q, k, v, causal: bool = False,
                           sm_scale: Optional[float] = None,
                           block_q: int = 512, block_k: int = 1024,
                           interpret: bool = False, return_lse: bool = False,
                           layout: str = "bhsd", dropout_rate: float = 0.0,
                           dropout_seed=None,
                           window: Optional[int] = None):
    """Pallas flash attention.

    k and v may have fewer heads than q (a divisor of its count; the
    index maps fetch head h // group).  `window` (causal only, no
    dropout): each query sees its last `window` keys, its own included,
    and only the tiles that band crosses are visited (_Band).

    layout="bhsd" (default): q,k,v [B, H, S, D] -> [B, H, S, D].
    layout="bshd": q,k,v [B, S, heads, D] -> [B, S, heads, D], converted
    to the kernel's [B, H, S, D] at this boundary.  (A native bshd
    BlockSpec — (1, rows, 1, d) indexing the head dim — is Mosaic-illegal
    and fails Pallas lowering on real TPUs, measured round 3; the
    transposes here are cheap relative to the attention itself and XLA
    fuses them into neighbors where it can.)
    logsumexp (when return_lse) is [B, H, S] in BOTH layouts."""
    return _flash_fwd_call(
        q, k, v, dropout_seed, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        return_lse=return_lse, layout=layout,
        dropout_rate=float(dropout_rate), window=window)


# The calls' own programs are traced once a process and shape: every
# pallas_call traces its kernel body in Python each time the function
# around it is traced (four times a kernel in one grad program: the
# custom_vjp's primal and forward rule, the rematerialised pass, the
# transpose), in every program that holds it, warm compile cache or not,
# and that time is the benchmark's gated setup_s.  Under an outer jit
# these are inlined calls, not programs of their own.
@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret", "return_lse",
    "layout", "dropout_rate", "window"))
def _flash_fwd_call(q, k, v, dropout_seed, *, causal, sm_scale, block_q,
                    block_k, interpret, return_lse, layout, dropout_rate,
                    window=None):
    """flash_attention_pallas, traced once a process and shape."""
    batch, heads, q_len, d = _dims(q, layout)
    kv_heads, k_len = _dims(k, layout)[1:3]
    d_v = _dims(v, layout)[3]   # the values' own head size (and out's)
    group = _kv_group(heads, kv_heads)
    _check_window(window, causal, dropout_rate)
    if layout == "bshd":
        q, k, v = _t_bhsd(q), _t_bhsd(k), _t_bhsd(v)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # fit to the lengths (largest aligned divisors <= requested blocks);
    # explicit small blocks are legal (kernel tests use 64x64) but a
    # degenerate 1-wide tiling (prime-ish length) is rejected loudly —
    # the flash_attention dispatcher falls back to XLA for those
    _, block_q, block_k = _resolve_blocks(q_len, k_len, block_q, block_k)
    if (block_q == 1 and q_len > 1) or (block_k == 1 and k_len > 1):
        raise ValueError(
            f"seq lengths ({q_len},{k_len}) only tile into 1-wide blocks "
            f"— use the flash_attention dispatcher (XLA fallback)")
    nq, nk = q_len // block_q, k_len // block_k
    seed = _seed_arg(dropout_seed)

    band = None if window is None else _Band(window, block_q, block_k,
                                             nq, nk)
    walk = ({} if band else
            _causal_walk(q_len, k_len, block_q, block_k, causal, False))
    steps = band.steps_k if band else nk
    kernel = functools.partial(
        _fa_kernel, causal=causal, sm_scale=float(sm_scale), block_q=block_q,
        block_k=block_k, num_k_blocks=nk, dropout_rate=float(dropout_rate),
        interpret=interpret, walk=walk, band=band)
    # an inner extent of one step carries nothing between steps
    scratch = [] if steps == 1 else [
        pltpu.VMEM((1, block_q), jnp.float32),   # running max
        pltpu.VMEM((1, block_q), jnp.float32),   # running sum
        pltpu.VMEM((d_v, block_q), jnp.float32),   # output accumulator
    ]
    k_spec, v_spec = (_tile_spec(block_k, width, "j", group,
                                 band.k_block if band else None)
                      for width in (d, d_v))

    out_specs = [
        _tile_spec(block_q, d_v, "i"),
        pl.BlockSpec((1, 1, block_q, _STATS_LANES),
                     lambda b, h, i, j, *_: (b, h, i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((batch, heads, q_len, d_v), q.dtype),
        jax.ShapeDtypeStruct((batch, heads, q_len, _STATS_LANES),
                             jnp.float32),
    ]
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, heads, nq, steps),
            in_specs=[_tile_spec(block_q, d, "i"), k_spec, v_spec],
            out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        interpret=interpret,
        name=_kernel_name("flash_fwd", band),
        **params,
    )(seed, q, k, v)
    if layout == "bshd":
        out = _t_bhsd(out)
    return (out, lse[..., 0]) if return_lse else out


# --------------------------------------------------------------------------- #
# Pallas backward kernel (FlashAttention-2 style, one visit of each tile)
# --------------------------------------------------------------------------- #
# What a call gets that declares no limit, the most a call may ask of
# the v5e's 128 MiB, and the float32 [block_q, block_k] arrays a step of
# the backward kernel is reckoned to hold at once (scores, probabilities,
# dO v^T, dS, the dropout bits: the v5e's compiler counts fewer).
_VMEM_DEFAULT = 16 * 1024 * 1024
_VMEM_LIMIT = 100 * 1024 * 1024
_BWD_TILE_TEMPS = 5


def _bwd_vmem(span_rows, d, block_q, block_k, itemsize, d_v=None):
    """The VMEM `flash_bwd_dkdv` asks for, in bytes and from above: what
    grows with the sequence (dq of a span of q rows: float32 scratch and
    its output, double-buffered), a key block's share (dk and dv alike,
    and k and v double-buffered), a q block's (q and dO, and the two row
    statistics at a lane tile a row, double-buffered) and the step's
    temporaries.  A head narrower than a lane tile still takes one;
    ``d_v`` is the values' head size where it is not ``d`` (dv, v and dO
    are that wide, dq, dk, q and k ``d``).  On
    two-byte operands in blocks of 512 x 1024: 15.5 MiB at 1,024 x 64
    where the v5e's compiler counts 6.1, 22.5 at 8,192 x 128 (15.0), 34
    at 8,192 x 256 (28.3), 82 at 32,768 x 256 (my compiles for the v5e,
    PR 52)."""
    lanes = -(-d // _LANES) * _LANES
    # dv, v and dO at the values' head size, where it is another
    both = lanes + (lanes if d_v is None else -(-d_v // _LANES) * _LANES)
    resident = span_rows * lanes * (4 + 2 * itemsize)
    keys = block_k * both * (4 + 2 * itemsize + 2 * itemsize)
    rows = 2 * block_q * (both * itemsize + 2 * _LANES * 4)
    tile = _BWD_TILE_TEMPS * 4 * block_q * block_k
    return resident + keys + rows + tile


def _bwd_spans(q_len, d, block_q, block_k, itemsize, d_v=None):
    """Spans of q rows the backward call walks: the fewest whole shares
    of the q blocks whose dq fits (_bwd_vmem under _VMEM_LIMIT).  One at
    every shape a cell or a test runs; from the shape alone."""
    nq = q_len // block_q
    for spans in range(1, nq):
        if nq % spans == 0 and _bwd_vmem(
                q_len // spans, d, block_q, block_k, itemsize,
                d_v) <= _VMEM_LIMIT:
            return spans
    return nq


def _fa_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                   *, causal, sm_scale, block_q, block_k, num_k_blocks,
                   steps, num_spans, dropout_rate, interpret: bool = False,
                   walk=None, band=None):
    """The backward kernel: each tile built once (q k^T, the
    exponentials, the mask, the dropout bits, dO v^T, dS) and dv, dk and
    dq made from it.  Key blocks outermost, `steps` q blocks inner: dk
    and dv of the key block are carried over the q blocks, and dq of the
    head's whole span of q rows stays in VMEM as float32 over the key
    blocks (added to key blocks ascending, a q row group at a time) and
    leaves once."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)
    qi = step = pl.program_id(3)
    window = None if band is None else band.window
    if num_spans > 1:   # the key blocks, once a span of `steps` q blocks
        ki, qi = ki % num_k_blocks, (ki // num_k_blocks) * steps + step
    elif band is not None:   # inner step -> q block of the band
        qi = band.first_q(ki) + step
    last_step = steps - 1
    # the q block's first row in dq_scr, which holds the span's rows
    dq_row = (qi if num_spans == 1 else step) * block_q

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when((ki == 0) & (step == 0))
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    unit = walk["sub_k"] if walk else block_k

    def _update(kj, n, rows, parts):
        """This tile's part of dk, dv and dq for the n units of `unit`
        keys from unit kj on: the whole block (n 1, rows None, kj == ki)
        or sub-tiles of it, key rows `rows` of the block (the
        accumulators' rows alike); in the static pieces `parts` (q rows,
        keys, first masked key)."""
        keep = None
        for qrows, width, mask_col in parts:
            krows = rows if width == n * unit else slice(0, width)
            q = _ld(q_ref, qrows)                        # [bq, d]
            k = _ld(k_ref, krows)                        # [width, d]
            v = _ld(v_ref, krows)                        # [width, d]
            do = _ld(do_ref, qrows)                      # [bq, d]
            lse = _ld(lse_ref, qrows)[:, :1]              # [bq, 1]
            delta = _ld(delta_ref, qrows)[:, :1]          # [bq, 1]

            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [bq, width]
            p = jnp.exp(s - lse)                          # [bq, width] fp32
            p = _step_mask(p, qi, kj, block_q, unit, qrows, mask_col, 0.0,
                           window)

            dp = jax.lax.dot_general(                # do @ v^T -> [bq, width]
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dropout_rate > 0.0:
                # same mask as the forward — regenerated from the
                # sub-tile coordinates.  dV sees the DROPPED
                # probabilities; dS = P*(D.dp - delta)
                if keep is None:   # the step's bits, once
                    keep = _step_keep(seed_ref, b, h, qi, kj, n,
                                      dropout_rate, block_q, unit,
                                      num_k_blocks * (block_k // unit),
                                      interpret)
                inv = _keep_scale(dropout_rate)
                kept = _piece(keep, qrows, width)
                p_drop = jnp.where(kept, p * inv, 0.0)
                dp = jnp.where(kept, dp * inv, 0.0)
            else:
                p_drop = p

            dv_scr[_at(krows)] += jax.lax.dot_general(   # p^T @ do
                p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [width, d]
            ds = (p * (dp - delta) * sm_scale).astype(q.dtype)  # [bq, width]
            dk_scr[_at(krows)] += jax.lax.dot_general(   # ds^T @ q
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [width, d]
            first, count = ((0, block_q) if qrows is None else
                            (qrows.start, qrows.stop - qrows.start))
            dq_scr[pl.ds(dq_row + first, count), :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),          # ds @ k
                preferred_element_type=jnp.float32)       # [bq, d]

    _walk_tile(qi, ki, block_q, block_k, causal, walk, _update, band)

    @pl.when(step == last_step)
    def _finalize():
        _st(dk_ref, dk_scr[...].astype(dk_ref.dtype))
        _st(dv_ref, dv_scr[...].astype(dv_ref.dtype))

    @pl.when((ki == num_k_blocks - 1) & (step == last_step))
    def _finalize_dq():
        _st(dq_ref, dq_scr[...].astype(dq_ref.dtype))


def flash_attention_bwd_pallas(q, k, v, out, lse, do, causal: bool = False,
                               sm_scale: Optional[float] = None,
                               block_q: int = 512, block_k: int = 1024,
                               interpret: bool = False,
                               layout: str = "bhsd",
                               dropout_rate: float = 0.0,
                               dropout_seed=None,
                               window: Optional[int] = None):
    """Block-wise dq, dk, dv — no [S, S] materialization in HBM.  Inputs
    and grads follow `layout` (lse is always [B, H, S]); "bshd" converts
    to the kernel's [B, H, S, D] at this boundary (see
    flash_attention_pallas).  Fewer key/value heads than query heads and
    `window` as there; dk and dv come back with k's and v's heads.  The
    dropout mask is regenerated from dropout_seed and the tile
    coordinates, the forward's own (_dropout_keep)."""
    return _flash_bwd_call(
        q, k, v, out, lse, do, dropout_seed, causal=causal,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret, layout=layout,
        dropout_rate=float(dropout_rate), window=window)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret", "layout",
    "dropout_rate", "window"))
def _flash_bwd_call(q, k, v, out, lse, do, dropout_seed, *, causal,
                    sm_scale, block_q, block_k, interpret, layout,
                    dropout_rate, window=None):
    """flash_attention_bwd_pallas, traced once a process and shape (see
    _flash_fwd_call): ONE pallas_call, `flash_bwd_dkdv` (`_band` under a
    window), for every caller.  Its grid is (batch, heads, key blocks, q
    blocks), the q blocks of the band alone under a window.  The kernel
    writes one dk and dv a QUERY head (its grid cell owns its output
    block); the heads of a group are summed here, in float32.  A
    sequence whose dq does not fit in VMEM (_bwd_spans: none that a cell
    runs) is walked in spans of q rows, the key blocks once a span on
    the same grid axis: dk and dv then leave as a partial a span, summed
    with the group's."""
    batch, heads, q_len, d = _dims(q, layout)
    kv_heads, k_len = _dims(k, layout)[1:3]
    d_v = _dims(v, layout)[3]   # of v, out, dO and dv
    group = _kv_group(heads, kv_heads)
    _check_window(window, causal, dropout_rate)
    if layout == "bshd":
        q, k, v = _t_bhsd(q), _t_bhsd(k), _t_bhsd(v)
        out, do = _t_bhsd(out), _t_bhsd(do)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # fit to the lengths (largest aligned divisors <= requested blocks);
    # explicit small blocks are legal (kernel tests use 64x64) but a
    # degenerate 1-wide tiling (prime-ish length) is rejected loudly —
    # the flash_attention dispatcher falls back to XLA for those
    _, block_q, block_k = _resolve_blocks(q_len, k_len, block_q, block_k)
    if (block_q == 1 and q_len > 1) or (block_k == 1 and k_len > 1):
        raise ValueError(
            f"seq lengths ({q_len},{k_len}) only tile into 1-wide blocks "
            f"— use the flash_attention dispatcher (XLA fallback)")
    nq, nk = q_len // block_q, k_len // block_k
    seed = _seed_arg(dropout_seed)

    # delta_i = rowsum(dO_i * O_i)  (cheap elementwise; leave to XLA).
    # With dropout this stays correct: rowsum(dO*O) = sum_j A_ij dA_ij for
    # A = dropout(P), which is exactly the subtrahend in dS = P*(D.dp - δ).
    # The stats ride [B, H, S, lanes] (tiny tensors).
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    stats_shape = (*delta.shape, _STATS_LANES)
    delta = jnp.broadcast_to(delta[..., None], stats_shape)
    lse = jnp.broadcast_to(lse[..., None], stats_shape)

    # as in the forward: the kernels agree on the sub-tiles, whose
    # coordinates seed the dropout bits
    band = None if window is None else _Band(window, block_q, block_k,
                                             nq, nk)
    walk = ({} if band else
            _causal_walk(q_len, k_len, block_q, block_k, causal, True))
    spans = _bwd_spans(q_len, d, block_q, block_k, q.dtype.itemsize, d_v)
    span_rows = q_len // spans
    # inner steps: the q blocks of the band, or of the span
    steps = band.steps_q if band is not None and spans == 1 else nq // spans
    params = {}
    if not interpret:
        # dq is carried over the key blocks: they run in order
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=max(_VMEM_DEFAULT, _bwd_vmem(
                span_rows, d, block_q, block_k, q.dtype.itemsize, d_v)))

    # the index maps: grid dim 2 walks the key blocks (once a span), grid
    # dim 3 the inner steps; the trailing *_ absorbs the dropout seed's ref
    def key_block(t):
        return t if spans == 1 else t % nk

    def span_of(t):
        return 0 if spans == 1 else t // nk

    def q_block(t, j):
        if band is not None and spans == 1:
            return band.q_block(t, j)
        return j if spans == 1 else span_of(t) * steps + j

    def q_spec(width):
        return pl.BlockSpec((1, 1, block_q, width), lambda b, h, t, j, *_: (
            b, h, q_block(t, j), 0))

    def kv_spec(width):
        return pl.BlockSpec((1, 1, block_k, width), lambda b, h, t, j, *_: (
            b, h // group if group > 1 else h, key_block(t), 0))

    # one dk and dv a query head, and a span: a partial's batch index is
    # span * batch + b
    def part_spec(width):
        return pl.BlockSpec((1, 1, block_k, width), lambda b, h, t, j, *_: (
            span_of(t) * batch + b, h, key_block(t), 0))
    # the resident output: written at the span's last step of the head
    dq_spec = pl.BlockSpec((1, 1, span_rows, d),
                           lambda b, h, t, j, *_: (b, h, span_of(t), 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _fa_bwd_kernel, causal=causal, sm_scale=float(sm_scale),
            block_q=block_q, block_k=block_k, num_k_blocks=nk,
            steps=steps, num_spans=spans,
            dropout_rate=float(dropout_rate), interpret=interpret,
            walk=walk, band=band),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, heads, spans * nk, steps),
            in_specs=[q_spec(d), kv_spec(d), kv_spec(d_v), q_spec(d_v),
                      q_spec(_STATS_LANES), q_spec(_STATS_LANES)],
            out_specs=[dq_spec, part_spec(d), part_spec(d_v)],
            scratch_shapes=[
                pltpu.VMEM((span_rows, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d_v), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((spans * batch, heads, k_len, d), k.dtype),
            jax.ShapeDtypeStruct((spans * batch, heads, k_len, d_v),
                                 v.dtype),
        ],
        interpret=interpret,
        name=_kernel_name("flash_bwd_dkdv", band),
        **params,
    )(seed, q, k, v, do, lse, delta)
    if spans > 1 or group > 1:
        dk, dv = (t.astype(jnp.float32).reshape(
            spans, batch, kv_heads, group, k_len, t.shape[-1]).sum(
                (0, 3)).astype(t.dtype) for t in (dk, dv))
    if layout == "bshd":
        dq, dk, dv = _t_bhsd(dq), _t_bhsd(dk), _t_bhsd(dv)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# Differentiable public entry point
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seed, causal, sm_scale, block_q, block_k,
           layout="bhsd", dropout_rate=0.0, window=None):
    return _flash_fwd(q, k, v, seed, causal, sm_scale, block_q, block_k,
                      layout, dropout_rate, window)[0]


# Auto-dispatch crossover (v5e, 2026-07-31, round 4's bert_ab
# 2x2): at S=128 the XLA attention beats the Pallas flash kernel
# by ~25% on the full BERT-large step (90.3 vs 115.5 ms dropout-on) —
# short sequences leave the streaming kernel overhead-bound while XLA
# fuses the whole [S, S] attention in registers/VMEM.  At S=1024 the
# Pallas kernel wins (round-3 2x2).  Sequences shorter than this take
# the XLA path under impl="auto"; impl="pallas" still forces the kernel.
AUTO_MIN_SEQ = 512


def _use_pallas(q_len, k_len, d, block_q, block_k):
    if not (pallas_available() or pallas_interpret()):
        return False
    usable, _, _ = _resolve_blocks(q_len, k_len, block_q, block_k)
    return usable


def _auto_prefers_xla(k_len):
    """impl='auto' short-sequence crossover (measured; see AUTO_MIN_SEQ).
    DS_FLASH_MIN_SEQ is read per call, not at import, so harnesses can
    re-tune the crossover after the module is loaded."""
    return k_len < int(os.environ.get("DS_FLASH_MIN_SEQ", AUTO_MIN_SEQ))


def _t_bhsd(t):
    """[B, S, heads, d] <-> [B, H, S, D] (its own inverse)."""
    return t.transpose(0, 2, 1, 3)


def _ref_in_layout(q, k, v, causal, sm_scale, layout, dropout_rate=0.0,
                   dropout_seed=None, window=None):
    """XLA fallback in the caller's layout."""
    if layout == "bhsd":
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             dropout_rate=dropout_rate,
                             dropout_seed=dropout_seed, window=window)
    return _t_bhsd(mha_reference(_t_bhsd(q), _t_bhsd(k), _t_bhsd(v),
                                 causal=causal, sm_scale=sm_scale,
                                 dropout_rate=dropout_rate,
                                 dropout_seed=dropout_seed, window=window))


# checkpoint_name of the residuals only the forward kernel can produce
# (out, lse): a jax.checkpoint policy that saves
# this name spares the backward pass a second run of the kernel; with no
# such policy the name is an identity.
RESIDUAL_NAME = "flash_residuals"


def _flash_fwd(q, k, v, seed, causal, sm_scale, block_q, block_k,
               layout="bhsd", dropout_rate=0.0, window=None):
    q_len, k_len = _dims(q, layout)[2], _dims(k, layout)[2]
    if _use_pallas(q_len, k_len, q.shape[3], block_q, block_k):
        _, bq, bk = _resolve_blocks(q_len, k_len, block_q, block_k)
        out, lse = checkpoint_name(flash_attention_pallas(
            q, k, v, causal=causal, sm_scale=sm_scale,
            block_q=bq, block_k=bk, return_lse=True, layout=layout,
            dropout_rate=dropout_rate, dropout_seed=seed,
            interpret=pallas_interpret(), window=window),
            RESIDUAL_NAME)
        return out, (q, k, v, seed, out, lse)
    out = _ref_in_layout(q, k, v, causal, sm_scale, layout, dropout_rate,
                         seed[0], window)
    return out, (q, k, v, seed, None, None)


def _flash_bwd(causal, sm_scale, block_q, block_k, layout, dropout_rate,
               window, res, g):
    q, k, v, seed, out, lse = res
    if lse is not None:
        q_len, k_len = _dims(q, layout)[2], _dims(k, layout)[2]
        _, bq, bk = _resolve_blocks(q_len, k_len, block_q, block_k)
        dq, dk, dv = flash_attention_bwd_pallas(
            q, k, v, out, lse, g, causal=causal, sm_scale=sm_scale,
            block_q=bq, block_k=bk, layout=layout,
            dropout_rate=dropout_rate, dropout_seed=seed,
            interpret=pallas_interpret(), window=window)
        return dq, dk, dv, None
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref_in_layout(q_, k_, v_, causal, sm_scale,
                                          layout, dropout_rate, seed[0],
                                          window),
        q, k, v)
    return (*vjp(g), None)


_flash.defvjp(_flash_fwd, _flash_bwd)

# Odd multiplier (murmur3's c1 as an int32 bit pattern) that spreads the
# shard index over the seed space: neighbouring shards' seeds land
# billions apart, far outside the (batch, head) offsets the kernel adds.
_SHARD_SEED_STRIDE = np.int32(-862048943)


def _flash_over_mesh(q, k, v, seed, causal, sm_scale, block_q, block_k,
                     layout, dropout_rate, window=None):
    """_flash, placed for the device mesh.  Where the Pallas kernel will
    run, the call sits in a region manual over every mesh axis
    (dispatch.manual_kernel_region) with the batch split over the data
    axes and the heads over the model axis, so each chip runs the kernel
    on the rows it already holds and no q/k/v gather precedes it.  The
    XLA reference needs no region: GSPMD partitions it."""
    q_len, k_len = _dims(q, layout)[2], _dims(k, layout)[2]
    if not _use_pallas(q_len, k_len, q.shape[3], block_q, block_k):
        return _flash(q, k, v, seed, causal, sm_scale, block_q, block_k,
                      layout, dropout_rate, window)
    dims = {0: BATCH_AXES, (1 if layout == "bhsd" else 2): HEAD_AXES}

    def local(shard_index, q, k, v, seed):
        # every shard numbers its rows and heads from 0, so on the step
        # seed alone all shards would draw the same dropout masks
        seed = seed + shard_index * _SHARD_SEED_STRIDE
        return _flash(q, k, v, seed, causal, sm_scale, block_q, block_k,
                      layout, dropout_rate, window)

    return manual_kernel_region(local, (q, k, v, seed),
                                (dims, dims, dims, None), dims)


# Default block sizes, tuned on v5e in round 3 (jax 0.4.37, a host-clock
# block sweep with state feedback and a fetch sync; the script is gone,
# git keeps it): large blocks dominate —
# 128x128 is grid-overhead-bound (S=4096 fwd+bwd: 28.1 ms at 128x128 vs
# 6.7 ms at 1024x1024; S=1024: 10.0 -> 4.3 ms).  With these blocks the
# Pallas kernel beats the batched-XLA attention at the kernel level for
# S >= 1024 (S=1024: 4.3 vs 6.3 ms; S=4096: 6.7 vs 23.9 ms) — but at
# SHORT lengths the FULL-STEP measurement goes the other way (round-4
# bert_ab 2x2: S=128 XLA attention wins by ~25%), hence the
# AUTO_MIN_SEQ crossover above.  512x1024 (not 1024x1024, statistically
# tied) keeps the bwd kernel's [bq, bk] fp32 score/ds tiles at 2 MB
# each for VMEM headroom at D>64.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None, bias=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    impl: str = "auto", dropout_rate: float = 0.0,
                    dropout_seed=None, window: Optional[int] = None):
    """Fused multi-head attention: q,k,v [B, H, S, D] -> [B, H, S, D].

    v may have a head size of its own, [B, H, S, Dv]: the output (and
    dO, dv) then has v's, the scores contract over q's and k's (latent
    attention at 128 + 64 rotated on values of 128: models/xing4.py).
    Both kernels take the two sizes in their block shapes and scratch,
    and nothing is padded; at one size they lower as they always did.

    k and v may have fewer heads than q (grouped key/value heads: head j
    serves query heads [j * group, (j + 1) * group)), found by index map
    in the kernels.  `window` (causal calls): each query sees its last
    `window` keys, its own included; the kernels visit the band's tiles
    only.  A call with neither lowers to the kernels it always did.

    impl: "auto" (default) runs the Pallas flash kernel with blocks fitted
    to the sequence lengths (_resolve_blocks), falling back to the XLA
    reference on CPU, unaligned lengths, or bias; "pallas" REQUIRES the
    Pallas kernel and raises where auto would fall back (so ablation
    harnesses can never silently measure the XLA path); "xla" forces the
    reference.  Additive-bias attention always takes the XLA path (the
    compiler fuses the bias add into the softmax).

    dropout_rate > 0 applies PROBABILITY dropout to the normalized
    attention (the reference's attn-dropout, dropout_kernels.cu:868) —
    IN-KERNEL on the Pallas path (the mask is regenerated from
    dropout_seed + tile coordinates in the backward, never stored) and via
    jax.random on the XLA path.  dropout_seed is a per-step int32 (array
    or scalar); the two paths use different PRNG streams."""
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    seed = _seed_arg(dropout_seed)
    if impl == "pallas":
        if bias is not None:
            raise ValueError(
                "impl='pallas': the Pallas kernel does not take an additive "
                "bias — use impl='auto'/'xla'")
        if not _use_pallas(q.shape[2], k.shape[2], q.shape[3],
                           block_q, block_k):
            raise ValueError(
                f"impl='pallas': no aligned tiling for seq lengths "
                f"({q.shape[2]},{k.shape[2]}) or Pallas unavailable on this "
                "backend — use impl='auto' for the XLA fallback")
        return _flash_over_mesh(q, k, v, seed, causal, sm_scale, block_q,
                                block_k, "bhsd", dropout_rate, window)
    if bias is not None:
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             bias=bias, dropout_rate=dropout_rate,
                             dropout_seed=seed[0], window=window)
    if impl == "xla" or _auto_prefers_xla(k.shape[2]):
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             dropout_rate=dropout_rate,
                             dropout_seed=seed[0], window=window)
    return _flash_over_mesh(q, k, v, seed, causal, sm_scale, block_q,
                            block_k, "bhsd", dropout_rate, window)


def flash_attention_bsh(q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None, bias=None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        impl: str = "auto", dropout_rate: float = 0.0,
                        dropout_seed=None):
    """Fused attention over [B, S, heads, d] activations.

    Callers holding [B, S, hidden] activations reshape (free) to
    [B, S, heads, d]; the layout conversion to the kernel's [B, H, S, D]
    happens at the Pallas boundary.  (Round-3 finding: a truly
    transpose-free bshd BlockSpec is Mosaic-illegal — its per-head tile
    puts (1, d) in the last-two-dims position — so this entry point is
    API convenience, not an HBM-traffic optimization; measured, the
    boundary transposes are <1% of step traffic.)  Semantics are
    identical to flash_attention — including impl='pallas' strictness —
    with bias/impl='xla'/unusable lengths falling back to the transposed
    XLA reference.  dropout_rate/dropout_seed as in flash_attention."""
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    seed = _seed_arg(dropout_seed)
    if impl == "pallas":
        if bias is not None:
            raise ValueError(
                "impl='pallas': the Pallas kernel does not take an additive "
                "bias — use impl='auto'/'xla'")
        if not _use_pallas(q.shape[1], k.shape[1], q.shape[3],
                           block_q, block_k):
            raise ValueError(
                f"impl='pallas': no aligned tiling for seq lengths "
                f"({q.shape[1]},{k.shape[1]}) or Pallas unavailable on this "
                "backend — use impl='auto' for the XLA fallback")
    if (bias is not None or impl == "xla"
            or (impl == "auto" and _auto_prefers_xla(k.shape[1]))):
        return _t_bhsd(mha_reference(_t_bhsd(q), _t_bhsd(k), _t_bhsd(v),
                                     causal=causal, sm_scale=sm_scale,
                                     bias=bias, dropout_rate=dropout_rate,
                                     dropout_seed=seed[0]))
    return _flash_over_mesh(q, k, v, seed, causal, sm_scale, block_q,
                            block_k, "bshd", dropout_rate)
