"""Attention over a set of keys that a learned score chooses per query
(the DeepSeek-V3.2 report's sparse attention, as Keye-VL-2.0's
``sa_config`` sizes it): an indexer scores every causal pair, each query
keeps its ``topk`` best keys, the main attention runs on those alone, and
an alignment term trains the indexer towards the main attention's own
distribution.  Four pieces, per batch row, ``t`` a query and ``s`` a key:

  index     ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` over the
            indexer's heads (one key head); ``w`` arrives with the two
            scale factors folded in
  select    ``S_t``: the ``min(t + 1, topk)`` keys ``s <= t`` of largest
            ``I[t, s]``, a tie to the lower index: EXACT, by a search for
            the k-th largest value over the scores' bit patterns (a
            float's bits, sign-folded, order as the float does: 32
            compare-and-count passes a row, no sort) and a second search
            over the index where the k-th value ties.  No gradient.
  core      ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] .
            k[s, g(h)] scale) v[s, g(h)]``, grouped key/value heads
  align     ``mean_t KL(pbar_t || softmax_{s in S_t} I[t, s])``, ``pbar_t``
            the heads' mean of the main attention's probabilities, a
            constant; its gradient reaches qI, kI and w alone

What passes from select to the others is the keep-set PACKED, one bit a
pair: int32 ``[B, S / 32, S]`` (S x S / 8 bytes, 32 MB at 16,384).  Rows
are packed in blocks of ``block_q`` = 32 x ``sub`` queries: word row
``qb sub + i``, bit ``b``, column ``s`` says whether query
``qb block_q + b sub + i`` keeps key ``s``, so a kernel unpacks a block's
``[sub, block_k]`` words into its ``[block_q, block_k]`` mask with 32
shifts and no shuffle across lanes.  No ``[S, S]`` float array exists on
any path: every form below works on ``[block_q, S]`` or ``[block_q,
block_k]`` at a time.

Each piece has two forms.  On a TPU (and under ``set_pallas_interpret``)
where ``kernels_take`` says the shape is theirs, Pallas kernels:
``dsa_select`` (index and select in one pass, a q block's scores held in
VMEM as sortable keys), ``dsa_attn_fwd`` / ``dsa_attn_bwd_dkdv``
(flash-style, the packed mask unpacked per tile, tiles beyond the
diagonal skipped; a tile with no kept pair is still computed: with a
random indexer almost none is empty; a forward step is a tile of a
KEY/VALUE head and serves that head's group of query heads, as many as
``_attn_fwd_heads`` says fit: the keep-set knows no head, so the key,
value and mask tiles arrive and the mask is unpacked once for them, and
the heads go through the step's stages four abreast; the backward is ONE
kernel that builds a tile once and makes dq, dk and dv from it) and
``dsa_align`` (value and the indexer's gradients in one pass: the loss is
a scalar, so its backward rule only scales them; a step is a tile and
goes through its stages with the heads abreast, eight main heads' products,
then their exponentials, then the sum in head order into ``pbar`` in VMEM;
the indexer's ReLU(qI . kI) products once, left in VMEM by the scores'
pass for the gradients'; that pass eight indexer heads abreast, and d kI
gathered transposed, ``[Di, S]``, so that a head's q block is turned and
not the tile).  Elsewhere blocked XLA
forms of the same mathematics, a q block at a time under ``lax.map``; the
tests hold the kernels to them.  ``ops/flash_attention.py`` knows nothing
of this file and is not touched by it.
"""

import functools
import math
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import pallas_available, pallas_interpret

# checkpoint names (runtime/activation_checkpointing/checkpointing.py):
# the packed keep-set is kept always (a select recomputed in other
# fusions could flip a near tie, and 32 passes are not cheap), the
# alignment term's gradients and the attention kernel's (out, lse) by the
# byte budget, in that order
KEEP_NAME = "dsa_keep"
ALIGN_NAME = "dsa_align"
RESIDUAL_NAME = "dsa_residuals"

PACK = 32                 # queries a packed word
# The kernels' tiles.  BLOCK_Q queries are a packed block of the keep-set
# (32 x 8: its words fill whole sublane tiles), a step of ``dsa_select``
# (the block's scores for every key lie in VMEM, 16 MB at 16,384, walked
# BLOCK_K columns at a time) and, by BLOCK_K keys, ``dsa_align``'s tile,
# which holds every head's q block.  The restricted attention's tiles are
# ATTN_BLOCK square, several packed blocks tall.  On the v5e at 16,384
# positions (my chip run, PR 50, the kernels alone): ``dsa_attn_fwd``, then
# a step a QUERY head, 44.7 ms at 256 x 512, 35.7 at 512 x 512, 26.7 at
# 512 x 1,024, 24.3 at 1,024 x 1,024 (a grid step costs what it costs
# whatever it holds, and the steps beyond the diagonal are not free; less
# 0.35 us a step that is 21 to 24 ms at every tile: the time followed the
# elements).  A step a key/value head's group of 8 at 1,024 x 1,024 (PR
# 54's builder's chip runs, the parent's 24.0 beside each; the first
# three read again to 0.05 ms by my chip run, PR 55): 17.9 ms head by
# head, whether the mask is added, selected on a stored boolean or
# compared and selected, and 17.3 with no mask at all; 16.3 with the heads
# two abreast stage by stage and 15.6 four abreast (16.7 and 16.6 with the
# stages skewed, 17.7 and 17.2 with only the first products ahead, 18.2
# with the loop unrolled whole).  ``dsa_align`` (PR 50): 28.4 to 31.6 at
# every block tried; ``dsa_select`` 10.2 to 10.5 at 512 to 2,048 columns a
# step.
# ``dsa_align``'s step at 256 x 512 (my chip runs, PR 57, the kernel alone
# with the XLA around it, 20 calls, every form's four outputs the parent's
# bit for bit but where said): 28.66 ms as PR 50 wrote it, the 32 main
# heads one after another into a carry of 128 registers, the indexer's 16
# unrolled head by head.  The main heads alone changed: 25.91 one at a
# time into ``pbar`` in VMEM, 23.81 two abreast, 22.45 four (22.44 as ONE
# product of four heads' rows), 21.71 eight (21.70 as one product), 21.41
# sixteen.  The indexer's passes alone changed, on the parent's main loop:
# the ReLU products kept in VMEM 28.56, the gradients' pass in stages two
# and four abreast 29.54 and 29.71 (30.07 as a loop), the scores' pass in
# stages 28.66: nothing.  On eight main heads abreast (21.71): the ReLU
# products kept 21.63; d kI gathered transposed 20.79, and with the
# products kept 19.89; then the gradients' pass as a LOOP over heads
# abreast, two 18.88, four 18.11, eight 17.78 (unrolled in stages of two
# and four 20.01 and 20.09; the scores' pass as a loop too 19.34; four
# heads' d qI as one product 19.50; their d kI as one product, NOT
# bit-equal, 20.90; sixteen main heads abreast 17.48).  **Shipped: 17.69**
# (first call 3.9 s, the parent's 5.2), and with ``_abreast`` held to four
# 18.75, to two 20.89, to one 25.09; of the shipped form's parts, each
# taken back alone: d kI as the parent gathered it 22.31, the ReLU
# products built twice 20.71, the gradients' pass unrolled 19.99.  By
# parts (a part left out, timed only): the main heads 6.5 ms (5.8 at the
# MXU's peak), the scores' pass 2.8 and the gradients' 4.8 (8.8 before),
# near what their half-filled products (64 deep or 64 wide) cost the MXU,
# the rest (the mask, the loss, dL / dI, the grid, 1.3 of XLA around the
# kernel) 3.6.  In the cell the kernel's own line reads 16.42 (27.39).
# The backward (my chip run, PR 51): 35.4 ms at 1,024 x 1,024, 37.0 at 512
# x 1,024, 37.6 at 1,024 x 512, 45.5 at 512 x 512, 38.0 at 2,048 x 1,024,
# 36.4 at 1,024 x 2,048 (the two kernels it replaced: 51.0).
BLOCK_Q = 256
BLOCK_K = 512
ATTN_BLOCK = 1024
_STATS_LANES = 8          # row statistics ride as [..., S, 8], lane 0 read
_INT_MIN = -2 ** 31
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_VMEM_LIMIT = 100 * 1024 * 1024
_ALIGN_ABREAST = 8        # heads side by side in ``dsa_align``'s step, at most


def _attn_bwd_vmem(seq, head_dim):
    """The VMEM ``dsa_attn_bwd_dkdv`` asks for on two-byte operands, in
    bytes and from above: what grows with the sequence (float32 dq, dk and
    dv of ``[seq, head_dim]`` as scratch, their outputs double-buffered)
    and a tile's share (four float32 ``[ATTN_BLOCK, ATTN_BLOCK]``
    temporaries; q, dO, k, v, two statistics at a lane tile a row and the
    mask's words, double-buffered).  68 MiB at 16,384 x 128, where the
    v5e's compiler counts 58 (82 at 24,576, and 107 at 32,768, which it
    refuses)."""
    resident = 3 * seq * head_dim * (4 + 2 * 2)
    tile = 4 * 4 * ATTN_BLOCK * ATTN_BLOCK + 2 * ATTN_BLOCK * (
        4 * head_dim * 2 + 2 * 128 * 4 + ATTN_BLOCK // PACK * 4)
    return resident + tile


def kernels_take(seq, head_dim, index_dim):
    """Whether the Pallas kernels take this shape (whole tiles of
    positions, heads of whole lane tiles, a sequence whose backward's
    resident gradients fit in VMEM, a TPU or the interpreter); else the
    blocked XLA forms run."""
    return bool((pallas_available() or pallas_interpret())
                and seq % ATTN_BLOCK == 0 and head_dim % 128 == 0
                and index_dim % 8 == 0
                and _attn_bwd_vmem(seq, head_dim) <= _VMEM_LIMIT)


def pack_block(seq):
    """Queries a packed block of the keep-set on ``seq`` positions:
    BLOCK_Q, cut to a sequence shorter than it (whole words of 32
    queries; the kernels take no such sequence)."""
    rows = min(BLOCK_Q, seq)
    if seq % rows or rows % PACK:
        raise ValueError(f"{seq} positions are no whole blocks of {rows} "
                         f"queries packed {PACK} a word")
    return rows


# ---------------------------------------------------------------------- #
# shared pieces
# ---------------------------------------------------------------------- #
def sort_key(x):
    """float32 -> int32 that orders as the float does (-0.0 as 0.0): the
    bits, with the magnitude of a negative number flipped.  Its own
    inverse on the bits (``key_value``)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32) + 0.0, jnp.int32)
    return bits ^ (lax.shift_right_arithmetic(bits, jnp.int32(31))
                   & jnp.int32(0x7FFFFFFF))


def key_value(key):
    """The float32 a ``sort_key`` came from."""
    return lax.bitcast_convert_type(
        key ^ (lax.shift_right_arithmetic(key, jnp.int32(31))
               & jnp.int32(0x7FFFFFFF)), jnp.float32)


def kth_largest_key(count_ge, k):
    """The k-th largest key of each row, given ``count_ge(c)`` = how many
    of the row's keys are >= c (c and k int32 [rows, 1]): the sign first,
    then 31 bits from the top, each kept if at least k keys still lie at
    or above the candidate."""
    t = jnp.where(count_ge(jnp.zeros_like(k)) >= k, jnp.int32(0),
                  jnp.int32(_INT_MIN))

    def bit(i, t):
        cand = t | lax.shift_left(jnp.int32(1), jnp.int32(30) - i)
        return jnp.where(count_ge(cand) >= k, cand, t)

    return lax.fori_loop(0, 31, bit, t)


def pack_keep(keep, block_q=BLOCK_Q):
    """bool [B, S, S] -> the packed int32 [B, S / 32, S] (the module's
    text has the layout).  For tests and the XLA forms: at size this
    array is never whole."""
    batch, seq, cols = keep.shape
    sub = block_q // PACK
    bits = keep.reshape(batch, seq // block_q, PACK, sub, cols).astype(
        jnp.int32)
    words = functools.reduce(
        jnp.bitwise_or, [bits[:, :, b] << b for b in range(PACK)])
    return words.reshape(batch, seq // PACK, cols)


def unpack_keep(packed, block_q=BLOCK_Q):
    """The inverse of ``pack_keep``: bool [B, S, S]."""
    batch, rows, cols = packed.shape
    sub = block_q // PACK
    words = packed.reshape(batch, rows // sub, 1, sub, cols)
    bits = (words >> jnp.arange(PACK, dtype=jnp.int32)[:, None, None]) & 1
    return bits.reshape(batch, rows * PACK, cols) != 0


def _unpack_tile(words, sub=None):
    """Whole packed blocks' words [n sub, cols] -> bool [32 n sub, cols]
    (``sub`` None: one block)."""
    sub = sub or words.shape[0]
    return jnp.concatenate(
        [(words[n:n + sub] >> b) & 1
         for n in range(0, words.shape[0], sub) for b in range(PACK)],
        axis=0) != 0


def _pack_tile(keep, sub):
    """int32 0/1 [32 sub, cols] -> words [sub, cols]."""
    return functools.reduce(
        jnp.bitwise_or,
        [keep[b * sub:(b + 1) * sub] << b for b in range(PACK)])


def kept_pairs(packed):
    """How many pairs the packed keep-set holds (float32 scalar)."""
    return jnp.sum(lax.population_count(packed).astype(jnp.float32))


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


def _nt(a, b):      # a [m, d] . b [n, d]^T
    return _dot(a, b, ((1,), (1,)))


def _nn(a, b):      # a [m, n] . b [n, d]
    return _dot(a, b, ((1,), (0,)))


def _tn(a, b):      # a [m, n]^T . b [m, d]
    return _dot(a, b, ((0,), (0,)))


def _scores_tile(q_of, w_of, heads, k_idx):
    """``sum_j w_j ReLU(q_j k^T)``: ``q_of(j)`` [rows, Di], ``w_of(j)``
    [rows, 1], ``k_idx`` [cols, Di] -> float32 [rows, cols]."""
    acc = None
    for j in range(heads):
        term = w_of(j) * jnp.maximum(_nt(q_of(j), k_idx), 0.0)
        acc = term if acc is None else acc + term
    return acc


# ---------------------------------------------------------------------- #
# blocked XLA forms
# ---------------------------------------------------------------------- #
def _blocks(x, axis, block):
    """Split ``axis`` of x into (blocks, block) and bring the blocks
    first: what ``lax.map`` walks."""
    shape = x.shape[:axis] + (x.shape[axis] // block, block) + x.shape[
        axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


def _select_rows(scores, row0, topk):
    """Exact select on a block of rows: ``scores`` float32 [rows, S], the
    block's first query ``row0`` -> (keep bool [rows, S], the log-sum-exp
    of the kept scores [rows])."""
    rows, seq = scores.shape
    t = row0 + jnp.arange(rows, dtype=jnp.int32)[:, None]
    s = jnp.arange(seq, dtype=jnp.int32)[None, :]
    key = jnp.where(s <= t, sort_key(scores), jnp.int32(_INT_MIN))
    k = jnp.minimum(t + 1, topk)
    kth = kth_largest_key(
        lambda c: jnp.sum(key >= c, axis=1, keepdims=True, dtype=jnp.int32),
        k)
    above, tie = key > kth, key == kth
    need = k - jnp.sum(above, axis=1, keepdims=True, dtype=jnp.int32)
    rank = jnp.cumsum(tie, axis=1, dtype=jnp.int32) - tie
    keep = above | (tie & (rank < need))
    kept = jnp.where(keep, scores, _MASKED)
    top = jnp.max(kept, axis=1, keepdims=True)
    lse = top + jnp.log(jnp.sum(jnp.where(keep, jnp.exp(kept - top), 0.0),
                                axis=1, keepdims=True))
    return keep, lse[:, 0]


def index_select_xla(q_idx, k_idx, w, topk, block_q=BLOCK_Q):
    """The blocked XLA form of ``index_select``."""
    batch, heads, seq, _ = q_idx.shape

    def one(args):
        qb, q_blk, w_blk = args         # [B, Hi, bq, Di], [B, Hi, bq]

        def row(q_b, k_b, w_b):
            scores = _scores_tile(lambda j: q_b[j],
                                  lambda j: w_b[j][:, None], heads, k_b)
            keep, lse = _select_rows(scores, qb * block_q, topk)
            return pack_keep(keep[None], block_q)[0], lse

        return jax.vmap(row)(q_blk, k_idx, w_blk)

    words, lse = lax.map(one, (
        jnp.arange(seq // block_q, dtype=jnp.int32),
        _blocks(q_idx, 2, block_q), _blocks(w, 2, block_q)))
    # [nq, B, sub, S] -> [B, S / 32, S]; [nq, B, bq] -> [B, S]
    return (jnp.moveaxis(words, 0, 1).reshape(batch, seq // PACK, seq),
            jnp.moveaxis(lse, 0, 1).reshape(batch, seq))


def kept_lse(q_idx, k_idx, w, packed, block_q):
    """The log-sum-exp of the index scores over a GIVEN keep-set, float32
    [B, S]: what ``index_select`` hands on beside its own choice, for a
    caller that forces another (a comparison with a reference on the same
    selection).  Blocked XLA on every backend."""
    q_idx, k_idx, w = (lax.stop_gradient(x) for x in (q_idx, k_idx, w))
    batch, heads, seq, _ = q_idx.shape

    def one(args):
        q_blk, w_blk, words = args

        def row(q_b, k_b, w_b, words_b):
            scores = _scores_tile(lambda j: q_b[j],
                                  lambda j: w_b[j][:, None], heads, k_b)
            return jax.nn.logsumexp(jnp.where(
                _unpack_tile(words_b), scores, -jnp.inf), axis=1)

        return jax.vmap(row)(q_blk, k_idx, w_blk, words)

    lse = lax.map(one, (_blocks(q_idx, 2, block_q), _blocks(w, 2, block_q),
                        _blocks(packed, 1, block_q // PACK)))
    return jnp.moveaxis(lse, 0, 1).reshape(batch, seq)


def _attn_rows(q_blk, k, v, keep, sm_scale):
    """One batch row's q block: q_blk [H, bq, D], k, v [KV, S, D], keep
    bool [bq, S] -> (out [H, bq, D], lse float32 [H, bq])."""
    heads, kv = q_blk.shape[0], k.shape[0]
    group = heads // kv
    q_g = q_blk.reshape(kv, group, *q_blk.shape[1:])
    s = jnp.einsum("kgqd,ksd->kgqs", q_g, k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(keep, s, _MASKED)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(keep, jnp.exp(s - top), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("kgqs,ksd->kgqd", (p / denom).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (out.reshape(q_blk.shape).astype(q_blk.dtype),
            (top + jnp.log(denom))[..., 0].reshape(heads, -1))


def indexed_attention_xla(q, k, v, packed, sm_scale, block_q=BLOCK_Q):
    """The blocked XLA form of the restricted attention: (out, lse),
    differentiable in q, k, v by JAX's own rules (a block's work is
    recomputed in its backward pass)."""
    batch, heads, seq, dim = q.shape
    sub = block_q // PACK

    @jax.checkpoint
    def one(args):
        q_blk, words = args             # [B, H, bq, D], [B, sub, S]
        return jax.vmap(lambda q_b, k_b, v_b, w_b: _attn_rows(
            q_b, k_b, v_b, _unpack_tile(w_b), sm_scale))(q_blk, k, v, words)

    out, lse = lax.map(one, (_blocks(q, 2, block_q),
                             _blocks(packed, 1, sub)))
    return (jnp.moveaxis(out, 0, 2).reshape(batch, heads, seq, dim),
            jnp.moveaxis(lse, 0, 2).reshape(batch, heads, seq))


def index_alignment_xla(q_idx, k_idx, w, q, k, lse, packed, lse_idx,
                        sm_scale, block_q=BLOCK_Q):
    """The blocked XLA form of the alignment term: ``mean_t KL(pbar_t ||
    softmax_{S_t} I[t])`` over every row of the batch, differentiable in
    q_idx, k_idx and w by JAX's own rules; q, k and lse are constants."""
    q, k, lse = (lax.stop_gradient(x) for x in (q, k, lse))
    batch, heads, seq, _ = q.shape
    idx_heads = q_idx.shape[1]
    sub = block_q // PACK
    group = heads // k.shape[1]

    @jax.checkpoint
    def one(args):
        qi_blk, w_blk, q_blk, lse_blk, words, lsei_blk = args

        def row(qi_b, ki_b, w_b, q_b, k_b, lse_b, words_b, lsei_b):
            keep = _unpack_tile(words_b)
            q_g = q_b.reshape(k_b.shape[0], group, *q_b.shape[1:])
            s = jnp.einsum("kgqd,ksd->kgqs", q_g, k_b,
                           preferred_element_type=jnp.float32) * sm_scale
            p = jnp.exp(s - lse_b.reshape(*s.shape[:3], 1))
            pbar = jnp.where(keep, jnp.sum(p, axis=(0, 1)) / heads, 0.0)
            scores = _scores_tile(lambda j: qi_b[j],
                                  lambda j: w_b[j][:, None], idx_heads, ki_b)
            # the kept scores' log-sum-exp anew from the row, so that
            # JAX's rules see the softmax whole (lsei_b is its value)
            top = lax.stop_gradient(lsei_b)[:, None]
            logp = scores - top - jnp.log(jnp.sum(jnp.where(
                keep, jnp.exp(scores - top), 0.0), axis=1, keepdims=True))
            live = keep & (pbar > 0.0)
            return jnp.sum(jnp.where(
                live, pbar * (jnp.log(jnp.where(live, pbar, 1.0)) - logp),
                0.0))

        return jnp.sum(jax.vmap(row)(qi_blk, k_idx, w_blk, q_blk, k,
                                     lse_blk, words, lsei_blk))

    parts = lax.map(one, (
        _blocks(q_idx, 2, block_q), _blocks(w, 2, block_q),
        _blocks(q, 2, block_q), _blocks(lse, 2, block_q),
        _blocks(packed, 1, sub), _blocks(lse_idx, 1, block_q)))
    return jnp.sum(parts) / (batch * seq)


# ---------------------------------------------------------------------- #
# dsa_select: index scores and the exact select, a q block a step
# ---------------------------------------------------------------------- #
def _stats(x, rows):
    """[rows, 1] -> the [rows, 8] block a statistic is stored as."""
    return jnp.broadcast_to(x, (rows, _STATS_LANES))


def _fold_lanes(x):
    """[rows, n 128] -> [rows, 128]: the lane tiles added element-wise
    (the cross-lane sum is left to the caller, once a pass)."""
    cols = x.shape[1]
    if cols % 128:
        return jnp.sum(x, axis=1, keepdims=True)
    return functools.reduce(
        jnp.add, [x[:, c:c + 128] for c in range(0, cols, 128)])


def _select_kernel(q_ref, k_ref, w_ref, keep_ref, lse_ref, key_scr, *,
                   topk, block_q, chunk, heads):
    # chunk: the key columns a step of the loops below takes (block_k)
    qb = pl.program_id(1)
    sub = block_q // PACK
    seq = key_scr.shape[1]
    chunks = seq // chunk
    # the chunks that hold a key some query of the block may see
    live = ((qb + 1) * block_q + chunk - 1) // chunk
    t = qb * block_q + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def cols(c):
        return c * chunk + lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def build(c, top):
        k_blk = k_ref[0, pl.ds(c * chunk, chunk), :]
        scores = _scores_tile(lambda j: q_ref[0, j],
                              lambda j: w_ref[0, j][:, :1], heads, k_blk)
        seen = cols(c) <= t
        key_scr[:, pl.ds(c * chunk, chunk)] = jnp.where(
            seen, sort_key(scores), jnp.int32(_INT_MIN))
        return jnp.maximum(top, jnp.max(
            jnp.where(seen, scores, _MASKED), axis=1, keepdims=True))

    top_value = lax.fori_loop(0, live, build,
                              jnp.full((block_q, 1), _MASKED, jnp.float32))

    def count(pred):
        """pred(key, cols) -> bool; the count a row, float32 [rows, 1]
        (whole numbers up to S: exact)."""
        def step(c, acc):
            hit = pred(key_scr[:, pl.ds(c * chunk, chunk)], cols(c))
            return acc + _fold_lanes(jnp.where(hit, 1.0, 0.0))
        width = 128 if chunk % 128 == 0 else 1
        acc = lax.fori_loop(0, live, step,
                            jnp.zeros((block_q, width), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    k = jnp.minimum(t + 1, topk).astype(jnp.float32)
    kth = kth_largest_key(lambda c: count(lambda key, _: key >= c), k)
    need = k - count(lambda key, _: key > kth)
    # ties at the k-th value beyond what is needed: the lower indices,
    # by a search for the largest J with at most `need` ties before it
    # (only where some row of the block has such ties)
    excess = jnp.sum(jnp.where(
        count(lambda key, _: key >= kth) > k, 1.0, 0.0)) > 0.0
    bits = seq.bit_length()

    def first_ties():
        def bit(i, j):
            cand = j + lax.shift_left(jnp.int32(1), jnp.int32(bits - 1) - i)
            ties = count(lambda key, s: (key == kth) & (s < cand))
            return jnp.where(ties <= need, cand, j)
        return lax.fori_loop(0, bits, bit, jnp.zeros_like(kth))

    bound = lax.cond(excess, first_ties,
                     lambda: jnp.full_like(kth, 2 ** 30))

    def write(c, denom):
        key = key_scr[:, pl.ds(c * chunk, chunk)]
        keep = (key > kth) | ((key == kth) & (cols(c) < bound))
        keep_ref[0, :, pl.ds(c * chunk, chunk)] = _pack_tile(
            jnp.where(keep, 1, 0).astype(jnp.int32), sub)
        p = jnp.where(keep, jnp.exp(key_value(key) - top_value), 0.0)
        return denom + jnp.sum(p, axis=1, keepdims=True)

    denom = lax.fori_loop(0, live, write,
                          jnp.zeros((block_q, 1), jnp.float32))

    def blank(c, carry):
        keep_ref[0, :, pl.ds(c * chunk, chunk)] = jnp.zeros(
            (sub, chunk), jnp.int32)
        return carry

    lax.fori_loop(live, chunks, blank, 0)
    lse_ref[0] = _stats(top_value + jnp.log(denom), block_q)


def _compiler_params(semantics, interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)}


@functools.partial(jax.jit, static_argnames=(
    "topk", "block_q", "block_k", "interpret"))
def index_select_pallas(q_idx, k_idx, w, *, topk, block_q=BLOCK_Q,
                        block_k=BLOCK_K, interpret=False):
    """``dsa_select``: q_idx [B, Hi, S, Di], k_idx [B, S, Di], w float32
    [B, Hi, S] -> (packed keep int32 [B, S / 32, S], the kept scores'
    log-sum-exp float32 [B, S])."""
    batch, heads, seq, dim = q_idx.shape
    sub = block_q // PACK
    kernel = functools.partial(_select_kernel, topk=topk, block_q=block_q,
                               chunk=min(block_k, seq), heads=heads)
    packed, lse = pl.pallas_call(
        kernel,
        grid=(batch, seq // block_q),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, dim), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, seq, dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, heads, block_q, _STATS_LANES),
                         lambda b, i: (b, 0, i, 0))],
        out_specs=[
            pl.BlockSpec((1, sub, seq), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STATS_LANES), lambda b, i: (b, i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq // PACK, seq), jnp.int32),
            jax.ShapeDtypeStruct((batch, seq, _STATS_LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, seq), jnp.int32)],
        interpret=interpret, name="dsa_select",
        **_compiler_params(("parallel", "arbitrary"), interpret),
    )(q_idx, k_idx, _wide(w))
    return packed, lse[..., 0]


# ---------------------------------------------------------------------- #
# dsa_attn_*: the restricted attention, flash-style
# ---------------------------------------------------------------------- #
def _last_k(i, block_q, block_k):
    """The last key block a query of q block i may see."""
    return ((i + 1) * block_q - 1) // block_k


def _first_q(j, block_q, block_k):
    """The first q block that may see a key of key block j."""
    return (j * block_k) // block_q


def _abreast(heads, most=4):
    """How many of a step's heads go through its stages side by side: the
    widest of 8, 4, 2, 1 that divides them, ``most`` at most."""
    return next(n for n in (8, 4, 2, 1) if n <= most and heads % n == 0)


def _attn_fwd_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref,
                     m_scr, l_scr, acc_scr, bias_scr, *, sm_scale, block_q,
                     block_k, sub):
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_k(i, block_q, block_k)
    heads = q_ref.shape[1]      # the query heads this step serves
    abreast = _abreast(heads)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _():
        # the tile's own, once for every head: the mask as what a score
        # takes on (a score is nothing beside _MASKED, so the sum IS
        # _MASKED).  A masked pair's exponential against a real row
        # maximum is 0.0 exactly; a row that has met no kept key yet
        # gathers ones, and ``alpha`` = 0.0 wipes them at the tile where
        # it meets one (every causal row keeps a key)
        bias_scr[...] = jnp.where(_unpack_tile(keep_ref[0], sub), 0.0,
                                  _MASKED)

        def some(n, carry):
            # stage by stage across the heads, not head by head: the
            # compiler keeps the order it is given, and so one head's
            # products run under another's vector work
            hs = [n * abreast + x for x in range(abreast)]
            s = [_nt(q_ref[0, h], k_ref[0, 0]) * sm_scale + bias_scr[...]
                 for h in hs]
            m_prev = [m_scr[h] for h in hs]
            m_next = [jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))
                      for m, x in zip(m_prev, s)]
            p = [jnp.exp(x - m) for x, m in zip(s, m_next)]
            v = v_ref[0, 0]
            for h, was, now, p_h in zip(hs, m_prev, m_next, p):
                alpha = jnp.exp(was - now)
                l_scr[h] = alpha * l_scr[h] + jnp.sum(p_h, axis=1,
                                                      keepdims=True)
                acc_scr[h] = alpha * acc_scr[h] + _nn(p_h.astype(v.dtype), v)
                m_scr[h] = now
            return carry

        lax.fori_loop(0, heads // abreast, some, 0)

    @pl.when(j == last)
    def _():
        def head(h, carry):
            denom = l_scr[h]
            o_ref[0, h] = (acc_scr[h] / denom).astype(o_ref.dtype)
            lse_ref[0, h] = _stats(m_scr[h] + jnp.log(denom), block_q)
            return carry

        lax.fori_loop(0, heads, head, 0)


def _attn_fwd_vmem(heads, block_q, block_k, head_dim):
    """The VMEM ``dsa_attn_fwd`` asks for with ``heads`` query heads a
    step on two-byte operands, in bytes and from above: a head's share (q
    and out double-buffered, the float32 accumulator, and the log-sum-exp
    block double-buffered, the running maximum and the running sum at a
    lane tile a row) and the tile's (the mask as float32, a float32
    ``[block_q, block_k]`` for each head abreast and one more; k, v and
    the mask's words, double-buffered).  53 MiB at 8 heads of 128 on
    1,024 x 1,024, where the v5e's compiler counts 52 (81 and 80 at 16
    heads)."""
    lanes = -(-head_dim // 128) * 128
    head = block_q * (2 * 2 * lanes * 2 + lanes * 4 + 4 * 128 * 4)
    tile = (2 + _abreast(heads)) * 4 * block_q * block_k + 2 * block_k * (
        2 * lanes * 2 + block_q // PACK * 4)
    return heads * head + tile


def _attn_fwd_heads(group, block_q, block_k, head_dim):
    """The query heads a step of ``dsa_attn_fwd`` serves: a key/value
    head's whole group where ``_attn_fwd_vmem`` is under the limit, else
    the group's largest divisor that is."""
    return next(n for n in range(group, 0, -1) if group % n == 0 and (
        n == 1 or _attn_fwd_vmem(n, block_q, block_k, head_dim)
        <= _VMEM_LIMIT))


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_q", "block_k", "pack", "interpret"))
def indexed_attention_fwd_pallas(q, k, v, packed, *, sm_scale,
                                 block_q=BLOCK_Q, block_k=BLOCK_K,
                                 pack=None, interpret=False):
    """``dsa_attn_fwd``: q [B, H, S, D], k, v [B, KV, S, D], packed keep
    (in blocks of ``pack`` queries, ``block_q`` a multiple of it; None:
    ``block_q``) -> (out [B, H, S, D], lse float32 [B, H, S]).  A grid
    step is a tile of a key/value head and serves ``_attn_fwd_heads`` of
    its query heads: the key, value and mask tiles arrive once for them."""
    batch, heads, seq, dim = q.shape
    group = heads // k.shape[1]
    sub = block_q // PACK
    block_k = min(block_k, seq)
    step = _attn_fwd_heads(group, block_q, block_k, dim)

    def key_block(i, j):
        # clamped to the last one the q block needs: a skipped step asks
        # for the block it already has
        return jnp.minimum(j, _last_k(i, block_q, block_k))

    kv = pl.BlockSpec((1, 1, block_k, dim), lambda b, n, i, j: (
        b, n * step // group, key_block(i, j), 0))
    q_spec = pl.BlockSpec((1, step, block_q, dim),
                          lambda b, n, i, j: (b, n, i, 0))
    out, lse = pl.pallas_call(
        functools.partial(_attn_fwd_kernel, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k,
                          sub=(pack or block_q) // PACK),
        grid=(batch, heads // step, seq // block_q, seq // block_k),
        in_specs=[q_spec, kv, kv, pl.BlockSpec(
            (1, sub, block_k), lambda b, n, i, j: (b, i, key_block(i, j)))],
        out_specs=[q_spec, pl.BlockSpec(
            (1, step, block_q, _STATS_LANES),
            lambda b, n, i, j: (b, n, i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq, _STATS_LANES),
                                 jnp.float32)],
        scratch_shapes=[pltpu.VMEM((step, block_q, 1), jnp.float32),
                        pltpu.VMEM((step, block_q, 1), jnp.float32),
                        pltpu.VMEM((step, block_q, dim), jnp.float32),
                        pltpu.VMEM((block_q, block_k), jnp.float32)],
        interpret=interpret, name="dsa_attn_fwd",
        **_compiler_params(("parallel", "parallel", "parallel", "arbitrary"),
                           interpret),
    )(q, k, v, packed)
    return out, lse[..., 0]


def _attn_bwd_kernel(q_ref, k_ref, v_ref, keep_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr,
                     dv_scr, *, sm_scale, block_q, block_k, sub):
    g, j, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    first_step = (j == 0) & (i == 0)
    last_step = ((j == pl.num_programs(3) - 1)
                 & (i == pl.num_programs(4) - 1))

    @pl.when(first_step)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(first_step & (g == 0))
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(i >= _first_q(j, block_q, block_k))
    def _():
        q, k, do = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]
        rows, cols = pl.ds(i * block_q, block_q), pl.ds(j * block_k, block_k)
        s = _nt(q, k) * sm_scale
        p = jnp.where(_unpack_tile(keep_ref[0], sub),
                      jnp.exp(s - lse_ref[0, 0][:, :1]), 0.0)
        dv_scr[cols, :] += _tn(p.astype(do.dtype), do)
        dp = _nt(do, v_ref[0, 0])
        ds = (p * (dp - delta_ref[0, 0][:, :1]) * sm_scale).astype(q.dtype)
        dk_scr[cols, :] += _tn(ds, q)
        dq_scr[rows, :] += _nn(ds, k)

    @pl.when(last_step)
    def _():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(last_step & (g == pl.num_programs(2) - 1))
    def _():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _wide(x):
    """[..., S] float32 -> the [..., S, 8] a kernel reads statistics as."""
    return jnp.broadcast_to(x[..., None].astype(jnp.float32),
                            (*x.shape, _STATS_LANES))


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_q", "block_k", "pack", "interpret"))
def indexed_attention_bwd_pallas(q, k, v, packed, out, lse, do, *, sm_scale,
                                 block_q=BLOCK_Q, block_k=BLOCK_K,
                                 pack=None, interpret=False):
    """``dsa_attn_bwd_dkdv``: (dq, dk, dv) from one visit of each tile.  A
    key/value head's query heads in turn, key blocks outermost: the head's
    whole dq and the key/value head's dk and dv stay in VMEM as float32
    (``_attn_bwd_vmem``) and leave once."""
    batch, heads, seq, dim = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    sub = block_q // PACK
    block_k = min(block_k, seq)
    delta = _wide(jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                          axis=-1))

    def first(i, j):
        return jnp.maximum(i, _first_q(j, block_q, block_k))

    q_of = pl.BlockSpec((1, 1, block_q, dim), lambda b, n, g, j, i: (
        b, n * group + g, first(i, j), 0))
    stat_of = pl.BlockSpec(
        (1, 1, block_q, _STATS_LANES), lambda b, n, g, j, i: (
            b, n * group + g, first(i, j), 0))
    kv_of = pl.BlockSpec((1, 1, block_k, dim),
                         lambda b, n, g, j, i: (b, n, j, 0))
    # the resident outputs: written at a head's (a group's) last step
    whole_q = pl.BlockSpec((1, 1, seq, dim), lambda b, n, g, j, i: (
        b, n * group + g, 0, 0))
    whole_kv = pl.BlockSpec((1, 1, seq, dim),
                            lambda b, n, g, j, i: (b, n, 0, 0))
    return tuple(pl.pallas_call(
        functools.partial(_attn_bwd_kernel, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k,
                          sub=(pack or block_q) // PACK),
        grid=(batch, kv_heads, group, seq // block_k, seq // block_q),
        in_specs=[q_of, kv_of, kv_of, pl.BlockSpec(
            (1, sub, block_k), lambda b, n, g, j, i: (b, first(i, j), j)),
            q_of, stat_of, stat_of],
        out_specs=[whole_q, whole_kv, whole_kv],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((seq, dim), jnp.float32)] * 3,
        interpret=interpret, name="dsa_attn_bwd_dkdv",
        **_compiler_params(("parallel", "parallel", "arbitrary", "arbitrary",
                            "arbitrary"), interpret),
    )(q, k, v, packed, do, _wide(lse), delta))


# ---------------------------------------------------------------------- #
# dsa_align: the alignment term and the indexer's gradients in one pass
# ---------------------------------------------------------------------- #
def _align_kernel(qi_ref, ki_ref, w_ref, q_ref, k_ref, lse_ref, keep_ref,
                  lsei_ref, loss_ref, dqi_ref, dkit_ref, dw_ref, loss_scr,
                  dw_scr, pbar_scr, relu_scr, *, sm_scale, block_q, block_k,
                  heads, idx_heads, group, scale, sub):
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_k(i, block_q, block_k)
    abreast = _abreast(heads, _ALIGN_ABREAST)
    idx_abreast = _abreast(idx_heads, _ALIGN_ABREAST)

    @pl.when((i == 0) & (j == 0))
    def _():
        dkit_ref[...] = jnp.zeros_like(dkit_ref)

    @pl.when(j == 0)
    def _():
        loss_scr[...] = jnp.zeros_like(loss_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)
        dqi_ref[...] = jnp.zeros_like(dqi_ref)

    @pl.when(j <= last)
    def _():
        keep = _unpack_tile(keep_ref[0], sub)
        pbar_scr[...] = jnp.zeros_like(pbar_scr)

        def some(n, carry):
            # stage by stage across the heads, as ``dsa_attn_fwd``'s step:
            # every product, every exponential, then the sum IN HEAD ORDER
            # (it is the sum head by head, bit for bit), held in VMEM and
            # not as a carry of 128 registers
            hs = [n * abreast + x for x in range(abreast)]
            s = [_nt(q_ref[0, h], k_ref[0, h // group]) for h in hs]
            p = [jnp.exp(x * sm_scale - lse_ref[0, h][:, :1])
                 for x, h in zip(s, hs)]
            pbar_scr[...] = functools.reduce(jnp.add, p, pbar_scr[...])
            return carry

        lax.fori_loop(0, heads // abreast, some, 0)
        pbar = jnp.where(keep, pbar_scr[...] * (1.0 / heads), 0.0)
        k_idx = ki_ref[0]
        # ``_scores_tile``, each head's ReLU(qI . kI) left in VMEM for the
        # gradients below: the product is built once
        scores = None
        for n in range(idx_heads):
            relu = jnp.maximum(_nt(qi_ref[0, n], k_idx), 0.0)
            relu_scr[n] = relu
            term = w_ref[0, n][:, :1] * relu
            scores = term if scores is None else scores + term
        logp = scores - lsei_ref[0][:, :1]
        live = keep & (pbar > 0.0)
        loss_scr[...] += _fold_lanes(jnp.where(
            live, pbar * (jnp.log(jnp.where(live, pbar, 1.0)) - logp), 0.0))
        # dL / dI on the tile; nothing outside the keep-set
        g = (jnp.where(keep, jnp.exp(logp), 0.0) - pbar) * scale
        cols = pl.ds(j * block_k, block_k)

        def some_idx(m, carry):
            # the indexer's heads likewise: the step's dI / dz tiles, then
            # its products, each sum in head order.  d kI is gathered
            # TRANSPOSED, [Di, S]: the head's q block is turned (64 wide)
            # and not the tile, and 64 rows go through the MXU, not 512
            ns = [m * idx_abreast + x for x in range(idx_abreast)]
            relu = [relu_scr[n] for n in ns]
            for n, r in zip(ns, relu):
                dw_scr[n] += _fold_lanes(g * r)
            gz = [jnp.where(r > 0.0, g * w_ref[0, n][:, :1], 0.0).astype(
                k_idx.dtype) for n, r in zip(ns, relu)]
            dq = [_nn(x, k_idx) for x in gz]
            dk = [_tn(qi_ref[0, n], x) for n, x in zip(ns, gz)]
            for n, x in zip(ns, dq):
                dqi_ref[0, n] += x
            dkit_ref[0, :, cols] = functools.reduce(
                jnp.add, dk, dkit_ref[0, :, cols])
            return carry

        lax.fori_loop(0, idx_heads // idx_abreast, some_idx, 0)

    @pl.when(j == last)
    def _():
        loss_ref[0] = _stats(jnp.sum(loss_scr[...], axis=1, keepdims=True),
                             block_q)
        for n in range(idx_heads):
            dw_ref[0, n] = _stats(
                jnp.sum(dw_scr[n], axis=1, keepdims=True), block_q)


def _align_vmem(seq, heads, kv_heads, head_dim, idx_heads, idx_dim, block_q,
                block_k):
    """The VMEM ``dsa_align`` asks for on two-byte operands, in bytes and
    from above: what the call declares (every block twice, its last axis
    whole lane tiles, the resident transposed d kI among them, and the
    scratch: two row sums, ``pbar`` and every indexer head's ReLU tile) and
    float32 ``[block_q, block_k]`` temporaries, four of the tile's own
    (the mask, ``pbar``, the scores, dL / dI) and one for each main head
    abreast or one and a half (the ReLU tile and, in two bytes, dI / dz)
    for each of the indexer's, whichever is more.  54.4 MiB at the cell's
    call (35.8 of blocks, 10.6 of scratch, 8 of temporaries), where the
    v5e's compiler counts 48."""
    def nbytes(*shape, size):
        return math.prod(shape[:-1]) * -(-shape[-1] // 128) * 128 * size

    blocks = (
        nbytes(idx_heads, block_q, idx_dim, size=2)             # qI
        + nbytes(block_k, idx_dim, size=2)                      # kI
        + nbytes(idx_heads, block_q, _STATS_LANES, size=4)      # w
        + nbytes(heads, block_q, head_dim, size=2)              # q
        + nbytes(kv_heads, block_k, head_dim, size=2)           # k
        + nbytes(heads, block_q, _STATS_LANES, size=4)          # lse
        + nbytes(block_q // PACK, block_k, size=4)              # the mask
        + 2 * nbytes(block_q, _STATS_LANES, size=4)             # lse_idx, loss
        + nbytes(idx_heads, block_q, idx_dim, size=4)           # d qI
        + nbytes(idx_dim, seq, size=4)                          # d kI^T
        + nbytes(idx_heads, block_q, _STATS_LANES, size=4))     # d w
    tile = nbytes(block_q, block_k, size=4)
    # a row sum (a lane tile a row at most) and a tile: the loss's and
    # ``pbar``, each indexer head's d w and ReLU
    scratch = (1 + idx_heads) * (nbytes(block_q, 1, size=4) + tile)
    return 2 * blocks + scratch + 4 * tile + max(
        _abreast(heads, _ALIGN_ABREAST) * tile,
        _abreast(idx_heads, _ALIGN_ABREAST) * tile * 3 // 2)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_q", "block_k", "pack", "interpret"))
def index_alignment_pallas(q_idx, k_idx, w, q, k, lse, packed, lse_idx, *,
                           sm_scale, block_q=BLOCK_Q, block_k=BLOCK_K,
                           pack=None, interpret=False):
    """``dsa_align``: (the term, its gradients (d q_idx, d k_idx, d w),
    float32 and shaped like their operands)."""
    batch, heads, seq, dim = q.shape
    idx_heads, idx_dim = q_idx.shape[1], q_idx.shape[3]
    kv_heads = k.shape[1]
    sub = block_q // PACK
    block_k = min(block_k, seq)
    lanes = 128 if block_k % 128 == 0 else 1

    def last(i, j):
        return jnp.minimum(j, _last_k(i, block_q, block_k))

    def rows(width, heads_=None):
        if heads_ is None:
            return pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0))
        return pl.BlockSpec((1, heads_, block_q, width),
                            lambda b, i, j: (b, 0, i, 0))

    loss, dqi, dkit, dw = pl.pallas_call(
        functools.partial(
            _align_kernel, sm_scale=sm_scale, block_q=block_q,
            block_k=block_k, heads=heads, idx_heads=idx_heads,
            group=heads // kv_heads, scale=1.0 / (batch * seq),
            sub=(pack or block_q) // PACK),
        grid=(batch, seq // block_q, seq // block_k),
        in_specs=[
            rows(idx_dim, idx_heads),
            pl.BlockSpec((1, block_k, idx_dim),
                         lambda b, i, j: (b, last(i, j), 0)),
            rows(_STATS_LANES, idx_heads),
            rows(dim, heads),
            pl.BlockSpec((1, kv_heads, block_k, dim),
                         lambda b, i, j: (b, 0, last(i, j), 0)),
            rows(_STATS_LANES, heads),
            pl.BlockSpec((1, sub, block_k),
                         lambda b, i, j: (b, i, last(i, j))),
            rows(_STATS_LANES)],
        out_specs=[
            rows(_STATS_LANES), rows(idx_dim, idx_heads),
            pl.BlockSpec((1, idx_dim, seq), lambda b, i, j: (b, 0, 0)),
            rows(_STATS_LANES, idx_heads)],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, _STATS_LANES), jnp.float32),
            jax.ShapeDtypeStruct(q_idx.shape, jnp.float32),
            jax.ShapeDtypeStruct((batch, idx_dim, seq), jnp.float32),
            jax.ShapeDtypeStruct((batch, idx_heads, seq, _STATS_LANES),
                                 jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((idx_heads, block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, block_k), jnp.float32),
            pltpu.VMEM((idx_heads, block_q, block_k), jnp.float32)],
        interpret=interpret, name="dsa_align",
        **_compiler_params(("arbitrary", "arbitrary", "arbitrary"),
                           interpret),
    )(q_idx, k_idx, _wide(w), q, k, _wide(lse), packed, _wide(lse_idx))
    return (jnp.sum(loss[..., 0]) / (batch * seq),
            (dqi, jnp.swapaxes(dkit, 1, 2), dw[..., 0]))


# ---------------------------------------------------------------------- #
# the three calls a model makes
# ---------------------------------------------------------------------- #
def index_select(q_idx, k_idx, w, topk, kernels=False):
    """The keep-set of every query: q_idx [B, Hi, S, Di] and k_idx [B, S,
    Di] (rotated, in the compute dtype), w float32 [B, Hi, S] (the scale
    factors folded in) -> (packed keep int32 [B, S / 32, S], the kept
    scores' log-sum-exp float32 [B, S]).  No gradient: the operands are
    taken as constants.  ``kernels``: ``kernels_take``'s answer."""
    q_idx, k_idx, w = (lax.stop_gradient(x) for x in (q_idx, k_idx, w))
    if kernels:
        packed, lse = index_select_pallas(
            q_idx, k_idx, w, topk=topk, interpret=pallas_interpret())
    else:
        packed, lse = index_select_xla(q_idx, k_idx, w, topk,
                                       pack_block(q_idx.shape[2]))
    return checkpoint_name(packed, KEEP_NAME), checkpoint_name(lse, KEEP_NAME)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention(q, k, v, packed, sm_scale, kernels):
    return _attention_fwd(q, k, v, packed, sm_scale, kernels)[0]


def _attention_fwd(q, k, v, packed, sm_scale, kernels):
    if kernels:
        out, lse = checkpoint_name(indexed_attention_fwd_pallas(
            q, k, v, packed, sm_scale=sm_scale, block_q=ATTN_BLOCK,
            block_k=ATTN_BLOCK, pack=BLOCK_Q,
            interpret=pallas_interpret()), RESIDUAL_NAME)
    else:
        out, lse = indexed_attention_xla(q, k, v, packed, sm_scale,
                                         pack_block(q.shape[2]))
    return (out, lse), (q, k, v, packed, out, lse)


def _attention_bwd(sm_scale, kernels, res, cotangents):
    q, k, v, packed, out, lse = res
    do, _ = cotangents          # lse is handed on as a constant
    if kernels:
        grads = indexed_attention_bwd_pallas(
            q, k, v, packed, out, lse, do, sm_scale=sm_scale,
            block_q=ATTN_BLOCK, block_k=ATTN_BLOCK, pack=BLOCK_Q,
            interpret=pallas_interpret())
    else:
        _, vjp = jax.vjp(lambda q_, k_, v_: indexed_attention_xla(
            q_, k_, v_, packed, sm_scale, pack_block(q.shape[2]))[0],
            q, k, v)
        grads = vjp(do)
    return (*grads, None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def indexed_attention(q, k, v, packed, sm_scale=None, kernels=False):
    """The restricted attention: q [B, H, S, D], k, v [B, KV, S, D], the
    packed keep-set -> (out [B, H, S, D], each row's log-sum-exp float32
    [B, H, S], a constant to whoever reads it)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = _attention(q, k, v, packed, float(sm_scale), kernels)
    return out, lax.stop_gradient(lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _alignment(q_idx, k_idx, w, q, k, lse, packed, lse_idx, sm_scale):
    return _alignment_fwd(q_idx, k_idx, w, q, k, lse, packed, lse_idx,
                          sm_scale)[0]


def _alignment_fwd(q_idx, k_idx, w, q, k, lse, packed, lse_idx, sm_scale):
    loss, grads = index_alignment_pallas(
        q_idx, k_idx, w, q, k, lse, packed, lse_idx, sm_scale=sm_scale,
        interpret=pallas_interpret())
    # in the operands' own types: what a budget keeps is half the bytes
    return checkpoint_name((loss, tuple(
        d.astype(x.dtype) for d, x in zip(grads, (q_idx, k_idx, w)))),
        ALIGN_NAME)


def _alignment_bwd(sm_scale, grads, g):
    return (*((g * d.astype(jnp.float32)).astype(d.dtype) for d in grads),
            None, None, None, None, None)


_alignment.defvjp(_alignment_fwd, _alignment_bwd)


def index_alignment(q_idx, k_idx, w, q, k, lse, packed, lse_idx,
                    sm_scale=None, kernels=False):
    """``mean_t KL(pbar_t || softmax_{s in S_t} I[t, s])``: differentiable
    in q_idx, k_idx and w; the main attention's q, k and lse, the keep-set
    and the kept scores' log-sum-exp are constants."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    q, k, lse, lse_idx = (lax.stop_gradient(x) for x in (q, k, lse, lse_idx))
    if kernels:
        return _alignment(q_idx, k_idx, w, q, k, lse, packed, lse_idx,
                          float(sm_scale))
    return index_alignment_xla(q_idx, k_idx, w, q, k, lse, packed, lse_idx,
                               float(sm_scale), pack_block(q.shape[2]))
