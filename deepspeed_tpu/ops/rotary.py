"""Rotary positions of q and k in one pass over the QKV product.

    rotate_qkv(qkv [B, S, (heads + 2 kv) x 128], cos, sin, half, heads, kv)
        -> q [B, heads, S, 128], k [B, kv, S, 128], v [B, kv, S, 128]

The projection leaves q, k and v side by side along the minor axis; the
attention kernels take them head-major.  A head of 128 is one lane tile,
so a block of heads is a 128 x g wide block of the flat product on the
way in and a ``[g, rows, 128]`` block on the way out: the split and the
head transpose are index maps, and each element is read once and
written once, in the activations' dtype.  q and k are rotated on the
way (rotate-half pairing (i, i + half) over the first 2 x half lanes of
a head, the rest unchanged), v is copied.  The arithmetic is
``apply_rotary``'s (models/laguna.py): every element widened to
float32, multiplied by float32 tables, summed in float32, rounded once;
the float32 values live in registers.

The tables are per lane, ``lane_tables(cos, sin)``: ``cos`` repeated
over both halves and 1 over the unrotated lanes, ``sin`` negated over
the first half and 0 over the unrotated lanes, so that

    y = x * cos + partner(x) * sin

with ``partner`` a roll along the lanes (by ``half`` down on the first
half, up on the second: one roll where 2 x half = 128).  The backward
pass is the same pass the other way with the sign of ``sin`` turned
(the transpose of a rotation by theta is a rotation by -theta): it
reads the cotangents head-major and writes d(qkv) flat, and nothing is
saved for it but the tables.

Pallas kernels ``rotary_fwd`` / ``rotary_bwd`` on the TPU (and through
the interpreter where dispatch.pallas_interpret() says so) for the
shapes ``rotary_block`` takes; a caller keeps its plain form for the
others.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import pallas_available, pallas_interpret

_LANES = 128
# Positions a grid cell.  With BLOCK_HEADS heads a cell moves 1 MB in
# and 1 MB out in bf16, against a grid step's fixed cost of under a
# microsecond; its table blocks stay while the heads, the inner grid
# axis, go by.
BLOCK_ROWS = 512
BLOCK_HEADS = 8
# Positions a loop iteration: four packed bf16 tiles, whose table rows
# (16 registers) are loaded once for all the heads of the block.
_ROWS = 64
_VMEM_LIMIT = 32 * 1024 * 1024


def rotary_block(seq, head_dim, heads, kv_heads):
    """(positions, heads) of a grid cell's block, or None for a shape
    the kernels do not take: a head that is not one lane tile, a
    sequence that is not whole blocks, no TPU and no interpreter."""
    if not (pallas_available() or pallas_interpret()):
        return None
    rows = min(BLOCK_ROWS, seq)
    if head_dim != _LANES or seq % rows or rows % _ROWS:
        return None
    # q, k and v each start and end on a block of heads
    group = math.gcd(heads, kv_heads)
    return rows, max(g for g in range(1, BLOCK_HEADS + 1) if group % g == 0)


def lane_tables(cos, sin, head_dim=_LANES):
    """(cos, sin) float32 [S, half] -> the per-lane tables [S, head_dim]:
    cos over both halves and 1 past them, -sin then sin and 0 past them."""
    rest = head_dim - 2 * cos.shape[-1]
    pad = jnp.zeros((cos.shape[0], rest), jnp.float32)
    return (jnp.concatenate([cos, cos, pad + 1.0], axis=-1),
            jnp.concatenate([-sin, sin, pad], axis=-1))


# ---------------------------------------------------------------------- #
# the kernels
# ---------------------------------------------------------------------- #
def _partner(x, half, low):
    """x [rows, 128]: lane i's partner of the rotation, i + half on the
    first half (``low``), i - half on the second."""
    if 2 * half == _LANES:
        return pltpu.roll(x, half, 1)
    return jnp.where(low, pltpu.roll(x, _LANES - half, 1),
                     pltpu.roll(x, half, 1))


def _walk(flat_ref, head_ref, cos_ref, sin_ref, *, heads, half, backward):
    """One block between its flat form ``[1, rows, heads x 128]`` and
    its head-major form ``[1, heads, rows, 128]`` (``backward``: from
    the second to the first), rotated where ``half``, else copied."""
    def chunk(i, carry):
        rows = pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS)
        if half:
            cos, sin = cos_ref[rows, :], sin_ref[rows, :]
            if backward:
                sin = -sin
            low = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1) < half
        for j in range(heads):
            lanes = slice(j * _LANES, (j + 1) * _LANES)
            x = (head_ref[0, j, rows, :] if backward
                 else flat_ref[0, rows, lanes])
            if half:
                wide = x.astype(jnp.float32)
                x = (wide * cos + _partner(wide, half, low) * sin).astype(
                    x.dtype)
            if backward:
                flat_ref[0, rows, lanes] = x
            else:
                head_ref[0, j, rows, :] = x
        return carry

    jax.lax.fori_loop(0, flat_ref.shape[1] // _ROWS, chunk, 0)


def _kernel(*refs, blocks, heads, half, backward):
    """The grid's inner axis walks the blocks of heads of q, then of k,
    then of v; ``blocks`` is how many each has."""
    if backward:
        q_ref, k_ref, v_ref, cos_ref, sin_ref, flat_ref = refs
    else:
        flat_ref, cos_ref, sin_ref, q_ref, k_ref, v_ref = refs
    h = pl.program_id(2)
    walk = functools.partial(_walk, flat_ref, cos_ref=cos_ref,
                             sin_ref=sin_ref, heads=heads, backward=backward)
    pl.when(h < blocks[0])(lambda: walk(q_ref, half=half))
    pl.when(jnp.logical_and(h >= blocks[0], h < blocks[0] + blocks[1]))(
        lambda: walk(k_ref, half=half))
    pl.when(h >= blocks[0] + blocks[1])(lambda: walk(v_ref, half=0))


# Traced once a process and shape, as the flash kernels' calls are
# (ops/flash_attention.py _flash_fwd_call): a pallas_call traces its
# body in Python each time the function around it is traced, several
# times a layer group in every program that holds it, and that time is
# the benchmark's gated setup_s.  Under an outer jit an inlined call.
@functools.partial(jax.jit, static_argnames=(
    "counts", "block", "half", "backward", "interpret"))
def _call(operands, cos, sin, *, counts, block, half, backward, interpret):
    """``operands``: the flat product, or (dq, dk, dv) head-major;
    ``counts`` the heads of q, k and v."""
    first = operands[0]
    batch, seq = (first.shape[0], first.shape[2]) if backward else (
        first.shape[:2])
    rows, g = block
    blocks = tuple(n // g for n in counts)
    starts = (0, blocks[0], blocks[0] + blocks[1])

    def head_map(start, count):
        # a part's block index stays at its first block before the
        # walk reaches it and at its last after: nothing is moved twice
        return lambda b, s, h: (b, jnp.clip(h - start, 0, count - 1), s, 0)

    flat = pl.BlockSpec((1, rows, g * _LANES), lambda b, s, h: (b, s, h))
    by_head = [pl.BlockSpec((1, g, rows, _LANES), head_map(start, count))
               for start, count in zip(starts, blocks)]
    tables = [pl.BlockSpec((rows, _LANES), lambda b, s, h: (s, 0))] * 2
    flat_shape = jax.ShapeDtypeStruct((batch, seq, sum(counts) * _LANES),
                                      first.dtype)
    head_shapes = [jax.ShapeDtypeStruct((batch, n, seq, _LANES), first.dtype)
                   for n in counts]
    return pl.pallas_call(
        functools.partial(_kernel, blocks=blocks, heads=g, half=half,
                          backward=backward),
        grid=(batch, seq // rows, sum(blocks)),
        in_specs=(by_head if backward else [flat]) + tables,
        out_specs=flat if backward else by_head,
        out_shape=flat_shape if backward else head_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="rotary_bwd" if backward else "rotary_fwd",
    )(*operands, cos, sin)


# ---------------------------------------------------------------------- #
# the op
# ---------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rotate_qkv(qkv, cos, sin, half, heads, kv_heads):
    return _rotate_qkv_fwd(qkv, cos, sin, half, heads, kv_heads)[0]


def _statics(seq, half, heads, kv_heads):
    return dict(counts=(heads, kv_heads, kv_heads), half=half,
                block=rotary_block(seq, _LANES, heads, kv_heads),
                interpret=pallas_interpret())


def _rotate_qkv_fwd(qkv, cos, sin, half, heads, kv_heads):
    out = _call((qkv,), cos, sin, backward=False,
                **_statics(qkv.shape[1], half, heads, kv_heads))
    return tuple(out), (cos, sin)


def _rotate_qkv_bwd(half, heads, kv_heads, tables, cotangents):
    dqkv = _call(tuple(cotangents), *tables, backward=True,
                 **_statics(cotangents[0].shape[2], half, heads, kv_heads))
    return dqkv, None, None


_rotate_qkv.defvjp(_rotate_qkv_fwd, _rotate_qkv_bwd)


def rotate_qkv(qkv, cos, sin, half, heads, kv_heads):
    """``qkv [B, S, (heads + 2 kv_heads) x 128]`` -> q, k rotated and v,
    each ``[B, n, S, 128]`` in qkv's dtype; ``cos``, ``sin`` the lane
    tables of ``lane_tables``, ``half`` the width they were made from.
    For the shapes ``rotary_block`` takes."""
    batch, seq, width = qkv.shape
    if (width != (heads + 2 * kv_heads) * _LANES
            or rotary_block(seq, _LANES, heads, kv_heads) is None):
        raise ValueError(f"rotate_qkv: qkv {qkv.shape} with {heads} + 2 x "
                         f"{kv_heads} heads is no shape of the kernels")
    return _rotate_qkv(qkv, cos, sin, half, heads, kv_heads)
