"""Latent attention's q, k and v between the up-projections' flat
products and the flash kernels' head-major operands, one pass each way.

    latent_heads(q_flat, k_lo, k_hi, v_flat, k_rope, cos, sin)
        -> q, k [B, H, S, nope + rope], v [B, H, S, vdim]
    heads_to_flat(a [B, H, S, D]) -> [B, S, H x D]

A query or key head is ``nope`` unrotated lanes and then ``rope`` rotated
ones, ``nope + rope`` whole lane tiles with the rotated slice the upper
half of the last (64 of 128 lanes; 192 + 64 as published).  The query
product ``q_flat [B, S, H x (nope + rope)]`` has every head on a tile
boundary.  The key/value product as the weights stand, ``H x (nope +
vdim)``, does not (a head of 448 lanes is 3.5 tiles), so the caller
takes it in three column sets of the same weight (``split_kv_columns``,
a copy of a 9 MB weight where the product is 294 MB):

    k_lo   [B, S, H x (nope - 64)]   a head's whole tiles of ``nope``
    k_hi   [B, S, H x 64]            its last 64, two heads a tile
    v_flat [B, S, H x vdim]

and ``k_rope [B, S, rope]``, the one rotated key a position.  A grid
cell moves a block of positions of a block of heads: q copied but for
its last half tile, which is rotated; k's whole tiles copied, its last
tile joined from the head's half of ``k_hi`` (an odd head's rolled down
64 lanes) and the rotated key, which is fetched once a block of
positions whatever the head and never exists per head in HBM; v copied.
The split, the head transpose, the join and the broadcast are index
maps and selects; each element is read once and written once in the
activations' dtype.  The arithmetic is ``apply_rotary``'s
(models/laguna.py): widened to float32, multiplied by float32 tables,
summed in float32, rounded once, the float32 values in registers; the
unrotated lanes are selected, not multiplied by one, and come through
bit for bit.

The backward pass is the same pass the other way with the sign of
``sin`` turned: it reads dq, dk, dv head-major and writes the four flat
cotangents, and d(k_rope) is the sum over the heads of ``dk``'s rotated
lanes, kept in float32 in a VMEM scratch along the heads' grid axis and
rotated back and rounded once after the last block of heads.  Nothing
is saved for it but the tables.  ``heads_to_flat`` is the copy back for
the output projection, its VJP the copy the other way.

Pallas kernels ``latent_heads_fwd`` / ``latent_heads_bwd`` /
``latent_flat_fwd`` / ``latent_flat_bwd`` on the TPU (and through the
interpreter where dispatch.pallas_interpret() says so) for the shapes
``latent_block`` takes; a caller keeps its plain form for the others.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import pallas_available, pallas_interpret
from .rotary import lane_tables

_LANES = 128
_HALF = _LANES // 2
# Positions and heads a grid cell.  With 4 heads of 256 a cell moves
# 2.75 MB in and 3 MB out in bf16; the rotated key's and the tables'
# blocks stay while the heads, the inner grid axis, go by.
BLOCK_ROWS = 512
BLOCK_HEADS = 4
# Positions a loop iteration: four packed bf16 tiles, whose table rows
# (16 registers) are loaded once for all the heads of the block.
_ROWS = 64
_VMEM_LIMIT = 32 * 1024 * 1024


def latent_block(seq, nope, rope, vdim, heads):
    """(positions, heads) of a grid cell's block, or None for a shape
    the kernels do not take: a query/key or value head that is not whole
    lane tiles, a rotated slice that is not the upper half of a tile
    after a whole tile or more of ``nope``, heads that do not pair up (two
    share a tile of ``k_hi``), a sequence that is not whole blocks, no
    TPU and no interpreter."""
    if not (pallas_available() or pallas_interpret()):
        return None
    rows = min(BLOCK_ROWS, seq)
    if ((nope + rope) % _LANES or vdim % _LANES or rope != _HALF
            or nope < _LANES or heads % 2 or seq % rows or rows % _ROWS):
        return None
    return rows, max(g for g in range(2, BLOCK_HEADS + 1, 2)
                     if heads % g == 0)


def latent_tables(cos, sin):
    """(cos, sin) float32 [S, rope / 2] -> the tables of a head's last
    tile [S, 128]: ``lane_tables``' cos twice and -sin, sin over the
    rotated half, zeros (never read) under the other."""
    return tuple(jnp.pad(table, ((0, 0), (_HALF, 0)))
                 for table in lane_tables(cos, sin, 2 * cos.shape[-1]))


def split_kv_columns(kv_b, heads, nope, vdim):
    """The key/value up-projection ``[r, H x (nope + vdim)]`` (a head's
    ``nope`` then its values) as the three weights whose products the
    kernels read: ``[r, H x (nope - 64)]``, ``[r, H x 64]``, ``[r, H x
    vdim]``.  The same columns, so the same values."""
    by_head = kv_b.reshape(kv_b.shape[0], heads, nope + vdim)
    return tuple(
        by_head[:, :, lo:hi].reshape(kv_b.shape[0], heads * (hi - lo))
        for lo, hi in ((0, nope - _HALF), (nope - _HALF, nope),
                       (nope, nope + vdim)))


# ---------------------------------------------------------------------- #
# the kernels
# ---------------------------------------------------------------------- #
def _chunks(rows, body):
    """``body(positions)`` over a block's positions, ``_ROWS`` a trip."""
    def chunk(i, carry):
        body(pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS))
        return carry

    jax.lax.fori_loop(0, rows // _ROWS, chunk, 0)


def _lanes():
    return jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)


def _turn(wide, cos, sin, lane):
    """A tile in float32 with its upper half rotated (pairs (i, i + 32)
    of that half), its lower half as it was; ``lane`` each lane's
    index."""
    partner = jnp.where(lane < _HALF + _HALF // 2,
                        pltpu.roll(wide, _LANES - _HALF // 2, 1),
                        pltpu.roll(wide, _HALF // 2, 1))
    return jnp.where(lane >= _HALF, wide * cos + partner * sin, wide)


def _heads_fwd_kernel(q_ref, lo_ref, hi_ref, v_ref, key_ref, cos_ref,
                      sin_ref, qo_ref, ko_ref, vo_ref, *, heads):
    last = qo_ref.shape[-1] - _LANES        # where a head's last tile starts
    wide_v = vo_ref.shape[-1]

    def body(rows):
        cos, sin, lane = cos_ref[rows, :], sin_ref[rows, :], _lanes()
        # lanes 64.. hold the position's one key (the lower half is the
        # caller's padding)
        key = _turn(key_ref[0, rows, :].astype(jnp.float32), cos, sin, lane)
        for j in range(heads):
            at = j * (last + _LANES)
            qo_ref[0, j, rows, :last] = q_ref[0, rows, at:at + last]
            turned = _turn(q_ref[0, rows, at + last:at + last + _LANES]
                           .astype(jnp.float32), cos, sin, lane)
            qo_ref[0, j, rows, last:] = turned.astype(qo_ref.dtype)
            ko_ref[0, j, rows, :last] = lo_ref[0, rows, j * last:(j + 1) * last]
            pair = hi_ref[0, rows, (j // 2) * _LANES:(j // 2 + 1) * _LANES]
            pair = pair.astype(jnp.float32)
            if j % 2:
                pair = pltpu.roll(pair, _HALF, 1)
            ko_ref[0, j, rows, last:] = jnp.where(
                lane >= _HALF, key, pair).astype(ko_ref.dtype)
            vo_ref[0, j, rows, :] = v_ref[0, rows, j * wide_v:(j + 1) * wide_v]

    _chunks(qo_ref.shape[2], body)


def _heads_bwd_kernel(dq_ref, dk_ref, dv_ref, cos_ref, sin_ref, qo_ref,
                      lo_ref, hi_ref, vo_ref, key_ref, sum_ref, *, heads):
    last = dq_ref.shape[-1] - _LANES
    wide_v = dv_ref.shape[-1]
    block = pl.program_id(2)

    def body(rows):
        cos, sin, lane = cos_ref[rows, :], -sin_ref[rows, :], _lanes()
        total = jnp.where(block == 0, 0.0, sum_ref[rows, :])
        for j in range(heads):
            at = j * (last + _LANES)
            qo_ref[0, rows, at:at + last] = dq_ref[0, j, rows, :last]
            turned = _turn(dq_ref[0, j, rows, last:].astype(jnp.float32),
                           cos, sin, lane)
            qo_ref[0, rows, at + last:at + last + _LANES] = turned.astype(
                qo_ref.dtype)
            lo_ref[0, rows, j * last:(j + 1) * last] = dk_ref[0, j, rows,
                                                              :last]
            tile = dk_ref[0, j, rows, last:].astype(jnp.float32)
            total = total + tile
            if j % 2:
                pair = jnp.where(lane >= _HALF, pltpu.roll(tile, _HALF, 1),
                                 even)
                hi_ref[0, rows, (j // 2) * _LANES:(j // 2 + 1) * _LANES] = (
                    pair.astype(hi_ref.dtype))
            else:
                even = tile
            vo_ref[0, rows, j * wide_v:(j + 1) * wide_v] = dv_ref[0, j, rows, :]
        sum_ref[rows, :] = total

        @pl.when(block == pl.num_programs(2) - 1)
        def _():
            key_ref[0, rows, :] = jnp.where(
                lane >= _HALF, _turn(total, cos, sin, lane), 0.0).astype(
                    key_ref.dtype)

    _chunks(dq_ref.shape[2], body)


def _flat_kernel(in_ref, out_ref, *, heads, backward):
    """A block between ``[1, heads, rows, D]`` and ``[1, rows, heads x
    D]`` (``backward``: from the second to the first)."""
    head_ref, flat_ref = (out_ref, in_ref) if backward else (in_ref, out_ref)
    wide = head_ref.shape[-1]

    def body(rows):
        for j in range(heads):
            lanes = slice(j * wide, (j + 1) * wide)
            if backward:
                head_ref[0, j, rows, :] = flat_ref[0, rows, lanes]
            else:
                flat_ref[0, rows, lanes] = head_ref[0, j, rows, :]

    _chunks(head_ref.shape[2], body)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _flat_side(batch, seq, heads, widths, block, dtype):
    """(block specs, shapes) of flat operands ``widths`` lanes a head."""
    rows, g = block
    return ([pl.BlockSpec((1, rows, g * w), lambda b, s, h: (b, s, h))
             for w in widths],
            [jax.ShapeDtypeStruct((batch, seq, heads * w), dtype)
             for w in widths])


def _head_side(batch, seq, heads, widths, block, dtype):
    """(block specs, shapes) of head-major operands ``widths`` wide."""
    rows, g = block
    return ([pl.BlockSpec((1, g, rows, w), lambda b, s, h: (b, h, s, 0))
             for w in widths],
            [jax.ShapeDtypeStruct((batch, heads, seq, w), dtype)
             for w in widths])


# Traced once a process and shape, as ops/rotary.py's call is: a
# pallas_call traces its body in Python each time the function around
# it is traced, and that time is the benchmark's gated setup_s.  Under
# an outer jit an inlined call.
@functools.partial(jax.jit, static_argnames=(
    "heads", "block", "backward", "interpret"))
def _heads_call(operands, cos, sin, *, heads, block, backward, interpret):
    """``operands``: (q_flat, k_lo, k_hi, v_flat, key [B, S, 128]), or
    (dq, dk, dv) head-major."""
    rows, g = block
    if backward:
        dq, _, dv = operands
        batch, _, seq, head = dq.shape
        wide_v = dv.shape[-1]
    else:
        batch, seq = operands[0].shape[:2]
        head, wide_v = (operands[0].shape[-1] // heads,
                        operands[3].shape[-1] // heads)
    dtype = operands[0].dtype
    flat, flat_shapes = _flat_side(
        batch, seq, heads, (head, head - _LANES, _HALF, wide_v), block, dtype)
    by_head, head_shapes = _head_side(
        batch, seq, heads, (head, head, wide_v), block, dtype)
    key = pl.BlockSpec((1, rows, _LANES), lambda b, s, h: (b, s, 0))
    tables = [pl.BlockSpec((rows, _LANES), lambda b, s, h: (s, 0))] * 2
    grid = (batch, seq // rows, heads // g)
    if backward:
        return pl.pallas_call(
            functools.partial(_heads_bwd_kernel, heads=g),
            grid=grid, in_specs=by_head + tables, out_specs=flat + [key],
            out_shape=flat_shapes + [
                jax.ShapeDtypeStruct((batch, seq, _LANES), dtype)],
            scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.float32)],
            compiler_params=_params("parallel", "parallel", "arbitrary"),
            interpret=interpret, name="latent_heads_bwd",
        )(*operands, cos, sin)
    return pl.pallas_call(
        functools.partial(_heads_fwd_kernel, heads=g),
        grid=grid, in_specs=flat + [key] + tables, out_specs=by_head,
        out_shape=head_shapes,
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret, name="latent_heads_fwd",
    )(*operands, cos, sin)


@functools.partial(jax.jit, static_argnames=(
    "block", "backward", "interpret"))
def _flat_call(x, *, block, backward, interpret):
    """``x`` head-major ``[B, H, S, D]``, or (``backward``) flat with
    ``block[2]`` heads."""
    rows, g, heads = block
    if backward:
        batch, seq, wide = x.shape[0], x.shape[1], x.shape[2] // heads
    else:
        batch, _, seq, wide = x.shape
    (flat,), (flat_shape,) = _flat_side(batch, seq, heads, (wide,),
                                        (rows, g), x.dtype)
    (by_head,), (head_shape,) = _head_side(batch, seq, heads, (wide,),
                                           (rows, g), x.dtype)
    return pl.pallas_call(
        functools.partial(_flat_kernel, heads=g, backward=backward),
        grid=(batch, seq // rows, heads // g),
        in_specs=[flat if backward else by_head],
        out_specs=by_head if backward else flat,
        out_shape=head_shape if backward else flat_shape,
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="latent_flat_bwd" if backward else "latent_flat_fwd",
    )(x)


# ---------------------------------------------------------------------- #
# the ops
# ---------------------------------------------------------------------- #
def _block_of(seq, head, vdim, heads):
    # a head of ``head`` lanes whose last 64 turn
    return latent_block(seq, head - _HALF, _HALF, vdim, heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _latent_heads(q_flat, k_lo, k_hi, v_flat, key, cos, sin, heads):
    return _latent_heads_fwd(q_flat, k_lo, k_hi, v_flat, key, cos, sin,
                             heads)[0]


def _latent_heads_fwd(q_flat, k_lo, k_hi, v_flat, key, cos, sin, heads):
    block = _block_of(q_flat.shape[1], q_flat.shape[2] // heads,
                      v_flat.shape[2] // heads, heads)
    out = _heads_call((q_flat, k_lo, k_hi, v_flat, key), cos, sin,
                      heads=heads, block=block, backward=False,
                      interpret=pallas_interpret())
    return tuple(out), (cos, sin)


def _latent_heads_bwd(heads, tables, cotangents):
    dq, _, dv = cotangents
    block = _block_of(dq.shape[2], dq.shape[3], dv.shape[3], heads)
    out = _heads_call(tuple(cotangents), *tables, heads=heads, block=block,
                      backward=True, interpret=pallas_interpret())
    return (*out, None, None)


_latent_heads.defvjp(_latent_heads_fwd, _latent_heads_bwd)


def latent_heads(q_flat, k_lo, k_hi, v_flat, k_rope, cos, sin, heads):
    """q, k ``[B, H, S, nope + rope]`` and v ``[B, H, S, vdim]`` in the
    products' dtype; ``k_lo``, ``k_hi``, ``v_flat`` the products of
    ``split_kv_columns``' weights, ``k_rope [B, S, 64]`` the position's
    key before its rotation, ``cos``, ``sin`` the tables of
    ``latent_tables``.  For the shapes ``latent_block`` takes."""
    batch, seq, width = q_flat.shape
    head, vdim = width // heads, v_flat.shape[-1] // heads
    if (width != heads * head or k_lo.shape[-1] != heads * (head - _LANES)
            or k_hi.shape[-1] != heads * _HALF or k_rope.shape[-1] != _HALF
            or _block_of(seq, head, vdim, heads) is None):
        raise ValueError(
            f"latent_heads: q {q_flat.shape}, k {k_lo.shape} + {k_hi.shape}"
            f" + {k_rope.shape}, v {v_flat.shape} with {heads} heads is no "
            "shape of the kernels")
    # the key on the lanes it takes in a head's last tile
    key = jnp.pad(k_rope, ((0, 0), (0, 0), (_HALF, 0)))
    return _latent_heads(q_flat, k_lo, k_hi, v_flat, key, cos, sin, heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _heads_to_flat(a, heads):
    return _heads_to_flat_fwd(a, heads)[0]


def _flat_statics(seq, wide, heads):
    rows, g = _block_of(seq, wide, wide, heads)
    return dict(block=(rows, g, heads), interpret=pallas_interpret())


def _heads_to_flat_fwd(a, heads):
    return _flat_call(a, backward=False,
                      **_flat_statics(*a.shape[2:], heads)), None


def _heads_to_flat_bwd(heads, _, d_flat):
    seq, wide = d_flat.shape[1], d_flat.shape[2] // heads
    return (_flat_call(d_flat, backward=True,
                       **_flat_statics(seq, wide, heads)),)


_heads_to_flat.defvjp(_heads_to_flat_fwd, _heads_to_flat_bwd)


def heads_to_flat(a):
    """``a [B, H, S, D] -> [B, S, H x D]``: the context on its way to the
    output projection.  For the shapes ``latent_block`` takes."""
    _, heads, seq, wide = a.shape
    if _block_of(seq, wide, wide, heads) is None:
        raise ValueError(f"heads_to_flat: {a.shape} is no shape of the "
                         "kernels")
    return _heads_to_flat(a, heads)
