"""The depthwise causal convolution of a state-space mixer, with its bias
and its silu, in one pass over the projection's output each way.

    causal_conv(x [B, S, W], w [C, taps], b [C], first=0, split=())
        -> silu(conv(x[..., first:first + C]) + b)  [B, S, C] in x's dtype,
           or its parts of the widths ``split`` along the channels

    y_t[c] = silu(b[c] + sum_j w[c, j] x_{t - (taps - 1) + j}[c]),  x_t = 0
             for t < 0

in float32, the taps summed in the order j = 0, 1, ..., the bias last,
rounded once.  A Mamba-2 mixer's projection leaves z and xBC side by side
and its scan reads x, B and C apart: ``first`` and ``split`` let the conv
read xBC where the projection wrote it and write each part where the scan
reads it, so no slice, no padded copy and no float32 array of the
activations' size stands between the projection and the scan.

With ``d pre = dy silu'(pre)`` (pre the sum above, rebuilt from x):

    dx_t = sum_j w[j] d pre_{t + (taps - 1) - j}     (d pre = 0 past the end)
    dw[j] = sum_t d pre_t x_{t - (taps - 1) + j},    db = sum_t d pre_t

Nothing is saved for the backward pass but the op's own inputs.

Two forms, one switch (the shapes and the backend, as for the other ops:
dispatch.py):

- Pallas kernels ``causal_conv_fwd`` / ``causal_conv_bwd`` on the TPU
  (and through the interpreter where dispatch.pallas_interpret() says
  so).  Channels lie along the lanes, positions along the sublanes; a
  grid cell is ``BLOCK_ROWS`` positions of one lane tile of channels, a
  strip that is whole 4 KB tiles of the array in HBM wherever it starts.
  The ``taps - 1`` rows before a block come through a second block spec
  on the same operand, the last ``_HALO`` rows of the block before it
  (clamped at the first block and zeroed there: that zero IS the causal
  pad); the backward pass also takes the first rows of the block after
  it, of x and of dy, and rebuilds from them the d pre its last rows
  need (zero past the last position: the pad transposed).  A part of
  ``split`` is its own output (its own cotangent on the way back) whose
  block index stays at its first block before the walk over the lane
  tiles reaches it and at its last after, so nothing moves twice.  The
  loop inside a cell takes ``_ROWS`` positions at a time, the float32
  values in registers, a row's delayed neighbours by a roll along the
  sublanes of the rows and the tile before them; backward it walks the
  rows in reverse, carrying the d pre of the tile after.  The taps' and
  the bias' gradients are sums over ROWS (sublanes, the cheap
  orientation), kept a tile a lane tile in a block that stays in VMEM
  over the whole grid.
- plain XLA elsewhere (``refusal`` says why in words): a bf16 pad, the
  shifted slices each cast to float32, XLA's own derivative.
"""

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import (BATCH_AXES, manual_kernel_region, pallas_available,
                       pallas_interpret)

_LANES = 128
# Positions a grid cell: a strip of 4,096 x 128 is 1 MB in bf16, and the
# backward pass holds five of them twice.  On the v5e both passes ran 5 to
# 10% faster at 4,096 than at 2,048 and 15% faster than at 1,024.
BLOCK_ROWS = 4096
# Positions a loop iteration: eight float32 tiles a value.
_ROWS = 64
# Rows of a halo block: one packed bf16 tile, two float32 tiles.
_HALO = 16
# Rows of a float32 tile: what a loop iteration hands the next, and the
# rows of the operand that holds the taps and the bias.
_SUB = 8


def xla_causal_conv(x, w, b):
    """The plain form: x [B, S, C], w [C, taps] (tap j reads position
    t - (taps - 1) + j), b [C] -> silu(conv + b), in float32, rounded
    once to x's dtype."""
    taps, seq = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    out = sum(padded[:, j:j + seq].astype(jnp.float32) * w[:, j]
              for j in range(taps))
    return jax.nn.silu(out + b.astype(jnp.float32)).astype(x.dtype)


def block_rows(seq):
    """Positions of a grid cell's block: the most whole loop iterations,
    up to BLOCK_ROWS, that divide ``seq`` (a multiple of _ROWS)."""
    return max(r for r in range(_ROWS, min(BLOCK_ROWS, seq) + 1, _ROWS)
               if seq % r == 0)


def refusal(seq, channels, taps, first=0, split=()):
    """Why the kernels are not written for these shapes, in words; None
    where they take them."""
    widths = tuple(split) or (channels,)
    if any(n % _LANES for n in (first, *widths)):
        return (f"channels {first} + " + " + ".join(map(str, widths))
                + f" are no whole lane tiles of {_LANES}")
    if seq % _ROWS:
        return f"{seq} positions are no whole blocks of {_ROWS}"
    if taps + 1 > _SUB:
        return (f"{taps} taps and a bias are more than the {_SUB} rows of "
                "a tile")
    return None


def uses_kernels(seq, channels, taps, first=0, split=()):
    """Whether a call of these shapes runs the Pallas kernels here."""
    return ((pallas_available() or pallas_interpret())
            and refusal(seq, channels, taps, first, split) is None)


# ---------------------------------------------------------------------- #
# the kernels
# ---------------------------------------------------------------------- #
def _wide(ref, rows=slice(None)):
    return ref[0, rows, :].astype(jnp.float32)


def _weights(wb_ref, taps):
    """(the taps, the bias) of the cell's lane tile, each [1, 128]."""
    return ([wb_ref[j:j + 1, :] for j in range(taps)],
            wb_ref[taps:taps + 1, :])


def _pre(before, x, w, bias):
    """(the pre-activation of the rows x [R, 128], x delayed by taps - 1,
    ..., 0 rows); ``before`` [_SUB, 128] the rows before them."""
    taps = len(w)
    joined = jnp.concatenate([before, x], axis=0)
    delayed = [pltpu.roll(joined, taps - 1 - j, 0)[_SUB:]
               for j in range(taps - 1)] + [x]
    pre = delayed[0] * w[0]
    for j in range(1, taps):
        pre = pre + delayed[j] * w[j]
    return pre + bias, delayed


def _silu_slope(pre):
    s = jax.nn.sigmoid(pre)
    return s * (1.0 + pre * (1.0 - s))


def _rows_before(prev_ref, block):
    """The tile of rows before block ``block``; the causal pad, zero,
    before the first."""
    return jnp.where(block > 0, _wide(prev_ref)[_HALO - _SUB:], 0.0)


def _walk_parts(walk, part_refs, blocks, j):
    """``walk(*refs)`` on the refs of the part that holds lane tile j:
    ``blocks`` is how many lane tiles each part has."""
    if len(blocks) == 1:
        return walk(*part_refs[0])
    start = 0
    for refs, count in zip(part_refs, blocks):
        pl.when(jnp.logical_and(j >= start, j < start + count))(
            functools.partial(walk, *refs))
        start += count


def _fwd_kernel(x_ref, prev_ref, wb_ref, *out_refs, taps, blocks):
    # (the grid's indices are read here, outside every branch and loop)
    block, j = pl.program_id(1), pl.program_id(2)

    def walk(out_ref):
        w, bias = _weights(wb_ref, taps)

        def chunk(c, before):
            at = pl.ds(pl.multiple_of(c * _ROWS, _ROWS), _ROWS)
            x = _wide(x_ref, at)
            pre, _ = _pre(before, x, w, bias)
            out_ref[0, at, :] = jax.nn.silu(pre).astype(out_ref.dtype)
            return x[_ROWS - _SUB:]

        jax.lax.fori_loop(0, x_ref.shape[1] // _ROWS, chunk,
                          _rows_before(prev_ref, block))

    _walk_parts(walk, [(ref,) for ref in out_refs], blocks, j)


def _bwd_kernel(x_ref, prev_ref, next_ref, *refs, taps, blocks):
    n = len(blocks)
    wb_ref, dx_ref, dwb_ref = refs[2 * n:]
    rows = x_ref.shape[1]
    batch, block, j = (pl.program_id(a) for a in range(3))
    more = block < pl.num_programs(1) - 1
    opening = jnp.logical_and(batch == 0, block == 0)

    def walk(dy_ref, dy_next_ref):
        w, bias = _weights(wb_ref, taps)
        before = _rows_before(prev_ref, block)
        # d pre of the tile after the block, from the rows on both sides
        # of the edge; zero past the last position
        pre, _ = _pre(_wide(x_ref, slice(rows - _HALO, rows))[_HALO - _SUB:],
                      _wide(next_ref)[:_SUB], w, bias)
        after = jnp.where(
            more, _wide(dy_next_ref)[:_SUB] * _silu_slope(pre), 0.0)

        def tile_sum(t):
            return sum(t[r:r + _SUB] for r in range(0, _ROWS, _SUB))

        def chunk(step, carry):
            after, sums = carry
            c = rows // _ROWS - 1 - step
            start = pl.multiple_of(c * _ROWS, _ROWS)
            at = pl.ds(start, _ROWS)
            x = _wide(x_ref, at)
            lead = _wide(x_ref, pl.ds(pl.multiple_of(
                jnp.maximum(start - _HALO, 0), _HALO), _HALO))
            pre, delayed = _pre(
                jnp.where(c > 0, lead[_HALO - _SUB:], before), x, w, bias)
            d_pre = _wide(dy_ref, at) * _silu_slope(pre)
            # tap j's weight on d pre at t + taps - 1 - j
            joined = jnp.concatenate([d_pre, after], axis=0)
            dx = d_pre * w[taps - 1]
            for k in range(1, taps):
                dx = dx + pltpu.roll(joined, _ROWS + _SUB - k, 0)[
                    :_ROWS] * w[taps - 1 - k]
            dx_ref[0, at, :] = dx.astype(dx_ref.dtype)
            # the taps' and the bias' sums, a tile of rows each
            sums = tuple(s + tile_sum(t) for s, t in zip(
                sums, (*(d_pre * t for t in delayed), d_pre)))
            return d_pre[:_SUB], sums

        zero = jnp.zeros((_SUB, _LANES), jnp.float32)
        _, sums = jax.lax.fori_loop(0, rows // _ROWS, chunk,
                                    (after, (zero,) * (taps + 1)))
        row = jax.lax.broadcasted_iota(jnp.int32, zero.shape, 0)
        total = zero
        for r, s in enumerate(sums):
            total = jnp.where(row == r, jnp.sum(s, axis=0, keepdims=True),
                              total)
        # (the block is the call's own: what it holds before the first
        # cell of a lane tile is not read into the sums)
        dwb_ref[j] = jnp.where(opening, total, dwb_ref[j] + total)

    _walk_parts(walk, list(zip(refs[:n], refs[n:2 * n])), blocks, j)


def _specs(batch, seq, first, split):
    """The grid (batch, block of positions, lane tile) and the
    BlockSpecs on it: of a block of x, of the rows before it and of the
    rows after it, at the lane tiles from ``first`` on; of a block of
    each part of ``split`` and of the rows after it; of the taps."""
    rows = block_rows(seq)
    halos, last = rows // _HALO, seq // _HALO - 1

    def strip(height, position, column):
        return pl.BlockSpec(
            (1, height, _LANES),
            lambda b, i, j: (b, position(i), column(j)))

    def here(i):
        return i

    def before(i):      # clamped: the first block reads zeros instead
        return jnp.maximum(i * halos - 1, 0)

    def after(i):       # clamped: past the last block d pre is zero
        return jnp.minimum((i + 1) * halos, last)

    parts, nexts, start = [], [], 0
    for width in split:
        count = width // _LANES

        def column(j, start=start, count=count):
            # a part's block index stays at its first block before the
            # walk reaches it and at its last after: nothing moves twice
            return jnp.clip(j - start, 0, count - 1)

        parts.append(strip(rows, here, column))
        nexts.append(strip(_HALO, after, column))
        start += count
    x_specs = [strip(height, position, lambda j: first // _LANES + j)
               for height, position in ((rows, here), (_HALO, before),
                                        (_HALO, after))]
    grid = (batch, seq // rows, sum(split) // _LANES)
    return grid, x_specs, parts, nexts, pl.BlockSpec(
        (_SUB, _LANES), lambda b, i, j: (0, j))


def _call(kernel, name, interpret, grid, in_specs, operands, out_specs,
          out_shape):
    """The Pallas call.  It asks for the VMEM its blocks need, each twice
    (the pipeline's two buffers), and a MiB for what the compiler spills;
    no more, so that the programs around the call keep theirs."""
    params = {}
    if not interpret:
        blocks = sum(
            math.prod(spec.block_shape) * t.dtype.itemsize
            for spec, t in zip((*in_specs, *out_specs),
                               (*operands, *out_shape)))
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=2 * blocks + (1 << 20))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        interpret=interpret, name=name, **params)(*operands)


def _packed(w, b):
    """w [C, taps], b [C] -> the kernels' operand [_SUB, C] float32: the
    taps a row each, then the bias."""
    f32 = jnp.float32
    return jnp.concatenate([
        w.astype(f32).T, b.astype(f32)[None],
        jnp.zeros((_SUB - w.shape[1] - 1, w.shape[0]), f32)])


# Traced once a process and shape, as the other ops' calls are
# (ops/rotary.py _call): that time is the benchmark's gated setup_s.
@functools.partial(jax.jit, static_argnames=(
    "taps", "first", "split", "interpret"))
def _forward(src, wb, *, taps, first, split, interpret):
    """The parts of silu(conv + b), each [B, S, its width] in src's
    dtype; ``wb`` the taps and the bias as ``_packed`` lays them."""
    batch, seq, _ = src.shape
    grid, (x_spec, prev_spec, _), parts, _, taps_spec = _specs(
        batch, seq, first, split)
    return _call(
        functools.partial(_fwd_kernel, taps=taps,
                          blocks=tuple(n // _LANES for n in split)),
        "causal_conv_fwd", interpret, grid,
        [x_spec, prev_spec, taps_spec], (src, src, wb), parts,
        [jax.ShapeDtypeStruct((batch, seq, n), src.dtype) for n in split])


@functools.partial(jax.jit, static_argnames=("taps", "first", "interpret"))
def _backward(src, cotangents, wb, *, taps, first, interpret):
    """(d xBC [B, S, C] in src's dtype, the taps' and the bias'
    gradients in wb's layout, [_SUB, C] float32)."""
    batch, seq, _ = src.shape
    channels = wb.shape[1]
    split = tuple(t.shape[2] for t in cotangents)
    grid, x_specs, parts, nexts, taps_spec = _specs(batch, seq, first, split)
    tiles = channels // _LANES
    d_conv, d_wb = _call(
        functools.partial(_bwd_kernel, taps=taps,
                          blocks=tuple(n // _LANES for n in split)),
        "causal_conv_bwd", interpret, grid,
        [*x_specs, *parts, *nexts, taps_spec],
        (src, src, src, *cotangents, *cotangents, wb),
        [pl.BlockSpec((1, block_rows(seq), _LANES),
                      lambda b, i, j: (b, i, j)),
         # the whole array, in VMEM from the first cell to the last
         pl.BlockSpec((tiles, _SUB, _LANES), lambda b, i, j: (0, 0, 0))],
        [jax.ShapeDtypeStruct((batch, seq, channels), src.dtype),
         jax.ShapeDtypeStruct((tiles, _SUB, _LANES), jnp.float32)])
    return d_conv, d_wb.transpose(1, 0, 2).reshape(_SUB, channels)


# ---------------------------------------------------------------------- #
# the op
# ---------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(src, w, b, first, split):
    return _conv_fwd(src, w, b, first, split)[0]


def _conv_fwd(src, w, b, first, split):
    parts = _forward(src, _packed(w, b), taps=w.shape[1], first=first,
                     split=split, interpret=pallas_interpret())
    return tuple(parts), (src, w, b)


def _conv_bwd(first, split, res, cotangents):
    src, w, b = res
    channels, taps = w.shape
    d_conv, d_wb = _backward(src, tuple(cotangents), _packed(w, b),
                             taps=taps, first=first,
                             interpret=pallas_interpret())
    rest = src.shape[2] - first - channels
    if first or rest:   # the columns the conv did not read
        d_conv = jnp.pad(d_conv, ((0, 0), (0, 0), (first, rest)))
    return (d_conv, d_wb[:taps].T.astype(w.dtype),
            d_wb[taps].astype(b.dtype))


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv(x, w, b, first=0, split=()):
    """silu(depthwise causal conv + b) over the channels ``first ..
    first + C`` of x [B, S, W], w [C, taps] (tap j reads position
    t - (taps - 1) + j), b [C]: [B, S, C] in x's dtype, or the tuple of
    its parts of the widths ``split`` (which sum to C).  Differentiable
    in x, w and b.  Under a device mesh the kernels run in a region
    manual over every axis, the batch split over the data axes."""
    channels, taps = w.shape
    if x.ndim != 3 or first + channels > x.shape[2] or b.shape != (
            channels,) or (split and sum(split) != channels):
        raise ValueError(
            f"causal_conv: x {x.shape} must be [batch, S, W] with W >= "
            f"{first} + {channels}, w {w.shape} [C, taps], b {b.shape} [C] "
            f"and the parts {tuple(split)} must sum to C")
    if not uses_kernels(x.shape[1], channels, taps, first, split):
        out = xla_causal_conv(x[..., first:first + channels], w, b)
        if not split:
            return out
        return tuple(jnp.split(
            out, list(itertools.accumulate(split[:-1])), axis=-1))
    rows = {0: BATCH_AXES}

    def local(_, x, w, b):
        return _conv(x, w, b, first, tuple(split) or (channels,))

    out = manual_kernel_region(local, (x, w, b), (rows, None, None), rows)
    return out if split else out[0]
