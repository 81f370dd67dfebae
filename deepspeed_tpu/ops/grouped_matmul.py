"""Grouped matrix product over ragged row counts: the experts' products
of a mixture-of-experts layer that drops nothing and pads to no capacity.

    gmm(x [rows, k], w [G, k, n], counts [G]) -> [rows, n]

The rows are sorted by group: group g owns rows ``[off_g, off_g +
counts_g)``, ``off`` the running sum of ``counts``, and row r of the
result is ``x[r] @ w[g(r)]``.  Rows past ``sum(counts)`` belong to no
group and come out zero (a caller sizes ``rows`` for the worst case and
fills what the routing gave).  Differentiable in x and w: the backward
pass is the same product on the transposed weights (``dy @ w[g]^T``) and
a per-group ``x_g^T dy_g`` for the weights.

Two forms behind one switch, the backend, as ops/selective_scan.py:

- Pallas kernels ``gmm_rows`` / ``gmm_rows_t`` / ``gmm_weights`` on the
  TPU (and through the interpreter where dispatch.pallas_interpret()
  says so).  Group boundaries fall anywhere in a tile of TILE_ROWS rows,
  so the grid walks work items, one per (group, row tile it touches), in
  row order: an item multiplies its tile by its group's weights and
  keeps the rows that are the group's (the tile's block stays in VMEM
  while successive items share it).  The item list is built by XLA from
  ``counts`` and handed to the kernels as scalar prefetch; it has a
  static length (tiles + groups), the items past the real ones zero the
  tiles no group reached.  An empty group gets one item with no rows, so
  that its weight gradient is written (zero).
- ``jax.lax.ragged_dot`` elsewhere (the CPU, ``DS_FORCE_XLA_OPS``), and
  as the plain form the tests hold the kernels to.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import pallas_available, pallas_interpret

# Rows a tile.  A group of r rows touches about r / TILE_ROWS + 1 tiles,
# so the MXU multiplies (1 + TILE_ROWS / r) times the rows it keeps: 1.5
# at the 512 rows an expert of the benchmark's cell sees on average.
TILE_ROWS = 256
# Columns of the result a grid cell where n is whole lane tiles: a bf16
# weight block of k x 512 is 2 MB at k = 2,048 and 2.75 at 2,688, its
# double buffer twice that.  An n that is NOT whole lane tiles (1,856 =
# 14.5 x 128) is one block, the whole of it: a block may be as wide as
# its array whatever the width, no tile hangs over the edge, nothing is
# padded or masked, and x is read once instead of once a column block
# (my chip run, PR 60, PERF.md section 6).  Its double buffer has to fit
# (``_whole_fits``): 20 MB at 2,688 x 1,856.
TILE_COLS = 512
_LANES = 128
# what a width that is not whole lane tiles must still be a multiple of:
# half a tile, the packing of a bf16 sublane pair
_HALF = 64
_VMEM_LIMIT = 64 * 1024 * 1024


def _whole_fits(k, n):
    """Whether a [k, n] bf16 weight block's double buffer leaves half of
    the kernels' VMEM to the row tiles and the accumulator."""
    return 2 * 2 * k * n <= _VMEM_LIMIT // 2


def _use_pallas(rows, k, n):
    """Whether the kernels take ``[rows, k] x [G, k, n]`` (and so its two
    backward products, which swap k and n): whole lane tiles, or half
    tiles (multiples of 64) taken as one block where that fits."""
    def takes(width, other):
        return width % _LANES == 0 or (
            width % _HALF == 0 and _whole_fits(other, width))
    return ((pallas_available() or pallas_interpret())
            and takes(k, n) and takes(n, k) and rows % 8 == 0)


# ---------------------------------------------------------------------- #
# the plain form
# ---------------------------------------------------------------------- #
def _xla_rows(x, w, counts, transposed):
    """x @ w[g] by group (``transposed``: x @ w[g]^T), fp32 accumulation."""
    if transposed:
        w = w.swapaxes(1, 2)
    out = jax.lax.ragged_dot(x, w, counts.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    # the TPU's ragged product leaves the rows past the sum as they fall
    # (my chip run, PR 36): the contract here is zero
    row = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(row < jnp.sum(counts), out, 0.0)


def _xla_weights(x, dy, counts):
    """[G, k, n]: x_g^T dy_g, the rows past sum(counts) left out."""
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(
        x, dy, counts.astype(jnp.int32), dims,
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------- #
# work items
# ---------------------------------------------------------------------- #
def work_items(counts, rows, tile):
    """The (group, row tile) pairs to visit, in row order, as int32
    arrays of the static length ``rows // tile + G``:

    group     the item's group; G (no group: lo = hi = 0) past the real
              items
    in_tile   the row tile to read
    out_tile  the row tile to write (past the real items: the tiles no
              group reached, one each, then the last tile again)
    first     1 where ``out_tile`` differs from the item before (zero it)
    first_g   1 where the group's weight block differs from the item
              before (zero the weight gradient's block)
    lo, hi    [G + 1] the groups' row ranges, the no-group entry last
    """
    n_groups = counts.shape[0]
    tiles = rows // tile
    counts = counts.astype(jnp.int32)
    hi = jnp.cumsum(counts)
    lo = hi - counts
    first_tile = jnp.minimum(lo // tile, tiles - 1)
    per_group = jnp.maximum((hi + tile - 1) // tile - lo // tile, 1)
    # a group that ends past the array (never: counts sum to <= rows)
    per_group = jnp.minimum(per_group, tiles - first_tile)
    stop = jnp.cumsum(per_group)
    start = stop - per_group
    total = stop[-1]
    i = jnp.arange(tiles + n_groups, dtype=jnp.int32)
    g = jnp.clip(jnp.searchsorted(stop, i, side="right"), 0,
                 n_groups - 1).astype(jnp.int32)
    tile_of = first_tile[g] + i - start[g]
    real = i < total
    last_tile = first_tile[-1] + per_group[-1] - 1
    group = jnp.where(real, g, n_groups)
    in_tile = jnp.where(real, tile_of, last_tile)
    out_tile = jnp.where(real, tile_of,
                         jnp.minimum(last_tile + 1 + i - total, tiles - 1))
    block = jnp.minimum(group, n_groups - 1)

    def changed(a):
        return jnp.concatenate(
            [jnp.ones((1,), jnp.int32), (a[1:] != a[:-1]).astype(jnp.int32)])

    zero = jnp.zeros((1,), jnp.int32)
    return {"group": group, "in_tile": in_tile, "out_tile": out_tile,
            "first": changed(out_tile), "first_g": changed(block),
            "lo": jnp.concatenate([lo, zero]),
            "hi": jnp.concatenate([hi, zero])}


def _rows_of_group(group_ref, tile_ref, lo_ref, hi_ref, tile):
    """(whether the item's group has rows in its tile, their [tile, 1]
    mask)."""
    i = pl.program_id(1)
    g = group_ref[i]
    row0 = tile_ref[i] * tile
    lo, hi = lo_ref[g], hi_ref[g]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    some = jnp.logical_and(hi > row0, lo < row0 + tile)
    return jnp.logical_and(some, hi > lo), jnp.logical_and(rows >= lo,
                                                           rows < hi)


# ---------------------------------------------------------------------- #
# the kernels
# ---------------------------------------------------------------------- #
def _rows_kernel(group_ref, in_ref, out_ref, first_ref, lo_ref, hi_ref,
                 x_ref, w_ref, o_ref, *, tile, transposed):
    del in_ref
    i = pl.program_id(1)

    @pl.when(first_ref[i] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    some, mine = _rows_of_group(group_ref, out_ref, lo_ref, hi_ref, tile)

    @pl.when(some)
    def _product():
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


def _weights_kernel(group_ref, in_ref, first_ref, lo_ref, hi_ref, x_ref,
                    dy_ref, o_ref, *, tile):
    i = pl.program_id(1)

    @pl.when(first_ref[i] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    some, mine = _rows_of_group(group_ref, in_ref, lo_ref, hi_ref, tile)

    @pl.when(some)
    def _product():
        x = jnp.where(mine, x_ref[...], jnp.zeros_like(x_ref))
        o_ref[0] += jax.lax.dot_general(
            x, dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _tile_rows(rows):
    tile = min(TILE_ROWS, rows)
    while rows % tile:
        tile //= 2
    return tile


def _tile_cols(n):
    """Columns a grid cell of an n-wide result: the largest multiple of
    128 up to TILE_COLS that divides an n of whole lane tiles; any other
    n whole (the module's text says why)."""
    if n % _LANES:
        return n
    cols = min(TILE_COLS, n)
    while n % cols:
        cols -= _LANES
    return cols


def _pallas_rows(x, w, counts, transposed):
    rows, k = x.shape
    n_groups = w.shape[0]
    n = w.shape[1] if transposed else w.shape[2]
    tile, cols = _tile_rows(rows), _tile_cols(n)
    items = work_items(counts, rows, tile)
    last = n_groups - 1
    w_block = (1, cols, k) if transposed else (1, k, cols)

    def w_map(j, i, group, *_):
        g = jnp.minimum(group[i], last)
        return (g, j, 0) if transposed else (g, 0, j)

    return pl.pallas_call(
        functools.partial(_rows_kernel, tile=tile, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(pl.cdiv(n, cols), rows // tile + n_groups),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda j, i, group, in_tile, *_: (in_tile[i], 0)),
                pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec(
                (tile, cols),
                lambda j, i, group, in_tile, out_tile, *_: (out_tile[i], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret(),
        name="gmm_rows_t" if transposed else "gmm_rows",
    )(items["group"], items["in_tile"], items["out_tile"], items["first"],
      items["lo"], items["hi"], x, w)


def _pallas_weights(x, dy, counts):
    rows, k = x.shape
    n = dy.shape[1]
    n_groups = counts.shape[0]
    tile, cols = _tile_rows(rows), _tile_cols(n)
    items = work_items(counts, rows, tile)
    last = n_groups - 1
    return pl.pallas_call(
        functools.partial(_weights_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(n, cols), rows // tile + n_groups),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda j, i, group, in_tile, *_: (in_tile[i], 0)),
                pl.BlockSpec((tile, cols),
                             lambda j, i, group, in_tile, *_: (in_tile[i], j))],
            out_specs=pl.BlockSpec(
                (1, k, cols),
                lambda j, i, group, *_: (jnp.minimum(group[i], last), 0, j))),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret(),
        name="gmm_weights",
    )(items["group"], items["in_tile"], items["first_g"], items["lo"],
      items["hi"], x, dy)


# ---------------------------------------------------------------------- #
# the op
# ---------------------------------------------------------------------- #
def _rows(x, w, counts, transposed):
    n = w.shape[1] if transposed else w.shape[2]
    if _use_pallas(x.shape[0], x.shape[1], n):
        return _pallas_rows(x, w, counts, transposed)
    return _xla_rows(x, w, counts, transposed).astype(x.dtype)


@jax.custom_vjp
def _gmm(x, w, counts):
    return _rows(x, w, counts, False)


def _gmm_fwd(x, w, counts):
    return _rows(x, w, counts, False), (x, w, counts)


def _gmm_bwd(res, dy):
    x, w, counts = res
    dy = dy.astype(x.dtype)
    dx = _rows(dy, w, counts, True)
    if _use_pallas(x.shape[0], x.shape[1], dy.shape[1]):
        dw = _pallas_weights(x, dy, counts)
    else:
        dw = _xla_weights(x, dy, counts)
    return dx, dw.astype(w.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(x, w, counts):
    """``x [rows, k] @ w[g] [k, n]`` by sorted group, ``counts [G]`` rows
    a group; zero past ``sum(counts)``; in x's dtype, fp32 accumulation.
    The kernels where they may run, else XLA's ragged product."""
    if w.shape[0] != counts.shape[0] or x.shape[1] != w.shape[1]:
        raise ValueError(f"gmm: x {x.shape}, w {w.shape}, counts "
                         f"{counts.shape} do not fit")
    return _gmm(x, w.astype(x.dtype), counts)
