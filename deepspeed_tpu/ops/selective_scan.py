"""The selective scan of a Mamba-1 mixer (Gu & Dao 2023, section 3.2;
the reference's selective_scan_fn), forward and hand-written backward
under one custom_vjp.  This is the scan of models/phi4flash.py: a decay
for every (channel, state) pair, walked position by position.  The
Mamba-2 mixer of models/granite_hybrid.py (one decay a head, B and C
shared by the heads) runs ops/ssd_scan.py, the same recurrence's chunked
matrix form on the MXU; neither op calls the other.

Per batch row, channel c and state n, over positions t:

    a_t[c, n] = exp(dt_t[c] * A[c, n])
    s_t[c, n] = a_t[c, n] * s_{t-1}[c, n] + dt_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n s_t[c, n] * C_t[n] + D[c] * x_t[c]

with s before the first position 0.  x, dt: [batch, S, channels] (dt
already positive: softplus of the projected step); A: [channels, N],
negative; B, C: [batch, S, N]; D: [channels].  Everything inside runs in
float32.

The sequence is walked in chunks of CHUNK positions.  The forward pass
carries the state [channels, N] from chunk to chunk and saves it at each
chunk's entry ([S / CHUNK, channels, N] a row: 21 MB at 8,192 x 5,120 x
16, where every position's state would be 2.7 GB); the backward pass
walks the chunks in reverse, rebuilds a chunk's states from its saved
entry, and carries the state's cotangent the other way.  Nothing of
size [S, channels, N] reaches HBM.  With g_t the cotangent of s_t
(g_t = dy_t[c] C_t[n] + a_{t+1} g_{t+1}):

    dC_t[n] = sum_c dy_t[c] s_t[c, n]       dB_t[n] = sum_c g_t dt_t x_t
    ddt_t[c] = sum_n g_t (s_{t-1} a_t A + x_t B_t[n])
    dx_t[c]  = dt_t[c] sum_n g_t B_t[n] + D[c] dy_t[c]
    dA[c, n] = sum_t g_t s_{t-1} a_t dt_t[c]      dD[c] = sum_t dy_t x_t

Two forms, one switch (the backend, as for the other ops: dispatch.py):

- Pallas kernels ``sscan_fwd`` / ``sscan_bwd`` on the TPU (and through
  the interpreter where dispatch.pallas_interpret() says so).  Channels
  lie on the lanes and the N states on the sublanes; a grid cell is one
  chunk of one block of SSCAN_BLOCK channels and walks its positions
  one after another on the vector unit, the state in registers.  B and
  C come in spread over a lane tile ([S, N, 128], 67 MB each at 8,192
  positions: the kernel needs B_t[n] as a column across the lanes, and a
  column of a [CHUNK, N] block is a relayout a step); the channel
  blocks are the inner grid axis, so a chunk's B and C are fetched once
  for all of them and dB, dC accumulate over them in their output
  block, by lane; the last sum over the 128 lanes is XLA's.
- plain XLA elsewhere: a lax.scan over the chunks, within a chunk an
  associative scan over the pairs (a_t, b_t) (every factor at most 1,
  so nothing overflows whatever dt is), the same saved entries and the
  same backward formulas.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import (BATCH_AXES, manual_kernel_region, pallas_available,
                       pallas_interpret)

# Positions a chunk: the backward kernel keeps a chunk's states in VMEM
# ([CHUNK, N, SSCAN_BLOCK] float32: 4 MB at 128 x 16 x 512).
CHUNK = 128
# Channels a grid cell: 4 lane tiles, so a [N, SSCAN_BLOCK] state of 16
# sublanes is 8 vector registers.
SSCAN_BLOCK = 512
_LANES = 128
# Positions a loop iteration: one aligned [8, channels] tile of x, dt, y.
_ROWS = 8


def entry_state_bytes(batch, seq, channels, states, chunk=CHUNK):
    """Bytes of the chunk-entry states one call saves for its backward."""
    return batch * -(-seq // chunk) * channels * states * 4


# ---------------------------------------------------------------------- #
# plain XLA, chunk by chunk
# ---------------------------------------------------------------------- #
def _combine(left, right):
    """(a, b) pairs of s -> a s + b, `left` applied first."""
    return left[0] * right[0], right[0] * left[1] + right[1]


def _chunk_states(s0, x, dt, a_mat, b_mat):
    """a_t and s_t for every position of one chunk: x, dt [L, ch],
    b_mat [L, N], s0 [ch, N] -> a, s [L, ch, N]."""
    a = jnp.exp(dt[:, :, None] * a_mat[None])
    b = (dt * x)[:, :, None] * b_mat[:, None, :]
    aa, bb = jax.lax.associative_scan(_combine, (a, b), axis=0)
    return a, aa * s0[None] + bb


def _xla_fwd(x, dt, a_mat, b_mat, c_mat, d_vec):
    """One batch row, [n_chunks, L, ...] operands: (y, entry states)."""
    def chunk(s0, xs):
        xc, dtc, bc, cc = xs
        _, s = _chunk_states(s0, xc, dtc, a_mat, bc)
        y = jnp.einsum("lcn,ln->lc", s, cc) + d_vec * xc
        return s[-1], (y, s0)
    s0 = jnp.zeros(a_mat.shape, jnp.float32)
    _, (y, entries) = jax.lax.scan(chunk, s0, (x, dt, b_mat, c_mat))
    return y, entries


def _xla_bwd(x, dt, a_mat, b_mat, c_mat, d_vec, entries, dy):
    """One batch row: (dx, ddt, dA, dB, dC) by the module's formulas."""
    def chunk(carry, xs):
        g_in, d_a = carry               # cotangent of the chunk's last state
        xc, dtc, bc, cc, s0, dyc = xs
        a, s = _chunk_states(s0, xc, dtc, a_mat, bc)
        s_prev = jnp.concatenate([s0[None], s[:-1]], axis=0)
        u = dyc[:, :, None] * cc[:, None, :]
        u = u.at[-1].add(g_in)
        a_next = jnp.concatenate([a[1:], jnp.ones_like(a[:1])], axis=0)
        _, g = jax.lax.associative_scan(_combine, (a_next, u), axis=0,
                                        reverse=True)
        gsa = g * s_prev * a
        g_b = jnp.einsum("lcn,ln->lc", g, bc)
        ddt = jnp.sum(gsa * a_mat[None], axis=-1) + g_b * xc
        dx = g_b * dtc + d_vec * dyc
        d_b = jnp.einsum("lcn,lc->ln", g, dtc * xc)
        d_c = jnp.einsum("lcn,lc->ln", s, dyc)
        d_a = d_a + jnp.einsum("lcn,lc->cn", gsa, dtc)
        return (a[0] * g[0], d_a), (dx, ddt, d_b, d_c)
    zero = jnp.zeros(a_mat.shape, jnp.float32)
    (_, d_a), (dx, ddt, d_b, d_c) = jax.lax.scan(
        chunk, (zero, zero), (x, dt, b_mat, c_mat, entries, dy), reverse=True)
    return dx, ddt, d_a, d_b, d_c


# ---------------------------------------------------------------------- #
# Pallas kernels
# ---------------------------------------------------------------------- #
def _lanes(tile, width):
    """A [N, 128] lane tile repeated to [N, width]."""
    return tile if width == _LANES else jnp.concatenate(
        [tile] * (width // _LANES), axis=1)


def _fold(x):
    """[N, width] -> [N, 128]: the sum of its lane tiles."""
    out = x[:, :_LANES]
    for at in range(_LANES, x.shape[1], _LANES):
        out = out + x[:, at:at + _LANES]
    return out


def _rows(ref, i):
    """The aligned [_ROWS, width] tile i of a (1, CHUNK, width) block."""
    return ref[0, pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS), :]


def _sscan_fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                      y_ref, entry_ref, state_scr, *, chunk):
    ci, blk = pl.program_id(1), pl.program_id(2)
    width = x_ref.shape[2]

    @pl.when(ci == 0)
    def _():
        state_scr[blk] = jnp.zeros(state_scr.shape[1:], jnp.float32)

    entry_ref[0, 0] = state_scr[blk]
    a_mat, d_vec = a_ref[...], d_ref[...]

    def tile(i, s):
        x8, dt8 = _rows(x_ref, i), _rows(dt_ref, i)
        ys = []
        for r in range(_ROWS):
            x, dt = x8[r:r + 1], dt8[r:r + 1]
            t = i * _ROWS + r
            s = jnp.exp(dt * a_mat) * s + (dt * x) * _lanes(b_ref[0, t],
                                                             width)
            ys.append(jnp.sum(s * _lanes(c_ref[0, t], width), axis=0,
                              keepdims=True) + d_vec * x)
        y_ref[0, pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS), :] = (
            jnp.concatenate(ys, axis=0))
        return s

    state_scr[blk] = jax.lax.fori_loop(0, chunk // _ROWS, tile,
                                       state_scr[blk])


def _sscan_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, entry_ref,
                      dy_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                      g_scr, da_scr, hist_scr, *, chunk):
    bi, step, blk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    width = x_ref.shape[2]

    @pl.when(step == 0)     # the row's last chunk: no cotangent from beyond
    def _():
        g_scr[blk] = jnp.zeros(g_scr.shape[1:], jnp.float32)

    @pl.when((step == 0) & (bi == 0))
    def _():
        da_scr[blk] = jnp.zeros(da_scr.shape[1:], jnp.float32)

    @pl.when(blk == 0)      # dB, dC: summed over the channel blocks
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    a_mat, d_vec = a_ref[...], d_ref[...]

    # the chunk's states again, from its saved entry: hist[t] = s_{t-1}
    def rebuild(i, s):
        x8, dt8 = _rows(x_ref, i), _rows(dt_ref, i)
        for r in range(_ROWS):
            t = i * _ROWS + r
            hist_scr[t] = s
            x, dt = x8[r:r + 1], dt8[r:r + 1]
            s = jnp.exp(dt * a_mat) * s + (dt * x) * _lanes(b_ref[0, t],
                                                             width)
        return s

    jax.lax.fori_loop(0, chunk // _ROWS, rebuild, entry_ref[0, 0])

    def tile(k, carry):
        g_in, d_a = carry
        i = chunk // _ROWS - 1 - k
        x8, dt8, dy8 = _rows(x_ref, i), _rows(dt_ref, i), _rows(dy_ref, i)
        dxs, ddts = [None] * _ROWS, [None] * _ROWS
        for r in reversed(range(_ROWS)):
            t = i * _ROWS + r
            x, dt, dy = x8[r:r + 1], dt8[r:r + 1], dy8[r:r + 1]
            b_t = _lanes(b_ref[0, t], width)
            a = jnp.exp(dt * a_mat)
            s_prev = hist_scr[t]
            g = dy * _lanes(c_ref[0, t], width) + g_in
            gsa = g * s_prev * a
            g_b = jnp.sum(g * b_t, axis=0, keepdims=True)
            ddts[r] = jnp.sum(gsa * a_mat, axis=0, keepdims=True) + g_b * x
            dxs[r] = g_b * dt + d_vec * dy
            d_a = d_a + gsa * dt
            db_ref[0, t] += _fold(g * (dt * x))
            dc_ref[0, t] += _fold(dy * (a * s_prev + (dt * x) * b_t))
            g_in = a * g
        at = pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS)
        dx_ref[0, at, :] = jnp.concatenate(dxs, axis=0)
        ddt_ref[0, at, :] = jnp.concatenate(ddts, axis=0)
        return g_in, d_a

    g_scr[blk], da_scr[blk] = jax.lax.fori_loop(
        0, chunk // _ROWS, tile, (g_scr[blk], da_scr[blk]))
    # the running total; the grid's last visit of this block leaves it
    da_ref[...] = da_scr[blk]


def _spread(m):
    """[batch, S, N] -> [batch, S, N, 128]: each value across a lane
    tile, so that a position's N values load as a column."""
    return jnp.broadcast_to(m[..., None], m.shape + (_LANES,))


def _block(channels):
    if channels % _LANES:
        raise ValueError(f"selective scan kernels: {channels} channels are "
                         "no multiple of 128 lanes")
    block = min(SSCAN_BLOCK, channels)
    while channels % block:
        block -= _LANES
    return block


def _specs(chunk, block, states, reverse_of=None):
    """BlockSpecs of the kernels' operands on the grid (batch, chunk,
    channel block); `reverse_of` = n_chunks walks the chunks backwards."""
    def c(i):
        return i if reverse_of is None else reverse_of - 1 - i
    seq = pl.BlockSpec((1, chunk, block), lambda b, i, j: (b, c(i), j))
    mat = pl.BlockSpec((states, block), lambda b, i, j: (0, j))
    col = pl.BlockSpec((1, chunk, states, _LANES),
                       lambda b, i, j: (b, c(i), 0, 0))
    vec = pl.BlockSpec((1, block), lambda b, i, j: (0, j))
    entry = pl.BlockSpec((1, 1, states, block),
                         lambda b, i, j: (b, c(i), 0, j))
    return seq, mat, col, vec, entry


def _compiler_params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)}


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _pallas_fwd(x, dt, a_t, b_mat, c_mat, d_vec, *, chunk, interpret):
    """x, dt [batch, S, ch] (S a multiple of chunk), a_t [N, ch], b_mat,
    c_mat [batch, S, N], d_vec [1, ch] -> (y, entries [batch, S / chunk,
    N, ch])."""
    batch, seq, channels = x.shape
    states, block = a_t.shape[0], _block(channels)
    n_chunks, n_blocks = seq // chunk, channels // block
    seq_s, mat, col, vec, entry = _specs(chunk, block, states)
    return pl.pallas_call(
        functools.partial(_sscan_fwd_kernel, chunk=chunk),
        grid=(batch, n_chunks, n_blocks),
        in_specs=[seq_s, seq_s, mat, col, col, vec],
        out_specs=[seq_s, entry],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((batch, n_chunks, states, channels),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n_blocks, states, block), jnp.float32)],
        interpret=interpret, name="sscan_fwd",
        **_compiler_params(interpret),
    )(x, dt, a_t, _spread(b_mat), _spread(c_mat), d_vec)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _pallas_bwd(x, dt, a_t, b_mat, c_mat, d_vec, entries, dy, *, chunk,
                interpret):
    """(dx, ddt, dA^T [N, ch], dB, dC) of _pallas_fwd's operands."""
    batch, seq, channels = x.shape
    states, block = a_t.shape[0], _block(channels)
    n_chunks, n_blocks = seq // chunk, channels // block
    seq_s, mat, col, vec, entry = _specs(chunk, block, states, n_chunks)
    lanes = jax.ShapeDtypeStruct((batch, seq, states, _LANES), jnp.float32)
    dx, ddt, d_a, d_b, d_c = pl.pallas_call(
        functools.partial(_sscan_bwd_kernel, chunk=chunk),
        grid=(batch, n_chunks, n_blocks),
        in_specs=[seq_s, seq_s, mat, col, col, vec, entry, seq_s],
        out_specs=[seq_s, seq_s, mat, col, col],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(a_t.shape, jnp.float32),
                   lanes, lanes],
        scratch_shapes=[
            pltpu.VMEM((n_blocks, states, block), jnp.float32),  # g
            pltpu.VMEM((n_blocks, states, block), jnp.float32),  # dA
            pltpu.VMEM((chunk, states, block), jnp.float32)],    # states
        interpret=interpret, name="sscan_bwd",
        **_compiler_params(interpret),
    )(x, dt, a_t, _spread(b_mat), _spread(c_mat), d_vec, entries, dy)
    return dx, ddt, d_a, d_b.sum(-1), d_c.sum(-1)


# ---------------------------------------------------------------------- #
# the op
# ---------------------------------------------------------------------- #
def _use_pallas(channels):
    return ((pallas_available() or pallas_interpret())
            and channels % _LANES == 0)


def _padded(t, chunk):
    """[batch, S, w] zero-padded to a whole number of chunks: a padded
    position has dt 0, so it leaves the state as it is."""
    pad = -t.shape[1] % chunk
    return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t


def _chunked(t, chunk):
    """[batch, S, w] -> [batch, n_chunks, chunk, w], zero-padded."""
    t = _padded(t, chunk)
    return t.reshape(t.shape[0], -1, chunk, t.shape[2])


def _f32(*ts):
    return tuple(t.astype(jnp.float32) for t in ts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a_mat, b_mat, c_mat, d_vec, chunk):
    return _scan_fwd(x, dt, a_mat, b_mat, c_mat, d_vec, chunk)[0]


def _scan_fwd(x, dt, a_mat, b_mat, c_mat, d_vec, chunk):
    seq = x.shape[1]
    xf, dtf, af, bf, cf, df = _f32(x, dt, a_mat, b_mat, c_mat, d_vec)
    if _use_pallas(x.shape[2]):
        y, entries = _pallas_fwd(
            *(_padded(t, chunk) for t in (xf, dtf)), af.T,
            *(_padded(t, chunk) for t in (bf, cf)), df[None], chunk=chunk,
            interpret=pallas_interpret())
    else:
        y, entries = jax.vmap(_xla_fwd, in_axes=(0, 0, None, 0, 0, None))(
            *(_chunked(t, chunk) for t in (xf, dtf)), af,
            *(_chunked(t, chunk) for t in (bf, cf)), df)
        y = y.reshape(y.shape[0], -1, y.shape[3])
    return y[:, :seq].astype(x.dtype), (x, dt, a_mat, b_mat, c_mat, d_vec,
                                        entries)


def _scan_bwd(chunk, res, dy):
    x, dt, a_mat, b_mat, c_mat, d_vec, entries = res
    seq = x.shape[1]
    xf, dtf, af, bf, cf, df, dyf = _f32(x, dt, a_mat, b_mat, c_mat, d_vec,
                                        dy)
    if _use_pallas(x.shape[2]):
        dx, ddt, d_a, d_b, d_c = _pallas_bwd(
            *(_padded(t, chunk) for t in (xf, dtf)), af.T,
            *(_padded(t, chunk) for t in (bf, cf)), df[None], entries,
            _padded(dyf, chunk), chunk=chunk, interpret=pallas_interpret())
        d_a = d_a.T
    else:
        dx, ddt, d_a, d_b, d_c = jax.vmap(
            _xla_bwd, in_axes=(0, 0, None, 0, 0, None, 0, 0))(
            *(_chunked(t, chunk) for t in (xf, dtf)), af,
            *(_chunked(t, chunk) for t in (bf, cf)), df, entries,
            _chunked(dyf, chunk))
        d_a = d_a.sum(0)
        dx, ddt, d_b, d_c = (t.reshape(t.shape[0], -1, t.shape[3])
                             for t in (dx, ddt, d_b, d_c))
    d_d = jnp.sum(dyf * xf, axis=(0, 1))
    return (dx[:, :seq].astype(x.dtype), ddt[:, :seq].astype(dt.dtype),
            d_a.astype(a_mat.dtype), d_b[:, :seq].astype(b_mat.dtype),
            d_c[:, :seq].astype(c_mat.dtype), d_d.astype(d_vec.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, a_mat, b_mat, c_mat, d_vec, chunk=CHUNK):
    """y [batch, S, channels] of the recurrence in the module's text, in
    x's dtype; differentiable in all six operands.  Under a device mesh
    the kernels run in a region manual over every axis, the batch split
    over the data axes."""
    if not _use_pallas(x.shape[2]):
        return _scan(x, dt, a_mat, b_mat, c_mat, d_vec, chunk)
    rows = {0: BATCH_AXES}

    def local(_, x, dt, a_mat, b_mat, c_mat, d_vec):
        return _scan(x, dt, a_mat, b_mat, c_mat, d_vec, chunk)

    return manual_kernel_region(
        local, (x, dt, a_mat, b_mat, c_mat, d_vec),
        (rows, rows, None, rows, rows, None), rows)
