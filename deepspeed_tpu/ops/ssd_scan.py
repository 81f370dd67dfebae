"""The scan of a Mamba-2 mixer in its chunked matrix form (Dao & Gu 2024,
"Transformers are SSMs", state-space duality), forward and hand-written
backward under one custom_vjp.  ops/selective_scan.py is the Mamba-1
scan (a decay for every channel and state, walked position by position
on the vector unit); this is the scan whose decay is ONE scalar a head
and whose B and C are shared by the heads of a group, which is what lets
it be written as matrix products.

Per batch row and head h (``P`` channels a head, ``N`` states), over
positions t, with ``S_0 = 0``:

    S_t = exp(dt_t[h] A[h]) S_{t-1} + dt_t[h] x_t[h] (x) B_t      [P, N]
    y_t[h] = S_t C_t + D[h] x_t[h]

x: [batch, S, H, P]; dt: [batch, S, H] (already positive: softplus of
the projected step); A: [H], negative; B, C: [batch, S, G, N]; D: [H].

The sequence is cut into chunks of ``chunk`` positions (Q).  With
``a_t = dt_t A``, ``s_i`` the running sum of ``a`` from its chunk's
first position to i, ``xb_t = dt_t x_t`` and ``G_c`` the state at
chunk c's entry:

    y_i = sum_{j <= i} exp(s_i - s_j) (C_i . B_j) xb_j            (diag)
          + exp(s_i) G_c C_i + D x_i                               (off)
    G_{c+1} = exp(s_Q) G_c + sum_j exp(s_Q - s_j) xb_j (x) B_j     (state)

Every exponent is <= 0, so nothing overflows whatever dt is.  ``C B^T``
is one [Q, Q] product a chunk for all the heads of a GROUP: head h of H
reads group ``h // (H / G)`` of the G that B and C come in (G = 1: one
product a chunk for every head), and ``dB`` / ``dC`` are sums over a
group's heads.  The forward pass saves
the chunk-entry states ([S / Q, H, P, N] float32 a row: 33.6 MB at 4,096
x 64 x 64 x 128, where every position's state would be 8.6 GB); the
backward pass walks the chunks in reverse from them.  No
array of size [S, H, P, N] and no [H, S / Q, Q, Q] decay matrix reaches
HBM on the kernel path.  With dy the cotangent of y, ``M_ij = exp(s_i -
s_j) (C_i . B_j)`` for i >= j, ``dG_c`` the cotangent of the state that
LEAVES chunk c (0 after the last) and ``e_i = exp(s_i)``, ``f_j =
exp(s_Q - s_j)``:

    dxb_j = sum_i M_ij dy_i + f_j dG_c B_j
    d(C B^T)_ij = sum_h exp(s_i - s_j) dy_i . xb_j     (i >= j; h: the group's)
    dC_i = sum_j d(CB^T)_ij B_j + sum_h e_i G_c^T dy_i
    dB_j = sum_i d(CB^T)_ij C_i + sum_h f_j dG_c^T xb_j
    dG_{c-1} = exp(s_Q) dG_c + sum_i e_i dy_i (x) C_i
    da_k = sum_{i >= k > j} exp(s_i - s_j) (C_i . B_j) dy_i . xb_j
           + sum_{i >= k} e_i dy_i . (G_c C_i)
           + sum_{j < k} f_j xb_j . (dG_c B_j)  +  exp(s_Q) <dG_c, G_c>

(a_k is in s_i - s_j exactly when i >= k > j, in e_i when i >= k, in f_j
when j < k, and in exp(s_Q) always.  Taken as ds_i first and summed from
the chunk's end, the first term is a difference of a row sum and a
column sum that all but cancel; as written no term cancels.)  Then
``ddt = x . dxb + A da``, ``dA = sum da dt``, ``dx = dt dxb + D dy``,
``dD = sum dy . x``.  The products, the sums that need a chunk's
[Q, Q] matrices or its states and every sum over a head's channels are
the kernels': ``ssd_bwd`` holds x, dy, dt and the float32 dxb of a
chunk, G_c and dG_c, so it writes dx itself (rounded once), four rows a
head and position (da's first term with ``exp(s_Q) <dG_c, G_c>`` added,
the summands of its second and third, ``x . dxb``) and ``x . dy`` a
channel summed over the chunk's positions; no dG_c and no float32 array
of x's size reaches HBM.  What follows the kernel is [S, H] work (the
running sums of the second and third term, ddt, dA) and dD's sum over
chunks and channels, shared by both forms; the XLA form makes the
channel sums as elementwise passes over [S, H, P] and ``<dG_c, G_c>``
from the cotangents its scan returns.

Two forms, one switch (the backend, as for the other ops: dispatch.py):

- Pallas kernels ``ssd_fwd`` / ``ssd_bwd`` on the TPU (and through the
  interpreter where dispatch.pallas_interpret() says so).  x lies flat
  ([S, H P], channels on the lanes); a grid cell is one chunk of one
  block of SSD_HEADS heads, the chunks walked in order (in reverse,
  backward) with the blocks' states ([H P, N] float32) carried in VMEM
  scratch.  A head block lies inside one group (``kernels_take``): it
  reads its group's N columns of B and C, the group's first block builds
  ``C B^T`` for the blocks after it and its last one turns the summed
  ``d(C B^T)`` into the group's dB and dC (G = H / SSD_HEADS: a block IS
  a group and does all three).  Heads of 64 lie two a lane tile: a head's [Q, Q] matrix
  multiplies the whole tile and the lanes of its own head are selected,
  which on a 128-wide MXU costs what the half tile would.  The products
  run on the MXU with bf16 operands and float32 accumulation: x, B, C and
  dy are bf16 as the model hands them; ``xb``, ``M``, the decayed
  operands and the carried states are ROUNDED to bf16 as operands, each
  once, from float32 values.  The running sums s, every exponential,
  the states themselves (scratch, saved entries) and every accumulation
  are float32.  That is the precision of the flash kernels (bf16
  probabilities and values into float32 sums), and the engine's parity
  limits (perf/families/granite_hybrid.py) are read with these kernels:
  a rounding of one operand by 2^-9 moves a sum of hundreds of terms by
  less than the bf16 of the projections around the scan does.
- plain XLA elsewhere, and as the kernels' twin in tests: a lax.scan
  over the chunks, the same products as einsums in float32, the same
  saved entries and the same backward formulas, mapped over the groups
  where there are several.

A sequence that is no multiple of the chunk is padded with positions of
dt = 0, which leave the state as it is.  Heads of another size than 64,
a group of heads that is no multiple of SSD_HEADS (a head block would
straddle two groups) or a state that is no multiple of 128 take the XLA
form; a head count that is no multiple of the groups is refused at trace
time.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import (BATCH_AXES, manual_kernel_region, pallas_available,
                       pallas_interpret)

# Positions a chunk where the caller gives none (Mamba-2's own default).
CHUNK = 256
# Heads a grid cell: 8 heads of 64 are 4 lane tiles of x.
SSD_HEADS = 8
_LANES = 128
_HEAD = 64


def entry_state_bytes(batch, seq, heads, head_dim, states, chunk=CHUNK):
    """Bytes of the chunk-entry states one call saves for its backward."""
    return batch * -(-seq // chunk) * heads * head_dim * states * 4


def kernels_take(heads, head_dim, states, chunk, groups=1):
    """Whether the Pallas kernels are written for these shapes: heads of
    64 whose every block of SSD_HEADS lies inside one of the ``groups``
    of B and C, ``states`` a group and ``chunk`` whole lane tiles."""
    return (head_dim == _HEAD and heads % groups == 0
            and (heads // groups) % SSD_HEADS == 0
            and states % _LANES == 0 and chunk % _LANES == 0)


def uses_kernels(heads, head_dim, states, chunk, groups=1):
    """Whether a call of these shapes runs the Pallas kernels here."""
    return ((pallas_available() or pallas_interpret())
            and kernels_take(heads, head_dim, states, chunk, groups))


# ---------------------------------------------------------------------- #
# plain XLA, chunk by chunk
# ---------------------------------------------------------------------- #
def _decay(s):
    """s [Q, H] -> L [Q, Q, H]: exp(s_i - s_j) for i >= j, else 0."""
    q = s.shape[0]
    keep = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    diff = jnp.minimum(s[:, None, :] - s[None, :, :], 0.0)
    return jnp.where(keep[:, :, None], jnp.exp(diff), 0.0)


def _xla_fwd(x, dt, s, b_mat, c_mat):
    """One batch row, [n_chunks, Q, ...] operands (x [.., H, P], dt and
    s [.., H], b_mat and c_mat [.., N]): (y without the D term [n_chunks,
    Q, H, P], entry states [n_chunks, H, P, N])."""
    def chunk(g, xs):
        xc, dtc, sc, bc, cc = xs
        cb = jnp.einsum("in,jn->ij", cc, bc)
        m = _decay(sc) * cb[:, :, None]
        xb = dtc[:, :, None] * xc
        y = jnp.einsum("ijh,jhp->ihp", m, xb)
        y += jnp.exp(sc)[:, :, None] * jnp.einsum("in,hpn->ihp", cc, g)
        left = jnp.exp(sc[-1] - sc)[:, :, None] * xb
        g_next = (jnp.exp(sc[-1])[:, None, None] * g
                  + jnp.einsum("jhp,jn->hpn", left, bc))
        return g_next, (y, g)
    g0 = jnp.zeros(x.shape[2:] + b_mat.shape[2:], jnp.float32)
    _, (y, entries) = jax.lax.scan(chunk, g0, (x, dt, s, b_mat, c_mat))
    return y, entries


def _xla_bwd(x, dt, s, b_mat, c_mat, entries, dy):
    """One batch row: (dxb [n_chunks, Q, H, P], dB, dC [n_chunks, Q, N],
    the cotangent of the state leaving each chunk [n_chunks, H, P, N],
    and the three per-position parts of da [n_chunks, Q, H] each: the
    first term whole, the summands of the second and of the third) by
    the module's formulas."""
    def chunk(dg, xs):
        xc, dtc, sc, bc, cc, g, dyc = xs
        q = xc.shape[0]
        decay = _decay(sc)
        cb = jnp.einsum("in,jn->ij", cc, bc)
        xb = dtc[:, :, None] * xc
        e = jnp.exp(sc)[:, :, None]
        f = jnp.exp(sc[-1] - sc)[:, :, None]
        m = decay * cb[:, :, None]
        from_state = f * jnp.einsum("jn,hpn->jhp", bc, dg)
        dxb = jnp.einsum("ijh,ihp->jhp", m, dyc) + from_state
        dm = jnp.einsum("ihp,jhp->ijh", dyc, xb)
        dcb = jnp.sum(decay * dm, axis=-1)
        edy = e * dyc
        d_c = (jnp.einsum("ij,jn->in", dcb, bc)
               + jnp.einsum("ihp,hpn->in", edy, g))
        d_b = (jnp.einsum("ij,in->jn", dcb, cc)
               + jnp.einsum("jhp,hpn->jn", f * xb, dg))
        dg_prev = (jnp.exp(sc[-1])[:, None, None] * dg
                   + jnp.einsum("ihp,in->hpn", edy, cc))
        # sum over i >= k > j of W_ij: down the rows from the end, then
        # over the columns before k
        below = jnp.cumsum((m * dm)[::-1], axis=0)[::-1]       # [k, j, h]
        before = jnp.arange(q)[None, :] < jnp.arange(q)[:, None]
        first = jnp.sum(jnp.where(before[:, :, None], below, 0.0), axis=1)
        second = jnp.sum(edy * jnp.einsum("in,hpn->ihp", cc, g), axis=-1)
        third = jnp.sum(xb * from_state, axis=-1)
        return dg_prev, (dxb, d_b, d_c, dg, first, second, third)
    zero = jnp.zeros(entries.shape[1:], jnp.float32)
    _, out = jax.lax.scan(chunk, zero,
                          (x, dt, s, b_mat, c_mat, entries, dy), reverse=True)
    return out


def _heads_split(t, groups, axis=2):
    """[.., H, ..] -> [.., G, H / G, ..] at ``axis``."""
    return t.reshape(*t.shape[:axis], groups, -1, *t.shape[axis + 1:])


def _heads_joined(t, axis=2):
    """[.., G, H / G, ..] -> [.., H, ..] at ``axis``."""
    return t.reshape(*t.shape[:axis], -1, *t.shape[axis + 2:])


def _xla_fwd_groups(x, dt, s, b_mat, c_mat):
    """``_xla_fwd`` for a batch row whose b_mat and c_mat come in groups,
    [n_chunks, Q, G, N]: the one-group form mapped over the G groups of
    H / G heads each.  One group: that form itself, the program of
    before."""
    groups = b_mat.shape[2]
    if groups == 1:
        return _xla_fwd(x, dt, s, b_mat[:, :, 0], c_mat[:, :, 0])
    y, entries = jax.vmap(_xla_fwd, in_axes=2, out_axes=(2, 1))(
        *(_heads_split(t, groups) for t in (x, dt, s)), b_mat, c_mat)
    return _heads_joined(y), _heads_joined(entries, 1)


def _xla_bwd_groups(x, dt, s, b_mat, c_mat, entries, dy):
    """``_xla_bwd`` for a row of G groups (as ``_xla_fwd_groups``); dB and
    dC [n_chunks, Q, G, N]."""
    groups = b_mat.shape[2]
    if groups == 1:
        dxb, d_b, d_c, *rest = _xla_bwd(x, dt, s, b_mat[:, :, 0],
                                        c_mat[:, :, 0], entries, dy)
        return (dxb, d_b[:, :, None], d_c[:, :, None], *rest)
    dxb, d_b, d_c, dg, first, second, third = jax.vmap(
        _xla_bwd, in_axes=(2, 2, 2, 2, 2, 1, 2),
        out_axes=(2, 2, 2, 1, 2, 2, 2))(
        *(_heads_split(t, groups) for t in (x, dt, s)), b_mat, c_mat,
        _heads_split(entries, groups, 1), _heads_split(dy, groups))
    return (_heads_joined(dxb), d_b, d_c, _heads_joined(dg, 1),
            *(_heads_joined(t) for t in (first, second, third)))


# ---------------------------------------------------------------------- #
# Pallas kernels
# ---------------------------------------------------------------------- #
def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):      # a [m, k] . b [k, n]
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):      # a [m, k] . b [n, k]^T
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):      # a [k, m]^T . b [k, n]
    return _dot(a, b, ((0,), (0,)))


def _by_head(lane, values):
    """[Q, 128] whose lanes of the tile's k-th head hold ``values[k]``
    ([Q, 1] or [Q, 128] each)."""
    out = values[0]
    for k in range(1, len(values)):
        out = jnp.where(lane >= k * _HEAD, values[k], out)
    return jnp.broadcast_to(out, lane.shape)


def _across(s_end, width):
    """exp of a [1, 1] value as a [1, width] row: spread over the lanes
    before the exponential, so that the rows it then scales see a
    broadcast over sublanes alone (Mosaic lowers none over both)."""
    return jnp.exp(jnp.broadcast_to(s_end, (1, width)))


def _in_group(grp, blocks, per_group):
    """Where head block ``grp`` of ``blocks`` lies among the ``per_group``
    blocks that read its group's B and C (one group: ``grp`` itself)."""
    return grp if per_group == blocks else grp % per_group


def _ssd_fwd_kernel(x_ref, col_ref, row_ref, b_ref, c_ref, d_ref,
                    y_ref, entry_ref, g_scr, cb_scr, *, heads, blocks,
                    per_group):
    ci, grp = pl.program_id(1), pl.program_id(2)
    q = x_ref.shape[1]
    per = _LANES // _HEAD
    bf16, f32 = jnp.bfloat16, jnp.float32

    @pl.when(ci == 0)
    def _():
        g_scr[grp] = jnp.zeros(g_scr.shape[1:], f32)

    b_mat, c_mat = b_ref[0], c_ref[0]                    # [Q, N] bf16

    # C B^T: once a chunk, for every head of the group
    @pl.when(_in_group(grp, blocks, per_group) == 0)
    def _():
        cb_scr[...] = _nt(c_mat, b_mat)

    g = g_scr[grp]                                       # [heads P, N]
    entry_ref[0, 0] = g
    cols, rows = col_ref[0, 0], row_ref[0, 0]   # [Q, 2 heads], [heads, Q]
    cb = cb_scr[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
    keep = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))

    for tile in range(heads // per):
        at = slice(tile * _LANES, (tile + 1) * _LANES)
        hs = [tile * per + k for k in range(per)]
        x = x_ref[0, :, at].astype(f32)                  # [Q, 128]
        dt = _by_head(lane, [cols[:, h:h + 1] for h in hs])
        s_col = [cols[:, heads + h:heads + h + 1] for h in hs]
        xb = x * dt
        xb16 = xb.astype(bf16)
        diag = []
        for k, h in enumerate(hs):
            decay = jnp.exp(jnp.minimum(s_col[k] - rows[h:h + 1, :], 0.0))
            m = jnp.where(keep, decay * cb, 0.0).astype(bf16)
            diag.append(_nn(m, xb16))                    # [Q, 128]
        s_end = [rows[h:h + 1, q - 1:q] for h in hs]     # [1, 1] each
        e = _by_head(lane, [jnp.exp(sc) for sc in s_col])
        f = _by_head(lane, [jnp.exp(se - sc)
                               for se, sc in zip(s_end, s_col)])
        y = (_by_head(lane, diag) + e * _nt(c_mat, g[at].astype(bf16))
             + d_ref[:, at] * x)
        y_ref[0, :, at] = y.astype(y_ref.dtype)
        state = _tn((f * xb).astype(bf16), b_mat)        # [128, N]
        for k in range(per):
            lo = tile * _LANES + k * _HEAD
            g_scr[grp, pl.ds(lo, _HEAD), :] = (
                _across(s_end[k], g.shape[1]) * g[lo:lo + _HEAD]
                + state[k * _HEAD:(k + 1) * _HEAD])


def _ssd_bwd_kernel(x_ref, col_ref, row_ref, b_ref, c_ref, entry_ref, dy_ref,
                    d_ref, dx_ref, db_ref, dc_ref, sums_ref, total_ref,
                    dg_scr, cbt_scr, dcbt_scr, *, heads, blocks, per_group):
    step, grp = pl.program_id(1), pl.program_id(2)
    in_group = _in_group(grp, blocks, per_group)
    q = x_ref.shape[1]
    per = _LANES // _HEAD
    bf16, f32 = jnp.bfloat16, jnp.float32

    @pl.when(step == 0)     # the row's last chunk: no cotangent from beyond
    def _():
        dg_scr[grp] = jnp.zeros(dg_scr.shape[1:], f32)

    b_mat, c_mat = b_ref[0], c_ref[0]                    # [Q, N] bf16

    @pl.when(in_group == 0)  # the transposes of C B^T and of its cotangent
    def _():
        cbt_scr[...] = _nt(b_mat, c_mat)                 # [j, i]
        dcbt_scr[...] = jnp.zeros(dcbt_scr.shape, f32)
        db_ref[...] = jnp.zeros(db_ref.shape, f32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, f32)

    g = entry_ref[0, 0]                                  # [heads P, N]
    dg = dg_scr[grp]
    cols, rows = col_ref[0, 0], row_ref[0, 0]
    cbt = cbt_scr[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    keep = col_i >= row_i       # [j, i]: position i reads position j
    before = row_i < col_i      # [j, k]: j before k
    from_k = (row_i >= col_i).astype(bf16)               # [i, k]: i >= k

    d_c = jnp.zeros((q, b_mat.shape[1]), f32)
    d_b = jnp.zeros((q, b_mat.shape[1]), f32)
    for tile in range(heads // per):
        at = slice(tile * _LANES, (tile + 1) * _LANES)
        hs = [tile * per + k for k in range(per)]
        own = [(lane >= k * _HEAD) & (lane < (k + 1) * _HEAD)
               for k in range(per)]
        x = x_ref[0, :, at].astype(f32)
        dy16 = dy_ref[0, :, at]
        dy = dy16.astype(f32)
        dt = _by_head(lane, [cols[:, h:h + 1] for h in hs])
        s_col = [cols[:, heads + h:heads + h + 1] for h in hs]
        xb = x * dt
        s_end = [rows[h:h + 1, q - 1:q] for h in hs]     # [1, 1] each
        diag = []
        for k, h in enumerate(hs):
            # decay^T [j, i] = exp(s_i - s_j) for i >= j
            decay = jnp.where(keep, jnp.exp(jnp.minimum(
                rows[h:h + 1, :] - s_col[k], 0.0)), 0.0)
            mt = decay * cbt
            diag.append(_nn(mt.astype(bf16), dy16))
            dmt = _nt(jnp.where(own[k], xb, 0.0).astype(bf16), dy16)
            dcbt_scr[...] += decay * dmt
            # da's first term: sum over i >= k of W^T[j, i], then over
            # the j before k; its last, exp(s_Q) <dG_c, G_c>, is the same
            # at every position of the chunk
            reach = _nn((mt * dmt).astype(bf16), from_k)     # [j, k]
            lo = tile * _LANES + k * _HEAD
            inner = jnp.sum(jnp.sum(dg[lo:lo + _HEAD] * g[lo:lo + _HEAD],
                                    axis=0, keepdims=True),
                            axis=1, keepdims=True)           # [1, 1]
            sums_ref[0, 0, h:h + 1, :] = jnp.sum(
                jnp.where(before, reach, 0.0), axis=0, keepdims=True) \
                + _across(s_end[k], q) * inner
        e = _by_head(lane, [jnp.exp(sc) for sc in s_col])
        f = _by_head(lane, [jnp.exp(se - sc)
                               for se, sc in zip(s_end, s_col)])
        g16, dg16 = g[at].astype(bf16), dg[at].astype(bf16)
        from_state = f * _nt(b_mat, dg16)
        dxb = _by_head(lane, diag) + from_state
        dx_ref[0, :, at] = (dt * dxb + d_ref[:, at] * dy).astype(dx_ref.dtype)
        edy = e * dy
        # the sums over a head's channels a position, after da's first
        # term: the summands of its second and third, then ddt's x . dxb.
        # Transposed, a head's channels are 64 rows to add and a sum is a
        # row of positions; as a sum over the lanes of a masked tile it
        # cost ten times as much (PERF.md section 6, PR 61)
        for i, t in enumerate((edy * _nt(c_mat, g16), xb * from_state,
                               dxb * x)):
            t = t.T                                      # [128, Q]
            for k, h in enumerate(hs):
                row = (i + 1) * heads + h
                sums_ref[0, 0, row:row + 1, :] = jnp.sum(
                    t[k * _HEAD:(k + 1) * _HEAD], axis=0, keepdims=True)
        # dD's x . dy a channel, over the chunk's positions
        total_ref[0, 0, :, at] = jnp.sum(dy * x, axis=0, keepdims=True)
        edy = edy.astype(bf16)
        d_c += _nn(edy, g16)
        d_b += _nn((f * xb).astype(bf16), dg16)
        own_state = _tn(edy, c_mat)                      # [128, N]
        for k in range(per):
            lo = tile * _LANES + k * _HEAD
            dg_scr[grp, pl.ds(lo, _HEAD), :] = (
                _across(s_end[k], dg.shape[1]) * dg[lo:lo + _HEAD]
                + own_state[k * _HEAD:(k + 1) * _HEAD])
    dc_ref[0] += d_c
    db_ref[0] += d_b

    # every head of the group has put its share of d(C B^T) in
    @pl.when(in_group == per_group - 1)
    def _():
        dcbt = dcbt_scr[...].astype(bf16)
        db_ref[0] += _nn(dcbt, c_mat)
        dc_ref[0] += _tn(dcbt, b_mat)


def _specs(chunk, width, heads, states, blocks, per_group, reverse_of=None):
    """BlockSpecs of the kernels' operands on the grid (batch, chunk,
    head block); ``reverse_of`` = n_chunks walks the chunks backwards.
    B and C lie flat, [batch, S, G N]: head block j of ``blocks`` reads
    the ``states`` columns of group ``j // per_group`` (one group, every
    block in it: the first columns)."""
    def c(i):
        return i if reverse_of is None else reverse_of - 1 - i

    def g(j):
        return 0 if per_group == blocks else j // per_group
    seq = pl.BlockSpec((1, chunk, width), lambda b, i, j: (b, c(i), j))
    cols = pl.BlockSpec((1, 1, chunk, 2 * heads),
                        lambda b, i, j: (b, j, c(i), 0))
    rows = pl.BlockSpec((1, 1, heads, chunk),
                        lambda b, i, j: (b, j, 0, c(i)))
    mat = pl.BlockSpec((1, chunk, states), lambda b, i, j: (b, c(i), g(j)))
    vec = pl.BlockSpec((1, width), lambda b, i, j: (0, j))
    state = pl.BlockSpec((1, 1, width, states),
                         lambda b, i, j: (b, c(i), j, 0))
    return seq, cols, rows, mat, vec, state


def _compiler_params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)}


def _head_blocks(dt, s, heads):
    """dt, s [batch, S, H] -> the kernels' two small operands: columns
    [batch, H / heads, S, 2 heads] (dt, then s) and rows [batch, H /
    heads, heads, S] (s)."""
    batch, seq, _ = dt.shape
    cols = jnp.concatenate([t.reshape(batch, seq, -1, heads)
                            for t in (dt, s)], axis=-1)
    rows = s.reshape(batch, seq, -1, heads)
    return cols.transpose(0, 2, 1, 3), rows.transpose(0, 2, 3, 1)


def _spread(d_vec, dim):
    """D [H] -> [1, H P] float32: a head's D at each of its channels."""
    return jnp.repeat(d_vec.astype(jnp.float32), dim)[None]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _pallas_fwd(x, dt, s, b_mat, c_mat, d_vec, *, chunk, interpret):
    """x [batch, S, H, P] (S a multiple of chunk), dt and s [batch, S, H]
    float32, b_mat and c_mat [batch, S, G, N], d_vec [H] -> (y, entries
    [batch, S / chunk, H, P, N])."""
    batch, seq, num_heads, dim = x.shape
    bc_groups, states = b_mat.shape[2:]
    width, heads = SSD_HEADS * dim, SSD_HEADS
    n_chunks, groups = seq // chunk, num_heads // heads
    per_group = groups // bc_groups     # head blocks a group of B and C
    seq_s, cols_s, rows_s, mat, vec, state = _specs(
        chunk, width, heads, states, groups, per_group)
    cols, rows = _head_blocks(dt, s, heads)
    bf16 = jnp.bfloat16
    y, entries = pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, heads=heads, blocks=groups,
                          per_group=per_group),
        grid=(batch, n_chunks, groups),
        in_specs=[seq_s, cols_s, rows_s, mat, mat, vec],
        out_specs=[seq_s, state],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, num_heads * dim), x.dtype),
            jax.ShapeDtypeStruct((batch, n_chunks, num_heads * dim, states),
                                 jnp.float32)],
        scratch_shapes=[pltpu.VMEM((groups, width, states), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        interpret=interpret, name="ssd_fwd",
        **_compiler_params(interpret),
    )(x.reshape(batch, seq, -1).astype(bf16), cols, rows,
      b_mat.reshape(batch, seq, -1).astype(bf16),
      c_mat.reshape(batch, seq, -1).astype(bf16),
      _spread(d_vec, dim))
    return (y.reshape(x.shape),
            entries.reshape(batch, n_chunks, num_heads, dim, states))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _pallas_bwd(x, dt, s, b_mat, c_mat, entries, dy, d_vec, *, chunk,
                interpret):
    """The backward's products and every sum over a head's channels:
    (dx [batch, S, H, P] bf16, finished: dt dxb + D dy from the float32
    values, rounded once; dB, dC [batch, S, G, N] float32; four [batch,
    S, H] float32 arrays: da's first term with exp(s_Q) <dG_c, G_c> of
    the position's chunk added, the summands of its second and of its
    third, and x . dxb; and dD [H] float32, x . dy summed over the
    positions).  No cotangent of a state leaves the kernel."""
    batch, seq, num_heads, dim = x.shape
    bc_groups, states = b_mat.shape[2:]
    width, heads = SSD_HEADS * dim, SSD_HEADS
    n_chunks, groups = seq // chunk, num_heads // heads
    per_group = groups // bc_groups     # head blocks a group of B and C
    seq_s, cols_s, rows_s, mat, vec, state = _specs(
        chunk, width, heads, states, groups, per_group, n_chunks)
    # four rows a head and position, one row a chunk and channel
    sums_s = pl.BlockSpec((1, 1, 4 * heads, chunk),
                          lambda b, i, j: (b, j, 0, n_chunks - 1 - i))
    total_s = pl.BlockSpec((1, 1, 1, width),
                           lambda b, i, j: (b, n_chunks - 1 - i, 0, j))
    cols, rows = _head_blocks(dt, s, heads)
    bf16 = jnp.bfloat16
    flat = jax.ShapeDtypeStruct((batch, seq, num_heads * dim), bf16)
    narrow = jax.ShapeDtypeStruct((batch, seq, bc_groups * states),
                                  jnp.float32)
    dx, d_b, d_c, sums, total = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, heads=heads, blocks=groups,
                          per_group=per_group),
        grid=(batch, n_chunks, groups),
        in_specs=[seq_s, cols_s, rows_s, mat, mat, state, seq_s, vec],
        out_specs=[seq_s, mat, mat, sums_s, total_s],
        out_shape=[flat, narrow, narrow,
                   jax.ShapeDtypeStruct((batch, groups, 4 * heads, seq),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((batch, n_chunks, 1, num_heads * dim),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((groups, width, states), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        interpret=interpret, name="ssd_bwd",
        **_compiler_params(interpret),
    )(x.reshape(batch, seq, -1).astype(bf16), cols, rows,
      b_mat.reshape(batch, seq, -1).astype(bf16),
      c_mat.reshape(batch, seq, -1).astype(bf16),
      entries.reshape(batch, n_chunks, num_heads * dim, states),
      dy.reshape(batch, seq, -1).astype(bf16), _spread(d_vec, dim))
    # back from the kernels' head blocks to [batch, S, H]
    sums = sums.reshape(batch, groups, 4, heads, seq).transpose(2, 0, 4, 1, 3)
    return (dx.reshape(x.shape), d_b.reshape(b_mat.shape),
            d_c.reshape(b_mat.shape), *sums.reshape(4, *dt.shape),
            jnp.sum(total.reshape(-1, num_heads, dim), axis=(0, 2)))


# ---------------------------------------------------------------------- #
# the op
# ---------------------------------------------------------------------- #
def _padded(t, chunk):
    """[batch, S, ...] zero-padded to a whole number of chunks: a padded
    position has dt 0, so it leaves the state as it is."""
    pad = -t.shape[1] % chunk
    return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) \
        if pad else t


def _chunked(t, chunk):
    """[batch, S, ...] (S whole chunks) -> [batch, n_chunks, chunk, ...]."""
    return t.reshape(t.shape[0], -1, chunk, *t.shape[2:])


def _running(dt, a_vec, chunk):
    """s [batch, S, H] float32: the sum of dt A from each chunk's first
    position on."""
    a = _chunked(dt * a_vec, chunk)
    return jnp.cumsum(a, axis=2).reshape(dt.shape)


def _takes_kernels(x, b_mat, chunk):
    return uses_kernels(x.shape[2], x.shape[3], b_mat.shape[3], chunk,
                        b_mat.shape[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a_vec, b_mat, c_mat, d_vec, chunk):
    return _scan_fwd(x, dt, a_vec, b_mat, c_mat, d_vec, chunk)[0]


def _scan_fwd(x, dt, a_vec, b_mat, c_mat, d_vec, chunk):
    seq, f32 = x.shape[1], jnp.float32
    xp, dtp, bp, cp = (_padded(t, chunk) for t in (
        x, dt.astype(f32), b_mat, c_mat))
    s = _running(dtp, a_vec.astype(f32), chunk)
    if _takes_kernels(x, b_mat, chunk):
        y, entries = _pallas_fwd(xp, dtp, s, bp, cp, d_vec, chunk=chunk,
                                 interpret=pallas_interpret())
    else:
        y, entries = jax.vmap(_xla_fwd_groups)(*(
            _chunked(t.astype(f32), chunk) for t in (xp, dtp, s, bp, cp)))
        y = y.reshape(xp.shape) + d_vec.astype(f32)[:, None] * xp
    return y[:, :seq].astype(x.dtype), (x, dt, a_vec, b_mat, c_mat, d_vec,
                                        entries)


def _scan_bwd(chunk, res, dy):
    x, dt, a_vec, b_mat, c_mat, d_vec, entries = res
    seq, f32 = x.shape[1], jnp.float32
    xp, dtp, bp, cp, dyp = (_padded(t, chunk) for t in (
        x, dt.astype(f32), b_mat, c_mat, dy))
    af = a_vec.astype(f32)
    s = _running(dtp, af, chunk)
    if _takes_kernels(x, b_mat, chunk):
        dx, d_b, d_c, first, second, third, x_dxb, d_d = _pallas_bwd(
            xp, dtp, s, bp, cp, entries, dyp, d_vec, chunk=chunk,
            interpret=pallas_interpret())
    else:
        dxb, d_b, d_c, dg, first, second, third = jax.vmap(_xla_bwd_groups)(
            *(_chunked(t.astype(f32), chunk) for t in (xp, dtp, s, bp, cp)),
            entries, _chunked(dyp.astype(f32), chunk))
        d_b, d_c = (t.reshape(bp.shape) for t in (d_b, d_c))
        # the sums over a head's channels that follow the products
        xf, dyf, dxb = xp.astype(f32), dyp.astype(f32), dxb.reshape(xp.shape)
        dx = dtp[..., None] * dxb + d_vec.astype(f32)[:, None] * dyf
        x_dxb = jnp.sum(dxb * xf, axis=-1)
        d_d = jnp.sum(dyf * xf, axis=(0, 1, 3))
        # da's last term, the same all through a chunk
        through = (jnp.exp(_chunked(s, chunk)[:, :, -1])
                   * jnp.einsum("bchpn,bchpn->bch", dg, entries))
        first = first + through[:, :, None]
    first, second, third = (_chunked(t.reshape(dtp.shape), chunk)
                            for t in (first, second, third))
    # da: the second term from each position to its chunk's end, the
    # third over the positions before it
    d_a = (first + jnp.cumsum(second[:, :, ::-1], axis=2)[:, :, ::-1]
           + jnp.cumsum(third, axis=2) - third).reshape(dtp.shape)
    d_dt = x_dxb + af * d_a
    return (dx[:, :seq].astype(x.dtype), d_dt[:, :seq].astype(dt.dtype),
            jnp.sum(d_a * dtp, axis=(0, 1)).astype(a_vec.dtype),
            d_b[:, :seq].astype(b_mat.dtype),
            d_c[:, :seq].astype(c_mat.dtype),
            d_d.astype(d_vec.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a, b, c, d, chunk=CHUNK):
    """y [batch, S, H, P] of the recurrence in the module's text, in x's
    dtype; differentiable in all six operands.  x [batch, S, H, P], dt
    [batch, S, H] (positive), a [H] (negative), b and c [batch, S, G, N]
    with H a multiple of G (head h reads group ``h // (H / G)``), d [H].
    Under a device mesh the kernels run in a region manual over every
    axis, the batch split over the data axes."""
    if x.ndim != 4 or dt.shape != x.shape[:3] or b.shape != c.shape \
            or b.ndim != 4 or b.shape[:2] != x.shape[:2]:
        raise ValueError(
            f"ssd_scan: x {x.shape} must be [batch, S, H, P], dt "
            f"{dt.shape} [batch, S, H], b {b.shape} and c {c.shape} "
            "[batch, S, G, N]")
    if x.shape[2] % b.shape[2]:
        raise ValueError(
            f"ssd_scan: {x.shape[2]} heads do not divide into the "
            f"{b.shape[2]} groups of B and C")
    if chunk < 1:
        raise ValueError(f"ssd_scan: chunk {chunk}")
    if not _takes_kernels(x, b, chunk):
        return _scan(x, dt, a, b, c, d, chunk)
    rows = {0: BATCH_AXES}

    def local(_, x, dt, a, b, c, d):
        return _scan(x, dt, a, b, c, d, chunk)

    return manual_kernel_region(
        local, (x, dt, a, b, c, d),
        (rows, rows, None, rows, rows, None), rows)
