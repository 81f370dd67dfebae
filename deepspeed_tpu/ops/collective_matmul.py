"""Fused collective-matmul — T3-style per-tile fusion of the qwZ/qgZ
transports with the GEMMs that produce/consume them (arXiv:2401.16677).

The modular low-bandwidth path (runtime/comm/low_bandwidth.py) moves each
layer group's quantized weights as ONE all-gather and each gradient as ONE
all-to-all: the wire is a monolithic event the scheduler must hide under
*other* work.  T3's observation is that the producer/consumer GEMM itself
is the natural cover — track the GEMM's tiles and trigger communication
per tile as tiles complete, so the transport is structurally interleaved
with the matmul instead of scheduled around it.  Two fused pairs:

  forward   the qwZ dequant-all-gather fused into the consuming GEMM's
            PROLOGUE: remote shard tiles (int8/int4 payload + fp32 block
            scales) arrive over a ring, double-buffered against the MXU's
            current tile, with the dequant epilogue folded in per tile
            (``fused_allgather_matmul``);
  backward  the qgZ grad reduce-scatter fused into the producer GEMM's
            EPILOGUE: as each output tile of dW = x^T @ dy completes it is
            blockwise-int8 quantized (error-feedback residual intact) and
            sent straight to its owner — a ring-scheduled all-to-all
            (``fused_matmul_reduce_scatter``).

Two implementation layers:

  1. The GEMM-fused ops above, for callers that hand us the matmul.  On
     TPU they are single Pallas kernels whose ring transport rides
     ``pltpu.make_async_remote_copy`` between per-step MXU tiles
     (UNVALIDATED on real chips — the on-chip numbers fold into ROADMAP
     item 1's measured sweep).  In interpret mode (CPU tier-1 coverage)
     the same per-tile GEMM kernels run under ``pallas_call(interpret=
     True)`` with the remote-copy path swapped for a mesh-simulated
     permute (``lax.ppermute``) — the flash_attention.py pattern.

  2. Per-tile TRANSPORT drop-ins for the streamed-ZeRO-3 scan, whose
     consumer/producer is an arbitrary model body rather than one GEMM
     we control: ``fcm_all_gather`` (drop-in for
     ``low_bandwidth_all_gather`` / ``_all_gather_f32grad``) and
     ``fcm_reduce_scatter`` (drop-in for ``quantized_psum_scatter``)
     realize the same per-tile schedule at program granularity: W-1
     independent quantize -> ppermute -> dequant tile chains replace the
     monolithic collective, giving the scheduler tile-level freedom and
     the Schedule Auditor a statically-checkable property.  Enabled via
     ``zero_optimization.low_bandwidth.fused_collective_matmul``
     (docs/fused_collective_matmul.md).  The DENSE scatter
     (``fcm_reduce_scatter(bits=0)``) is ``comm/low_bandwidth.
     dense_psum_scatter``, which the streamed stack takes with or
     without the knob: that transport, shifted permutes of whole chunks,
     has run on the chip (four v5e chips, PERF.md section 6, PR 59).

Every transport here traces under ``jax.named_scope(constants.FCM_SCOPE)``
— the Schedule Auditor's overlap classifier (analysis/overlap.py) reads
the marker off equation name stacks and classifies the per-tile wire as
``fused`` (hidden by construction, the carried-like static property),
and the cost model prices it in the hidden-comm lane.

Numerics contract (pinned by tests/unit/test_collective_matmul.py):

  - the fused qwZ gather is BITWISE-identical to the modular path — the
    same blockwise quantization runs once at the source and the same
    per-tile dequant math runs at each receiver, only the transport
    schedule differs;
  - the fused qgZ scatter keeps the modular path's accumulation-order
    contract — every receiver dequantizes the full source table and
    reduces in shard-index order (``jnp.sum(deq, axis=0)``), bitwise
    matching ``quantized_psum_scatter`` / ``qgz_reduce_scatter_inner``;
  - the error-feedback residual is computed from the same compensated
    quantization (``new_error = (x + error) - deq(quant(x + error))``).

The qgz_bits=0 fallback (``dense_psum_scatter``) moves the chunks in
the gradient's own dtype and accumulates what arrives in fp32 (widen ->
accumulate fp32 -> narrow once), which matches ``f32_psum_scatter``'s
accumulation DTYPE but fixes the accumulation ORDER (shard-index) where
``lax.psum_scatter`` leaves it to XLA — equal up to fp reassociation,
exactly equal when qgZ is on.
"""

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .. import constants as C
from ..runtime.comm.low_bandwidth import (DEFAULT_BLOCK, blockwise_dequantize,
                                          blockwise_quantize,
                                          dense_psum_scatter)

FCM_SCOPE = C.FCM_SCOPE


def _fcm_scope():
    """The name scope every fused transport traces under — the single
    handle the Schedule Auditor keys its ``fused`` classification on."""
    return jax.named_scope(FCM_SCOPE)


def _axes_tuple(axes) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


# --------------------------------------------------------------------- #
# per-tile ring transport (the mesh-level schedule both layers share)
# --------------------------------------------------------------------- #
def _ring_tiles(payloads, axis_name):
    """Ring-circulate per-device payload tiles and return them in SOURCE
    order.

    ``payloads`` is a tuple of arrays (one shard tile each, e.g. the
    quantized payload and its scales).  Devices forward along a
    send-left ring (device d sends to d-1, receives from d+1), so after
    step ``t`` device ``d`` holds the tile originated at ``(d+t) % W``
    — W-1 hops total, the same wire volume as a tiled all-gather, but
    as W-1 INDEPENDENT per-tile transfers the scheduler can interleave
    with the consuming compute.  The returned tables are stacked
    ``[W, ...]`` in source-index order (``jnp.roll`` by the device's own
    index converts arrival order to source order)."""
    world = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i - 1) % world) for i in range(world)]
    rows = [list(payloads)]
    cur = list(payloads)
    for _t in range(1, world):
        cur = [None if p is None else lax.ppermute(p, axis_name, perm)
               for p in cur]
        rows.append(cur)
    tables = []
    for k, p in enumerate(payloads):
        if p is None:
            tables.append(None)
            continue
        stacked = jnp.stack([row[k] for row in rows], axis=0)
        tables.append(jnp.roll(stacked, my, axis=0))
    return tables


def _scatter_tiles(payloads, axis_name):
    """Ring-scheduled all-to-all of per-destination tiles, returning
    each device's received tiles in SOURCE order.

    ``payloads`` is a tuple of ``[W, ...]`` tables where row ``j`` is the
    tile this device owes destination ``j``.  Round ``t`` (t=1..W-1)
    moves every device's distance-``t`` tile in one shifted permutation
    (a ring-scheduled all-to-all: balanced link use, one tile per round
    — per-tile communication as the producer's output tiles complete).
    Row ``my`` stays local.  Returns ``[W, ...]`` tables where row ``s``
    is the tile SOURCE ``s`` sent here."""
    world = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    # rolled[t] = my tile for destination (my + t) % W
    rolled = [None if p is None else jnp.roll(p, -my, axis=0)
              for p in payloads]
    arrivals = [[None if r is None else r[0] for r in rolled]]
    for t in range(1, world):
        perm = [(i, (i + t) % world) for i in range(world)]
        arrivals.append([
            None if r is None else lax.ppermute(r[t], axis_name, perm)
            for r in rolled])
    tables = []
    for k, p in enumerate(payloads):
        if p is None:
            tables.append(None)
            continue
        # arrivals[t][k] came from source (my - t) % W; reversing gives a
        # rotation of source order, fixed up by one roll
        rev = jnp.stack([arrivals[t][k] for t in range(world)][::-1],
                        axis=0)
        tables.append(jnp.roll(rev, my + 1, axis=0))
    return tables


def _quantize_scatter_reduce(chunk_tab, axis_name, bits, block,
                             applied_dtype=None):
    """The fused scatter's ONE accumulation pipeline (shared by every
    reduce-scatter entry point so the bitwise contract cannot fork):
    quantize the destination-index chunk table once (per-chunk scales —
    the modular qgZ layout), move each tile in a ring-scheduled
    all-to-all round, dequantize the received source table and reduce
    in SHARD-INDEX order (``jnp.sum(axis=0)`` — the modular
    accumulation contract, bitwise).  bits=0 moves fp32 chunks
    unquantized.

    Returns ``(reduced, applied)``: ``applied`` is
    ``deq(quant(chunk_tab))`` in ``applied_dtype`` for error-feedback
    callers (None when not requested; bits=0 quantizes nothing, so
    ``applied == chunk_tab``)."""
    if bits:
        q, s = blockwise_quantize(chunk_tab, dim=0, bits=bits,
                                  block=block)
        applied = (blockwise_dequantize(q, s, chunk_tab.shape, dim=0,
                                        dtype=applied_dtype, bits=bits)
                   if applied_dtype is not None else None)
        q_tab, s_tab = _scatter_tiles((q, s), axis_name)
        deq = blockwise_dequantize(q_tab, s_tab, chunk_tab.shape,
                                   dim=0, dtype=jnp.float32, bits=bits)
    else:
        applied = (chunk_tab.astype(applied_dtype)
                   if applied_dtype is not None else None)
        (deq,) = _scatter_tiles((chunk_tab.astype(jnp.float32),),
                                axis_name)
    return jnp.sum(deq, axis=0), applied


# --------------------------------------------------------------------- #
# layer 2: per-tile transport drop-ins for the streamed-ZeRO-3 scan
# --------------------------------------------------------------------- #
def _fcm_gather_one_axis(parts, axis_name, cdim):
    """One axis of the fused gather: ring the payload tiles gathered so
    far (concatenated along ``cdim`` for transport) and return the new
    per-source tile lists.  ``parts`` is a tuple of lists, one list per
    payload kind (e.g. quantized values and their scales), each in
    source order along the axes already rung."""
    world = lax.axis_size(axis_name)
    cats = tuple(jnp.concatenate(pl, axis=cdim) if len(pl) > 1 else pl[0]
                 for pl in parts)
    tabs = _ring_tiles(cats, axis_name)
    return tuple([tab[p] for p in range(world)] for tab in tabs)


def _fcm_gather_impl(x, axes, dim, bits, block):
    """Per-tile ring gather over one or more mesh axes.  The shard is
    quantized ONCE at the source (identical to the modular qwZ path —
    re-quantizing a partially-gathered result would change the block
    boundaries and break bitwise parity); the (payload, scales) tiles
    then ride the rings — innermost axis first, so the final source
    order matches the joint tiled all_gather's axis-major layout — and
    each final tile gets its own dequant epilogue."""
    if bits:
        q, s = blockwise_quantize(x, dim=dim, bits=bits, block=block)
        pq, ps = [q], [s]
        for ax in reversed(axes):
            pq, ps = _fcm_gather_one_axis((pq, ps), ax, 0)
        shard_m = x.shape[dim]
        tiles = []
        for qt, st in zip(pq, ps):
            mult = st.shape[0] // s.shape[0]
            tshape = (tuple(x.shape[:dim]) + (shard_m * mult,)
                      + tuple(x.shape[dim + 1:]))
            tiles.append(blockwise_dequantize(qt, st, tshape, dim=dim,
                                              dtype=x.dtype, bits=bits))
        return jnp.concatenate(tiles, axis=dim) if len(tiles) > 1 \
            else tiles[0]
    px = [x]
    for ax in reversed(axes):
        (px,) = _fcm_gather_one_axis((px,), ax, dim)
    return jnp.concatenate(px, axis=dim) if len(px) > 1 else px[0]


def _fcm_scatter_one_axis(x, axis_name, dim, bits, block):
    """One axis of the fused QUANTIZED scatter: split into per-owner
    chunks, quantize the compensated chunk table (per-chunk scales —
    identical to the modular qgZ quantization), move each tile in a
    ring-scheduled all-to-all round, dequantize the received source
    table and reduce in shard-index order (``jnp.sum(axis=0)`` — the
    modular accumulation contract, bitwise)."""
    world = lax.axis_size(axis_name)
    xt = jnp.moveaxis(x, dim, 0)
    m = xt.shape[0]
    if m % world != 0:
        raise ValueError(
            f"fused reduce-scatter: dim {dim} (size {m}) must be "
            f"divisible by the {axis_name!r} axis size {world}")
    tail = xt.shape[1:]
    chunks = xt.reshape((world, m // world) + tail)
    red, _ = _quantize_scatter_reduce(chunks, axis_name, bits, block)
    return jnp.moveaxis(red.astype(x.dtype), 0, dim)


def fcm_reduce_scatter(x, axes, dim, bits: int = 0,
                       block: int = DEFAULT_BLOCK):
    """Per-tile drop-in for ``quantized_psum_scatter`` (bits=4/8): the
    backward GEMM's gradient leaves as per-owner tiles on a
    ring-scheduled all-to-all instead of one monolithic collective.
    Multiple axes reduce sequentially in tuple order, matching the
    modular path's staging.  ``bits=0`` is ``dense_psum_scatter``, the
    streamed stack's own dense transpose whether or not the knob is
    set: the same shifted permutes, the chunks in the gradient's dtype
    (one axis: bit for bit what the fp32 table of this function's
    first form summed, since widening commutes with the wire)."""
    if not bits:
        return dense_psum_scatter(x, axes, dim)
    axes = _axes_tuple(axes)
    with _fcm_scope():
        for ax in axes:
            x = _fcm_scatter_one_axis(x, ax, dim, bits, block)
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def fcm_all_gather(x, axes, dim, qwz_bits=0, qgz_bits=0,
                   block=DEFAULT_BLOCK):
    """Per-tile drop-in for ``low_bandwidth_all_gather`` (and, at
    qwz_bits=0, for ``_all_gather_f32grad``): the consuming GEMM's
    weights arrive tile-by-tile over a ring with the dequant folded in
    per tile.  Forward values are BITWISE-identical to the modular
    path; the transpose reduce-scatters through
    :func:`fcm_reduce_scatter` (qgZ-quantized when ``qgz_bits``, the
    fp32-accumulation table otherwise — the straight-through-quantizer
    contract of the modular custom_vjp, preserved)."""
    axes = _axes_tuple(axes)
    with _fcm_scope():
        return _fcm_gather_impl(x, axes, dim, qwz_bits, block)


def _fcm_ag_fwd(x, axes, dim, qwz_bits, qgz_bits, block):
    return fcm_all_gather(x, axes, dim, qwz_bits, qgz_bits, block), None


def _fcm_ag_bwd(axes, dim, qwz_bits, qgz_bits, block, _, g):
    del qwz_bits  # straight-through: the forward quantizer is identity
    return (fcm_reduce_scatter(g, axes, dim, bits=qgz_bits, block=block),)


fcm_all_gather.defvjp(_fcm_ag_fwd, _fcm_ag_bwd)


def fcm_qgz_reduce_scatter_inner(x, error, axis_name: str, dim: int = 0,
                                 bits: int = 8,
                                 block: int = DEFAULT_BLOCK):
    """Error-compensated fused reduce-scatter; call inside shard_map.

    The per-tile analog of ``qgz_reduce_scatter_inner`` with the
    identical error-feedback contract: the persistent ``error`` buffer
    absorbs this step's quantization residual (``new_error = (x +
    error) - deq(quant(x + error))``), so repeated reductions of a
    persistent signal converge on the exact mean.  Returns
    ``(reduced_chunk, new_error)`` — both bitwise-equal to the modular
    variant's (same quantization, same shard-order accumulation), only
    the transport is per-tile."""
    from ..runtime.comm.low_bandwidth import _check_bits
    _check_bits(bits, "qgz_bits")
    world = lax.axis_size(axis_name)
    compensated = x + error
    xt = jnp.moveaxis(compensated, dim, 0)
    m = xt.shape[0]
    if m % world != 0:
        raise ValueError(
            f"fused qgz reduce-scatter: dim {dim} (size {m}) must be "
            f"divisible by the {axis_name!r} axis size {world}")
    tail = xt.shape[1:]
    chunks = xt.reshape((world, m // world) + tail)
    with _fcm_scope():
        red, applied = _quantize_scatter_reduce(
            chunks, axis_name, bits, block,
            applied_dtype=compensated.dtype)
        reduced = jnp.moveaxis(red.astype(x.dtype), 0, dim)
    new_error = compensated - jnp.moveaxis(
        applied.reshape((m,) + tail), 0, dim)
    return reduced, new_error


# --------------------------------------------------------------------- #
# layer 1: the GEMM-fused kernels
# --------------------------------------------------------------------- #
def _use_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return bool(interpret)
    from .dispatch import pallas_available
    return not pallas_available()


def _dequant_tile(q, s, kc, n, bits):
    """In-kernel dequant prologue: [kc, nb, bs(/2)] int8 payload + fp32
    block scales -> [kc, n] fp32 weight tile (bits=0: native tile, no
    scales)."""
    if not bits:
        return q.astype(jnp.float32).reshape(kc, n)
    if bits == 4 and 2 * int(np.prod(q.shape)) == kc * n:
        from ..runtime.comm.low_bandwidth import unpack_int4
        q = unpack_int4(q)
    return (q.astype(jnp.float32) * s[..., None]).reshape(kc, n)


def _ag_mm_tile_kernel(x_ref, q_ref, s_ref, o_ref, *, bits, kc, n):
    """One ring step's MXU tile: dequantize the arrived shard (prologue)
    and accumulate its partial product.  ``x_ref`` is the [m, kc] column
    block matching the shard's rows."""
    w = _dequant_tile(q_ref[...], s_ref[...], kc, n, bits)
    o_ref[...] = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _ag_mm_tile_t_kernel(g_ref, q_ref, s_ref, o_ref, *, bits, kc, n):
    """Transposed tile for the dx backward: g @ deq(q)^T."""
    w = _dequant_tile(q_ref[...], s_ref[...], kc, n, bits)
    o_ref[...] = jax.lax.dot_general(
        g_ref[...].astype(jnp.float32), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _rs_mm_tile_kernel(a_ref, b_ref, o_ref):
    """One producer-GEMM output tile of dW = a^T @ b (the tile about to
    be quantized and sent in the epilogue)."""
    o_ref[...] = jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _tile_call(kernel, out_shape, interpret, *args, **static):
    return pl.pallas_call(
        functools.partial(kernel, **static),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
    )(*args)


def _ag_matmul_interp(x, q, s, axis_name, bits, out_dtype, transpose):
    """Interpret-mode fused allgather-matmul: the per-tile GEMM kernels
    run under ``pallas_call(interpret=True)`` while the remote-copy ring
    is mesh-simulated with ``lax.ppermute`` (the flash_attention.py
    pattern: same kernel math, swappable transport).  Tile t's GEMM
    consumes the shard that arrived at hop t — the arriving tile t+1 is
    independent of it, which is exactly the double-buffering the TPU
    kernel realizes in VMEM."""
    world = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    kc = q.shape[0]
    n = _tile_n(q, kc, bits) if bits else q.shape[1]
    m = x.shape[0]
    ones = jnp.ones((kc, 1), jnp.float32)
    perm = [(i, (i - 1) % world) for i in range(world)]
    cq, cs = q, s
    acc = jnp.zeros((m, kc * world), jnp.float32) if transpose else None
    for t in range(world):
        if t > 0:
            cq = lax.ppermute(cq, axis_name, perm)
            if cs is not None:
                cs = lax.ppermute(cs, axis_name, perm)
        src = lax.rem(my + t, world)
        if transpose:
            # dx backward: the OUTPUT's column block selects the source
            part = _tile_call(_ag_mm_tile_t_kernel, (m, kc), True,
                              x, cq, cs if cs is not None else ones,
                              bits=bits, kc=kc, n=n)
            acc = lax.dynamic_update_slice(acc, part, (0, src * kc))
        else:
            xcols = lax.dynamic_slice_in_dim(x, src * kc, kc, axis=1)
            part = _tile_call(_ag_mm_tile_kernel, (m, n), True,
                              xcols, cq, cs if cs is not None else ones,
                              bits=bits, kc=kc, n=n)
            acc = part if acc is None else acc + part
    return acc.astype(out_dtype)


def _tile_n(q, kc, bits):
    """Columns of the dequantized weight tile for a quantized payload."""
    elems = int(np.prod(q.shape))
    if bits == 4:
        elems *= 2
    return elems // kc


def _quantize_shard(w_shard, bits, block):
    if not bits:
        return w_shard, None
    return blockwise_quantize(w_shard, dim=0, bits=bits, block=block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def fused_allgather_matmul(x, w_shard, axis_name, qwz_bits=8,
                           qgz_bits=0, block=DEFAULT_BLOCK,
                           interpret=None):
    """``x @ all_gather(w_shard, axis=0)`` with the qwZ dequant-all-gather
    fused into the GEMM's prologue.  Call inside shard_map over
    ``axis_name``; ``w_shard`` is this device's ``[K/W, N]`` row shard,
    ``x`` is ``[M, K]`` (replicated or batch-sharded rows).

    The shard is blockwise-quantized ONCE at the source; the ring then
    moves int8 payload + fp32 scales per tile while the MXU multiplies
    the tile that already arrived — remote arrival double-buffered
    against the current tile, dequant folded into each tile's prologue.
    Backward: dx re-rings the quantized shards through the transposed
    tile GEMM; dW takes :func:`fused_matmul_reduce_scatter` — the qgZ
    scatter fused into the producer GEMM's epilogue (straight-through
    quantizer: with qgz_bits=0 the dW wire is fp32, matching the
    modular custom_vjp's contract)."""
    return _fused_ag_matmul_fwd_impl(x, w_shard, axis_name, qwz_bits,
                                     block, interpret)


def _fused_ag_matmul_fwd_impl(x, w_shard, axis_name, qwz_bits, block,
                              interpret):
    kc = w_shard.shape[0]
    if x.shape[-1] != kc * lax.axis_size(axis_name):
        raise ValueError(
            f"fused_allgather_matmul: x has K={x.shape[-1]} but the "
            f"gathered weight has {kc * lax.axis_size(axis_name)} rows "
            f"({kc} x {lax.axis_size(axis_name)} shards)")
    q, s = _quantize_shard(w_shard, qwz_bits, block)
    with _fcm_scope():
        if _use_interpret(interpret):
            return _ag_matmul_interp(x, q, s, axis_name, qwz_bits,
                                     x.dtype, transpose=False)
        return _ag_matmul_tpu(x, q, s, axis_name, qwz_bits, x.dtype)


def _fused_ag_mm_fwd(x, w_shard, axis_name, qwz_bits, qgz_bits, block,
                     interpret):
    y = _fused_ag_matmul_fwd_impl(x, w_shard, axis_name, qwz_bits, block,
                                  interpret)
    return y, (x, w_shard)


def _fused_ag_mm_bwd(axis_name, qwz_bits, qgz_bits, block, interpret,
                     res, g):
    x, w_shard = res
    q, s = _quantize_shard(w_shard, qwz_bits, block)
    with _fcm_scope():
        if _use_interpret(interpret):
            dx = _ag_matmul_interp(g, q, s, axis_name, qwz_bits, x.dtype,
                                   transpose=True)
        else:
            dx = _ag_matmul_tpu(g, q, s, axis_name, qwz_bits, x.dtype,
                                transpose=True)
    dw, _ = fused_matmul_reduce_scatter(
        x, g, None, axis_name, qgz_bits=qgz_bits, block=block,
        interpret=interpret)
    return dx, dw.astype(w_shard.dtype)


fused_allgather_matmul.defvjp(_fused_ag_mm_fwd, _fused_ag_mm_bwd)


def fused_matmul_reduce_scatter(lhs, rhs, error, axis_name,
                                qgz_bits: int = 8,
                                block: int = DEFAULT_BLOCK,
                                interpret: Optional[bool] = None):
    """``reduce_scatter(lhs^T @ rhs, dim=0)`` with the qgZ transport
    fused into the producer GEMM's epilogue.  Call inside shard_map over
    ``axis_name``; returns ``(my_chunk, new_error)`` where ``my_chunk``
    is this device's ``[K/W, N]`` row chunk of the summed gradient.

    The output tiles of dW = lhs^T @ rhs are computed per DESTINATION in
    ring order (distance-1 neighbor first); as each tile completes it is
    compensated with its ``error`` slice, blockwise-quantized and sent
    straight to its owner (per-tile communication as tiles complete).
    Receivers dequantize the full source table and reduce in shard-index
    order — bitwise-matching ``qgz_reduce_scatter_inner``'s accumulation
    contract, with the identical error-feedback residual
    (``new_error = compensated - deq(quant(compensated))``).  ``error``
    may be None (straight-through, no feedback — the dW wire of
    :func:`fused_allgather_matmul`'s backward); qgz_bits=0 sends fp32
    tiles (no quantization, error passes through zero).

    On TPU with qgz_bits=8 the whole pipeline runs as ONE Pallas kernel
    whose per-tile sends ride ``pltpu.make_async_remote_copy``
    (:func:`_matmul_rs_tpu`); other widths keep the per-tile structure
    below with compiled tile GEMMs and mesh-level transport."""
    world = lax.axis_size(axis_name)
    k, n = lhs.shape[1], rhs.shape[1]
    if k % world != 0:
        raise ValueError(
            f"fused_matmul_reduce_scatter: K={k} must be divisible by "
            f"the {axis_name!r} axis size {world}")
    kc = k // world
    use_interp = _use_interpret(interpret)
    if not use_interp and qgz_bits == 8:
        with _fcm_scope():
            return _matmul_rs_tpu(lhs, rhs, error, axis_name, block)
    with _fcm_scope():
        my = lax.axis_index(axis_name)
        tiles = []
        for t in range(world):
            dst = lax.rem(my + t, world)
            a_cols = lax.dynamic_slice_in_dim(lhs, dst * kc, kc, axis=1)
            tile = _tile_call(_rs_mm_tile_kernel, (kc, n), use_interp,
                              a_cols, rhs)
            if error is not None:
                tile = tile + lax.dynamic_slice_in_dim(
                    error.astype(jnp.float32), dst * kc, kc, axis=0)
            tiles.append(tile)
        # destination-order [W, kc, n] table (row t -> dst (my + t) % W);
        # roll to destination-index order for the quantizer (per-chunk
        # scales, identical to the modular chunk-table quantization)
        dest_tab = jnp.roll(jnp.stack(tiles, axis=0), my, axis=0)
        my_chunk, applied = _quantize_scatter_reduce(
            dest_tab, axis_name, qgz_bits, block,
            applied_dtype=jnp.float32 if error is not None else None)
    if error is not None:
        new_error = (dest_tab - applied).reshape(k, n)
        return my_chunk, new_error.astype(error.dtype)
    return my_chunk, None


# --------------------------------------------------------------------- #
# TPU path: in-kernel RDMA ring (UNVALIDATED on chip — ROADMAP item 1)
# --------------------------------------------------------------------- #
def _ag_matmul_tpu(x, q, s, axis_name, bits, out_dtype,
                   transpose: bool = False):  # pragma: no cover - TPU only
    """Single-kernel fused dequant-all-gather-matmul: the quantized
    shard circulates the ring via ``pltpu.make_async_remote_copy`` into
    double-buffered VMEM slots while the MXU multiplies the tile that
    arrived last step — the T3 schedule realized in-kernel.

    DOES NOT LOWER on jax 0.9.0 ("LOGICAL device_id_type does not
    support device_id as a tuple or dict" — docs/
    fused_collective_matmul.md); never run on a chip.  Written against
    the Pallas TPU RDMA contract (neighbor barrier before the first
    remote write, per-slot DMA semaphores, send-wait before slot reuse).
    Interpret-mode callers take :func:`_ag_matmul_interp`, which pins
    the identical numerics with the transport mesh-simulated."""
    world = int(lax.axis_size(axis_name))
    kc = q.shape[0]
    n = _tile_n(q, kc, bits)
    m = x.shape[0]
    if s is None:
        s = jnp.ones((kc, 1), jnp.float32)
    me = lax.axis_index(axis_name).astype(jnp.int32).reshape((1,))

    def kernel(me_ref, x_ref, q0_ref, s0_ref, o_ref, qbuf, sbuf, acc,
               qsend, qrecv, ssend, srecv):
        me_i = me_ref[0]
        left = lax.rem(me_i - 1 + world, world)
        right = lax.rem(me_i + 1, world)
        # stage my own payload in slot 0
        qbuf[0] = q0_ref[...]
        sbuf[0] = s0_ref[...]
        acc[...] = jnp.zeros_like(acc)
        # both neighbors must have staged before any remote write lands
        barrier = pltpu.get_barrier_semaphore()
        for nb in (left, right):
            pltpu.semaphore_signal(barrier, inc=1, device_id=(nb,))
        pltpu.semaphore_wait(barrier, 2)

        def step(t, _):
            slot = lax.rem(t, 2)
            nxt = lax.rem(t + 1, 2)

            @pl.when(t < world - 1)
            def _send():
                # forward the current tile to the left neighbor while
                # the MXU works on it — the double buffer
                for buf, snd, rcv in ((qbuf, qsend, qrecv),
                                      (sbuf, ssend, srecv)):
                    pltpu.make_async_remote_copy(
                        src_ref=buf.at[slot], dst_ref=buf.at[nxt],
                        send_sem=snd.at[slot], recv_sem=rcv.at[nxt],
                        device_id=(left,),
                        device_id_type=pltpu.DeviceIdType.LOGICAL,
                    ).start()

            src = lax.rem(me_i + t, world)
            w = _dequant_tile(qbuf[slot], sbuf[slot], kc, n, bits)
            if transpose:
                part = jax.lax.dot_general(
                    x_ref[...].astype(jnp.float32), w,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc[:, pl.ds(src * kc, kc)] = part
            else:
                xc = x_ref[:, pl.ds(src * kc, kc)]
                acc[...] += jax.lax.dot_general(
                    xc.astype(jnp.float32), w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            @pl.when(t < world - 1)
            def _wait():
                for snd, rcv in ((qsend, qrecv), (ssend, srecv)):
                    pltpu.semaphore_wait(rcv.at[nxt], 1)
                    pltpu.semaphore_wait(snd.at[slot], 1)
            return 0

        lax.fori_loop(0, world, step, 0)
        o_ref[...] = acc[...].astype(o_ref.dtype)

    out_shape = (m, kc * world) if transpose else (m, n)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(),
            in_specs=[pl.BlockSpec(x.shape, lambda *_: (0, 0)),
                      pl.BlockSpec(q.shape, lambda *_: (0,) * q.ndim),
                      pl.BlockSpec(s.shape, lambda *_: (0,) * s.ndim)],
            out_specs=pl.BlockSpec(out_shape, lambda *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2,) + q.shape, q.dtype),
                pltpu.VMEM((2,) + s.shape, s.dtype),
                pltpu.VMEM(out_shape, jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=0),
    )(me, x, q, s)


def _matmul_rs_tpu(lhs, rhs, error, axis_name,
                   block):  # pragma: no cover - TPU only
    """Single-kernel fused GEMM + qgZ reduce-scatter (int8): each output
    tile of dW = lhs^T @ rhs is computed per DESTINATION in ring order,
    compensated with its error slice, blockwise-int8 quantized in the
    epilogue and sent straight to its owner via
    ``pltpu.make_async_remote_copy`` (a ring-scheduled all-to-all:
    round t sends the distance-t tile while the MXU computes the next
    one); the receiver dequantizes the source table and reduces in
    shard-index order — the modular accumulation contract.

    DOES NOT LOWER on jax 0.9.0 ("Must wait semaphores of the following
    types ... Got dma_sem" — docs/fused_collective_matmul.md); never
    run on a chip.  Interpret-mode callers take the per-tile path in
    :func:`fused_matmul_reduce_scatter`, which pins the identical
    numerics with the transport mesh-simulated."""
    from ..runtime.comm.low_bandwidth import largest_divisor_at_most
    world = int(lax.axis_size(axis_name))
    k, n = lhs.shape[1], rhs.shape[1]
    kc = k // world
    rest = kc * n
    bs = largest_divisor_at_most(rest, block)
    nb = rest // bs
    qmax = 127.0
    track_error = error is not None
    err_in = (error.astype(jnp.float32) if track_error
              else jnp.zeros((k, n), jnp.float32))
    me = lax.axis_index(axis_name).astype(jnp.int32).reshape((1,))

    def kernel(me_ref, lhs_ref, rhs_ref, err_ref, out_ref, nerr_ref,
               qtab, stab, qstage, sstage, qsend, ssend, qrecv, srecv):
        me_i = me_ref[0]
        barrier = pltpu.get_barrier_semaphore()
        for d in range(world):
            if d != 0:  # every peer must arrive before remote writes
                pltpu.semaphore_signal(
                    barrier, inc=1,
                    device_id=(lax.rem(me_i + d, world),))
        pltpu.semaphore_wait(barrier, world - 1)

        def quantize(tile):
            g = tile.reshape(nb, bs)
            amax = jnp.max(jnp.abs(g), axis=-1)
            scale = jnp.where(amax > 0, amax / qmax, 1.0)
            q = jnp.clip(jnp.round(g / scale[:, None]), -qmax, qmax
                         ).astype(jnp.int8)
            return q, scale.reshape(1, nb)

        def one_tile(t):
            """producer-GEMM tile for destination (me + t) % W, with the
            error-feedback epilogue."""
            dst = lax.rem(me_i + t, world)
            a = lhs_ref[:, pl.ds(dst * kc, kc)]
            tile = jax.lax.dot_general(
                a.astype(jnp.float32), rhs_ref[...].astype(jnp.float32),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            comp = tile + err_ref[pl.ds(dst * kc, kc), :]
            q, scale = quantize(comp)
            deq = (q.astype(jnp.float32)
                   * scale.reshape(nb, 1)).reshape(kc, n)
            nerr_ref[pl.ds(dst * kc, kc), :] = comp - deq
            return dst, q, scale

        def step(t, _):
            slot = lax.rem(t, 2)
            dst, q, scale = one_tile(t)
            qstage[slot] = q
            sstage[slot] = scale

            @pl.when(t >= 3)
            def _reuse():  # the slot's previous send must have landed
                pltpu.semaphore_wait(qsend.at[slot], 1)
                pltpu.semaphore_wait(ssend.at[slot], 1)
            # remote tables are indexed by SOURCE: my row is `me_i`
            pltpu.make_async_remote_copy(
                src_ref=qstage.at[slot], dst_ref=qtab.at[me_i],
                send_sem=qsend.at[slot], recv_sem=qrecv.at[me_i],
                device_id=(dst,),
                device_id_type=pltpu.DeviceIdType.LOGICAL).start()
            pltpu.make_async_remote_copy(
                src_ref=sstage.at[slot], dst_ref=stab.at[me_i],
                send_sem=ssend.at[slot], recv_sem=srecv.at[me_i],
                device_id=(dst,),
                device_id_type=pltpu.DeviceIdType.LOGICAL).start()
            return 0

        # rounds 1..W-1: send each tile as it completes; own tile last
        lax.fori_loop(1, world, step, 0)
        dst0, q0, s0 = one_tile(0)
        del dst0
        qtab[me_i] = q0
        stab[me_i] = s0

        def collect(s_idx, acc):
            @pl.when(s_idx != me_i)
            def _wait():
                pltpu.semaphore_wait(qrecv.at[s_idx], 1)
                pltpu.semaphore_wait(srecv.at[s_idx], 1)
            deq = (qtab[s_idx].astype(jnp.float32)
                   * stab[s_idx].reshape(nb, 1)).reshape(kc, n)
            return acc + deq  # shard-index order: the modular contract

        acc = lax.fori_loop(0, world, collect,
                            jnp.zeros((kc, n), jnp.float32))
        out_ref[...] = acc.astype(out_ref.dtype)
        # drain outstanding sends before kernel exit: the step loop only
        # waits a slot's send when REUSING it (t >= 3), so the last two
        # rounds' sends (one round when world == 2) were never waited
        for t in range(max(1, world - 2), world):
            pltpu.semaphore_wait(qsend.at[t % 2], 1)
            pltpu.semaphore_wait(ssend.at[t % 2], 1)

    chunk, nerr = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(),
            in_specs=[pl.BlockSpec(lhs.shape, lambda *_: (0, 0)),
                      pl.BlockSpec(rhs.shape, lambda *_: (0, 0)),
                      pl.BlockSpec((k, n), lambda *_: (0, 0))],
            out_specs=[pl.BlockSpec((kc, n), lambda *_: (0, 0)),
                       pl.BlockSpec((k, n), lambda *_: (0, 0))],
            scratch_shapes=[
                pltpu.VMEM((world, nb, bs), jnp.int8),
                pltpu.VMEM((world, 1, nb), jnp.float32),
                pltpu.VMEM((2, nb, bs), jnp.int8),
                pltpu.VMEM((2, 1, nb), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((world,)),
                pltpu.SemaphoreType.DMA((world,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((kc, n), jnp.float32),
                   jax.ShapeDtypeStruct((k, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=1),
    )(me, lhs, rhs, err_in)
    if track_error:
        return chunk, nerr.astype(error.dtype)
    return chunk, None
