"""ZeRO++-style low-bandwidth collectives: qwZ / qgZ building blocks.

Reference: ZeRO++ (https://arxiv.org/pdf/2306.10209) and the Frontier
low-bandwidth-partitioning recipe (https://arxiv.org/pdf/2501.04266).
Three techniques cut ZeRO-3's communication volume:

  qwZ  — blockwise-int8 quantize BEFORE the weight all-gather, dequantize
         after: the gathered bytes shrink ~4x (int8 payload + small fp32
         scales) while the master weights stay fp32.  The backward is the
         UNCHANGED fp32 reduce-scatter (straight-through: the quantizer is
         treated as identity under differentiation, so grads flow exactly
         as in the fp32 path).
  qgZ  — quantized gradient reduce-scatter.  A psum cannot reduce int8
         operands with per-shard scales, so the transport is the ZeRO++
         all-to-all form: quantize my chunk-table, all-to-all so every
         shard receives all copies of ITS chunk, dequantize in fp32 and
         reduce locally.  Optional int4 packing halves the wire again.
         A persistent error-feedback variant (qgz_reduce_scatter)
         generalizes the 1-bit machinery in comm/compressed.py from
         sign+scale to multi-bit blockwise quantization.
  hpZ  — hierarchical secondary partition: see zero/partition.py
         (resolve_hpz_axes / ZeroPartitioner.secondary_shardings) and the
         consumer in zero/stage3_streaming.py.

The scale layout follows ops/quant.py's QuantizedWeight convention —
symmetric per-group scales along the leading (gather) dimension — extended
with optional sub-blocks over the remaining flattened elements so a block
never straddles a shard boundary along the gathered dimension (tiled
all-gathers and chunked reduce-scatters stay self-describing).

The dense transpose they fall back to is here too
(``dense_psum_scatter``): a gradient reduce-scatter that leaves as
shifted collective-permutes of whole chunks in the gradient's own dtype,
summed in fp32 on arrival, because the TPU's compiler overlaps a permute
with compute and a reduce-scatter with nothing.

Wire-width note: qwZ/qgZ pay off when the gather/reduce crosses the SLOW
mesh dimension (DCN between slices, or the long ICI axis).  On a short
intra-slice axis measure first: the dense fp32 reduce-scatters of GPT-2
XL on the four chips of a v5e host were NOT overlapped behind compute
(95 of a step's 1,064 ms, ledger PR 58), and what cured that was the
form of the transfer, not its width (PERF.md section 6, PR 59; same
honesty stance as comm/compressed.py).
"""

from functools import partial
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ...constants import FCM_SCOPE
from ...parallel.mesh import DATA_AXIS

_QMAX = {8: 127, 4: 7}
DEFAULT_BLOCK = 256


def _check_bits(bits: int, what: str) -> None:
    if bits not in _QMAX:
        raise ValueError(f"{what}={bits} unsupported — use 4 or 8 "
                         "(0 disables)")


def largest_divisor_at_most(n: int, bound: int, even: bool = False) -> int:
    bound = max(1, min(n, bound))
    for g in range(bound, 0, -1):
        if n % g == 0 and (not even or g % 2 == 0):
            return g
    return 1


# --------------------------------------------------------------------- #
# blockwise symmetric quantization
# --------------------------------------------------------------------- #
def blockwise_quantize(x: jnp.ndarray, dim: int = 0, bits: int = 8,
                       block: int = DEFAULT_BLOCK
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize ``x`` to int8 (optionally int4-packed) with per-block
    fp32 scales.

    The gather/scatter dimension ``dim`` is moved to the front and kept
    whole in the scale layout — every index along ``dim`` owns its own
    row of blocks, so a tiled collective along ``dim`` moves (q, scale)
    pairs that stay consistent on every receiver.  The remaining
    elements are flattened and split into blocks of at most ``block``
    (the largest divisor, so no padding).

    Returns ``(q, scale)``:
      q     int8 ``[m, nb, bs]`` (bits=8) or ``[m, nb, bs//2]`` packed
            (bits=4; bs forced even, falling back to bits=8 layout only
            when the flattened remainder is odd and indivisible),
      scale fp32 ``[m, nb]``.
    """
    _check_bits(bits, "bits")
    xt = jnp.moveaxis(x, dim, 0)
    m = xt.shape[0]
    rest = int(np.prod(xt.shape[1:])) if xt.ndim > 1 else 1
    flat = xt.reshape(m, rest)
    bs = largest_divisor_at_most(rest, block, even=(bits == 4))
    if bits == 4 and bs % 2 != 0:  # odd `rest` with no even divisor
        bs = largest_divisor_at_most(rest, block)
    nb = rest // bs
    g = flat.reshape(m, nb, bs)
    qmax = _QMAX[bits]
    amax = jnp.max(jnp.abs(g), axis=-1)                       # [m, nb]
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(g / scale[..., None]), -qmax, qmax
                 ).astype(jnp.int8)
    if bits == 4 and bs % 2 == 0:
        q = pack_int4(q)
    return q, scale


def blockwise_dequantize(q: jnp.ndarray, scale: jnp.ndarray, shape,
                         dim: int = 0, dtype=jnp.float32,
                         bits: int = 8) -> jnp.ndarray:
    """Inverse of :func:`blockwise_quantize` for a target array ``shape``
    (the shape AFTER any collective — ``shape[dim]`` may be a gathered
    multiple of the quantized shard's)."""
    _check_bits(bits, "bits")
    shape = tuple(shape)
    moved = (shape[dim],) + tuple(s for i, s in enumerate(shape)
                                  if i != dim)
    if bits == 4 and 2 * int(np.prod(q.shape)) == int(np.prod(moved)):
        q = unpack_int4(q)
    deq = q.astype(jnp.float32) * scale[..., None]
    out = deq.reshape(moved).astype(dtype)
    return jnp.moveaxis(out, 0, dim)


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int8 values in [-7, 7] two-per-byte along the last axis
    (which must be even): out[..., i] holds q[..., 2i] in the low nibble
    and q[..., 2i+1] in the high nibble."""
    lo = q[..., 0::2] & jnp.int8(0xF)
    hi = q[..., 1::2] & jnp.int8(0xF)
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(p: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4` (sign-extending both nibbles)."""
    lo = ((p & jnp.int8(0xF)) ^ jnp.int8(8)) - jnp.int8(8)
    hi = ((p >> 4) & jnp.int8(0xF) ^ jnp.int8(8)) - jnp.int8(8)
    return jnp.stack([lo, hi], axis=-1).reshape(
        *p.shape[:-1], p.shape[-1] * 2)


def quantized_gather_saves_bytes(shape, dim: int, dtype, bits: int,
                                 block: int = DEFAULT_BLOCK) -> bool:
    """True when a blockwise-quantized collective over an array of
    ``shape``/``dtype`` along ``dim`` moves fewer wire bytes than the
    native-width collective.  Skinny leaves (a bias gathered one layer
    at a time has one element per scale block) pay 4 fp32 scale bytes
    per payload byte — quantizing those INFLATES traffic, so callers
    fall back to the dense path."""
    shape = tuple(shape)
    m = shape[dim]
    rest = int(np.prod(shape)) // max(m, 1)
    bs = largest_divisor_at_most(rest, block, even=(bits == 4))
    if bits == 4 and bs % 2 != 0:
        bs = largest_divisor_at_most(rest, block)
    payload = rest // 2 if (bits == 4 and bs % 2 == 0) else rest
    scale_bytes = (rest // bs) * 4
    native = rest * jnp.dtype(dtype).itemsize
    return payload + scale_bytes < native


def as_quantized_weight(q: jnp.ndarray, scale: jnp.ndarray):
    """Bridge to ops/quant.py's carrier for the 2-D, one-block-per-row
    case: a ``blockwise_quantize(w, dim=0)`` result with ``nb == 1``
    IS a per-row QuantizedWeight (groups == rows, scale ``[rows, 1]``),
    so the fused dequant-matmul kernels accept the gathered payload
    directly."""
    from ...ops.quant import QuantizedWeight
    if q.ndim != 3 or scale.shape[1] != 1:
        raise ValueError(
            f"QuantizedWeight bridge needs a [rows, 1, cols] blockwise "
            f"layout, got q{q.shape} scale{scale.shape}")
    return QuantizedWeight(q.reshape(q.shape[0], -1),
                           scale.reshape(-1, 1))


def f32_psum_scatter(g, axes, dim):
    """Tiled ``psum_scatter`` that promotes half dtypes to fp32 for the
    reduction and demotes after: cross-shard accumulation happens in fp32
    regardless of compute dtype, and the only reduction collective stays
    out of XLA-CPU's AllReducePromotion pass, which hard-aborts on
    half-precision reduction collectives.  The native form of
    :func:`dense_psum_scatter`: what a leaf takes where the permutes are
    not wanted (``permute_scatter_takes``)."""
    half = (jnp.issubdtype(g.dtype, jnp.floating) and
            jnp.dtype(g.dtype).itemsize < 4)
    if half:
        shard = lax.psum_scatter(g.astype(jnp.float32), axes,
                                 scatter_dimension=dim, tiled=True)
        return shard.astype(g.dtype)
    return lax.psum_scatter(g, axes, scatter_dimension=dim, tiled=True)


# --------------------------------------------------------------------- #
# the dense gradient reduce-scatter: shifted permutes of whole chunks
# --------------------------------------------------------------------- #
# A leaf costs W - 1 permutes an axis.  Past this width the native
# collective's one instruction is kept: the TPU's compiler makes no
# reduce-scatter asynchronous, but it does not issue 3 x W operations a
# leaf either.  Measured on four chips only (PERF.md section 6, PR 59).
PERMUTE_SCATTER_MAX_WORLD = 8


def permute_scatter_takes(extent: int, world: int) -> bool:
    """Whether a scattered dimension of ``extent`` over ``world`` shards
    leaves as permutes: a width the op count allows and whole chunks."""
    return 1 < world <= PERMUTE_SCATTER_MAX_WORLD and extent % world == 0


def _permute_scatter_one_axis(g, axis_name, dim):
    """One axis of :func:`dense_psum_scatter` as ``W - 1`` shifted
    permutes.  Shard ``me`` keeps chunk ``me`` of ``dim`` and sends chunk
    ``(me + t) % W`` to its owner under the shift-``t`` permutation, in
    ``g``'s own dtype: only ORIGINAL values travel, and a half value
    widened before or after the wire is the same fp32 number.  What
    arrives is widened and summed in SOURCE-index order, which
    ``lax.select_n`` on the shard's index recovers inside the adding
    fusion (a roll of the stacked arrivals by a traced amount would copy
    them all).  Returns the fp32 sum; the caller narrows once."""
    world = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    chunk = g.shape[dim] // world
    acc_dtype = jnp.promote_types(g.dtype, jnp.float32)

    def owed(t):
        return lax.dynamic_slice_in_dim(g, ((me + t) % world) * chunk,
                                        chunk, axis=dim)

    # arrived[t] left shard (me - t) % W
    arrived = [owed(0)] + [
        lax.ppermute(owed(t), axis_name,
                     [(i, (i + t) % world) for i in range(world)])
        for t in range(1, world)]
    arrived = [a.astype(acc_dtype) for a in arrived]
    total = None
    for source in range(world):
        term = lax.select_n((me - source) % world, *arrived)
        total = term if total is None else total + term
    return total


def dense_psum_scatter(g, axes, dim):
    """Drop-in for ``lax.psum_scatter(g, axes, scatter_dimension=dim,
    tiled=True)`` with fp32 accumulation: the transpose of every dense
    streamed gather (``stage3_streaming``'s ``scatter_grads`` and
    ``_ag_bwd``, ``low_bandwidth_all_gather`` with qgZ off, and
    ``fcm_reduce_scatter(bits=0)``, which IS this function).

    The TPU's compiler runs a reduce-scatter synchronously, nothing
    beside it; collective-permutes it starts early and finishes late,
    compute between.  So each axis that ``permute_scatter_takes`` leaves
    as shifted permutes of the owners' chunks
    (``_permute_scatter_one_axis``), traced under
    ``constants.FCM_SCOPE`` so that the Schedule Auditor prices them as
    the hidden transport they are; any other axis keeps the native
    collective on an fp32 wire.  Several axes reduce one after another
    in tuple order (the joint tiled collective's axis-major chunks);
    partial sums stay fp32 between them and the result is narrowed to
    ``g``'s dtype ONCE."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if not any(permute_scatter_takes(g.shape[dim], lax.axis_size(a))
               for a in axes):
        return f32_psum_scatter(g, axes, dim)
    acc = g
    for ax in axes:
        if permute_scatter_takes(acc.shape[dim], lax.axis_size(ax)):
            with jax.named_scope(FCM_SCOPE):
                acc = _permute_scatter_one_axis(acc, ax, dim)
        else:
            acc = lax.psum_scatter(
                acc.astype(jnp.promote_types(g.dtype, jnp.float32)), ax,
                scatter_dimension=dim, tiled=True)
    return acc.astype(g.dtype)


def dense_scatter_wire(shape, dtype, dim, worlds):
    """What :func:`dense_psum_scatter` puts on the wire for an array of
    ``shape`` / ``dtype`` scattered along ``dim`` over axes of sizes
    ``worlds``, from the shapes alone: ``(form, bytes)`` with ``form``
    ``"permute"`` (every axis left as permutes) or ``"native"`` (some
    axis kept the collective) and ``bytes`` what one shard sends."""
    extent = shape[dim]
    count = int(np.prod(shape))
    itemsize = jnp.dtype(dtype).itemsize
    wide = jnp.dtype(jnp.promote_types(dtype, jnp.float32)).itemsize
    takes = [permute_scatter_takes(extent // int(np.prod(worlds[:i])), w)
             for i, w in enumerate(worlds)]
    if not any(takes):
        world = int(np.prod(worlds))
        return "native", count * wide * (world - 1) // world
    sent = 0
    for w, t in zip(worlds, takes):
        sent += count * (itemsize if t else wide) * (w - 1) // w
        count //= w
        itemsize = wide  # partial sums travel from the second axis on
    return ("permute" if all(takes) else "native"), sent


# --------------------------------------------------------------------- #
# qwZ: quantized weight all-gather (drop-in for _all_gather_f32grad)
# --------------------------------------------------------------------- #
@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def low_bandwidth_all_gather(x, axes, dim, qwz_bits=8, qgz_bits=0,
                             block=DEFAULT_BLOCK):
    """Tiled all-gather with a quantized forward wire (qwZ) and an
    optionally quantized reduce-scatter transpose (qgZ).

    qwz_bits=8/4: the shard is blockwise-quantized before the gather and
    dequantized after — the wire moves int8 (or packed int4) plus the
    fp32 block scales.  qwz_bits=0 gathers at native width.
    qgz_bits=8/4: the backward reduce-scatters the gradient through
    :func:`quantized_psum_scatter`; qgz_bits=0 keeps the fp32
    reduce-scatter of stage3_streaming._all_gather_f32grad, so with qgZ
    off the gradients are BIT-IDENTICAL to the fp32 gather path
    (straight-through quantizer).
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if not qwz_bits:
        return lax.all_gather(x, axes, axis=dim, tiled=True)
    q, scale = blockwise_quantize(x, dim=dim, bits=qwz_bits, block=block)
    q_g = lax.all_gather(q, axes, axis=0, tiled=True)
    s_g = lax.all_gather(scale, axes, axis=0, tiled=True)
    world = int(np.prod([lax.axis_size(a) for a in axes]))
    shape = tuple(x.shape[:dim]) + (x.shape[dim] * world,) + \
        tuple(x.shape[dim + 1:])
    return blockwise_dequantize(q_g, s_g, shape, dim=dim, dtype=x.dtype,
                                bits=qwz_bits)


def _lbag_fwd(x, axes, dim, qwz_bits, qgz_bits, block):
    return low_bandwidth_all_gather(x, axes, dim, qwz_bits, qgz_bits,
                                    block), None


def _lbag_bwd(axes, dim, qwz_bits, qgz_bits, block, _, g):
    del qwz_bits
    if qgz_bits:
        return (quantized_psum_scatter(g, axes, dim, bits=qgz_bits,
                                       block=block),)
    return (dense_psum_scatter(g, axes, dim),)


low_bandwidth_all_gather.defvjp(_lbag_fwd, _lbag_bwd)


# --------------------------------------------------------------------- #
# qgZ: quantized gradient reduce-scatter (all-to-all transport)
# --------------------------------------------------------------------- #
def _quantized_reduce_scatter_one_axis(x, axis_name, dim, bits, block):
    """One axis of :func:`quantized_psum_scatter`: quantize my chunk
    table, transpose ownership with all_to_all, dequantize + reduce in
    fp32 (the ZeRO++ qgZ pipeline — a psum cannot weight int8 operands
    by per-shard scales, an all-to-all can because dequantization
    happens AFTER transport, on the receiver)."""
    world = lax.axis_size(axis_name)
    xt = jnp.moveaxis(x, dim, 0)
    m = xt.shape[0]
    if m % world != 0:
        raise ValueError(
            f"quantized reduce-scatter: dim {dim} (size {m}) must be "
            f"divisible by the {axis_name!r} axis size {world}")
    tail = xt.shape[1:]
    chunks = xt.reshape((world, m // world) + tail)
    q, scale = blockwise_quantize(chunks, dim=0, bits=bits, block=block)
    # int8 payload + fp32 scales ride the wire; dim 0 == world, so the
    # non-tiled all_to_all is exactly a (device, chunk) transpose
    q_t = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    s_t = lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0)
    deq = blockwise_dequantize(q_t, s_t, (world,) + (m // world,) + tail,
                               dim=0, dtype=jnp.float32, bits=bits)
    red = jnp.sum(deq, axis=0)
    return jnp.moveaxis(red.astype(x.dtype), 0, dim)


def quantized_psum_scatter(x, axes, dim, bits: int = 8,
                           block: int = DEFAULT_BLOCK):
    """Drop-in for ``lax.psum_scatter(x, axes, scatter_dimension=dim,
    tiled=True)`` with a quantized wire.  Multiple axes reduce
    sequentially in tuple order, which matches the joint tiled
    psum_scatter's axis-major chunk assignment (each stage re-quantizes
    its partial sums — errors stay blockwise-bounded per stage)."""
    _check_bits(bits, "qgz_bits")
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    for ax in axes:
        x = _quantized_reduce_scatter_one_axis(x, ax, dim, bits, block)
    return x


def qgz_reduce_scatter_inner(x, error, axis_name: str = DATA_AXIS,
                             dim: int = 0, bits: int = 8,
                             block: int = DEFAULT_BLOCK):
    """Error-compensated quantized reduce-scatter; call inside shard_map.

    Generalizes comm/compressed.py's 1-bit error feedback to multi-bit
    blockwise quantization: the persistent ``error`` buffer (same shape
    as ``x``, carried by the caller across steps) absorbs this step's
    quantization residual, so repeated reductions of a persistent signal
    converge on the exact mean (same telescoping argument as 1-bit Adam,
    reference runtime/comm/nccl.py:47).

    Returns ``(reduced_chunk, new_error)`` where ``reduced_chunk`` is
    this shard's SUM over workers of its ``dim``-chunk (divide by the
    axis size for a mean), and ``new_error = (x + error) -
    dequant(quant(x + error))``.
    """
    _check_bits(bits, "qgz_bits")
    world = lax.axis_size(axis_name)
    compensated = x + error
    xt = jnp.moveaxis(compensated, dim, 0)
    m = xt.shape[0]
    if m % world != 0:
        raise ValueError(
            f"qgz reduce-scatter: dim {dim} (size {m}) must be divisible "
            f"by the {axis_name!r} axis size {world}")
    tail = xt.shape[1:]
    chunks = xt.reshape((world, m // world) + tail)
    q, scale = blockwise_quantize(chunks, dim=0, bits=bits, block=block)
    applied = blockwise_dequantize(
        q, scale, chunks.shape, dim=0, dtype=compensated.dtype, bits=bits)
    q_t = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    s_t = lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0)
    deq = blockwise_dequantize(q_t, s_t, chunks.shape, dim=0,
                               dtype=jnp.float32, bits=bits)
    reduced = jnp.moveaxis(jnp.sum(deq, axis=0).astype(x.dtype), 0, dim)
    new_error = compensated - jnp.moveaxis(
        applied.reshape((m,) + tail), 0, dim)
    return reduced, new_error


def init_error_feedback(tree):
    """Zero-initialized persistent error buffers matching a grad tree —
    the caller carries these across steps (the analog of the reference's
    worker_error allocation, runtime/comm/nccl.py:47)."""
    return jax.tree.map(jnp.zeros_like, tree)


def qgz_reduce_scatter(x_stacked, error_stacked, mesh_ctx=None,
                       axis_name: str = DATA_AXIS, bits: int = 8,
                       block: int = DEFAULT_BLOCK):
    """Worker-stacked wrapper (same calling convention as
    comm/compressed.py's compressed_allreduce): ``x_stacked [W, ...]``
    holds worker i's tensor in row i, sharded over ``axis_name``.

    Returns ``(reduced [W, chunk...], new_error [W, ...])`` — row i of
    ``reduced`` is worker i's reduce-scattered chunk (sum over workers
    of chunk i of the element dim 0 after the worker dim), and
    ``new_error`` is the per-worker compensation state to carry into the
    next call.
    """
    from ...parallel.mesh import get_mesh_context
    from jax.sharding import PartitionSpec as P
    ctx = mesh_ctx or get_mesh_context()
    spec = P(axis_name)

    def inner(a, e):
        r, ne = qgz_reduce_scatter_inner(a[0], e[0], axis_name, dim=0,
                                         bits=bits, block=block)
        return r[None], ne[None]

    fn = jax.shard_map(inner, mesh=ctx.mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec), check_vma=False)
    return fn(x_stacked, error_stacked)


# --------------------------------------------------------------------- #
# wire-byte accounting (for tests / perf triage)
# --------------------------------------------------------------------- #
_GATHER_PRIMS = ("all_gather",)
_REDUCE_PRIMS = ("psum_scatter", "reduce_scatter", "all_to_all", "psum")


def collective_wire_bytes(jaxpr) -> dict:
    """Walk a (closed) jaxpr — recursing into every sub-jaxpr
    (scan/while/cond/remat/shard_map/custom_vjp bwd...) via the shared
    dispatcher in analysis/jaxpr_walk.py — and sum an approximate wire
    volume per collective family: output bytes for gathers (the payload
    that landed), operand bytes for reductions/all-to-alls (the payload
    that left), plus ``fcm_bytes`` for per-tile fused-collective-matmul
    ring hops (ppermutes traced under constants.FCM_SCOPE — a generic
    ppermute stays excluded, ring attention's hops are lockstep-only).
    Loop trip counts are NOT multiplied in, so use this for
    same-structure A/B ratios (quantized vs fp32 path), not absolute
    traffic — the Program Auditor's comm-budget lint
    (analysis/rules.py:step_wire_bytes) does the trip-weighted version."""
    from ... import constants as _C
    from ...analysis.jaxpr_walk import (aval_bytes, iter_eqns,
                                        scope_has_component)
    out = {"gather_bytes": 0, "reduce_bytes": 0, "fcm_bytes": 0,
           "onebit_bytes": 0}
    for ctx in iter_eqns(jaxpr):
        name = ctx.eqn.primitive.name
        onebit = scope_has_component(ctx.scope, _C.ONEBIT_SCOPE)
        if name in _GATHER_PRIMS:
            b = sum(aval_bytes(v) for v in ctx.eqn.outvars)
            out["gather_bytes"] += b
            if onebit:
                # attribution breakout (docs/onebit.md): the packed-sign
                # exchange is already counted in the gather/reduce totals;
                # this keys how much of the wire is the 1-bit momentum sync
                out["onebit_bytes"] += b
        elif name in _REDUCE_PRIMS:
            b = sum(aval_bytes(v) for v in ctx.eqn.invars)
            out["reduce_bytes"] += b
            if onebit:
                out["onebit_bytes"] += b
        elif name == "ppermute" and scope_has_component(ctx.scope,
                                                        _C.FCM_SCOPE):
            out["fcm_bytes"] += sum(aval_bytes(v)
                                    for v in ctx.eqn.invars)
    return out
