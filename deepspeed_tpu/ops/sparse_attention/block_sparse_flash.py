"""Pallas block-sparse flash attention over a SparsityConfig layout.

Reference: deepspeed/ops/sparse_attention/matmul.py:749 (Triton SDD/DSD/DDS
block-sparse matmuls) + softmax.py:315 (block softmax) — the reference
composes three Triton kernels, materializing the block-sparse score tensor
in HBM between them.

TPU-native design: ONE kernel per direction, flash-style.  The static
layout becomes scalar-prefetched gather indices — for grid cell
(b, h, qi, j) the BlockSpec index_map reads idx[h, qi, j] to DMA exactly
the j-th allowed k-block of query block qi, so HBM traffic and MXU work
are O(S · deg · block) and the softmax is the streaming online softmax
(no score materialization anywhere, unlike the gather-einsum path in
sparse_self_attention.py which builds an O(S · deg · block) fp32 score
tensor in HBM).  Padded entries repeat the row's last valid k-block —
the Pallas pipeline skips the DMA when the mapped block is unchanged —
and are masked off with `@pl.when`.

Backward is FlashAttention-2 over the sparse layout: dq walks the same
forward indices; dk/dv walk the TRANSPOSED layout (for each k-block, the
q-blocks that attend to it).  Both recompute P block-wise from the saved
logsumexp.
"""

import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..flash_attention import (DEFAULT_MASK_VALUE, _STATS_LANES, _LANES,
                               causal_keep_mask)


def layout_gather(layout: np.ndarray, transpose: bool = False
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """[H, nb, nb] bool -> (idx [H, nb, max_deg] int32, valid int32).

    Rows pad by REPEATING the last valid index (or 0 for empty rows) so
    consecutive grid steps map the same block and the pipeline elides the
    DMA.  transpose=True gathers over the first block axis instead (the
    dk/dv direction: for k-block i, the q-blocks attending to it).  Shares
    its gather core with layout_to_gather_indices
    (sparse_self_attention.py) — one builder, two pad policies."""
    from .sparse_self_attention import _gather_core
    if transpose:
        layout = layout.transpose(0, 2, 1)
    idx, valid = _gather_core(layout, pad_last_valid=True,
                              allow_empty_rows=True)
    return idx, valid.astype(np.int32)


def _bsf_fwd_kernel(idx_ref, val_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                    m_scr, l_scr, acc_scr, *, causal, sm_scale, block,
                    max_deg):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ki = idx_ref[h, qi, j]
    live = val_ref[h, qi, j] == 1
    if causal:  # a fully-above-diagonal block contributes nothing
        live = jnp.logical_and(live, ki * block <= qi * block + block - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                                   # [block, d]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [block, block]
        if causal:
            s = jnp.where(causal_keep_mask(qi, ki, block, block), s,
                          DEFAULT_MASK_VALUE)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev[:, :1] - m_next[:, :1])
        p = jnp.exp(s - m_next[:, :1])
        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_next
        l_scr[...] = jnp.broadcast_to(l_next[:, :1], l_scr.shape)
        v_blk = v_ref[0, 0]
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == max_deg - 1)
    def _finalize():
        denom = l_scr[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse = m_scr[...][:, :1] + jnp.log(l_scr[...][:, :1] + 1e-37)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _bsf_dq_kernel(idx_ref, val_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, causal, sm_scale, block,
                   max_deg):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    ki = idx_ref[h, qi, j]
    live = val_ref[h, qi, j] == 1
    if causal:
        live = jnp.logical_and(live, ki * block <= qi * block + block - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(causal_keep_mask(qi, ki, block, block), p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == max_deg - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bsf_dkdv_kernel(idx_ref, val_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, causal,
                     sm_scale, block, max_deg):
    h = pl.program_id(1)
    ki = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    qi = idx_ref[h, ki, j]
    live = val_ref[h, ki, j] == 1
    if causal:
        live = jnp.logical_and(live, ki * block <= qi * block + block - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(causal_keep_mask(qi, ki, block, block), p, 0.0)
        pt = p.astype(do.dtype)
        dv_scr[...] += jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == max_deg - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _q_spec(block, d):
    return pl.BlockSpec((1, 1, block, d),
                        lambda b, h, i, j, *refs: (b, h, i, 0))


def _gathered_spec(block, d):
    return pl.BlockSpec((1, 1, block, d),
                        lambda b, h, i, j, idx, val: (b, h, idx[h, i, j], 0))


def _stats_spec(block):
    return pl.BlockSpec((1, 1, block, _STATS_LANES),
                        lambda b, h, i, j, *refs: (b, h, i, 0))


def sparse_tiling_ok(block: int) -> bool:
    """The kernel tiles at layout-block granularity: Mosaic needs the lane
    dim (k block) % 128 and sublane (q block) % 8."""
    return block % _LANES == 0


def block_sparse_flash_fwd(q, k, v, idx, valid, block: int, causal: bool,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False,
                           return_lse: bool = False):
    """q,k,v [B, H, S, D]; idx/valid [H, nb, max_deg] (layout_gather)."""
    batch, heads, s, d = q.shape
    if s % block:
        raise ValueError(f"seq len {s} not divisible by block {block}")
    nb = s // block
    max_deg = idx.shape[-1]
    scale = float(sm_scale if sm_scale is not None else 1.0 / math.sqrt(d))
    kernel = functools.partial(_bsf_fwd_kernel, causal=causal,
                               sm_scale=scale, block=block, max_deg=max_deg)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, heads, nb, max_deg),
        in_specs=[
            _q_spec(block, d),
            _gathered_spec(block, d),
            _gathered_spec(block, d),
        ],
        out_specs=[
            _q_spec(block, d),
            _stats_spec(block),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, _LANES), jnp.float32),
            pltpu.VMEM((block, _LANES), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ])
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, heads, s, _STATS_LANES),
                                 jnp.float32),
        ],
        interpret=interpret,
        **params,
    )(idx, valid, q, k, v)
    return (out, lse[..., 0]) if return_lse else out


def block_sparse_flash_bwd(q, k, v, out, lse, do, idx, valid, idx_t, valid_t,
                           block: int, causal: bool,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False):
    batch, heads, s, d = q.shape
    nb = s // block
    max_deg = idx.shape[-1]
    max_deg_t = idx_t.shape[-1]
    scale = float(sm_scale if sm_scale is not None else 1.0 / math.sqrt(d))

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    stats_shape = (*delta.shape, _STATS_LANES)
    delta = jnp.broadcast_to(delta[..., None], stats_shape)
    lse = jnp.broadcast_to(lse[..., None], stats_shape)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    def gathered_stats_spec(blk):
        return pl.BlockSpec((1, 1, blk, _STATS_LANES),
                            lambda b, h, i, j, idx, val:
                            (b, h, idx[h, i, j], 0))

    # dq: grid over q blocks, walking the forward gather indices
    dq_kernel = functools.partial(_bsf_dq_kernel, causal=causal,
                                  sm_scale=scale, block=block,
                                  max_deg=max_deg)
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, heads, nb, max_deg),
            in_specs=[
                _q_spec(block, d),            # q
                _gathered_spec(block, d),     # k via idx
                _gathered_spec(block, d),     # v via idx
                _q_spec(block, d),            # do
                _stats_spec(block),           # lse
                _stats_spec(block),           # delta
            ],
            out_specs=_q_spec(block, d),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        **params,
    )(idx, valid, q, k, v, do, lse, delta)

    # dk/dv: grid over k blocks, walking the transposed gather indices —
    # q/do/lse/delta tiles are gathered by q-block index
    dkdv_kernel = functools.partial(_bsf_dkdv_kernel, causal=causal,
                                    sm_scale=scale, block=block,
                                    max_deg=max_deg_t)
    dk, dv = pl.pallas_call(
        dkdv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, heads, nb, max_deg_t),
            in_specs=[
                _gathered_spec(block, d),     # q via idx_t
                _q_spec(block, d),            # k (this grid's row)
                _q_spec(block, d),            # v
                _gathered_spec(block, d),     # do via idx_t
                gathered_stats_spec(block),   # lse via idx_t
                gathered_stats_spec(block),   # delta via idx_t
            ],
            out_specs=[
                _q_spec(block, d),
                _q_spec(block, d),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, d), jnp.float32),
                pltpu.VMEM((block, d), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        **params,
    )(idx_t, valid_t, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _bsf(q, k, v, idx, valid, idx_t, valid_t, block, causal, sm_scale,
         interpret):
    return _bsf_fwd(q, k, v, idx, valid, idx_t, valid_t, block, causal,
                    sm_scale, interpret)[0]


def _bsf_fwd(q, k, v, idx, valid, idx_t, valid_t, block, causal, sm_scale,
             interpret):
    out, lse = block_sparse_flash_fwd(
        q, k, v, idx, valid, block, causal, sm_scale, interpret=interpret,
        return_lse=True)
    return out, (q, k, v, out, lse, idx, valid, idx_t, valid_t)


def _bsf_bwd(block, causal, sm_scale, interpret, res, g):
    q, k, v, out, lse, idx, valid, idx_t, valid_t = res
    dq, dk, dv = block_sparse_flash_bwd(
        q, k, v, out, lse, g, idx, valid, idx_t, valid_t, block, causal,
        sm_scale, interpret=interpret)
    return dq, dk, dv, None, None, None, None


_bsf.defvjp(_bsf_fwd, _bsf_bwd)


def block_sparse_flash_attention(q, k, v, idx, valid, idx_t, valid_t,
                                 block: int, causal: bool = False,
                                 sm_scale: Optional[float] = None,
                                 interpret: bool = False):
    """Differentiable block-sparse flash attention.

    q,k,v: [B, H, S, D]; idx/valid from layout_gather(layout),
    idx_t/valid_t from layout_gather(layout, transpose=True); block is the
    SparsityConfig block size (must satisfy sparse_tiling_ok on TPU)."""
    return _bsf(q, k, v, jnp.asarray(idx), jnp.asarray(valid),
                jnp.asarray(idx_t), jnp.asarray(valid_t), int(block),
                bool(causal), sm_scale, interpret)
