"""Quantized-weight carrier + fused dequant-matmul.

Reference: the int8 weight path of
csrc/transformer/inference/csrc/dequantize.cu + pt_binding.cpp (vector_matmul
int8 variants): weights live in HBM as int8 with per-group fp scales and are
dequantized into the gemm, so HBM sees ONE int8 read per token — never a
materialized fp copy.

TPU equivalents, in dispatch order:
  1. a Pallas kernel (fused_dequant_matmul) that DMAs int8 tiles into VMEM,
     converts + scales there, and feeds the MXU — int8 HBM traffic by
     construction (the dequantize.cu role);
  2. a reshape-free XLA path whose dequant producer (convert + per-row
     scale multiply) is a plain elementwise chain XLA can fuse into the
     dot operand read.  (The earlier group-reshape -> multiply -> reshape
     chain defeated that fusion, which is why int8 decode measured SLOWER
     than bf16 in round 3.)
"""

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


class QuantizedWeight(NamedTuple):
    """Per-group symmetric int8 weight (reference: weight_quantizer.py:5).

    scale groups split the leading (input) dimension; scale shape is
    [groups, 1] (per layer) or [L, groups, 1] when layers are stacked."""
    qweight: jnp.ndarray
    scale: jnp.ndarray

    @property
    def shape(self):
        return self.qweight.shape

    @property
    def dtype(self):
        return self.qweight.dtype


def _row_scales(w: QuantizedWeight, dtype):
    """[rows] per-row scale vector from the per-group scales."""
    rows = w.qweight.shape[0]
    groups = w.scale.shape[0]
    return jnp.repeat(w.scale.reshape(groups).astype(dtype),
                      rows // groups)


def _dq_kernel(x_ref, qw_ref, s_ref, o_ref, acc, *, num_k_blocks):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]                                    # [bm, bk]
    # dequant in VMEM: int8 -> fp32, per-row (K-dim) scale, then down to
    # the compute dtype — HBM only ever saw the int8 bytes.  The scale
    # multiply stays in fp32: s_ref is a [bk, 1] fp32 tile (a 1-D vector
    # operand trips Mosaic's layout verifier when bk < K, and a bf16
    # minor-dim insert is rejected outright).
    qw = (qw_ref[...].astype(jnp.float32) * s_ref[...]).astype(x.dtype)
    acc[...] += jax.lax.dot_general(
        x, qw, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _fin():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _fit_blocks(m, k, n, block_m, block_n, block_k):
    """Largest aligned divisors <= the targets (sublane for M, lane for
    K/N; a block equal to a short full dim is always legal)."""
    from .flash_attention import _fit_block
    return (_fit_block(m, block_m, 8), _fit_block(n, block_n, 128),
            _fit_block(k, block_k, 128))


def fused_dequant_matmul(x, w: QuantizedWeight, block_m: int = 256,
                         block_n: int = 512, block_k: int = 512,
                         interpret: bool = False):
    """x [M, K] @ dequant(w) [K, N] -> [M, N] with int8-only HBM reads.

    Blocks are fitted to the shapes (callers go through
    matmul_maybe_int8, which falls back to the XLA path when no aligned
    tiling exists)."""
    m, k = x.shape
    k2, n = w.qweight.shape
    assert k == k2, (x.shape, w.qweight.shape)
    fit = _dq_fit_or_none(m, k, n, block_m, block_n, block_k)
    if fit is None:
        raise ValueError(f"shapes ({m},{k},{n}) have no legal tiling — "
                         "use the XLA dequant path")
    bm, bn, bk = fit
    scales = _row_scales(w, jnp.float32)[:, None]     # [K, 1]
    grid = (m // bm, n // bn, k // bk)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_dq_kernel, num_k_blocks=k // bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, 1), lambda i, j, kk: (kk, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        **params,
    )(x, w.qweight, scales)


def _dq_fit_or_none(m, k, n, block_m=256, block_n=512, block_k=512):
    """The ONE tiling decision for the fused kernel: returns the fitted
    (bm, bn, bk) when a legal Mosaic tiling exists (sublane/lane-aligned,
    or block == full short dim; no degenerate 1-wide tiling), else None.
    Callers pass the result straight into fused_dequant_matmul so the
    gate and the kernel can never disagree."""
    bm, bn, bk = _fit_blocks(m, k, n, block_m, block_n, block_k)

    def legal(b, length, lane):
        return ((b % lane == 0 or b == length) and b > 1) or length == 1

    if legal(bm, m, 8) and legal(bn, n, 128) and legal(bk, k, 128):
        return bm, bn, bk
    return None


@jax.custom_vjp
def _fused_dq(x, qweight, scales):
    """Differentiable wrapper: forward = Pallas fused kernel; backward =
    one XLA matmul against the (fusably) dequantized transpose.  The int8
    weight is non-differentiable; the scale cotangent IS computed (so the
    fused path and the XLA fallback produce the same gradients — e.g. for
    learned scales), but XLA dead-code-eliminates its extra matmul
    whenever the caller doesn't use it."""
    return fused_dequant_matmul(x, QuantizedWeight(qweight, scales))


def _fused_dq_fwd(x, qweight, scales):
    return _fused_dq(x, qweight, scales), (x, qweight, scales)


def _fused_dq_bwd(res, g):
    x, qweight, scales = res
    w = QuantizedWeight(qweight, scales)
    # dL/dW = x^T g; dL/dscale_group = sum over the group's rows of
    # (x^T g) * float(qweight), matching d/ds [x @ (s * qf)].
    gw = jnp.einsum("mk,mn->kn", x.astype(jnp.float32),
                    g.astype(jnp.float32))
    per_row = jnp.sum(gw * qweight.astype(jnp.float32), axis=1)   # [K]
    groups = scales.shape[0]
    dscale = per_row.reshape(groups, -1).sum(axis=1).reshape(scales.shape)
    return (g @ dequant(w, g.dtype).T, None, dscale.astype(scales.dtype))


_fused_dq.defvjp(_fused_dq_fwd, _fused_dq_bwd)


def dequant(w: QuantizedWeight, dtype):
    """Reshape-free dequantization: convert + per-row scale, a fusable
    elementwise producer for the XLA dot path."""
    if w.qweight.ndim != 2:
        raise ValueError(
            f"QuantizedWeight matmul expects a 2-D weight, got "
            f"{w.qweight.shape} — unstack layer-stacked weights first")
    return w.qweight.astype(dtype) * _row_scales(w, dtype)[:, None]


def matmul_maybe_int8(x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """x @ w with just-in-time dequantization for QuantizedWeight.

    2-D x on the Pallas-capable backend takes the fused kernel; other
    ranks/backends use the XLA path, whose dequant producer XLA fuses
    into the dot operand read."""
    if isinstance(w, QuantizedWeight):
        from .dispatch import pallas_available
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        if (w.qweight.ndim == 2 and pallas_available()
                and _dq_fit_or_none(x2.shape[0],
                                    *w.qweight.shape) is not None):
            out = _fused_dq(x2, w.qweight, w.scale)
        else:
            out = x2 @ dequant(w, x.dtype)
        return out.reshape(*shape[:-1], -1)
    return x @ w.astype(x.dtype)
