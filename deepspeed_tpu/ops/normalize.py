"""Fused LayerNorm — the analog of the reference's fused LN kernels
(csrc/transformer/normalize_kernels.cu:2103, fwd/bwd incl. the "invertible"
variant that recomputes the input from the output).

On TPU, XLA already fuses mean/var/normalize/scale into one loop nest, so the
default path is plain jnp (fp32 statistics).  A Pallas row-block kernel is
provided for the hot transformer path where we want LN fused into the
surrounding kernel schedule explicitly.
"""

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl


def layer_norm_reference(x, gamma, beta, eps: float = 1e-5):
    """LN over the last dim with fp32 statistics (normalize_kernels.cu
    fused_bias_residual_layer_norm semantics, minus the fused residual which
    callers express as x + residual before the call)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) +
            beta.astype(jnp.float32)).astype(x.dtype)


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * g_ref[...].astype(jnp.float32) +
                  b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _pick_block_rows(rows: int, block_rows: int) -> int:
    """Largest divisor of rows <= block_rows — keeps each block VMEM-sized
    (never one giant block).  Shared by the forward and backward kernels
    so their block policies cannot diverge."""
    if rows <= 0:
        return 0
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows -= 1
    return block_rows


def _ln_tiling_ok(rows: int, hidden: int, block_rows: int) -> bool:
    """Mosaic requires the last two block dims divisible by (8, 128) or
    equal to the respective array dims; reject shapes that would fail
    lowering so the dispatcher can fall back to the XLA vjp instead of
    erroring.  Every block here spans the full hidden dim (== array dim,
    always legal), so only the row tiling needs checking."""
    del hidden
    return rows > 0 and (block_rows % 8 == 0 or block_rows == rows)


def layer_norm_pallas(x, gamma, beta, eps: float = 1e-5,
                      block_rows: int = 256, interpret: bool = False):
    """Pallas LN over the last dim of a 2-D [rows, hidden] view."""
    orig_shape = x.shape
    hidden = orig_shape[-1]
    x2 = x.reshape(-1, hidden)
    rows = x2.shape[0]
    block_rows = _pick_block_rows(rows, block_rows)
    if not _ln_tiling_ok(rows, hidden, block_rows):
        raise ValueError(
            f"layer_norm_pallas: rows={rows}, hidden={hidden} has no "
            "usable block tiling — use layer_norm_reference")
    kernel = functools.partial(_ln_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, gamma, beta)
    return out.reshape(orig_shape)


def _ln_bwd_kernel(x_ref, g_ref, dy_ref, dx_ref, dg_ref, db_ref, *, eps):
    """One-pass LN backward per row block (the normalize_kernels.cu
    backward's role): recompute the fp32 statistics, produce dx, and
    accumulate dgamma/dbeta row sums across the sequential TPU grid into
    a single [1, hidden] block (block == array dims, which satisfies the
    Mosaic tiling rule that a (1, hidden) window over an (nb, hidden)
    array does not)."""
    x = x_ref[...].astype(jnp.float32)                 # [rows, hidden]
    dy = dy_ref[...].astype(jnp.float32)
    gamma = g_ref[...].astype(jnp.float32)             # [hidden]
    n = x.shape[-1]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    dyg = dy * gamma
    m1 = jnp.sum(dyg, axis=-1, keepdims=True) / n
    m2 = jnp.sum(dyg * xhat, axis=-1, keepdims=True) / n
    dx = (dyg - m1 - xhat * m2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def layer_norm_bwd_pallas(x, gamma, dy, eps: float = 1e-5,
                          block_rows: int = 256, interpret: bool = False):
    """Pallas LN backward over the last dim: returns (dx, dgamma, dbeta)
    with fp32 gamma/beta grads (their accumulation dtype)."""
    orig_shape = x.shape
    hidden = orig_shape[-1]
    x2 = x.reshape(-1, hidden)
    dy2 = dy.reshape(-1, hidden)
    rows = x2.shape[0]
    block_rows = _pick_block_rows(rows, block_rows)
    if not _ln_tiling_ok(rows, hidden, block_rows):
        # awkward row counts would fail Mosaic lowering — the XLA vjp is
        # strictly better there
        raise ValueError(
            f"layer_norm_bwd_pallas: rows={rows}, hidden={hidden} has no "
            "usable block tiling — use the XLA backward")
    nb = rows // block_rows
    kernel = functools.partial(_ln_bwd_kernel, eps=eps)
    dx, dg, db = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
            pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
        ],
        interpret=interpret,
    )(x2, gamma, dy2)
    return (dx.reshape(orig_shape), dg[0], db[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ln(x, gamma, beta, eps):
    return _fused_ln_fwd(x, gamma, beta, eps)[0]


def _fused_ln_usable(x) -> bool:
    # The default LN impl is XLA, by measurement — see dispatch.ln_impl
    # (v5e: XLA LN beats the Pallas kernels by ~2 ms/step because a
    # pallas_call is opaque to XLA's elementwise fusion).
    from .dispatch import ln_impl, pallas_available
    if ln_impl() != "pallas":
        return False
    if not pallas_available():
        return False
    rows = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    hidden = x.shape[-1]
    return _ln_tiling_ok(rows, hidden, _pick_block_rows(rows, 256))


def _fused_ln_fwd(x, gamma, beta, eps):
    if _fused_ln_usable(x):
        out = layer_norm_pallas(x, gamma, beta, eps)
    else:
        out = layer_norm_reference(x, gamma, beta, eps)
    return out, (x, gamma, beta)


def _fused_ln_bwd(eps, res, g):
    x, gamma, beta = res
    if _fused_ln_usable(x):
        dx, dgamma, dbeta = layer_norm_bwd_pallas(x, gamma, g, eps)
        return (dx, dgamma.astype(jnp.asarray(gamma).dtype),
                dbeta.astype(jnp.asarray(beta).dtype))
    _, vjp = jax.vjp(
        lambda x_, g_, b_: layer_norm_reference(x_, g_, b_, eps),
        x, gamma, beta)
    return vjp(g)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, gamma, beta, eps: float = 1e-5):
    """Differentiable fused LayerNorm.  Default implementation is the
    XLA reference (the measured winner on v5e — see dispatch.ln_impl);
    DS_LN_IMPL=pallas / dispatch.set_ln_impl("pallas") selects the
    Pallas kernels."""
    return _fused_ln(x, gamma, beta, eps)


def rms_norm(x, gamma, eps: float = 1e-6):
    """RMSNorm over the last dim with fp32 statistics: ``x / sqrt(mean(x^2)
    + eps) * gamma``, in x's dtype.  Plain jnp: XLA fuses it into the
    neighbouring element-wise work, as it does the default LayerNorm."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)
