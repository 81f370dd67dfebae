"""Fused LayerNorm — the analog of the reference's fused LN kernels
(csrc/transformer/normalize_kernels.cu:2103, fwd/bwd incl. the "invertible"
variant that recomputes the input from the output).

On TPU, XLA already fuses mean/var/normalize/scale into one loop nest and
into the neighbouring element-wise work, so LayerNorm is plain jnp (fp32
statistics): a Pallas row-block kernel, opaque to that fusion, lost to it
by about 2 ms a step on the v5e (round 4) and is gone.
"""

import functools

import jax
import jax.numpy as jnp


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ln(x, gamma, beta, eps):
    return _fused_ln_fwd(x, gamma, beta, eps)[0]


def _fused_ln_fwd(x, gamma, beta, eps):
    return layer_norm_reference(x, gamma, beta, eps), (x, gamma, beta)


def _fused_ln_bwd(eps, res, g):
    # the forward is recomputed here from (x, gamma, beta): the rule
    # saves the layer's input and nothing of its statistics
    x, gamma, beta = res
    _, vjp = jax.vjp(
        lambda x_, g_, b_: layer_norm_reference(x_, g_, b_, eps),
        x, gamma, beta)
    return vjp(g)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, gamma, beta, eps: float = 1e-5):
    """Differentiable LayerNorm whose backward recomputes the forward
    from the layer's input (x, gamma, beta are the residuals)."""
    return _fused_ln(x, gamma, beta, eps)


def rms_norm(x, gamma, eps: float = 1e-6):
    """RMSNorm over the last dim with fp32 statistics: ``x / sqrt(mean(x^2)
    + eps) * gamma``, in x's dtype.  Plain jnp: XLA fuses it into the
    neighbouring element-wise work, as it does the default LayerNorm."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)
