"""Manifold-constrained hyper-connections: a residual path of ``n``
streams that every sublayer reads through a learned, input-dependent
mixture and writes back through a doubly-stochastic ``n x n`` matrix.

The parameterisation is that of "mHC: Manifold-Constrained
Hyper-Connections" (arXiv:2512.24880); copying the embedding into the
streams and summing them after the last layer are Hyper-Connections'
(arXiv:2409.19606) and the model's business (models/xing4.py).  For one
sublayer ``F`` on the streams ``X`` (n of them, each ``C`` wide a
token), with ``phi`` [n C, n^2 + 2 n], ``b`` [n^2 + 2 n] and three
scalars ``alpha`` (the columns of ``phi`` and ``b`` ordered pre, post,
res; ``res`` row-major):

    x~     = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)           float32
    H~     = alpha_k (x~ phi_k) + b_k          k in (pre, post, res)
    H_pre  = sigmoid(H~pre);  H_post = 2 sigmoid(H~post)
    M      = exp(clamp(H~res, lo, hi));  `iters` rounds of
             M <- M / (M 1 + eps)  (rows),  M <- M / (1^T M + eps)
    u      = H_pre X                        F's input, one stream wide
    X'     = H_res X + H_post^T F(u)

``hc_pre`` gives ``u`` and the mixes, ``hc_post`` writes back.  The
norm, the projection's accumulation, the Sinkhorn rounds and the two
mixing sums are float32 whatever ``X`` is carried in, and the gradient
is plain differentiation through all of it, every round included.

TPU-native layout.  The streams are carried ``[B, n, S, C]``, streams
before positions: the last two dimensions are what the TPU tiles, and
4 streams there would fill 4 rows of a tile of 8 (float32) or 16
(bfloat16).  ``vec(X)`` is never formed: the projection is the sum of
the streams' own products with their ``C`` rows of ``phi``, scaled a
token by the norm's factor afterwards (the projection is linear).  The
mixes live as ``[n, B, S]`` and ``[n, n, B, S]``, the tokens along the
lanes, so that a Sinkhorn round is sums and quotients of whole vectors
and no matrix of 4 x 4 is ever a tile.  All of it is XLA's to fuse; it
lies under scope ``hc`` (profiling/scope_map.py).

What a checkpointed layer keeps (runtime/activation_checkpointing/
checkpointing.py): ``hc_mix``, the projection's ``n^2 + 2 n`` sums and
the mean square, 25 numbers a token at n = 4, always (ALWAYS_KEPT): the
recomputation pass then reads ``X`` for the mixing sums alone and runs
no second norm nor projection, and the backward pass differentiates the
rounds from them; ``hc_input``, the sublayer's input ``u``, where the
byte budget has room (last of RESIDUAL_ORDER).
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

SCOPE = "hc"
MIX_NAME = "hc_mix"
INPUT_NAME = "hc_input"


class HyperConnection(NamedTuple):
    """The numbers of the equations above; hashable."""
    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6            # beside the Sinkhorn rounds' sums
    clamp: tuple = (-30.0, 30.0)  # of H~res before the exponential
    norm_eps: float = 1e-6       # beside the mean square of vec(X)

    @property
    def mixes(self):
        """Columns of ``phi``: n for pre, n for post, n^2 for res."""
        return self.streams * (self.streams + 2)


class Mixes(NamedTuple):
    """A sublayer's three mixes, float32, the tokens last."""
    pre: jax.Array     # [n, ...]
    post: jax.Array    # [n, ...]
    res: jax.Array     # [n, n, ...]: res[i, j] of stream j in new stream i


def init_params(rng, hc: HyperConnection, width: int, std: float = 0.02,
                alpha: float = 0.01, off_diagonal: float = -8.0):
    """One sublayer's parameters, float32: ``phi`` normal(0, std); the
    three ``alpha`` small, so that the mixes start as their biases say;
    ``b`` such that they start at ``H_pre = 1 / n`` (the sublayer reads
    the streams' mean), ``H_post = 1`` (and adds its output to every
    stream) and ``H_res`` the identity but for exp(off_diagonal) a pair:
    the plain residual network on ``n`` equal streams."""
    n = hc.streams
    # sigmoid(b) = 1 / n; one stream reads itself whole
    pre = -jnp.log(n - 1.0) if n > 1 else 30.0
    res = jnp.where(jnp.eye(n, dtype=bool), 0.0, off_diagonal)
    return {
        "phi": std * jax.random.normal(rng, (n * width, hc.mixes),
                                       jnp.float32),
        "b": jnp.concatenate([jnp.full((n,), pre, jnp.float32),
                              jnp.zeros((n,), jnp.float32),
                              res.reshape(-1).astype(jnp.float32)]),
        "alpha": jnp.full((3,), alpha, jnp.float32)}


def sinkhorn(logits, hc: HyperConnection):
    """[n, n, ...] -> the same shape, rows then columns normalised
    ``hc.sinkhorn_iters`` times from exp(clamp(logits))."""
    m = jnp.exp(jnp.clip(logits, *hc.clamp))
    for _ in range(hc.sinkhorn_iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc.eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + hc.eps)
    return m


def _streams(x):
    """The n streams of ``x`` [..., n, S, C], each [..., S, C]."""
    return [x[..., j, :, :] for j in range(x.shape[-3])]


def mixes(x, p, hc: HyperConnection) -> Mixes:
    """The three mixes of one sublayer from the streams ``x``
    [..., n, S, C] and its parameters ``p`` (``init_params``)."""
    n, width = hc.streams, x.shape[-1]
    # A stream's part of vec(X) phi, [mixes, ..., S], summed in float32.
    # Operands that arrive in bfloat16 multiply exactly in one pass of
    # the MXU (what a float32 product at the default precision is on a
    # TPU); float32 operands take the passes that keep them whole.
    phi = p["phi"].astype(jnp.float32).reshape(n, width, hc.mixes)
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    raw = sum(jnp.einsum("...c,ck->k...", xj.astype(jnp.float32), phi[j],
                         precision=precision)
              for j, xj in enumerate(_streams(x)))
    square = sum(jnp.sum(jnp.square(xj.astype(jnp.float32)), axis=-1)
                 for xj in _streams(x)) / (n * width)
    raw, square = checkpoint_name((raw, square), MIX_NAME)
    tokens = (1,) * square.ndim
    scale = jnp.repeat(p["alpha"].astype(jnp.float32),
                       np.array([n, n, n * n]))
    logits = (scale.reshape(-1, *tokens) * raw
              * jax.lax.rsqrt(square + hc.norm_eps)
              + p["b"].astype(jnp.float32).reshape(-1, *tokens))
    return Mixes(jax.nn.sigmoid(logits[:n]),
                 2.0 * jax.nn.sigmoid(logits[n:2 * n]),
                 sinkhorn(logits[2 * n:].reshape(n, n, *square.shape), hc))


def read(x, pre):
    """``H_pre X``: [..., n, S, C], [n, ..., S] -> [..., S, C] in x's
    dtype, summed in float32."""
    return sum(pre[j][..., None] * xj.astype(jnp.float32)
               for j, xj in enumerate(_streams(x))).astype(x.dtype)


def hc_pre(x, p, hc: HyperConnection):
    """(the sublayer's input ``u`` [..., S, C], its ``Mixes``)."""
    with jax.named_scope(SCOPE):
        mixed = mixes(x, p, hc)
        return checkpoint_name(read(x, mixed.pre), INPUT_NAME), mixed


def hc_post(x, y, mixed: Mixes):
    """``H_res X + H_post^T y``: the streams after a sublayer whose
    output is ``y`` [..., S, C]."""
    with jax.named_scope(SCOPE):
        streams = [s.astype(jnp.float32) for s in _streams(x)]
        y = y.astype(jnp.float32)
        return jnp.stack([
            sum(mixed.res[i, j][..., None] * s
                for j, s in enumerate(streams))
            + mixed.post[i][..., None] * y
            for i in range(len(streams))], axis=-3).astype(x.dtype)


def mix_counters(mixed: Mixes):
    """float32 [4] of one sublayer: the worst ``|row sum - 1|`` and
    ``|column sum - 1|`` of ``H_res`` over its tokens, the mean of
    ``H_pre`` and of ``H_post``."""
    with jax.named_scope(SCOPE):
        return jnp.stack([
            jnp.max(jnp.abs(jnp.sum(mixed.res, axis=1) - 1.0)),
            jnp.max(jnp.abs(jnp.sum(mixed.res, axis=0) - 1.0)),
            jnp.mean(mixed.pre), jnp.mean(mixed.post)])
