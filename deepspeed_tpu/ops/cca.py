"""Compressed convolutional attention's mixing (Zyphra's CCA,
arXiv:2510.04476, as ZAYA1 uses it, arXiv:2511.17127): what lies between
the latent projections of a position and the attention call.

With ``q~ [B, S, H x D]`` and ``k~ [B, S, K x D]`` the query and key
projections of the layer's normed input (H query heads on K key/value
heads of D, ``group = H / K`` query heads a key head), ``t`` a position
and zeros before position 0:

  mean    ``m_q[i] = (q~[i] + k~[i // group]) / 2`` a query head;
          ``m_k[j]`` the mean of ``m_q[i]`` over the heads i of key head j.
  conv0   over the SEQUENCE, depthwise on ``u = [q~ | k~]`` ((H + K) x D
          channels), ``taps0`` taps and a bias: ``u'_t[c] = sum_j w0[c, j]
          u_{t - taps0 + 1 + j}[c] + b0[c]``.
  conv1   over the sequence again, ONE GROUP A HEAD (H + K groups of D
          channels), dense within a head: ``u''_t[g] = sum_j u'_{t - taps1
          + 1 + j}[g] W1[g, j] + b1[g]``, ``W1[g, j]`` a D x D matrix.  What
          it reads before position 0 is ``b0``, conv0's output on the zeros
          before the sequence (the released module pads the zeros in front
          of conv0, not between the convs).  No activation between the
          two or after them.
  sum     ``q = u''[: H D] + m_q``, ``k = u''[H D :] + m_k``.
  values  key/value head j reads the position ``j`` before: head 0 is
          this position's, head 1 the one before (the value shift), zeros
          before position 0.
  norm    a head of q to ``sqrt(D) q / |q|``, a head of k to ``tau_j
          sqrt(D) k / |k|`` with ``tau`` one learned scalar a key head.

``mix_heads`` is the first four, ``unit_norm_heads`` the last: the XLA
form, float32 arithmetic on arrays that come and go in the activations'
dtype.  The mixed q and k pass from the one to the other in float32 and
are rounded ONCE, after the norm (rounded between the two, an element
carries two roundings, which is what sums kept in bf16 cost).  conv1's
products are float32 at the highest precision: its operand is a float32
sum, and a default float32 product on the TPU would round it to bf16
first.

Every step works on ONE HEAD's 128 channels at a time, a slice of whole
lane tiles of the flat arrays, and a head's two taps are one product over
``taps x D``: a reshape that puts the head count on the sublanes (10
heads, 4 query heads a key head, 2 key heads) makes the TPU lay the array
out again, and the mixing alone ran 3.8 ms forward and 8.9 with its
backward at 2 x 8,192 tokens written that way, 0.9 and 4.0 written this
way, to the same bits (my chip run, PR 64).
"""

import math

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def shift(x, by: int, fill=None):
    """x [B, S, C] read ``by`` positions earlier: ``y_t = x_{t - by}``,
    and ``fill`` ([C], or zeros) where that lies before position 0."""
    if by == 0:
        return x
    seq = x.shape[1]
    front = jnp.zeros_like(x[:, :1]) if fill is None else jnp.broadcast_to(
        fill.astype(x.dtype), x[:, :1].shape)
    return jnp.concatenate([jnp.repeat(front, min(by, seq), axis=1),
                            x[:, :max(seq - by, 0)]], axis=1)


def depthwise_conv(u, w, b):
    """conv0 on any number of channels: u [B, S, C] float32, w [C, taps],
    b [C]."""
    taps = w.shape[1]
    return sum(shift(u, taps - 1 - j) * w[:, j] for j in range(taps)) + b


def head_conv(u, w, b, before):
    """conv1 on ONE head: u [B, S, D] float32, w [taps, D, D] (a tap's
    matrix takes the head's D channels in and gives its D out), b [D];
    ``before`` [D] is what the conv reads before position 0.  The taps
    are one product over ``taps x D``."""
    taps, dim, _ = w.shape
    read = jnp.concatenate(
        [shift(u, taps - 1 - j, before) for j in range(taps)], axis=-1)
    return jnp.dot(read, w.reshape(taps * dim, dim),
                   precision=_HIGHEST) + b


def _heads(x, count):
    """x [B, S, count x D] -> its ``count`` heads, lane-tile slices."""
    dim = x.shape[-1] // count
    return [x[..., i * dim:(i + 1) * dim] for i in range(count)]


def shift_values(v, kv_heads: int):
    """v [B, S, K D]: key/value head j read j positions earlier."""
    return jnp.concatenate(
        [shift(head, j) for j, head in enumerate(_heads(v, kv_heads))],
        axis=-1)


def mix_heads(q, k, v, p, heads: int, kv_heads: int):
    """``q [B, S, H D]``, ``k [B, S, K D]``, ``v [B, S, K D]`` (the
    projections) -> (the H mixed query heads, the K mixed key heads, each
    FLOAT32 [B, S, D] for ``unit_norm_heads`` to round; the shifted v in
    its dtype).  ``p``: ``conv0_w [C, taps0]``, ``conv0_b [C]``,
    ``conv1_w [H + K, taps1, D, D]``, ``conv1_b [C]``, ``C = (H + K) D``."""
    f32 = jnp.float32
    group = heads // kv_heads
    q_lat = _heads(q.astype(f32), heads)
    k_lat = _heads(k.astype(f32), kv_heads)
    m_q = [0.5 * (q_lat[i] + k_lat[i // group]) for i in range(heads)]
    m_k = [sum(m_q[j * group:(j + 1) * group]) / group
           for j in range(kv_heads)]
    w0, b0 = p["conv0_w"].astype(f32), p["conv0_b"].astype(f32)
    w1, b1 = p["conv1_w"].astype(f32), p["conv1_b"].astype(f32)
    dim = q.shape[-1] // heads
    mixed = []
    for g, (latent, mean) in enumerate(zip(q_lat + k_lat, m_q + m_k)):
        own = slice(g * dim, (g + 1) * dim)
        u = depthwise_conv(latent, w0[own], b0[own])
        mixed.append(head_conv(u, w1[g], b1[own], b0[own]) + mean)
    return mixed[:heads], mixed[heads:], shift_values(v, kv_heads)


def unit_norm_heads(q_heads, k_heads, tau, dtype):
    """The heads of ``mix_heads`` (sequences of ``[B, S, D]``) -> q ``[B,
    S, H D]`` with every head at norm ``sqrt(D)`` and k ``[B, S, K D]`` at
    ``tau_j sqrt(D)`` (``tau`` [K]), in float32, rounded once to
    ``dtype``."""
    def unit(x, scale):
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True))
        return (math.sqrt(x.shape[-1]) * scale * x).astype(dtype)

    tau = tau.astype(jnp.float32)
    return (jnp.concatenate([unit(x, 1.0) for x in q_heads], axis=-1),
            jnp.concatenate([unit(x, tau[j])
                             for j, x in enumerate(k_heads)], axis=-1))
