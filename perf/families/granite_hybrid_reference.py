"""Granite 4.0-H (``model_type: granitemoehybrid`` without experts) in
plain ``jax.numpy`` and float32: the loss of a decoder of Mamba-2 mixers
and position-free grouped-query attention layers, and its gradients.  No
kernel, no mixed precision, no fused cross-entropy and NO chunked matrix
form: the state-space recurrence is a ``lax.scan`` over POSITIONS, one
at a time, so that it shares no algebra with ``ops/ssd_scan.py``;
attention is a masked softmax head by head; the logits are whole rows of
the vocabulary.  Written from the equations below (ISSUE 53 took them
from the keys of the released ``config.json``, catalog row
``granite-4.0-h-micro``, and from the published description of
``GraniteMoeHybridMambaLayer`` / ``...Attention`` / ``...MLP``), not
from the program's model file.

x is a batch row, [S, 2048]; no product has a bias; ``N(x) = x /
sqrt(mean(x^2) + 1e-5) * w``; ``r`` = 0.22.

  model      h0 = 12 E[ids]; the layers; logits = (N(h) E^T) / 8; the
             loss the mean of -log softmax(logits)[next token].
  layer      h = h + r Mixer(N1(h));  h = h + r FFN(N2(h)).
  FFN        [g, v] = u W_in (halves in that order, 8,192 each);
             (silu(g) * v) W_out.
  attention  q = u Wq as [S, 32, 64], k, v = u Wk, u Wv as [S, 8, 64];
             NO rotation or other positional operation; query head h
             reads key/value head h // 4; a_h = softmax(0.015625 q_h
             k^T + causal mask) v; concat_h(a_h) Wo.
  mamba      [z, xBC, dt] = u W_in (4,096, 4,352, 64);
             xBC = silu(conv(xBC) + b), depthwise over all 4,352
             channels, 4 taps, tap j reading position t - 3 + j;
             [x, B, C] = xBC (4,096, 128, 128);
             dt = softplus(dt + dt_bias); A = -exp(A_log);
             per head h of 64 (64 channels each), S_0 = 0:
               S_t = exp(dt_t[h] A[h]) S_{t-1} + dt_t[h] x_t[h] (x) B_t
               y_t[h] = S_t C_t + D[h] x_t[h];
             y = N(y * silu(z)) over all 4,096 channels, its own gain;
             y W_out.

Departures from the published module, each noted: (1) every position's
state is 8.6 GB a layer at 4,096 positions, so the scan over positions
sits under ``jax.checkpoint`` in blocks of ``pos_block`` positions (a
nest of two scans: the block-entry states are kept, a block's states are
rebuilt for its backward pass): the same sums in the same order,
recomputed instead of kept; it is the one departure in the arithmetic's
organisation.  (2) ``time_step_limit`` is HF's default (0, inf): no
clamp of dt.  (3) The published module computes the scan in its own
chunked form; this file does not, on purpose.  (4) Heads of attention
are mapped one after another, the logits are taken by blocks of rows,
and every layer runs under ``jax.checkpoint``, for memory alone.

On a TPU a float32 product runs in reduced precision unless told
otherwise, so the entry point sets ``default_matmul_precision(
"highest")``.
"""

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

MAMBA, ATTENTION = "mamba", "attention"


class Spec(NamedTuple):
    """The numbers of the equations; hashable, a static argument."""
    kinds: Tuple[str, ...] = ()    # the kind of each run of ``layers``
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    ssm_heads: int = 64
    ssm_dim: int = 64
    states: int = 128
    eps: float = 1e-5
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    row_block: int = 1024      # positions whose logits are held at once
    pos_block: int = 64        # positions whose states are held at once
    # a run's layers as a ``lax.scan`` over one traced layer instead of a
    # Python loop over them
    rolled: bool = False


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def mm(a, b):
    """Every product the MXU would take (weights, attention and head
    alike) goes through here, so that a check can lower its precision
    and see the comparison fail."""
    return a @ b


def carried(state):
    """The state as the recurrence carries it from one position to the
    next: itself.  A check replaces this to round it (a bf16 or fp8
    state) and see the comparison fail."""
    return state


def scores(q_h, k_h, scale):
    """[S, S]: one head's scaled scores, before the mask."""
    return scale * mm(q_h, k_h.T)


def attention(p, u, spec):
    """u [S, hidden] -> [S, hidden]; no position enters but through the
    causal mask."""
    seq, dim = u.shape[0], spec.head_dim
    q = mm(u, p["Wq"]).reshape(seq, spec.heads, dim)
    k = mm(u, p["Wk"]).reshape(seq, spec.kv_heads, dim)
    v = mm(u, p["Wv"]).reshape(seq, spec.kv_heads, dim)
    group = spec.heads // spec.kv_heads
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    causal = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args                                  # [S, D]
        return mm(jax.nn.softmax(jnp.where(
            causal, scores(q_h, k_h, spec.attention_multiplier), -jnp.inf),
            axis=-1), v_h)

    a = jax.lax.map(head, tuple(x.transpose(1, 0, 2) for x in (q, k, v)))
    return mm(a.transpose(1, 0, 2).reshape(seq, spec.heads * dim), p["Wo"])


def conv(x, w, b):
    """x [S, C], w [C, taps], b [C]: tap j reads position t - (taps - 1)
    + j, positions before the first are 0."""
    taps, seq = w.shape[1], x.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + seq] * w[:, j] for j in range(taps)) + b


def recurrence(x, dt, a, b_mat, c_mat, d, spec):
    """x [S, H, P], dt [S, H], a [H], b_mat and c_mat [S, N], d [H] ->
    y [S, H, P], position by position."""
    seq = x.shape[0]
    block = min(spec.pos_block, seq)
    pad = -seq % block

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[:, None, None] * carried(state)
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    @jax.checkpoint
    def positions(state, xs):
        return jax.lax.scan(step, state, xs)

    def blocked(t):
        # a padded position has dt 0 and x 0: it leaves the state as is
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape(-1, block, *t.shape[1:])

    state = jnp.zeros(x.shape[1:] + (b_mat.shape[1],), jnp.float32)
    _, y = jax.lax.scan(positions, state,
                        tuple(blocked(t) for t in (x, dt, b_mat, c_mat)))
    return y.reshape(-1, *x.shape[1:])[:seq] + d[:, None] * x


def mamba(p, u, spec):
    """u [S, hidden] -> [S, hidden]."""
    seq, n = u.shape[0], spec.states
    inner = spec.ssm_heads * spec.ssm_dim
    z, xbc, dt = jnp.split(mm(u, p["Win"]), [inner, 2 * inner + 2 * n],
                           axis=-1)
    xbc = silu(conv(xbc, p["conv_w"], p["conv_b"]))
    x, b_mat, c_mat = jnp.split(xbc, [inner, inner + n], axis=-1)
    y = recurrence(x.reshape(seq, spec.ssm_heads, spec.ssm_dim),
                   softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                   b_mat, c_mat, p["D"], spec)
    y = rms_norm(y.reshape(seq, inner) * silu(z), p["norm_w"], spec.eps)
    return mm(y, p["Wout"])


def ffn(p, u):
    g, v = jnp.split(mm(u, p["Wffn_in"]), 2, axis=-1)
    return mm(silu(g) * v, p["Wffn_out"])


def layer(p, x, kind, spec):
    r = spec.residual_multiplier
    mixer = mamba if kind == MAMBA else attention
    h = x + r * mixer(p, rms_norm(x, p["norm1"], spec.eps), spec)
    return h + r * ffn(p, rms_norm(h, p["norm2"], spec.eps))


def token_losses(h, table, targets, spec):
    """[S]: -log softmax(h E^T / logits_scaling)[target] of every
    position, the logits of ``row_block`` positions at a time."""
    @jax.checkpoint
    def rows(h_rows, t_rows):
        logp = jax.nn.log_softmax(
            mm(h_rows, table.T) / spec.logits_scaling, axis=-1)
        return -jnp.take_along_axis(logp, t_rows[:, None], axis=-1)[:, 0]

    step = min(spec.row_block, h.shape[0])
    return jnp.concatenate([rows(h[i:i + step], targets[i:i + step])
                            for i in range(0, h.shape[0], step)])


def hidden(params, ids, spec):
    """One row, ids [S] -> the final norm's output [S, hidden].
    ``params["layers"]`` is one dict a RUN of like layers (``spec.kinds``
    names each run's kind), its arrays stacked over the run's layers."""
    h = spec.embedding_multiplier * params["embed"][ids]
    for kind, run in zip(spec.kinds, params["layers"]):
        one = jax.checkpoint(lambda p_, h_, kind=kind: layer(p_, h_, kind,
                                                             spec))
        if spec.rolled:
            h, _ = jax.lax.scan(lambda h_, p_: (one(p_, h_), None), h, run)
        else:
            for i in range(jax.tree.leaves(run)[0].shape[0]):
                h = one(jax.tree.map(lambda a: a[i], run), h)
    return rms_norm(h, params["norm"], spec.eps)


def logits(params, ids, spec):
    """[B, S, vocab]."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            mm(hidden(params, row, spec), params["embed"].T)
            / spec.logits_scaling for row in ids])


def forward(params, ids, spec):
    """int32 ``ids`` [B, S] -> the mean next-token cross-entropy over the
    B (S - 1) positions that have a next token."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(jnp.concatenate([
            token_losses(hidden(params, row, spec)[:-1], params["embed"],
                         row[1:], spec) for row in ids]))


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, spec):
    """(the loss, its gradient in the tree of ``params``)."""
    return jax.value_and_grad(forward)(params, ids, spec)
