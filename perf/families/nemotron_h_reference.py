"""Nemotron-H with sparse experts (``model_type: nemotron_h``,
NVIDIA-Nemotron-3-Nano-30B-A3B) in plain ``jax.numpy`` and float32: the
loss of a decoder whose every layer is ONE sublayer (a Mamba-2 mixer, a
sparse expert layer or grouped-query attention, by the pattern string),
and its gradients, given the same held experts and vocabulary rows as the
program.  No kernel, no mixed precision, no sort, no chunk, no fused
cross-entropy and NO chunked matrix form: the state-space recurrence is a
``lax.scan`` over POSITIONS, one at a time, with G groups of B and C;
attention is a masked softmax head by head; the experts are a Python loop
over the held ones.  Written from the equations below (ISSUE 60 took them
from the keys of the released ``config.json``, catalog row
``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, and from the published
description of the ``nemotron_h`` modules), not from the program's model
file; it imports no sibling reference.

u is a layer's normed input, [S, 2688] a batch row; no product has a
bias; ``N(x) = x / sqrt(mean(x^2) + 1e-5) * w``.

  model      h0 = E[ids]; layer i: h = h + Mixer_i(N_i(h)), Mixer_i by
             the i-th character of the pattern; logits = Nf(h) W_head
             (its own matrix); the loss the mean of -log
             softmax(logits)[next token].
  M          [z, xBC, dt] = u W_in (4,096, 6,144, 64);
             xBC = silu(conv(xBC) + b), depthwise over all 6,144
             channels, 4 taps, tap j reading position t - 3 + j;
             [x, B, C] = xBC (4,096, 8 x 128, 8 x 128);
             dt = softplus(dt + dt_bias); A = -exp(A_log);
             per head h of 64 (64 channels each), g = h // 8, S_0 = 0:
               S_t = exp(dt_t[h] A[h]) S_{t-1} + dt_t[h] x_t[h] (x) B_t[g]
               y_t[h] = S_t C_t[g] + D[h] x_t[h];
             y = N_8(y * silu(z)) * gain: the gate BEFORE the norm, the
             mean square over each group's 512 channels; y W_out.
  *          q = u Wq as [S, 32, 128], k, v = u Wk, u Wv as [S, 2, 128];
             NO rotation or other positional operation; query head h
             reads key/value head h // 16; a_h = softmax(q_h k^T /
             sqrt(128) + causal mask) v; concat_h(a_h) Wo.
  E          s = sigmoid(u Wr) over 128 experts; P the 6 largest of
             s + b; w_e = 2.5 s_e / sum_{j in P} s_j; E(u) = Shared(u) +
             sum_{e in P, e held} w_e Expert_e(u); Expert_e(x) =
             (relu(x W_up)^2) W_down of width 1,856, Shared the same of
             width 3,712: NO gate.  A pick on an expert not held here
             adds nothing and keeps its part of the normalisation.  b has
             no gradient; after an optimizer step b_e += gamma
             sign(mean(c) - c_e), c the picks an expert over the step's
             tokens (``bias_update``).

Departures from the published modules, each noted (``assumed`` in
perf/configs/nemotron-3-nano-30b-a3b.json has the grounds): (1) NO
positional operation in attention although the config carries
``rope_theta`` (the Nemotron-H report, arXiv:2504.03624); (2) the
router's score a sigmoid and its selection bias, with gamma 0.001, on the
ground of the router's keys; (3) every position's state is 4.3 GB a row
and layer at 8,192 positions, so the scan over positions sits under
``jax.checkpoint`` in blocks of ``pos_block`` positions: the same sums in
the same order, recomputed instead of kept; (4) the published module
computes the scan in its own chunked form; this file does not, on
purpose; (5) heads of attention are mapped one after another, the logits
are made again for the gradient and every layer runs under
``jax.checkpoint``, for memory alone; (6) ``time_step_limit`` is the
default (0, inf): no clamp of dt.

``picks`` (int32 [gates, S, 6]) replaces every gate's choice of P and
keeps the rest: a top-6 choice is discontinuous, so a comparison of
gradients is made on the program's picks (perf/families/nemotron_h.py).

On a TPU a float32 product runs in reduced precision unless told
otherwise, so the entry point sets ``default_matmul_precision("highest")``.
"""

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


class Spec(NamedTuple):
    """The numbers of the equations; hashable, a static argument."""
    pattern: Tuple[str, ...] = ()   # the kind of each entry of ``layers``
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    ssm_heads: int = 64
    ssm_dim: int = 64
    states: int = 128
    groups: int = 8
    eps: float = 1e-5
    picked: int = 6
    scale: float = 2.5
    held_first: int = 0
    gamma: float = 0.001
    pos_block: int = 64        # positions whose states are held at once


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def mm(a, b):
    """Every product the MXU would take (weights, router, attention and
    head alike) goes through here, so that a check can lower its
    precision and see the comparison fail."""
    return a @ b


def carried(state):
    """The state as the recurrence carries it from one position to the
    next: itself.  A check replaces this to round it (an fp8 state) and
    see the comparison fail."""
    return state


def router_scores(u, w_router):
    """[S, E]: a sigmoid score an expert."""
    return jax.nn.sigmoid(mm(u, w_router))


def relu2_mlp(p, u):
    """An expert WITHOUT a gate: the square of the rectified first
    product, then the second."""
    return mm(jnp.square(jnp.maximum(mm(u, p["Wup"]), 0.0)), p["Wdown"])


# ---------------------------------------------------------------------- #
# attention
# ---------------------------------------------------------------------- #
def placed(t):
    """q or k [S, heads, D] as the scores read it: itself, NO positional
    operation.  A check replaces this with a rotation and sees the
    comparison fail."""
    return t


def attention(p, u, spec):
    """u [S, hidden] -> [S, hidden]; no position enters but through the
    causal mask."""
    seq, dim = u.shape[0], spec.head_dim
    q = placed(mm(u, p["Wq"]).reshape(seq, spec.heads, dim))
    k = placed(mm(u, p["Wk"]).reshape(seq, spec.kv_heads, dim))
    v = mm(u, p["Wv"]).reshape(seq, spec.kv_heads, dim)
    group = spec.heads // spec.kv_heads
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    causal = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args                                  # [S, D]
        scores = mm(q_h, k_h.T) / math.sqrt(dim)
        return mm(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                                 axis=-1), v_h)

    a = jax.lax.map(head, tuple(x.transpose(1, 0, 2) for x in (q, k, v)))
    return mm(a.transpose(1, 0, 2).reshape(seq, spec.heads * dim), p["Wo"])


# ---------------------------------------------------------------------- #
# the mixer
# ---------------------------------------------------------------------- #
def conv(x, w, b):
    """x [S, C], w [C, taps], b [C]: tap j reads position t - (taps - 1)
    + j, positions before the first are 0."""
    taps, seq = w.shape[1], x.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + seq] * w[:, j] for j in range(taps)) + b


def group_of_head(t, heads):
    """[G, N] a position's B or C -> [H, N]: head h reads group h // (H /
    G)."""
    return jnp.repeat(t, heads // t.shape[0], axis=0)


def recurrence(x, dt, a, b_mat, c_mat, d, spec):
    """x [S, H, P], dt [S, H], a [H], b_mat and c_mat [S, G, N], d [H] ->
    y [S, H, P], position by position."""
    seq, heads = x.shape[:2]
    block = min(spec.pos_block, seq)
    pad = -seq % block

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        b_h, c_h = group_of_head(b_t, heads), group_of_head(c_t, heads)
        state = (jnp.exp(dt_t * a)[:, None, None] * carried(state)
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    @jax.checkpoint
    def positions(state, xs):
        return jax.lax.scan(step, state, xs)

    def blocked(t):
        # a padded position has dt 0 and x 0: it leaves the state as is
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape(-1, block, *t.shape[1:])

    state = jnp.zeros(x.shape[1:] + (b_mat.shape[-1],), jnp.float32)
    _, y = jax.lax.scan(positions, state,
                        tuple(blocked(t) for t in (x, dt, b_mat, c_mat)))
    return y.reshape(-1, *x.shape[1:])[:seq] + d[:, None] * x


def grouped_gated_norm(y, z, gain, spec):
    """``N_G(y * silu(z)) * gain``: the gate first, then the mean square
    over each group's channels."""
    seq = y.shape[0]
    g = (y * silu(z)).reshape(seq, spec.groups, -1)
    g = g / jnp.sqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                     + spec.eps)
    return g.reshape(seq, -1) * gain


def mamba(p, u, spec):
    """u [S, hidden] -> [S, hidden]."""
    seq, n, groups = u.shape[0], spec.states, spec.groups
    inner = spec.ssm_heads * spec.ssm_dim
    z, xbc, dt = jnp.split(mm(u, p["Win"]),
                           [inner, 2 * inner + 2 * groups * n], axis=-1)
    xbc = silu(conv(xbc, p["conv_w"], p["conv_b"]))
    x, b_mat, c_mat = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    y = recurrence(x.reshape(seq, spec.ssm_heads, spec.ssm_dim),
                   softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                   b_mat.reshape(seq, groups, n),
                   c_mat.reshape(seq, groups, n), p["D"], spec)
    return mm(grouped_gated_norm(y.reshape(seq, inner), z, p["norm_w"],
                                 spec), p["Wout"])


# ---------------------------------------------------------------------- #
# the experts
# ---------------------------------------------------------------------- #
def choose(scores, bias, picked):
    """The ``picked`` largest of score + bias (one expert group: the
    group step of the router is the identity)."""
    return jax.lax.top_k(scores + bias, picked)[1]


def experts(p, u, spec, picks=None):
    """(E(u), (scores [S, E], picks [S, 6]))."""
    scores = router_scores(u, p["Wr"])
    if picks is None:
        picks = choose(scores, p["bias"], spec.picked)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = spec.scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    out = relu2_mlp(p["shared"], u)
    held = p["experts"]["Wup"].shape[0]
    for e in range(held):
        one = {name: w[e] for name, w in p["experts"].items()}
        weight = jnp.sum(jnp.where(picks == spec.held_first + e, weights,
                                   0.0), axis=-1)
        out = out + weight[:, None] * relu2_mlp(one, u)
    return out, (scores, picks)


def layer(p, x, kind, spec, picks=None):
    """(h + Mixer(N(h)), the gate's (scores, picks) or None)."""
    u = rms_norm(x, p["norm"], spec.eps)
    if kind == MAMBA:
        return x + mamba(p, u, spec), None
    if kind == ATTENTION:
        return x + attention(p, u, spec), None
    out, routing = experts(p, u, spec, picks)
    return x + out, routing


@jax.checkpoint
def cross_entropy(h, w_head, targets):
    """Sum of -log p(target) over the positions of ``h`` [S', hidden];
    the logits are made again for the gradient, not kept."""
    logp = jax.nn.log_softmax(mm(h, w_head), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def hidden(params, ids_row, spec, picks=None):
    """One row [S]: (the final norm's output [S, hidden], [(scores,
    picks)] of the gates in order)."""
    h = params["embed"][ids_row]
    routed = []
    for p, kind in zip(params["layers"], spec.pattern):
        forced = picks[len(routed)] if (
            kind == EXPERTS and picks is not None) else None
        h, routing = jax.checkpoint(
            lambda p_, h_, f_, kind=kind: layer(p_, h_, kind, spec, f_))(
            p, h, forced)
        if kind == EXPERTS:
            routed.append(routing)
    return rms_norm(h, params["norm"], spec.eps), routed


def logits(params, ids, spec):
    """[B, S, vocab]."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([mm(hidden(params, row, spec)[0], params["head"])
                          for row in ids])


def forward(params, ids, spec, picks=None):
    """(L, (scores [G, B S, E], picks [G, B S, 6])) of int32 ``ids``
    [B, S] over the G gates: the mean next-token cross-entropy over the
    B (S - 1) positions that have a next token."""
    with jax.default_matmul_precision("highest"):
        rows, seq = ids.shape
        total, routed = 0.0, []
        for b in range(rows):
            forced = None if picks is None else picks.reshape(
                picks.shape[0], rows, seq, -1)[:, b]
            h, row_routed = hidden(params, ids[b], spec, forced)
            total = total + cross_entropy(h[:-1], params["head"], ids[b, 1:])
            routed.append(row_routed)
        gates = len(routed[0])
        scores, chosen = (
            jnp.stack([jnp.concatenate([r[g][part] for r in routed])
                       for g in range(gates)]) if gates else None
            for part in (0, 1))
        return total / (rows * (seq - 1)), (scores, chosen)


def bias_update(bias, counts, gamma):
    """The selection bias after an optimizer step: ``counts`` [E] the
    picks an expert over the step's tokens."""
    counts = counts.astype(jnp.float32)
    return bias + gamma * jnp.sign(jnp.mean(counts) - counts)


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, spec, picks=None):
    """((L, (scores, picks)), L's gradient in the tree of ``params``; the
    biases' is zero)."""
    return jax.value_and_grad(forward, has_aux=True)(params, ids, spec, picks)
