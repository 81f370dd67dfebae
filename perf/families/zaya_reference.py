"""ZAYA (``model_type: zaya``, Zyphra ZAYA1-8B) in plain ``jax.numpy``
and float32: the loss of a decoder whose every layer is an attention
sublayer in a compressed latent (compressed convolutional attention) and
a top-1 expert sublayer chosen by an MLP router that carries a state from
layer to layer, and its gradients, given the same held experts and
vocabulary rows as the program.  No kernel, no mixed precision, no sort,
no chunk, no fused cross-entropy, no recomputation plan and no stacking:
the layers are a Python list, the convs are shifted sums, the value shift
is an index, attention is a masked softmax head by head, the experts are
a loop over the held ones.  Written from the equations below
(ISSUE 64 took them from the keys of the released ``config.json``,
catalog row ``ZAYA1-8B``, the ZAYA1 technical report, arXiv:2511.17127,
and "Compressed Convolutional Attention", arXiv:2510.04476), not from
the program's model file; it imports no sibling reference.

x is the stream of one batch row, [S, 2048]; ``N(x) = x / sqrt(mean(x^2)
+ 1e-5) * w``; ``t`` a position, zeros before position 0.

  merge      every sublayer f with its own norm: ``x <- a * (x + c) + g *
             (f(N(x)) + d)``; the model's first sublayer (layer 0's
             attention) has no a, c: ``x <- x + g * (f(N(x)) + d)``.
  attention  h = N(x).  ``q~ = h W_q`` as [S, 8, 128], ``k~ = h W_k`` as
             [S, 2, 128].  ``m_q[i] = (q~[i] + k~[i // 4]) / 2``,
             ``m_k[j]`` the mean of m_q[i] over i // 4 == j.  ``u = [q~ |
             k~]`` (1,280 channels); conv0, depthwise, 2 taps: ``u'_t[c] =
             w0[c, 0] u_{t-1}[c] + w0[c, 1] u_t[c] + b0[c]``; conv1, one
             group a head, 2 taps: ``u''_t[g] = u'_{t-1}[g] W1[g, 0] +
             u'_t[g] W1[g, 1] + b1[g]`` with ``u'_{-1} = b0``.  ``q =
             u''[:1024] + m_q``, ``k = u''[1024:] + m_k``.  Values: head
             0 is ``h_t W_v1``, head 1 is ``h_{t-1} W_v2``.  ``q^ =
             sqrt(128) q / |q|`` a head, ``k^ = tau_j sqrt(128) k / |k|``.
             Rotate-half over the first 64 of a head's 128 dimensions at
             theta 5e6; query head i on key/value head i // 4; ``softmax(
             q^ k^T / sqrt(128) + causal mask) v``; ``y = o W_o``.
  router     h = N(x) (the expert sublayer's norm).  ``r_l = h W_d + b_d``;
             ``r_l <- r_l + gamma_l * r_{l-1}`` for l > 0; layer l + 1
             reads r_l as it now stands.  ``logits = gelu(gelu(N(r_l) W_1
             + b_1) W_2 + b_2) W_3`` (exact gelu, 16 outputs); ``p =
             softmax(logits)``; the pick is ``argmax(p + beta)``; the
             weight is ``p[pick]`` as it is (not renormalised).  beta has
             no gradient; after an optimizer step ``beta_e += gamma_b
             sign(mean(c) - c_e)``, c the picks an expert over the step's
             tokens (``bias_update``).
  experts    ``y = p[pick] (silu(h W_g) * (h W_u)) W_dn`` of the picked
             expert if it is held here; a pick on an expert not held adds
             nothing (y = 0 there, and d still enters the merge).
  head       ``logits = N_f(x) E^T`` on the embedding's own rows; the loss
             the mean of -log softmax(logits)[next token].

Departures from the papers and the released module, each noted
(``assumed`` in perf/configs/zaya1-8b.json has the grounds): (1) the skip
"expert" of the sibling configurations (mixture of depths) is NOT here:
the row's config has no key for it and neither paper gives its equation;
(2) the balancing bias moves by the sign rule with gamma_b 0.001, not by
the report's own controller; (3) ``tau`` multiplies the normalised key
directly (its parametrisation is assumed); (4) which sublayer owns which
``a, c`` (the first has none) and that gamma_0 does not exist; (5)
``u'_{-1} = b0``: the released module pads its zeros in front of conv0;
(6) heads of attention are mapped one after another, the logits are made
again for the gradient and every layer runs under ``jax.checkpoint``,
for memory alone: the same sums.

``picks`` (int32 [layers, S, 1]) replaces every layer's choice and keeps
the rest: a top-1 choice is discontinuous, so a comparison of gradients
is made on the program's picks (perf/families/zaya.py).

On a TPU a float32 product runs in reduced precision unless told
otherwise, so the entry points set ``default_matmul_precision("highest")``.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Spec(NamedTuple):
    """The numbers of the equations; hashable, a static argument."""
    heads: int = 8
    kv_heads: int = 2
    head_dim: int = 128
    rotated: int = 64
    theta: float = 5e6
    eps: float = 1e-5
    held_first: int = 0
    gamma: float = 0.001       # the balancing bias's step


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gelu(x):
    """The exact form."""
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def mm(a, b):
    """Every product the MXU would take (projections, the conv within a
    head, attention, experts and head) goes through here, so that a check
    can lower its precision and see the comparison fail."""
    return a @ b


def router_mm(a, b):
    """The router's products, apart: a check lowers their precision
    alone."""
    return a @ b


def summed(x):
    """A float32 sum of the mixing as it is handed on (the q-k mean, a
    conv's taps and bias, the normalised head): itself.  A check replaces
    this to round it to bf16 and see the comparison fail."""
    return x


# ---------------------------------------------------------------------- #
# attention
# ---------------------------------------------------------------------- #
def before(x, first=None):
    """x [S, C] read one position earlier; ``first`` ([C], or zeros) at
    position 0."""
    row = jnp.zeros_like(x[:1]) if first is None else first[None, :]
    return jnp.concatenate([row, x[:-1]])


def conv0(u, w, b):
    """Depthwise, 2 taps: tap 0 reads the position before."""
    return summed(before(u) * w[:, 0] + u * w[:, 1] + b)


def conv1(u, w, b, first):
    """One group a head: u [S, G x D], w [G, 2, D, D] (tap, in, out);
    ``first`` is what tap 0 reads at position 0."""
    seq, (groups, _, dim, _) = u.shape[0], w.shape
    now = u.reshape(seq, groups, dim)
    prev = before(u, first).reshape(seq, groups, dim)
    out = jnp.stack([mm(prev[:, g], w[g, 0]) + mm(now[:, g], w[g, 1])
                     for g in range(groups)], axis=1)
    return summed(out.reshape(seq, groups * dim) + b)


def rotate(t, spec):
    """t [S, heads, D]: rotate-half over the first ``rotated`` dimensions
    of a head, pairs (i, i + rotated / 2), position t at angle t theta^(-2
    i / rotated)."""
    half = spec.rotated // 2
    i = jnp.arange(half, dtype=jnp.float32)
    angle = (jnp.arange(t.shape[0], dtype=jnp.float32)[:, None]
             * spec.theta ** (-2.0 * i / spec.rotated))[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = t[..., :half], t[..., half:2 * half], t[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def unit(t, dim):
    """Every head [.., D] to norm sqrt(D)."""
    return summed(math.sqrt(dim) * t
                  / jnp.sqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True)))


def mixed(p, q_lat, k_lat, v_flat, spec):
    """The projections of one row (``q~ [S, 1024]``, ``k~ [S, 256]``, the
    two value heads ``[S, 256]``) -> q [S, 8, 128] and k [S, 2, 128],
    mixed and of unit norm (k times tau), and v [S, 2, 128] with head 1
    shifted: everything between the projections and the rotation."""
    seq, dim = q_lat.shape[0], spec.head_dim
    heads, kv = spec.heads, spec.kv_heads
    q3 = q_lat.reshape(seq, kv, heads // kv, dim)
    m_q = summed(0.5 * (q3 + k_lat.reshape(seq, kv, 1, dim)))
    m_k = summed(jnp.mean(m_q, axis=2))
    u = conv0(jnp.concatenate([q_lat, k_lat], axis=-1), p["conv0_w"],
              p["conv0_b"])
    u = conv1(u, p["conv1_w"], p["conv1_b"], p["conv0_b"])
    q = u[:, :heads * dim].reshape(seq, heads, dim) + m_q.reshape(
        seq, heads, dim)
    k = u[:, heads * dim:].reshape(seq, kv, dim) + m_k
    # the value shift: head j reads the position j before, as an index
    v = v_flat.reshape(seq, kv, dim)
    at = jnp.arange(seq)[:, None] - jnp.arange(kv)[None, :]       # [S, K]
    v = jnp.where((at >= 0)[:, :, None],
                  v[jnp.maximum(at, 0), jnp.arange(kv)[None, :]], 0.0)
    return unit(q, dim), unit(k, dim) * p["tau"][:, None], v


def attention(p, h, spec):
    """h [S, hidden] -> [S, hidden]."""
    seq, dim = h.shape[0], spec.head_dim
    q, k, v = mixed(
        p, mm(h, p["Wq"]), mm(h, p["Wk"]),
        jnp.concatenate([mm(h, p["Wv1"]), mm(h, p["Wv2"])], axis=-1), spec)
    q, k = rotate(q, spec), rotate(k, spec)
    k, v = (jnp.repeat(t, spec.heads // spec.kv_heads, axis=1)
            for t in (k, v))
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
# the router and the experts
# ---------------------------------------------------------------------- #
def router_state(p, h, carried):
    """r_l [S, 256]: the down-projection, plus gamma times the layer
    before's where the layer has a gamma (every layer but the first)."""
    state = router_mm(h, p["Wd"]) + p["bd"]
    return state + p["gamma"] * carried if "gamma" in p else state


def router_scores(p, state, eps):
    """p [S, E]: the softmax of the router MLP's logits on its state."""
    z = rms_norm(state, p["norm"], eps)
    z = gelu(router_mm(z, p["W1"]) + p["b1"])
    z = gelu(router_mm(z, p["W2"]) + p["b2"])
    return jax.nn.softmax(router_mm(z, p["W3"]), axis=-1)


def choose(scores, bias):
    """The largest of p + beta, [S, 1]."""
    return jnp.argmax(scores + bias, axis=-1)[:, None]


def gated_mlp(p, h):
    return mm(silu(mm(h, p["Wg"])) * mm(h, p["Wu"]), p["Wdn"])


def experts(p, h, scores, spec, picks=None):
    """(the held experts' part of the sublayer's output, picks [S, 1]);
    the held experts one after the other (a ``lax.scan`` over them: one
    traced expert, so that the compiled program stays under the compile
    cache's size for a value)."""
    if picks is None:
        picks = choose(scores, p["bias"])
    weight = jnp.take_along_axis(scores, picks, axis=-1)[:, 0]
    held = p["experts"]["Wg"].shape[0]

    def one(out, at):
        e, weights = at
        here = jnp.where(picks[:, 0] == spec.held_first + e, weight, 0.0)
        return out + here[:, None] * gated_mlp(weights, h), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (jnp.arange(held), p["experts"]))
    return out, picks


def merge(x, y, s):
    kept = s["a"] * (x + s["c"]) if "a" in s else x
    return kept + s["g"] * (y + s["d"])


def layer(p, x, carried, spec, picks=None):
    """(the stream after the layer, r_l, (p [S, E], picks [S, 1]))."""
    x = merge(x, attention(p, rms_norm(x, p["norm_attn"], spec.eps), spec),
              p["merge_attn"])
    h = rms_norm(x, p["norm_moe"], spec.eps)
    state = router_state(p["router"], h, carried)
    scores = router_scores(p["router"], state, spec.eps)
    y, picks = experts(p, h, scores, spec, picks)
    return merge(x, y, p["merge_moe"]), state, (scores, picks)


@jax.checkpoint
def cross_entropy(h, table, targets):
    """Sum of -log p(target) over the positions of ``h`` [S', hidden] on
    the table's own rows; the logits are made again for the gradient, not
    kept."""
    logp = jax.nn.log_softmax(mm(h, table.T), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def hidden(params, ids_row, spec, picks=None):
    """One row [S]: (the final norm's output [S, hidden], [(p, picks)] of
    the layers in order)."""
    x = params["embed"][ids_row]
    carried = jnp.zeros((), jnp.float32)
    routed = []
    for i, p in enumerate(params["layers"]):
        forced = None if picks is None else picks[i]
        x, carried, routing = jax.checkpoint(
            lambda p_, x_, c_, f_: layer(p_, x_, c_, spec, f_))(
            p, x, carried, forced)
        routed.append(routing)
    return rms_norm(x, params["norm"], spec.eps), routed


def logits(params, ids, spec):
    """[B, S, vocab]."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([mm(hidden(params, row, spec)[0], params["embed"].T)
                          for row in ids])


def forward(params, ids, spec, picks=None):
    """(L, (p [layers, B S, E], picks [layers, B S, 1])) of int32 ``ids``
    [B, S]: the mean next-token cross-entropy over the B (S - 1)
    positions that have a next token."""
    with jax.default_matmul_precision("highest"):
        rows, seq = ids.shape
        total, routed = 0.0, []
        for b in range(rows):
            forced = None if picks is None else picks.reshape(
                picks.shape[0], rows, seq, -1)[:, b]
            h, row_routed = hidden(params, ids[b], spec, forced)
            total = total + cross_entropy(h[:-1], params["embed"], ids[b, 1:])
            routed.append(row_routed)
        scores, chosen = (
            jnp.stack([jnp.concatenate([r[g][part] for r in routed])
                       for g in range(len(routed[0]))])
            for part in (0, 1))
        return total / (rows * (seq - 1)), (scores, chosen)


def bias_update(bias, counts, gamma):
    """The balancing bias after an optimizer step: ``counts`` [E] the
    picks an expert over the step's tokens."""
    counts = counts.astype(jnp.float32)
    return bias + gamma * jnp.sign(jnp.mean(counts) - counts)


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, spec, picks=None):
    """((L, (p, picks)), L's gradient in the tree of ``params``; the
    biases' is zero)."""
    return jax.value_and_grad(forward, has_aux=True)(params, ids, spec, picks)
