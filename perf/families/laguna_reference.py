"""Laguna-XS.2 in plain ``jax.numpy`` and float32: loss and gradients of
next-token prediction, given the same held experts and vocabulary rows as
the program.  No kernel, no mixed precision, no sort: attention is a
masked softmax head by head and the experts are a Python loop.  Written
from the equations below, not from the program's model file.

Where each equation comes from (the builder had no network; the
equations are those of ISSUE 36, which took them from the keys of the
released ``config.json``, catalog row ``Laguna-XS.2``).  u is a layer's
normed input, [S, 2048] a batch row; every product is without bias.

  block       h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h));  after
              the last layer a final RMSNorm and logits = x W_head; the
              loss is the mean next-token cross-entropy over as many rows
              as W_head has columns.  RMSNorm(x) = x / sqrt(mean(x^2) +
              1e-6) * w.
  attention   layer with H heads (48 in a full layer, 64 in a sliding
              one): q = u Wq as [S, H, 128]; k = u Wk, v = u Wv as
              [S, 8, 128].  Rotary on q and k, rotate-half pairing
              (i, i + r/2) over the first r dimensions, the rest
              unchanged.  Sliding layers: r = 128, inv_freq_i =
              10000^(-2i/128), i = 0..63.  Full layers: r = 64, YaRN over
              i = 0..31: f_i = 500000^(-2i/64); low, high = floor, ceil
              of 64 ln(4096 / (2 pi b)) / (2 ln 500000) at b = 64 and
              b = 1, clipped to [0, 63]; ramp_i = clip((i - low) / (high
              - low), 0, 1); inv_freq_i = (f_i / 64) ramp_i + f_i (1 -
              ramp_i); cos and sin times 1.4158883083359672.  Key/value
              head j serves query heads [j g, (j + 1) g), g = H / 8.
              a_h = softmax(q_h k^T / sqrt(128) + mask) v, mask causal,
              and in sliding layers key c is visible to query t iff
              t - 512 < c <= t.  gate = sigmoid(u Wg), Wg [2048, H];
              Attn = concat_h(gate_h a_h) Wo.
  dense FFN   (layer 0) (silu(u W_gate) * (u W_up)) W_down, width 8,192.
  sparse FFN  s = sigmoid(u Wr) over 256 experts; P the 8 largest;
              w_e = 2.5 s_e / sum_{j in P} s_j; FFN = Shared(u) +
              sum_{e in P, e held} w_e Expert_e(u); Shared and Expert_e
              gated silu MLPs of width 512.  A pick that lands on an
              expert not held here adds nothing and still takes its part
              of the normalisation.

Departures that could be wrong, each because the config names no tensor
or rule for it (``assumed`` in perf/configs/laguna-xs2.json has the
reasoning): ``gating: true`` is read as ONE sigmoid gate a query head
(a full-width gate would make the model 34.1B, the per-head one keeps the
33.4B the source states); the router's scores are sigmoids renormalised
over the picks, with no expert groups and no bias on the selection;
pre-norm with two RMSNorms a layer and none on q or k; silu; the window
holds 512 keys with the query's own; no auxiliary routing loss.

``picks`` (int32 [sparse layers, S, 8]) replaces every layer's choice of
P and keeps the rest: a top-8 choice is discontinuous, so a comparison
of gradients is made on the program's picks (perf/families/laguna.py).

For 8,192 positions the float32 scores of all heads at once do not fit,
so heads are mapped one after another and every layer runs under
``jax.checkpoint``: the same sums in the same order, recomputed instead
of kept.  On a TPU a float32 product runs in reduced precision unless
told otherwise, so the entry point sets ``default_matmul_precision
("highest")``.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Spec(NamedTuple):
    """The numbers of the equations; hashable, a static argument."""
    layers: tuple              # ((kind, heads, sparse), ...): "full"|"sliding"
    kv_heads: int = 8
    head_dim: int = 128
    window: int = 512
    eps: float = 1e-6
    picked: int = 8
    scale: float = 2.5
    held_first: int = 0
    sliding_theta: float = 10000.0
    full_theta: float = 500000.0
    full_rotated: int = 64
    yarn_factor: float = 64.0
    yarn_original: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.4158883083359672


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def mm(a, b):
    """Every product the MXU would take (weights, router and attention
    alike) goes through here, so that a check can lower its precision
    and see the comparison fail."""
    return a @ b


def head_gate(u, w_gate):
    """[S, H]: one sigmoid gate a query head, from the normed input."""
    return jax.nn.sigmoid(mm(u, w_gate))


def router_scores(u, w_router):
    """[S, E]: a sigmoid score an expert."""
    return jax.nn.sigmoid(mm(u, w_router))


def gated_mlp(p, u):
    return mm(silu(mm(u, p["Wgate"])) * mm(u, p["Wup"]), p["Wdown"])


def rotary_angles(seq, kind, spec):
    """(cos, sin) [seq, r / 2] and r of a layer of ``kind``."""
    if kind == "sliding":
        r = spec.head_dim
        i = jnp.arange(r // 2, dtype=jnp.float32)
        inv_freq, factor = spec.sliding_theta ** (-2.0 * i / r), 1.0
    else:
        r = spec.full_rotated
        i = jnp.arange(r // 2, dtype=jnp.float32)
        f = spec.full_theta ** (-2.0 * i / r)

        def bound(b):
            return (r * math.log(spec.yarn_original / (2 * math.pi * b))
                    / (2 * math.log(spec.full_theta)))

        low = min(max(math.floor(bound(spec.yarn_beta_fast)), 0), r - 1)
        high = min(max(math.ceil(bound(spec.yarn_beta_slow)), 0), r - 1)
        ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        inv_freq = f / spec.yarn_factor * ramp + f * (1.0 - ramp)
        factor = spec.attention_factor
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    return factor * jnp.cos(angle), factor * jnp.sin(angle), r


def rotate(x, cos, sin, r):
    """x [S, heads, D]: pairs (i, i + r/2) of the first r dimensions."""
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def attention(p, u, kind, heads, spec):
    """u [S, hidden] -> [S, hidden]."""
    seq, dim = u.shape[0], spec.head_dim
    q = mm(u, p["Wq"]).reshape(seq, heads, dim)
    k = mm(u, p["Wk"]).reshape(seq, spec.kv_heads, dim)
    v = mm(u, p["Wv"]).reshape(seq, spec.kv_heads, dim)
    cos, sin, r = rotary_angles(seq, kind, spec)
    q, k = rotate(q, cos, sin, r), rotate(k, cos, sin, r)
    t = jnp.arange(seq)[:, None]
    c = jnp.arange(seq)[None, :]
    keep = c <= t
    if kind == "sliding":
        keep &= c > t - spec.window
    group = heads // spec.kv_heads

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args                                  # [S, D]
        scores = mm(q_h, k_h.T) / math.sqrt(dim)
        return mm(jax.nn.softmax(jnp.where(keep, scores, -jnp.inf),
                                 axis=-1), v_h)

    def by_head(x, repeat):       # [S, n, D] -> [n * repeat, S, D]
        return jnp.repeat(x.transpose(1, 0, 2), repeat, axis=0)

    a = jax.lax.map(head, (by_head(q, 1), by_head(k, group),
                           by_head(v, group)))               # [H, S, D]
    a = a.transpose(1, 0, 2) * head_gate(u, p["Wg"])[..., None]
    return mm(a.reshape(seq, heads * dim), p["Wo"])


def sparse_ffn(p, u, spec, picks=None):
    """(FFN(u), (scores [S, E], picks [S, 8]))."""
    scores = router_scores(u, p["Wr"])
    if picks is None:
        _, picks = jax.lax.top_k(scores, spec.picked)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = spec.scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    out = gated_mlp(p["shared"], u)
    held = p["experts"]["Wgate"].shape[0]
    for e in range(held):
        one = {name: w[e] for name, w in p["experts"].items()}
        weight = jnp.sum(jnp.where(picks == spec.held_first + e, weights,
                                   0.0), axis=-1)
        out = out + weight[:, None] * gated_mlp(one, u)
    return out, (scores, picks)


def layer(p, x, kind, heads, sparse, spec, picks=None):
    h = x + attention(p, rms_norm(x, p["norm1"], spec.eps), kind, heads,
                      spec)
    u = rms_norm(h, p["norm2"], spec.eps)
    if not sparse:
        return h + gated_mlp(p["ffn"], u), None
    out, routing = sparse_ffn(p, u, spec, picks)
    return h + out, routing


def forward(params, ids, spec, picks=None):
    """(mean next-token cross-entropy of int32 ``ids`` [B, S], (scores
    [L, B S, E], picks [L, B S, 8]) of the L sparse layers)."""
    with jax.default_matmul_precision("highest"):
        rows = []
        for b in range(ids.shape[0]):
            h = params["embed"][ids[b]]
            routed, sparse_seen = [], 0
            for p, (kind, heads, sparse) in zip(params["layers"],
                                                spec.layers):
                forced = None
                if sparse and picks is not None:
                    forced = picks[sparse_seen].reshape(
                        ids.shape[0], ids.shape[1], -1)[b]
                sparse_seen += bool(sparse)
                h, routing = jax.checkpoint(
                    lambda p_, h_, f_, k=kind, n=heads, s=sparse: layer(
                        p_, h_, k, n, s, spec, f_))(p, h, forced)
                if sparse:
                    routed.append(routing)
            rows.append((h, routed))
        h = jnp.stack([h for h, _ in rows])
        logits = mm(rms_norm(h, params["norm"], spec.eps), params["head"])
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        layers = len(rows[0][1])
        routing = tuple(
            jnp.stack([jnp.concatenate([r[i][part] for _, r in rows])
                       for i in range(layers)]) if layers else None
            for part in (0, 1))
        return -jnp.mean(picked), routing


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, spec, picks=None):
    """((loss, (scores, picks)), the loss's gradient in the tree of
    ``params``)."""
    return jax.value_and_grad(forward, has_aux=True)(params, ids, spec, picks)
