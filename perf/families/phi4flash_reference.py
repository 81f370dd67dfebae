"""Phi-4-mini-flash-reasoning (SambaY) in plain ``jax.numpy`` and float32:
loss and gradients of next-token prediction.  No kernel, no mixed
precision; the recurrence is a sequential scan over positions and
attention a masked softmax.  Written from the equations below, not from
the program's model file.

Where each equation comes from (the builder had no network; the
equations are those of ISSUE 34, which took them from these): the layer
pattern by index (``mb_per_layer`` = 2, the Mamba layer at n/2 that
saves its memory, the full-attention layer at n/2 + 1 that saves its
keys and values), the gated-memory unit and the cross-decoder from the
SambaY paper (Ren et al. 2025, arXiv:2507.06607) and the decoder layer
of the released ``modeling_phi4flash.py``; the Mamba mixer from Gu & Dao
2023 (Mamba-1: in, conv, x, dt and out projections, ``A_log``, ``D``) as
that file's mixer has it; differential attention from Ye et al. 2024
(arXiv:2410.05258: ``lambda_init`` = 0.8 - 0.6 exp(-0.3 i), the
sub-layer RMSNorm over each pair's 2d values, the ``(1 - lambda_init)``
factor) as that file's flash path composes it from four attention calls
on adjacent head pairs; LayerNorm with bias and eps 1e-5, the gated FFN
(``hidden_act`` silu, no bias), the window of 512 and the tied head
from the released ``config.json``.

x is a layer's input, [S, H] a batch row; every dropout of the source
is 0 and there is no positional encoding of any kind.

  every layer   h = x + Mixer(LN1(x));  out = h + FFN(LN2(h))
  FFN           u = x W1;  gate, up = split(u, 2);  (up * silu(gate)) W2
  kind by PUBLISHED index i (n = 32): even i < n/2 "mamba", odd i < n/2
      "window" (attention over the last 512 keys); i = n/2 "mamba_mem"
      (saves m); i = n/2 + 1 "full" (saves k, v); beyond, even i "gmu",
      odd i "cross"
  mamba         xs, z = split(x Win);  xs = silu(conv(xs) + conv_b), conv
                causal and depthwise: tap j of 4 reads position t - 3 + j
                dt, B, C = split(xs Wx, [R, N, N]);  D_t = softplus(dt Wdt
                + bdt);  A = -exp(A_log)
                s_t[c,n] = exp(D_t[c] A[c,n]) s_{t-1}[c,n]
                           + D_t[c] xs_t[c] B_t[n]
                y_t[c]   = sum_n s_t[c,n] C_t[n] + Dskip[c] xs_t[c]
                Mixer = (y * silu(z)) Wout;   mamba_mem saves m = y
  gmu           Mixer = (m * silu(x Win)) Wout
  attention     q, k, v = split(x Wqkv + bqkv): 40, 20 and 20 heads of
                64, taken in adjacent pairs: q1, q2 the even and the odd
                query heads (20 each), k1, k2, v1, v2 alike (10 each);
                key/value pair j serves the query pairs 2j and 2j + 1.
                Att(q, k, v) = softmax over the keys c <= t (window: and
                c > t - 512) of q.k / 8, times v
                a1 = [Att(q1,k1,v1), Att(q1,k1,v2)]  (128 values a pair)
                a2 = [Att(q2,k2,v1), Att(q2,k2,v2)]
                lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0(i)
                a = RMSNorm_128(a1 - lam a2; g) * (1 - lam0(i))
                Mixer = merge(a) Wo + bo;   "full" saves k1, k2, v1, v2
  cross         the same with q = x Wq + bq alone, on the saved k, v,
                full causal, its own lam vectors and g
  head          logits = LN(h; ln_f) embed^T;  loss = mean over the
                B x (S-1) predicted positions of -log softmax(logits_t)
                [ids_{t+1}], over as many rows as ``embed`` has

For a sequence of 8,192 positions at the published widths the float32
scores of all heads at once (5 GB a call) and every position's state
(2.7 GB a layer) do not fit beside the weights and their gradients, so
heads are mapped one pair after another and positions are scanned in
two levels (chunks of 128, then positions), each under
``jax.checkpoint``, and so is every layer: the same sums in the same
order, recomputed instead of kept.

On a TPU a float32 product runs in reduced precision unless told
otherwise, so every entry point sets ``default_matmul_precision
("highest")``.
"""

import math

import jax
import jax.numpy as jnp

WINDOW = 512
SCAN_CHUNK = 128


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["w"] + p["b"]


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def lam0(index):
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def mm(a, b):
    """Every product the MXU would take (weights and attention alike)
    goes through here, so that a check can lower its precision and see
    the comparison fail (PERF.md section 6, PR 34)."""
    return a @ b


def ffn(p, x):
    gate, up = jnp.split(mm(x, p["W1"]), 2, axis=-1)
    return mm(up * silu(gate), p["W2"])


def recurrence(xs, dt, a_mat, b_mat, c_mat, d_skip):
    """y [S, C] of one batch row: positions one after another, in two
    levels so that the backward pass keeps a state a chunk."""
    seq = xs.shape[0]
    pad = -seq % SCAN_CHUNK

    def position(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        s = (jnp.exp(dt_t[:, None] * a_mat) * s
             + (dt_t * x_t)[:, None] * b_t[None, :])
        return s, s @ c_t + d_skip * x_t

    @jax.checkpoint
    def chunk(s, inputs):
        return jax.lax.scan(position, s, inputs)

    def chunks(t):   # a padded position has dt 0: it leaves the state
        t = jnp.pad(t, ((0, pad), (0, 0)))
        return t.reshape(-1, SCAN_CHUNK, t.shape[-1])

    _, y = jax.lax.scan(chunk, jnp.zeros(a_mat.shape, jnp.float32),
                        tuple(chunks(t) for t in (xs, dt, b_mat, c_mat)))
    return y.reshape(-1, y.shape[-1])[:seq]


def mamba(p, x):
    """(mixer output, y) for x [B, S, H]."""
    states = p["A_log"].shape[1]
    rank = p["Wdt"].shape[0]
    xs, z = jnp.split(mm(x, p["Win"]), 2, axis=-1)
    taps, seq = p["conv_w"].shape[1], xs.shape[1]
    padded = jnp.pad(xs, ((0, 0), (taps - 1, 0), (0, 0)))
    xs = silu(sum(padded[:, j:j + seq] * p["conv_w"][:, j]
                  for j in range(taps)) + p["conv_b"])
    dt, b_mat, c_mat = jnp.split(mm(xs, p["Wx"]), [rank, rank + states],
                               axis=-1)
    dt = jax.nn.softplus(mm(dt, p["Wdt"]) + p["bdt"])
    a_mat = -jnp.exp(p["A_log"])
    y = jax.vmap(recurrence, in_axes=(0, 0, None, 0, 0, None))(
        xs, dt, a_mat, b_mat, c_mat, p["Dskip"])
    return mm(y * silu(z), p["Wout"]), y


def pairs(t, head_dim):
    """[B, S, heads * d] -> (even heads, odd heads), each [B, heads / 2,
    S, d]."""
    batch, seq, width = t.shape
    t = t.reshape(batch, seq, width // (2 * head_dim), 2, head_dim)
    return (t[:, :, :, 0].transpose(0, 2, 1, 3),
            t[:, :, :, 1].transpose(0, 2, 1, 3))


def att(q, k, v1, v2, window):
    """[Att(q, k, v1), Att(q, k, v2)] for q [B, P, S, d] on k, v1, v2
    [B, P / group, S, d]: one query head after another."""
    group = q.shape[1] // k.shape[1]
    seq, head_dim = q.shape[2], q.shape[3]
    t = jnp.arange(seq)[:, None]
    c = jnp.arange(seq)[None, :]
    keep = c <= t
    if window:
        keep &= c > t - window

    @jax.checkpoint
    def head(args):
        q_h, k_h, v1_h, v2_h = args          # [B, S, d]
        scores = mm(q_h, k_h.swapaxes(-1, -2)) / math.sqrt(head_dim)
        p = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.concatenate([mm(p, v1_h), mm(p, v2_h)], axis=-1)

    def by_head(t_):   # [B, P, S, d] -> [P, B, S, d]
        return t_.transpose(1, 0, 2, 3)

    served = [jnp.repeat(by_head(t_), group, axis=0) for t_ in (k, v1, v2)]
    return jax.lax.map(head, (by_head(q), *served)).transpose(1, 0, 2, 3)


def differential(p, q, kv, index, window, eps, head_dim):
    """merge(RMSNorm(a1 - lam a2) (1 - lam0)) Wo + bo; q [B, S, H]."""
    q1, q2 = pairs(q, head_dim)
    k1, k2, v1, v2 = kv
    a1 = att(q1, k1, v1, v2, window)             # [B, P, S, 2d]
    a2 = att(q2, k2, v1, v2, window)
    lam = (jnp.exp(jnp.sum(p["lq1"] * p["lk1"]))
           - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0(index))
    a = a1 - lam * a2
    a = a / jnp.sqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + eps)
    a = a * p["g"] * (1.0 - lam0(index))
    batch, _, seq, _ = a.shape
    return mm(a.transpose(0, 2, 1, 3).reshape(batch, seq, -1),
              p["Wo"]) + p["bo"]


def attention(p, x, index, window, eps, head_dim):
    """(mixer output, (k1, k2, v1, v2))."""
    width = x.shape[-1]
    qkv = mm(x, p["Wqkv"]) + p["bqkv"]
    kv_width = (qkv.shape[-1] - width) // 2
    q, k, v = jnp.split(qkv, [width, width + kv_width], axis=-1)
    kv = (*pairs(k, head_dim), *pairs(v, head_dim))
    return differential(p, q, kv, index, window, eps, head_dim), kv


def layer(p, x, index, kind, saved, eps, head_dim, window=WINDOW):
    """(layer output, what this layer saves for later ones)."""
    a = layer_norm(x, p["ln1"], eps)
    m = p["mixer"]
    keeps = None
    if kind in ("mamba", "mamba_mem"):
        out, y = mamba(m, a)
        keeps = {"m": y} if kind == "mamba_mem" else None
    elif kind in ("window", "full"):
        out, kv = attention(m, a, index, window if kind == "window" else 0,
                            eps, head_dim)
        keeps = {"kv": kv} if kind == "full" else None
    elif kind == "gmu":
        out = mm(saved["m"] * silu(mm(a, m["Win"])), m["Wout"])
    elif kind == "cross":
        out = differential(m, mm(a, m["Wq"]) + m["bq"], saved["kv"], index, 0,
                           eps, head_dim)
    else:
        raise ValueError(kind)
    h = x + out
    return h + ffn(p["ffn"], layer_norm(h, p["ln2"], eps)), keeps


def loss(params, ids, plan, eps, head_dim, window=WINDOW):
    """Mean next-token cross-entropy of int32 ``ids`` [B, S]; ``plan`` is
    the static ((published index, kind), ...) of ``params['layers']``
    (``window``: the band of its "window" layers, for a small test)."""
    with jax.default_matmul_precision("highest"):
        h = params["embed"][ids]
        saved = {}
        for p, (index, kind) in zip(params["layers"], plan):
            h, keeps = jax.checkpoint(
                lambda p_, h_, s_, i=index, k=kind: layer(
                    p_, h_, i, k, s_, eps, head_dim, window))(p, h, saved)
            saved = {**saved, **(keeps or {})}
        logits = mm(layer_norm(h, params["ln_f"], eps), params["embed"].T)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        return -jnp.mean(picked)


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, plan, eps, head_dim, window=WINDOW):
    """(loss, its gradient in the tree of ``params``)."""
    return jax.value_and_grad(loss)(params, ids, plan, eps, head_dim, window)
