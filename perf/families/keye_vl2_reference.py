"""Keye-VL-2.0's language model in plain ``jax.numpy`` and float32: the
two loss terms and their gradients, given the same held experts and
vocabulary rows as the program.  No kernel, no mixed precision, no
packed mask on the inside: index scores are a full row of the ``[S, S]``
matrix, the selection is ``lax.top_k`` per row, attention is a masked
softmax.  Written from the equations below, not from the program's
files.

Where each equation comes from (the builder had no network; the
equations are those of ISSUE 50, which took the sizes from the released
``config.json``, catalog row ``Keye-VL-2.0-30B-A3B``, and the indexer's
form from the DeepSeek-V3.2 report that the catalog's ``described_as``
names).  u is a layer's normed input, [S, 2048] a batch row; every
product is without bias.

  block       h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  after
              the last layer a final RMSNorm and logits = x W_head; L_lm
              the mean next-token cross-entropy.  RMSNorm(x) = x /
              sqrt(mean(x^2) + 1e-6) * w.
  attention   q = u Wq as [S, 32, 128]; k = u Wk, v = u Wv as [S, 4,
              128]; q and k RMSNormed over the 128 of every head (one
              weight vector for q's heads, one for k's); rotary,
              rotate-half pairing (i, i + 64), inv_freq_i =
              1e7^(-2i/128).  Key/value head j serves query heads
              [8 j, 8 j + 8).
  indexer     on stop_gradient(u): qI = rot(u WqI) as [S, 16, 64]; kI =
              rot(LayerNorm(u WkI)) [S, 64] (mean and variance over the
              64, eps 1e-6, weight and bias); w = u Ww [S, 16]; both
              rotations over all 64 at the same theta;
              I[t, s] = 64^-1/2 16^-1/2 sum_j w[t, j] ReLU(qI[t, j] .
              kI[s]).  S_t = the 2,048 keys s <= t of largest I[t, s],
              a tie to the lower index; every s <= t while t < 2,048.
  core        o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] .
              k[s, g(h)] / sqrt(128)) v[s, g(h)];  Attn = concat_h(o) Wo.
  experts     p = softmax(z Wr) over 128; P the 8 largest; w_e = p_e /
              sum_{j in P} p_j; MoE = sum_{e in P, e held} w_e
              Expert_e(z), gated silu MLPs of width 768.  A pick on an
              expert not held here adds nothing and still takes its part
              of the normalisation.
  loss        L = L_lm + c sum_layers L_I;  L_I = mean_t KL(pbar_t ||
              softmax_{s in S_t} I[t, s]), pbar_t the mean over the 32
              heads of the main attention's probabilities on S_t, a
              constant.  The selection has no gradient.

Departures that could be wrong are the configuration file's ``assumed``.

``picks`` (int32 [layers, S, 8]) replaces every router's choice and
``keep`` (the program's packed keep-sets, int32 [layers, S / 32, S],
``unpack_rows`` has the layout) every layer's selection: both choices are
discontinuous, so gradients are compared on the program's.  With ``keep``
given, a layer also reports how its OWN selection differs from it.

For 16,384 positions a layer's attention runs a block of ``spec.block``
queries at a time under ``jax.checkpoint`` (the same sums in the same
order, recomputed instead of kept), and every layer is checkpointed.  On
a TPU a float32 product runs in reduced precision unless told otherwise,
so the entry point sets ``default_matmul_precision("highest")``.
"""

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class Spec(NamedTuple):
    """The numbers of the equations; hashable, a static argument."""
    layers: int
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    eps: float = 1e-6
    theta: float = 1e7
    picked: int = 8
    renormalize: bool = True
    held_first: int = 0
    idx_heads: int = 16
    idx_dim: int = 64
    topk: int = 2048
    idx_eps: float = 1e-6
    index_weight: float = 1.0
    # queries a block of the attention (None: the row whole) and, where
    # ``keep`` is handed in, queries a packed block of it
    block: Optional[int] = None
    pack_block: int = 256
    # a pair chosen differently is explained by rounding if its score is
    # within this of the query's k-th largest, relative to the rms of the
    # query's scores; the pairs beyond it are counted
    gap_delta: float = 0.0


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def contract(spec, a, b):
    """Every product the MXU would take (weights, router, indexer and
    attention alike) goes through here, so that a check can lower its
    precision and see the comparison fail."""
    return jnp.einsum(spec, a, b)


def mm(a, b):
    return contract("...ij,jk->...ik", a, b)


def gated_mlp(p, u):
    return mm(silu(mm(u, p["Wgate"])) * mm(u, p["Wup"]), p["Wdown"])


def rotate(x, theta):
    """x [S, heads, D]: rotate-half over all D, pairs (i, i + D/2)."""
    seq, _, dim = x.shape
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * (
        theta ** (-2.0 * i / dim))[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def router_probs(z, w_router):
    """[S, E]: the softmax over all experts."""
    return jax.nn.softmax(mm(z, w_router), axis=-1)


def unpack_rows(words, first, rows, pack_block):
    """The program's packed keep-set for queries ``[first, first +
    rows)``: ``words`` int32 [S / 32, S]; queries are packed in blocks of
    ``pack_block`` = 32 x sub, word row ``qb sub + i`` bit ``b`` being
    query ``qb pack_block + b sub + i``.  -> bool [rows, S]."""
    sub = pack_block // 32
    t = first + jnp.arange(rows)
    within = t % pack_block
    row = (t // pack_block) * sub + within % sub
    return ((words[row] >> (within // sub)[:, None]) & 1) != 0


def indexer(p, u, spec):
    """(qI [S, 16, 64], kI [S, 64], w [S, 16]) of a constant ``u``."""
    seq = u.shape[0]
    q_idx = rotate(mm(u, p["WqI"]).reshape(seq, spec.idx_heads, spec.idx_dim),
                   spec.theta)
    k_idx = rotate(layer_norm(mm(u, p["WkI"]), p["kI_norm_w"],
                              p["kI_norm_b"], spec.idx_eps)[:, None, :],
                   spec.theta)[:, 0]
    return q_idx, k_idx, mm(u, p["Ww"])


def index_scores(q_idx, k_idx, w, spec):
    """I[t, s] for the queries of ``q_idx`` / ``w`` against every key."""
    dots = jnp.maximum(contract("tjd,sd->tjs", q_idx, k_idx), 0.0)
    return jnp.sum(w[:, :, None] * dots, axis=1) * (
        spec.idx_dim ** -0.5 * spec.idx_heads ** -0.5)


def select(scores, first, spec):
    """bool [rows, S]: for query t = first + row the ``min(t + 1, topk)``
    keys s <= t of largest score, a tie to the lower index."""
    rows, seq = scores.shape
    t = first + jnp.arange(rows)[:, None]
    causal = jnp.arange(seq)[None, :] <= t
    _, chosen = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                              min(spec.topk, seq))
    picked = jnp.zeros((rows, seq), bool).at[
        jnp.arange(rows)[:, None], chosen].set(True)
    return picked & causal


def attention_block(q, k, v, q_idx, k_idx, w, first, keep, spec):
    """One block of queries: q [rows, 32, 128] against k, v [S, 4, 128],
    the indexer's q_idx [rows, 16, 64] and w [rows, 16] against k_idx
    [S, 64]; ``keep`` the program's selection for these queries or None.
    -> (o [rows, 32, 128], the block's sum of KL terms, (pairs kept,
    pairs the own selection and ``keep`` disagree on, those of them that
    rounding does not explain (``spec.gap_delta``)))."""
    rows, seq = q.shape[0], k.shape[0]
    group = spec.heads // spec.kv_heads
    scores = index_scores(q_idx, k_idx, w, spec)
    own = select(jax.lax.stop_gradient(scores), first, spec)
    chosen = own if keep is None else keep
    # the main attention, head by head of a key/value group
    q_g = q.reshape(rows, spec.kv_heads, group, spec.head_dim)
    s = contract("tngd,snd->ngts", q_g, k) / math.sqrt(spec.head_dim)
    p = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)
    o = contract("ngts,snd->tngd", p, v).reshape(
        rows, spec.heads, spec.head_dim)
    # the alignment term: pbar a constant, the indexer's softmax over S_t
    pbar = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
    logp = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    live = chosen & (pbar > 0.0)
    kl = jnp.sum(jnp.where(
        live, pbar * (jnp.log(jnp.where(live, pbar, 1.0))
                      - jnp.where(live, logp, 0.0)), 0.0))
    # how the own selection differs from the one used
    t = first + jnp.arange(rows)[:, None]
    causal = jnp.arange(seq)[None, :] <= t
    kth = jnp.min(jnp.where(own, scores, jnp.inf), axis=-1, keepdims=True)
    rms = jnp.sqrt(jnp.sum(jnp.where(causal, jnp.square(scores), 0.0),
                           axis=-1, keepdims=True) / (t + 1))
    differ = own != chosen
    counts = jnp.stack([
        jnp.sum(chosen), jnp.sum(differ),
        jnp.sum(differ & (jnp.abs(scores - kth) > spec.gap_delta * rms))
    ]).astype(jnp.float32)
    return o, kl, jax.lax.stop_gradient(counts)


def attention(p, u, spec, keep=None):
    """u [S, hidden] -> (Attn(u) [S, hidden], L_I, the selection's three
    counts)."""
    seq = u.shape[0]
    q = mm(u, p["Wq"]).reshape(seq, spec.heads, spec.head_dim)
    k = mm(u, p["Wk"]).reshape(seq, spec.kv_heads, spec.head_dim)
    v = mm(u, p["Wv"]).reshape(seq, spec.kv_heads, spec.head_dim)
    q = rotate(rms_norm(q, p["q_norm"], spec.eps), spec.theta)
    k = rotate(rms_norm(k, p["k_norm"], spec.eps), spec.theta)
    q_idx, k_idx, w = indexer(p, jax.lax.stop_gradient(u), spec)
    block = spec.block or seq

    @jax.checkpoint
    def one(args):
        first, q_b, qi_b, w_b = args
        kept = None if keep is None else unpack_rows(
            keep, first, block, spec.pack_block)
        return attention_block(q_b, k, v, qi_b, k_idx, w_b, first, kept,
                               spec)

    def blocks(x):
        return x.reshape(seq // block, block, *x.shape[1:])

    o, kl, counts = jax.lax.map(one, (
        jnp.arange(0, seq, block), blocks(q), blocks(q_idx), blocks(w)))
    out = mm(o.reshape(seq, spec.heads * spec.head_dim), p["Wo"])
    return out, jnp.sum(kl) / seq, jnp.sum(counts, axis=0)


def sparse_ffn(p, z, spec, picks=None):
    """(MoE(z), (probabilities [S, E], picks [S, 8]))."""
    probs = router_probs(z, p["Wr"])
    if picks is None:
        _, picks = jax.lax.top_k(probs, spec.picked)
    picked = jnp.take_along_axis(probs, picks, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) if (
        spec.renormalize) else picked
    out = jnp.zeros_like(z)
    for e in range(p["experts"]["Wgate"].shape[0]):
        one = {name: w[e] for name, w in p["experts"].items()}
        weight = jnp.sum(jnp.where(picks == spec.held_first + e, weights,
                                   0.0), axis=-1)
        out = out + weight[:, None] * gated_mlp(one, z)
    return out, (probs, picks)


def layer(p, x, spec, picks=None, keep=None):
    a, index_loss, counts = attention(p, rms_norm(x, p["norm1"], spec.eps),
                                      spec, keep)
    h = x + a
    out, routing = sparse_ffn(p, rms_norm(h, p["norm2"], spec.eps), spec,
                              picks)
    return h + out, routing, index_loss, counts


def forward(params, ids, spec, picks=None, keep=None):
    """(L, (L_lm, sum of L_I, router probabilities [layers, B S, E], picks
    [layers, B S, 8], the selections' counts [layers, 3])) on int32
    ``ids`` [B, S]; a term of the batch is the mean of its rows'."""
    with jax.default_matmul_precision("highest"):
        rows, seq = ids.shape
        hidden, index_loss = [], 0.0
        routed, counted = [], []
        for b in range(rows):
            h = params["embed"][ids[b]]
            row_routed, row_counts = [], []
            for i, p in enumerate(params["layers"]):
                forced = None if picks is None else picks[i].reshape(
                    rows, seq, -1)[b]
                kept = None if keep is None else keep[i].reshape(
                    rows, seq // 32, seq)[b]
                h, routing, term, counts = jax.checkpoint(
                    lambda p_, h_, f_, k_: layer(p_, h_, spec, f_, k_))(
                        p, h, forced, kept)
                index_loss = index_loss + term / rows
                row_routed.append(routing)
                row_counts.append(counts)
            hidden.append(h)
            routed.append(row_routed)
            counted.append(jnp.stack(row_counts))
        h = jnp.stack(hidden)
        logits = mm(rms_norm(h, params["norm"], spec.eps), params["head"])
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        main = -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None],
                                             axis=-1))
        probs, chosen = (
            jnp.stack([jnp.concatenate([r[i][part] for r in routed])
                       for i in range(spec.layers)]) for part in (0, 1))
        return main + spec.index_weight * index_loss, (
            main, index_loss, probs, chosen, sum(counted))


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, spec, picks=None, keep=None):
    """((L, (L_lm, sum L_I, probabilities, picks, counts)), L's gradient
    in the tree of ``params``)."""
    return jax.value_and_grad(forward, has_aux=True)(params, ids, spec,
                                                     picks, keep)
