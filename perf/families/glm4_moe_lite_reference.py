"""GLM-4.7-Flash (``glm4_moe_lite``) in plain ``jax.numpy`` and float32:
the objective, its two terms and their gradients, given the same held
experts and vocabulary rows as the program.  No kernel, no mixed
precision, no sort, no chunk, no scan over layers: attention is a masked
softmax head by head, the experts are a Python loop over the held ones,
the bias update is three lines.  Written from the equations below, not
from the program's model file.

Where each equation comes from (the builder had no network; the
equations are those of ISSUE 42, which took them from the keys of the
released ``config.json``, catalog row ``GLM-4.7-Flash``).  u is a layer's
normed input, [S, 2048] a batch row; every product is without bias;
RMSNorm(x) = x / sqrt(mean(x^2) + 1e-5) * w.

  block       h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h)); layer 0
              a dense gated FFN of width 10,240, the others the sparse
              FFN; a final RMSNorm and logits = x W_head (its own
              matrix).
  attention   20 heads.  cq = RMSNorm(u Wqa) [S, 768]; q = cq Wqb as
              [S, 20, 256], a head [q_nope 192 | q_rope 64].
              [ckv | k_rope] = u Wkva [S, 512 + 64]; ckv = RMSNorm(ckv),
              k_rope is not normed; [k_nope 192 | v 256] = ckv Wkvb a
              head.  Rotary on q_rope of every head and on the one k_rope
              a position: inv_freq_i = 1e6^(-2i/64), i = 0..31,
              rotate-half pairing (i, i + 32).  k = [k_nope | k_rope for
              every head]; a_h = softmax(q_h k_h^T / sqrt(256) + causal
              mask) v_h; Attn = concat_h(a_h) Wo.
  sparse FFN  s = sigmoid(u Wr) over 64 experts; P the 4 largest of
              s + b; w_e = 1.8 s_e / sum_{j in P} s_j; FFN = Shared(u) +
              sum_{e in P, e held} w_e Expert_e(u); Shared and Expert_e
              gated silu MLPs of width 1,536.  A pick on an expert not
              held here adds nothing and keeps its part of the
              normalisation.  b has no gradient; after an optimizer step
              b_e += gamma sign(mean(c) - c_e), c the picks an expert
              over the step's tokens, gamma 0.001 (``bias_update``).
  prediction  z_i = [RMSNorm_e(E[t_{i+1}]) | RMSNorm_h(H_i)] Wp with H the
              stack's output before the final norm; one sparse block with
              its own weights; RMSNorm_s; the shared head; L_mtp the mean
              cross-entropy of t_{i+2} over the positions that have one;
              L = L_main + 0.3 L_mtp.

Departures that could be wrong (``assumed`` in
perf/configs/glm47-flash.json has the sources): gamma, lambda and the
module's wiring are the DeepSeek-V3 report's, which ``noaux_tc`` and
``num_nextn_predict_layers`` follow; H is taken before the final norm;
rotate-half pairing (with seeded weights the interleaved pairing is the
same model under a permutation of columns of Wqb and Wkva); no norm on q
or k beyond the latents'.  And one of shape: the module runs on all S
positions, with the row's FIRST token standing where position S-1 has no
next one; positions S-2 and S-1 carry no loss, nothing of them reaches a
scored position through a causal mask, and their picks are counted.

``picks`` (int32 [gates, S, 4]; the gates are the stack's sparse layers
and then the module's block) replaces every gate's choice of P and keeps
the rest: a top-4 choice is discontinuous, so a comparison of gradients
is made on the program's picks (perf/families/glm4_moe_lite.py).

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
    sparse: tuple              # (False, True, ...): a layer's FFN
    heads: int = 20
    kv_rank: int = 512
    nope: int = 192
    rope: int = 64
    theta: float = 1000000.0
    eps: float = 1e-5
    picked: int = 4
    scale: float = 1.8
    held_first: int = 0
    mtp_weight: float = 0.3
    gamma: float = 0.001


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


def router_scores(u, w_router):
    """[S, E]: a sigmoid score an expert."""
    return jax.nn.sigmoid(mm(u, w_router))


def gated_mlp(p, u):
    return mm(silu(mm(u, p["Wgate"])) * mm(u, p["Wup"]), p["Wdown"])


def rotate(x, spec):
    """x [S, heads, rope]: position s turns pairs (i, i + rope/2)."""
    half = spec.rope // 2
    inv_freq = spec.theta ** (
        -2.0 * jnp.arange(half, dtype=jnp.float32) / spec.rope)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def shared_key(k_rope, heads):
    """The one rotated key a position, for every head: [S, 1, rope] ->
    [S, heads, rope]."""
    return jnp.broadcast_to(k_rope, (k_rope.shape[0], heads,
                                     k_rope.shape[-1]))


def latents(p, u, spec):
    """(cq [S, 768], ckv [S, 512], k_rope [S, 64] unrotated)."""
    cq = rms_norm(mm(u, p["Wqa"]), p["q_norm"], spec.eps)
    kva = mm(u, p["Wkva"])
    # the norm is over the latent alone, the rotated key is not normed
    return (cq, rms_norm(kva[:, :spec.kv_rank], p["kv_norm"], spec.eps),
            kva[:, spec.kv_rank:])


def attention(p, u, spec):
    """u [S, hidden] -> [S, hidden], the expanded form."""
    seq, heads, nope = u.shape[0], spec.heads, spec.nope
    dim = nope + spec.rope
    cq, ckv, k_rope = latents(p, u, spec)
    q = mm(cq, p["Wqb"]).reshape(seq, heads, dim)
    kv = mm(ckv, p["Wkvb"]).reshape(seq, heads, -1)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], spec)], -1)
    k = jnp.concatenate([kv[..., :nope], shared_key(
        rotate(k_rope[:, None, :], spec), heads)], -1)
    v = kv[..., nope:]
    keep = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args                                  # [S, D]
        scores = mm(q_h, k_h.T) / math.sqrt(dim)
        return mm(jax.nn.softmax(jnp.where(keep, scores, -jnp.inf),
                                 axis=-1), v_h)

    a = jax.lax.map(head, tuple(x.transpose(1, 0, 2) for x in (q, k, v)))
    return mm(a.transpose(1, 0, 2).reshape(seq, -1), p["Wo"])


def absorbed_attention(p, u, spec):
    """The same function as serving computes it: the scores against the
    key/value latent itself, Wkvb's key half folded into the query and
    its value half applied after the softmax.  For the test that the two
    forms agree; nothing times it."""
    seq, heads, nope = u.shape[0], spec.heads, spec.nope
    cq, ckv, k_rope = latents(p, u, spec)
    q = mm(cq, p["Wqb"]).reshape(seq, heads, nope + spec.rope)
    w = p["Wkvb"].reshape(spec.kv_rank, heads, -1)
    w_k, w_v = w[..., :nope], w[..., nope:]
    q_latent = jnp.einsum("shn,chn->shc", q[..., :nope], w_k)
    q_rope = rotate(q[..., nope:], spec)
    k_rope = rotate(k_rope[:, None, :], spec)[:, 0]
    scores = (jnp.einsum("shc,tc->hst", q_latent, ckv)
              + jnp.einsum("shr,tr->hst", q_rope, k_rope)) / math.sqrt(
        nope + spec.rope)
    keep = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hst,tc,chv->shv", probs, ckv, w_v)
    return mm(a.reshape(seq, -1), p["Wo"])


def choose(scores, bias, picked):
    """The ``picked`` largest of score + bias (one group: the group step
    of ``noaux_tc`` is the identity)."""
    return jax.lax.top_k(scores + bias, picked)[1]


def sparse_ffn(p, u, spec, picks=None):
    """(FFN(u), (scores [S, E], picks [S, 4]))."""
    scores = router_scores(u, p["Wr"])
    if picks is None:
        picks = choose(scores, p["bias"], spec.picked)
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


def layer(p, x, sparse, spec, picks=None):
    h = x + attention(p, rms_norm(x, p["norm1"], spec.eps), spec)
    u = rms_norm(h, p["norm2"], spec.eps)
    if not sparse:
        return h + gated_mlp(p["ffn"], u), None
    out, routing = sparse_ffn(p, u, spec, picks)
    return h + out, routing


@jax.checkpoint
def cross_entropy(h, w_head, targets):
    """Sum of -log p(target) over the positions of ``h`` [S', hidden];
    the logits are made again for the gradient, not kept."""
    logp = jax.nn.log_softmax(mm(h, w_head), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def mtp_inputs(ids_row):
    """The token the module reads at each position: the next one; at the
    last position, where there is none, the row's first (unscored)."""
    return jnp.roll(ids_row, -1)


def row_terms(params, ids_row, spec, picks=None):
    """One row [S]: (sum of the main head's S-1 losses, sum of the
    module's S-2, [(scores, picks)] of the gates)."""
    def run(p, h, sparse, forced):
        return jax.checkpoint(
            lambda p_, h_, f_: layer(p_, h_, sparse, spec, f_))(p, h, forced)

    h = params["embed"][ids_row]
    routed = []
    for p, sparse in zip(params["layers"], spec.sparse):
        forced = picks[len(routed)] if (
            sparse and picks is not None) else None
        h, routing = run(p, h, sparse, forced)
        if sparse:
            routed.append(routing)
    main = cross_entropy(rms_norm(h, params["norm"], spec.eps)[:-1],
                         params["head"], ids_row[1:])
    module = params.get("mtp")
    if module is None:
        return main, 0.0, routed
    z = mm(jnp.concatenate([
        rms_norm(params["embed"][mtp_inputs(ids_row)], module["enorm"],
                 spec.eps),
        rms_norm(h, module["hnorm"], spec.eps)], axis=-1), module["Wp"])
    z, routing = run(module["block"], z, True,
                     picks[len(routed)] if picks is not None else None)
    routed.append(routing)
    mtp = cross_entropy(rms_norm(z, module["norm"], spec.eps)[:-2],
                        params["head"], ids_row[2:])
    return main, mtp, routed


def forward(params, ids, spec, picks=None):
    """(L, (L_main, L_mtp, scores [G, B S, E], picks [G, B S, 4])) of
    int32 ``ids`` [B, S] over the G gates; each term the mean over the
    batch's positions that have a target."""
    with jax.default_matmul_precision("highest"):
        rows, seq = ids.shape
        main = mtp = 0.0
        routed = []
        for b in range(rows):
            forced = None if picks is None else picks.reshape(
                picks.shape[0], rows, seq, -1)[:, b]
            row_main, row_mtp, row_routed = row_terms(params, ids[b], spec,
                                                      forced)
            main, mtp = main + row_main, mtp + row_mtp
            routed.append(row_routed)
        main = main / (rows * (seq - 1))
        mtp = mtp / (rows * (seq - 2))
        gates = len(routed[0])
        scores, chosen = (
            jnp.stack([jnp.concatenate([r[g][part] for r in routed])
                       for g in range(gates)]) if gates else None
            for part in (0, 1))
        return main + spec.mtp_weight * mtp, (main, mtp, scores, chosen)


def bias_update(bias, counts, gamma):
    """The selection bias after an optimizer step: ``counts`` [E] the
    picks an expert over the step's tokens."""
    counts = counts.astype(jnp.float32)
    return bias + gamma * jnp.sign(jnp.mean(counts) - counts)


def pick_counts(picks, experts):
    """[G, E] picks an expert of ``picks`` [G, T, k]."""
    return jnp.sum(picks[..., None] == jnp.arange(experts), axis=(1, 2))


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, spec, picks=None):
    """((L, (L_main, L_mtp, scores, picks)), L's gradient in the tree of
    ``params``; the biases' is zero)."""
    return jax.value_and_grad(forward, has_aux=True)(params, ids, spec, picks)
