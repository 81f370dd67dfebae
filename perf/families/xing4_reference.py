"""Xing4.0-29B-A4B (``xing4_0``) in plain ``jax.numpy`` and float32: the
objective, its two terms, their gradients and every sublayer's mixes,
given the same held experts and vocabulary rows as the program.  No
kernel, no mixed precision, no scan over layers: the Sinkhorn rounds are
a Python loop, attention is a masked softmax head by head (a head's
4,096 x 4,096 scores are the block that fits), the experts a loop over
the held ones.  Written from the equations below, not from the program's
model file; independent of ``deepspeed_tpu``.  What is GLM-4.7-Flash's
equation for equation (the latents, the biased sigmoid router and its
experts, the prediction module's inputs, the cross-entropy) is that
family's reference, imported.

Where each equation comes from (the builder had no network; these are
ISSUE 58's, from the keys of the released ``config.json``, catalog row
``Xing4.0-29B-A4B``, and the two papers the keys name).  C = 3584,
n = 4; every product without bias; RMSNorm(x) = x / sqrt(mean(x^2) +
1e-6) * w.

  streams     X^0 = the embedding E[t] copied into n streams.  For each
              of a layer's two sublayers F (attention, then FFN or
              experts), with its own phi [n C, n^2 + 2 n], b [n^2 + 2 n]
              and alpha [3] (columns pre, post, res; res row-major):
                x~     = vec(X) / sqrt(mean(vec(X)^2) + 1e-6)
                H~pre  = alpha_pre  (x~ phi_pre)  + b_pre          [n]
                H~post = alpha_post (x~ phi_post) + b_post         [n]
                H~res  = alpha_res mat(x~ phi_res) + b_res         [n, n]
                H_pre  = sigmoid(H~pre);  H_post = 2 sigmoid(H~post)
                M_0    = exp(clamp(H~res, -30, 30)); 20 rounds of
                         M <- M / (M 1 + 1e-6); M <- M / (1^T M + 1e-6)
                H_res  = M_20
                u      = H_pre X;  y = F(RMSNorm(u))
                X'     = H_res X + H_post^T y
              After the last layer h = sum_j X_j, the final RMSNorm,
              logits = h W_head ("mHC", arXiv:2512.24880; copy-in and
              sum-out are Hyper-Connections', arXiv:2409.19606).
  attention   32 heads.  cq = RMSNorm(u Wqa) [S, 768]; q = cq Wqb as
              [S, 32, 192], a head [q_nope 128 | q_rope 64]; [ckv |
              k_rope] = u Wkva [S, 512 + 64], ckv = RMSNorm(ckv), k_rope
              not normed; [k_nope 128 | v 128] = ckv Wkvb a head.
              Rotary on q_rope of every head and the ONE k_rope a
              position, rotate-half pairing (i, i + 32), at YaRN's
              frequencies: plain 1e4^(-2i/64) where the wavelength makes
              more than beta_fast = 32 turns over the original 4,096
              positions, divided by factor = 64 where it makes fewer
              than beta_slow = 1, a linear ramp over the dimensions
              between; cos and sin times mscale / mscale_all_dim = 1.
              a_h = softmax(f q_h k_h^T / sqrt(192) + causal mask) v_h
              with f = (0.1 ln 64 + 1)^2 = 2.0048; Attn = concat_h(a_h)
              Wo, 32 x 128 to 3,584.
  FFN         layers before ``first_k_dense_replace`` a gated silu FFN of
              9,216; the others GLM-4.7-Flash's sparse FFN at 64
              experts, 4 picks, scale 2, width 1,024, one shared expert.
  prediction  GLM-4.7-Flash's module: z = [RMSNorm_e(E[t_{i+1}]) |
              RMSNorm_h(h_i)] Wp with h the summed streams before the
              final norm; z copied into n streams, one sparse block with
              two hyper-connections of its own, the streams summed,
              RMSNorm_s, the shared head on t_{i+2}; L = L_main + 0.3
              L_mtp.

Departures that could be wrong are ``assumed`` in
perf/configs/xing4.0-29b-a4b.json with their sources.
"""

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from perf.families import glm4_moe_lite_reference as base

# the sparse FFN, the latents, the head's loss and the small functions
# are GLM-4.7-Flash's, on this model's numbers
rms_norm, silu, gated_mlp = base.rms_norm, base.silu, base.gated_mlp
router_scores, sparse_ffn = base.router_scores, base.sparse_ffn
cross_entropy, global_norm = base.cross_entropy, base.global_norm
bias_update, pick_counts = base.bias_update, base.pick_counts


class Spec(NamedTuple):
    """The numbers of the equations; hashable, a static argument."""
    sparse: tuple              # (False, True, ...): a layer's FFN
    heads: int = 32
    kv_rank: int = 512
    nope: int = 128
    rope: int = 64
    theta: float = 10000.0
    # YaRN: (factor, original positions, beta_fast, beta_slow) or None
    yarn: Optional[tuple] = (64.0, 4096, 32.0, 1.0)
    softmax_factor: float = (0.1 * math.log(64.0) + 1.0) ** 2
    eps: float = 1e-6
    picked: int = 4
    scale: float = 2.0
    held_first: int = 0
    mtp_weight: float = 0.3
    gamma: float = 0.001
    streams: int = 4
    rounds: int = 20
    hc_eps: float = 1e-6
    clamp: Optional[tuple] = (-30.0, 30.0)
    post_scale: float = 2.0
    dynamic: bool = True       # False drops alpha (x~ phi): the biases alone
    mix_dtype: str = "float32"  # what the three mixes are rounded to


def mm(a, b):
    """Every product goes through GLM-4.7-Flash's reference's, so that
    a check can lower the precision of all of them at once."""
    return base.mm(a, b)


def frequencies(spec):
    """The rope / 2 rotary frequencies."""
    half = spec.rope // 2
    i = jnp.arange(half, dtype=jnp.float32)
    plain = spec.theta ** (-2.0 * i / spec.rope)
    if spec.yarn is None:
        return plain
    factor, original, fast, slow = spec.yarn

    def dimension(turns):
        return (spec.rope * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(spec.theta)))

    low = max(math.floor(dimension(fast)), 0)
    high = min(math.ceil(dimension(slow)), spec.rope - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotate(x, spec):
    """x [S, heads, rope]: position s turns pairs (i, i + rope/2)."""
    half = spec.rope // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * frequencies(
        spec)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, u, spec):
    """u [S, hidden] -> [S, hidden]: heads of nope + rope against values
    of their own size, whatever Wkvb gives beyond nope."""
    seq, heads, nope = u.shape[0], spec.heads, spec.nope
    dim = nope + spec.rope
    cq, ckv, k_rope = base.latents(p, u, spec)
    q = mm(cq, p["Wqb"]).reshape(seq, heads, dim)
    kv = mm(ckv, p["Wkvb"]).reshape(seq, heads, -1)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], spec)], -1)
    k = jnp.concatenate([kv[..., :nope], base.shared_key(
        rotate(k_rope[:, None, :], spec), heads)], -1)
    v = kv[..., nope:]
    keep = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args                          # [S, dim], [S, v]
        scores = spec.softmax_factor * mm(q_h, k_h.T) / math.sqrt(dim)
        return mm(jax.nn.softmax(jnp.where(keep, scores, -jnp.inf),
                                 axis=-1), v_h)

    a = jax.lax.map(head, tuple(x.transpose(1, 0, 2) for x in (q, k, v)))
    return mm(a.transpose(1, 0, 2).reshape(seq, -1), p["Wo"])


def mixes(p, x, spec):
    """x [n, S, C] -> (H_pre [n, S], H_post [n, S], H_res [n, n, S]):
    the tokens last, so that a round's sums and quotients are of whole
    vectors (an array whose last dimensions are 4 x 4 fills a
    thirty-second of a TPU's tile)."""
    n, seq, width = x.shape
    vec = x.transpose(1, 0, 2).reshape(seq, n * width)
    normed = vec / jnp.sqrt(jnp.mean(jnp.square(vec), axis=-1,
                                     keepdims=True) + spec.eps)
    proj = mm(normed, p["phi"]).T * (1.0 if spec.dynamic else 0.0)
    alpha, b = p["alpha"], p["b"][:, None]
    pre = jax.nn.sigmoid(alpha[0] * proj[:n] + b[:n])
    post = spec.post_scale * jax.nn.sigmoid(
        alpha[1] * proj[n:2 * n] + b[n:2 * n])
    logits = (alpha[2] * proj[2 * n:] + b[2 * n:]).reshape(n, n, seq)
    if spec.clamp is not None:
        logits = jnp.clip(logits, *spec.clamp)
    m = jnp.exp(logits)
    for _ in range(spec.rounds):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + spec.hc_eps)   # rows
        m = m / (jnp.sum(m, axis=0, keepdims=True) + spec.hc_eps)   # columns
    return tuple(h.astype(spec.mix_dtype).astype(jnp.float32)
                 for h in (pre, post, m))


def sublayer(p, x, norm, fn, spec):
    """(X' [n, S, C], what ``fn`` gave beside its output, the mixes)."""
    pre, post, res = mixes(p, x, spec)
    n = x.shape[0]
    u = sum(pre[j][:, None] * x[j] for j in range(n))
    y, beside = fn(rms_norm(u, norm, spec.eps))
    out = jnp.stack([
        sum(res[i, j][:, None] * x[j] for j in range(n))
        + post[i][:, None] * y for i in range(n)])
    return out, beside, (pre, post, res)


def layer(p, x, sparse, spec, picks=None):
    """(X', the sparse FFN's (scores, picks) or None, the two
    sublayers' mixes)."""
    x, _, first = sublayer(p["hc_attn"], x, p["norm1"],
                           lambda u: (attention(p, u, spec), None), spec)
    if sparse:
        def ffn(u):
            return sparse_ffn(p, u, spec, picks)
    else:
        def ffn(u):
            return gated_mlp(p["ffn"], u), None
    x, routing, second = sublayer(p["hc_ffn"], x, p["norm2"], ffn, spec)
    return x, routing, tuple(jnp.stack(pair) for pair in zip(first, second))


def row_terms(params, ids_row, spec, picks=None):
    """One row [S]: (sum of the main head's S-1 losses, sum of the
    module's S-2, [(scores, picks)] of the gates, [mixes] of the
    blocks, a block's (pre [2, n, S], post [2, n, S], res [2, n, n,
    S]))."""
    def run(p, x, sparse, forced):
        return jax.checkpoint(
            lambda p_, x_, f_: layer(p_, x_, sparse, spec, f_))(p, x, forced)

    def streams(h):
        return jnp.broadcast_to(h, (spec.streams, *h.shape))

    x = streams(params["embed"][ids_row])
    routed, mixed = [], []
    for p, sparse in zip(params["layers"], spec.sparse):
        forced = picks[len(routed)] if (
            sparse and picks is not None) else None
        x, routing, kept = run(p, x, sparse, forced)
        mixed.append(kept)
        if sparse:
            routed.append(routing)
    h = jnp.sum(x, axis=0)
    main = cross_entropy(rms_norm(h, params["norm"], spec.eps)[:-1],
                         params["head"], ids_row[1:])
    module = params.get("mtp")
    if module is None:
        return main, 0.0, routed, mixed
    z = mm(jnp.concatenate([
        rms_norm(params["embed"][base.mtp_inputs(ids_row)], module["enorm"],
                 spec.eps),
        rms_norm(h, module["hnorm"], spec.eps)], axis=-1), module["Wp"])
    z, routing, kept = run(module["block"], streams(z), True,
                           picks[len(routed)] if picks is not None else None)
    routed.append(routing)
    mixed.append(kept)
    mtp = cross_entropy(
        rms_norm(jnp.sum(z, axis=0), module["norm"], spec.eps)[:-2],
        params["head"], ids_row[2:])
    return main, mtp, routed, mixed


def forward(params, ids, spec, picks=None):
    """(L, (L_main, L_mtp, scores [G, B S, E], picks [G, B S, 4], mixes))
    of int32 ``ids`` [B, S] over the G gates; each term the mean over
    the batch's positions that have a target; ``mixes`` (pre [L, 2, B S,
    n], post [L, 2, B S, n], res [L, 2, B S, n, n]) over the L blocks,
    the module's last."""
    with jax.default_matmul_precision("highest"):
        rows, seq = ids.shape
        main = mtp = 0.0
        routed, mixed = [], []
        for b in range(rows):
            forced = None if picks is None else picks.reshape(
                picks.shape[0], rows, seq, -1)[:, b]
            row_main, row_mtp, row_routed, row_mixed = row_terms(
                params, ids[b], spec, forced)
            main, mtp = main + row_main, mtp + row_mtp
            routed.append(row_routed)
            mixed.append(row_mixed)
        main = main / (rows * (seq - 1))
        mtp = mtp / (rows * max(seq - 2, 1))
        gates = len(routed[0])
        scores, chosen = (
            jnp.stack([jnp.concatenate([r[g][part] for r in routed])
                       for g in range(gates)]) if gates else None
            for part in (0, 1))
        # the rows' tokens side by side, then the tokens before the
        # streams: [L, 2, B S, n] and [L, 2, B S, n, n]
        kept = tuple(
            jnp.moveaxis(jnp.stack([
                jnp.concatenate([m[block][part] for m in mixed], axis=-1)
                for block in range(len(mixed[0]))]), -1, 2)
            for part in range(3))
        return main + spec.mtp_weight * mtp, (main, mtp, scores, chosen,
                                              kept)


def loss_and_grads(params, ids, spec, picks=None):
    """((L, (L_main, L_mtp, scores, picks, mixes)), L's gradient in the
    tree of ``params``; the biases' is zero)."""
    return jax.value_and_grad(forward, has_aux=True)(params, ids, spec, picks)
