"""Ouro-2.6B (the LoopLM of arXiv:2510.25741) in plain ``jax.numpy`` and
float32: the loss of a stack of layers run T times on the same weights
with a learned exit gate, its terms and its gradients.  No kernel, no
mixed precision, no fused cross-entropy: attention is a masked softmax
head by head, the passes and the layers are Python loops (at the
published size on the chip two ``lax.scan``s of the same body,
``Spec.rolled``, so that the executable fits the compile cache), the
logits are whole rows of the vocabulary.  Written from the equations
below, not from the program's model file.

Where each equation comes from (the builder had no network; the
equations are those of ISSUE 45, which took them from the keys of the
released ``config.json``, catalog row ``Ouro-2.6B``, and from the
paper's stage-one objective).  x is a batch row, [S, 2048]; every
product is without bias; ``N*`` is an RMSNorm with its own gain,
``N(x) = x / sqrt(mean(x^2) + 1e-6) * w``.

  block       a = x + N2(Attn(N1(x)));  y = a + N4(FFN(N3(a))).
  attention   q, k, v = u Wq, u Wk, u Wv as [S, 16, 128]; rotary on q and
              k, rotate-half pairing (i, i + 64) over all 128 dimensions,
              inv_freq_i = 1e6^(-2i/128), i = 0..63, no scaling;
              a_h = softmax(q_h k_h^T / sqrt(128) + causal mask) v_h;
              Attn = concat_h(a_h) Wo.
  FFN         (silu(u Wg) * (u Wu)) Wd, width 5,632.
  recurrence  h_0 = E[ids]; h_t = Nf(Stack(h_{t-1})), t = 1..T (T = 4),
              Stack the held layers in order, Nf the one final norm, the
              same weights every pass.  h_t is what the head and the gate
              read and what pass t + 1 starts from.
  gate        lam_t(i) = sigmoid(h_t(i) . w_g + b_g), t = 1..T-1;
              p_1 = lam_1, p_t = lam_t prod_{j<t} (1 - lam_j),
              p_T = prod_{j<T} (1 - lam_j).
  loss        l_t(i) = -log softmax(h_t(i) W_head)[y_i], y_i the next
              token, over the positions that have one;
              L = mean_i [ sum_t p_t(i) l_t(i) + beta KL(p(i) || uniform
              over T) ], KL = ln T + sum_t p_t ln p_t, beta = 0.1.

Departures that could be wrong, each because the config names no tensor
or rule for it (``assumed`` in perf/configs/ouro-2.6b.json has the
reasoning): the sandwich norms and the norm between passes are from the
released modelling code as ISSUE 45's writer recalled it; no bias on any
projection; the prior of the exit distribution is uniform and beta 0.1.

For 4,096 positions the float32 scores of all heads at once do not fit
beside the weights and their gradients, so heads are mapped one after
another, the logits are taken by blocks of rows and every layer
application and block runs under ``jax.checkpoint``: the same sums in
the same order, recomputed instead of kept.  On a TPU a float32 product
runs in reduced precision unless told otherwise, so the entry point sets
``default_matmul_precision("highest")``.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Spec(NamedTuple):
    """The numbers of the equations; hashable, a static argument."""
    heads: int = 16
    head_dim: int = 128
    theta: float = 1e6
    eps: float = 1e-6
    passes: int = 4
    beta: float = 0.1
    row_block: int = 1024      # positions whose logits are held at once
    # the passes and the layers as ``lax.scan``s over one traced layer
    # instead of Python loops over 32 of them (``row_terms_rolled``)
    rolled: bool = False


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def mm(a, b):
    """Every product the MXU would take (weights, attention and head
    alike) goes through here, so that a check can lower its precision
    and see the comparison fail."""
    return a @ b


def scores(q_h, k_h, dim):
    """[S, S]: one head's scaled scores, before the mask."""
    return mm(q_h, k_h.T) / math.sqrt(dim)


def exit_gate(h, w, b):
    """[S]: lam, the probability of leaving after this pass."""
    return jax.nn.sigmoid(h @ w + b)


def exit_distribution(lams):
    """lam [T - 1, S] -> p [T, S]."""
    stay, p = jnp.ones_like(lams[0]), []
    for lam in lams:
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


def kl_to_uniform(p):
    """[S]: KL(p || uniform over the T exits)."""
    return math.log(p.shape[0]) + jnp.sum(
        jax.scipy.special.xlogy(p, p), axis=0)


def rotate(x, spec):
    """x [S, heads, 128]: pairs (i, i + 64) turned by the position's
    angle."""
    half = spec.head_dim // 2
    i = jnp.arange(half, dtype=jnp.float32)
    inv_freq = spec.theta ** (-2.0 * i / spec.head_dim)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, u, spec):
    """u [S, hidden] -> [S, hidden]."""
    seq, heads, dim = u.shape[0], spec.heads, spec.head_dim
    q = rotate(mm(u, p["Wq"]).reshape(seq, heads, dim), spec)
    k = rotate(mm(u, p["Wk"]).reshape(seq, heads, dim), spec)
    v = mm(u, p["Wv"]).reshape(seq, heads, dim)
    causal = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args                                  # [S, D]
        return mm(jax.nn.softmax(jnp.where(
            causal, scores(q_h, k_h, dim), -jnp.inf), axis=-1), v_h)

    a = jax.lax.map(head, tuple(x.transpose(1, 0, 2) for x in (q, k, v)))
    return mm(a.transpose(1, 0, 2).reshape(seq, heads * dim), p["Wo"])


def ffn(p, u):
    return mm(silu(mm(u, p["Wgate"])) * mm(u, p["Wup"]), p["Wdown"])


def block(p, x, spec):
    a = x + rms_norm(attention(p, rms_norm(x, p["norm1"], spec.eps), spec),
                     p["norm2"], spec.eps)
    return a + rms_norm(ffn(p, rms_norm(a, p["norm3"], spec.eps)),
                        p["norm4"], spec.eps)


def token_losses(h, head, targets, spec):
    """[S]: -log softmax(h W_head)[target] of every position, the logits
    of ``row_block`` positions at a time."""
    @jax.checkpoint
    def rows(h_rows, t_rows):
        logp = jax.nn.log_softmax(mm(h_rows, head), axis=-1)
        return -jnp.take_along_axis(logp, t_rows[:, None], axis=-1)[:, 0]

    step = min(spec.row_block, h.shape[0])
    return jnp.concatenate([rows(h[i:i + step], targets[i:i + step])
                            for i in range(0, h.shape[0], step)])


def row_terms(params, ids, spec):
    """One row, ids [S]: (l [T, S - 1], p [T, S - 1]) over the positions
    that have a next token."""
    h = params["embed"][ids]
    losses, lams = [], []
    for t in range(spec.passes):
        for p in params["layers"]:
            h = jax.checkpoint(lambda p_, h_: block(p_, h_, spec))(p, h)
        h = rms_norm(h, params["norm"], spec.eps)
        losses.append(token_losses(h[:-1], params["head"], ids[1:], spec))
        if t < spec.passes - 1:
            lams.append(exit_gate(h[:-1], params["gate_w"],
                                  params["gate_b"]))
    return jnp.stack(losses), exit_distribution(jnp.stack(lams))


def row_terms_rolled(params, ids, spec):
    """``row_terms`` with its two Python loops as ``lax.scan``s, for the
    published size on the chip: 32 unrolled layer applications and their
    backward passes compile to an executable of 293 MB in 150 s, too
    large for the compile cache, so every run paid the compile; one
    traced layer compiles in seconds.  The same sums in the same order
    forward; tests/perf/test_ouro_reference.py holds it to ``row_terms``.
    The gate is also evaluated after the last pass and that value
    dropped.  ``params["layers"]`` may be the list of layers or one dict
    of their arrays stacked (no copy of the weights then, and the
    gradients come back stacked)."""
    stacked = params["layers"]
    if not isinstance(stacked, dict):
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *stacked)

    def one_layer(h, p):
        return jax.checkpoint(lambda p_, h_: block(p_, h_, spec))(p, h), None

    def one_pass(h, _):
        h, _ = jax.lax.scan(one_layer, h, stacked)
        h = rms_norm(h, params["norm"], spec.eps)
        return h, (token_losses(h[:-1], params["head"], ids[1:], spec),
                   exit_gate(h[:-1], params["gate_w"], params["gate_b"]))

    _, (losses, lams) = jax.lax.scan(one_pass, params["embed"][ids], None,
                                     length=spec.passes)
    return losses, exit_distribution(lams[:-1])


def forward(params, ids, spec):
    """int32 ``ids`` [B, S] -> (L, {"task_loss", "exit_kl", "exit_losses"
    [T] the mean l_t, "exit_mass" [T] the mean p_t}), every mean over the
    B (S - 1) positions that have a next token."""
    with jax.default_matmul_precision("highest"):
        one_row = row_terms_rolled if spec.rolled else row_terms
        terms = [one_row(params, ids[b], spec) for b in range(ids.shape[0])]
        losses = jnp.concatenate([l for l, _ in terms], axis=1)   # [T, N]
        p = jnp.concatenate([p for _, p in terms], axis=1)
        task = jnp.mean(jnp.sum(p * losses, axis=0))
        kl = jnp.mean(kl_to_uniform(p))
        return task + spec.beta * kl, {
            "task_loss": task, "exit_kl": kl,
            "exit_losses": jnp.mean(losses, axis=1),
            "exit_mass": jnp.mean(p, axis=1)}


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, spec):
    """((L, its terms), L's gradient in the tree of ``params``)."""
    return jax.value_and_grad(forward, has_aux=True)(params, ids, spec)
