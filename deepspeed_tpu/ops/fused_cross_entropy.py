"""Fused (chunked) linear + softmax cross-entropy — the LM-head memory fix.

The naive path materializes fp32 logits [B, S, V] (GPT-2 124M at B=8,
S=1024: 1.6 GB) and reads them again for the softmax — pure HBM traffic
the MXU waits on.  This op never materializes more than one vocab CHUNK of
logits: the forward streams logsumexp over chunks (online softmax), and
the custom VJP recomputes each chunk to emit dh and dW incrementally —
O(B·S·chunk) live instead of O(B·S·V).

Non-divisible vocabularies (e.g. GPT-2's unpadded 50257) are padded up to
a whole number of chunks; padded columns are masked to -inf in the
forward (zero probability) so they contribute nothing to the loss or the
gradients, and the dW pad columns are sliced away.

Reference counterpart: the training softmax kernels
(csrc/transformer/softmax_kernels.cu) fuse scale+mask+softmax for the same
reason — do not round-trip the big tensor through HBM.  (The chunked
linear-CE formulation matches public "fused linear cross entropy" practice
in TPU/GPU LM stacks.)
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


# Auto chunk policy: bound the transient [N, chunk] fp32 logits block.
# Measured on v5e in round 2 (jax 0.4.37, a host-clock chunk sweep at
# the step level; the script is gone, git keeps it): larger chunks are
# faster (fewer scan steps, bigger matmuls) — 105ms vs 111ms full-step at
# the flagship shape for whole-vocab vs 8192 — so "auto" picks the largest
# chunk whose transient stays under this budget.
_CE_CHUNK_ELEM_BUDGET = 1 << 29  # 512M fp32 elements = 2 GB transient


def _plan(vocab: int, chunk_size, n_tokens: int):
    """(chunk, n_chunks, padded_vocab) with chunk*n_chunks == padded."""
    if chunk_size is None:
        chunk_size = max(4096, _CE_CHUNK_ELEM_BUDGET // max(1, n_tokens))
    c = max(1, min(chunk_size, vocab))
    n_chunks = -(-vocab // c)
    return c, n_chunks, c * n_chunks


def _padded_w(w, padded_vocab):
    hid, vocab = w.shape
    if padded_vocab == vocab:
        return w
    return jnp.pad(w, ((0, 0), (0, padded_vocab - vocab)))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(h, w, labels, chunk_size=None,
                               ignore_index=None):
    """mean over (valid) tokens of CE(softmax(h @ w), labels).

    h: [N, H] hidden states (any float dtype; matmuls accumulate fp32)
    w: [H, V] head projection
    labels: [N] int
    ignore_index: labels equal to this contribute nothing to the loss or
      gradients and are excluded from the mean (the masked-LM convention,
      reference bing_bert objective / torch F.cross_entropy semantics).
    """
    loss, _ = _forward(h, w, labels, chunk_size, ignore_index)
    return loss


def _valid_mask(labels, ignore_index):
    if ignore_index is None:
        return jnp.ones(labels.shape, jnp.float32), jnp.float32(
            labels.shape[0])
    valid = (labels != ignore_index).astype(jnp.float32)
    return valid, jnp.maximum(valid.sum(), 1.0)


def _chunked(h, w, chunk_size):
    """(w as [n_chunks, H, chunk] padded to whole chunks, its vocab)."""
    hid, vocab = w.shape
    c, n_chunks, padded = _plan(vocab, chunk_size, h.shape[0])
    return _padded_w(w, padded).reshape(hid, n_chunks, c).transpose(
        1, 0, 2), vocab


def _token_losses(h, chunks, labels):
    """(lse, label logit), each [N] fp32: the streamed pass over the
    vocabulary's ``chunks`` that both forms share; a token's loss is
    their difference."""
    wc, vocab = chunks
    n, c = h.shape[0], wc.shape[2]

    def body(carry, w_i):
        m, s, idx = carry
        logits = jnp.einsum(
            "nh,hc->nc", h, w_i.astype(h.dtype),
            preferred_element_type=jnp.float32)  # [N, c] fp32
        cols = idx * c + jnp.arange(c)
        logits = jnp.where(cols[None, :] < vocab, logits, -jnp.inf)
        m_new = jnp.maximum(m, logits.max(axis=1))
        s = s * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[:, None]).sum(axis=1)
        # label logit if it falls in this chunk
        local = labels - idx * c
        in_chunk = (local >= 0) & (local < c)
        lab = jnp.take_along_axis(
            logits, jnp.clip(local, 0, c - 1)[:, None], axis=1)[:, 0]
        return (m_new, s, idx + 1), jnp.where(in_chunk, lab, 0.0)

    m0 = jnp.full((n,), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((n,), jnp.float32)
    (m, s, _), lab_parts = lax.scan(body, (m0, s0, jnp.int32(0)), wc)
    return m + jnp.log(s), lab_parts.sum(axis=0)


def _forward(h, w, labels, chunk_size, ignore_index):
    chunks = _chunked(h, w, chunk_size)
    valid, denom = _valid_mask(labels, ignore_index)
    lse, label_logit = _token_losses(h, chunks, labels)
    loss = ((lse - label_logit) * valid).sum() / denom
    return loss.astype(jnp.float32), (lse,)


def _fwd(h, w, labels, chunk_size, ignore_index):
    loss, (lse,) = _forward(h, w, labels, chunk_size, ignore_index)
    return loss, (h, w, labels, lse)


def _bwd(chunk_size, ignore_index, res, g):
    h, w, labels, lse = res
    chunks = _chunked(h, w, chunk_size)
    valid, denom = _valid_mask(labels, ignore_index)
    scale = (g / denom) * valid  # [N] d mean / d token (0 on ignored)
    return _input_grads(h, chunks, labels, lse, scale, w.dtype)


def _input_grads(h, chunks, labels, lse, scale, w_dtype):
    """(dh, dw, None) for the cotangent ``scale`` [N] of the tokens'
    losses: each chunk's logits recomputed, ``(softmax - onehot) x
    scale`` sent back through the product."""
    wc, vocab = chunks
    n_chunks, hid, c = wc.shape

    def body(carry, w_i):
        dh, idx = carry
        logits = jnp.einsum("nh,hc->nc", h, w_i.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        cols = idx * c + jnp.arange(c)
        logits = jnp.where(cols[None, :] < vocab, logits, -jnp.inf)
        p = jnp.exp(logits - lse[:, None])   # softmax chunk (0 on padding)
        local = labels - idx * c
        onehot = (local[:, None] == jnp.arange(c)[None, :])
        grad_logits = (p - onehot.astype(p.dtype)) * scale[:, None]
        # dh accumulates fp32 across chunks — rounding per-chunk to bf16
        # would compound error the unchunked path doesn't have
        dh = dh + jnp.einsum("nc,hc->nh", grad_logits, w_i,
                             preferred_element_type=jnp.float32)
        dw_i = jnp.einsum("nh,nc->hc", h, grad_logits,
                          preferred_element_type=jnp.float32)
        return (dh, idx + 1), dw_i

    dh0 = jnp.zeros(h.shape, jnp.float32)
    (dh, _), dw_chunks = lax.scan(body, (dh0, jnp.int32(0)), wc)
    dw = dw_chunks.transpose(1, 0, 2).reshape(hid, n_chunks * c)[:, :vocab]
    return dh.astype(h.dtype), dw.astype(w_dtype), None


fused_linear_cross_entropy.defvjp(_fwd, _bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy_per_token(h, w, labels, chunk_size=None,
                                         ignore_index=None):
    """CE(softmax(h @ w), labels) of every token, fp32 [N], 0 where the
    label is ``ignore_index``; the logits never materialised, as in the
    mean form above, which is this under a mean.  For an objective that
    weighs each token's loss itself (a per-token exit probability that
    takes a gradient through the loss: models/ouro.py): the VJP takes
    the cotangent [N] of the losses."""
    return _per_token_fwd(h, w, labels, chunk_size, ignore_index)[0]


def _per_token_fwd(h, w, labels, chunk_size, ignore_index):
    valid, _ = _valid_mask(labels, ignore_index)
    lse, label_logit = _token_losses(h, _chunked(h, w, chunk_size), labels)
    return (lse - label_logit) * valid, (h, w, labels, lse)


def _per_token_bwd(chunk_size, ignore_index, res, g):
    h, w, labels, lse = res
    valid, _ = _valid_mask(labels, ignore_index)
    return _input_grads(h, _chunked(h, w, chunk_size), labels, lse,
                        g * valid, w.dtype)


fused_linear_cross_entropy_per_token.defvjp(_per_token_fwd, _per_token_bwd)


def even_chunk(vocab: int, n_tokens: int):
    """A ``chunk_size`` that splits ``vocab`` into EQUAL parts of whole
    lane tiles (128) under the auto policy's transient budget, in at most
    twice the chunks the auto plan takes, or None where there is none
    (the auto plan pads its last chunk: 49,152 rows at 16,384 tokens go
    in two chunks of 32,768, a quarter of the products' columns
    padding)."""
    limit = max(4096, _CE_CHUNK_ELEM_BUDGET // max(1, n_tokens))
    fewest = -(-vocab // limit)
    for parts in range(fewest, 2 * fewest + 1):
        if vocab % parts == 0 and (vocab // parts) % 128 == 0:
            return vocab // parts
    return None
