"""GPT-2 as Radford et al. (2019) and the released model describe it,
in plain ``jax.numpy`` and float32: loss and gradients of next-token
prediction.  No kernel, no scan, no cache, no mixed precision; written
from the description and from the parameter names of the released
checkpoints, not from the program's model file.

    h_0   = wte[ids] + wpe[0..S)
    a     = LN(h; ln_1);  q, k, v = split(a c_attn.w + c_attn.b)
    h     = h + merge(softmax(mask(q k^T / sqrt(d))) v) c_proj.w + c_proj.b
    m     = LN(h; ln_2)
    h     = h + gelu_new(m c_fc.w + c_fc.b) c_proj.w + c_proj.b
    logit = LN(h_L; ln_f) wte^T            (the head is tied to wte)
    loss  = mean over the B x (S-1) predicted positions of
            -log softmax(logit_t)[ids_{t+1}]

Departures from the released model: none in the mathematics.  Dropout is
left out (the parity check runs the program with dropout off), and the
softmax runs over as many rows as the ``wte`` it is given has, so a
program that pads the table is compared on the padded table.

On a TPU a float32 product runs in reduced precision unless told
otherwise, so every entry point sets ``default_matmul_precision
("highest")``.
"""

import math

import jax
import jax.numpy as jnp


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["w"] + p["b"]


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def linear(x, p):
    return x @ p["w"] + p["b"]


def attention(p, x, n_head):
    batch, seq, width = x.shape
    head_dim = width // n_head
    q, k, v = jnp.split(linear(x, p["c_attn"]), 3, axis=-1)

    def heads(t):  # [B, S, h] -> [B, n_head, S, d]
        return t.reshape(batch, seq, n_head, head_dim).transpose(0, 2, 1, 3)

    scores = heads(q) @ heads(k).transpose(0, 1, 3, 2) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jax.nn.softmax(scores, axis=-1) @ heads(v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(batch, seq, width)
    return linear(ctx, p["c_proj"])


def block(p, x, n_head, eps):
    x = x + attention(p["attn"], layer_norm(x, p["ln_1"], eps), n_head)
    m = layer_norm(x, p["ln_2"], eps)
    return x + linear(gelu_new(linear(m, p["mlp"]["c_fc"])),
                      p["mlp"]["c_proj"])


def loss(params, ids, n_head, eps):
    """Mean next-token cross-entropy of int32 ``ids`` [B, S]."""
    with jax.default_matmul_precision("highest"):
        seq = ids.shape[1]
        h = params["wte"][ids] + params["wpe"][:seq]
        for p in params["h"]:
            h = block(p, h, n_head, eps)
        logits = layer_norm(h, params["ln_f"], eps) @ params["wte"].T
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        return -jnp.mean(picked)


def global_norm(tree):
    """L2 norm over every entry of every leaf, in float32."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def loss_and_grads(params, ids, n_head, eps):
    """(loss, its gradient in the tree of ``params``)."""
    return jax.value_and_grad(loss)(params, ids, n_head, eps)
