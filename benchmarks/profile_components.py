"""Component-level TPU microbenchmarks for the GPT-2 step (round-2 MFU work).

Times each op class in isolation (attention, LN, dropout, matmul-only layer,
embedding, fused CE, scan-vs-unrolled, fp32-master-vs-bf16-params) so the
gap between the full step and the matmul roofline can be attributed.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import _harness  # noqa: F401 — compile cache
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import GPT2Config, GPT2Model
from deepspeed_tpu.ops.flash_attention import flash_attention, mha_reference
from deepspeed_tpu.ops.normalize import fused_layer_norm
from deepspeed_tpu.ops.activations import dropout

BATCH, SEQ, H, HEADS, LAYERS = 8, 1024, 768, 12, 12
D = H // HEADS


def timeit(name, fn, *args, iters=20, warmup=3, flops=None):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.time() - t0) / iters
    extra = f"  ({flops / dt / 1e12:7.1f} TFLOPS)" if flops else ""
    print(f"{name:50s} {dt * 1e3:9.3f} ms{extra}")
    return dt


def main():
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 10)

    # ---- attention --------------------------------------------------- #
    q = jax.random.normal(ks[0], (BATCH, HEADS, SEQ, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (BATCH, HEADS, SEQ, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (BATCH, HEADS, SEQ, D), jnp.bfloat16)
    # causal: ~half the S^2 work
    attn_flops = 2 * 2 * BATCH * HEADS * SEQ * SEQ * D / 2

    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    timeit("flash attention fwd (pallas)", fa, q, k, v, flops=attn_flops)
    ref = jax.jit(lambda q, k, v: mha_reference(q, k, v, causal=True))
    timeit("mha_reference fwd (xla)", ref, q, k, v, flops=attn_flops)

    fab = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    timeit("flash attention fwd+bwd (pallas)", fab, q, k, v,
           flops=attn_flops * 3.5)
    refb = jax.jit(jax.grad(
        lambda q, k, v: mha_reference(q, k, v, causal=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    timeit("mha_reference fwd+bwd (xla)", refb, q, k, v,
           flops=attn_flops * 3.5)

    # ---- layernorm / dropout ---------------------------------------- #
    x = jax.random.normal(ks[3], (BATCH, SEQ, H), jnp.bfloat16)
    w = jnp.ones((H,), jnp.float32)
    b = jnp.zeros((H,), jnp.float32)
    ln = jax.jit(lambda x: fused_layer_norm(x, w, b, 1e-5))
    timeit("layernorm fwd [8,1024,768] (x24 per step fwd)", ln, x)
    dr = jax.jit(lambda x, r: dropout(x, 0.1, r, False))
    timeit("dropout fwd [8,1024,768] (x37 per step fwd)", dr, x, ks[4])

    # ---- matmul-only transformer layer (the MXU floor) --------------- #
    wqkv = jax.random.normal(ks[5], (H, 3 * H), jnp.bfloat16)
    wo = jax.random.normal(ks[6], (H, H), jnp.bfloat16)
    wi = jax.random.normal(ks[7], (H, 4 * H), jnp.bfloat16)
    wout = jax.random.normal(ks[8], (4 * H, H), jnp.bfloat16)
    x2 = x.reshape(-1, H)
    layer_flops = 2 * BATCH * SEQ * H * (3 * H + H + 4 * H + 4 * H)

    @jax.jit
    def mm_layer(x2):
        h = x2 @ wqkv
        h = h[:, :H] @ wo
        h = h @ wi
        return h @ wout

    timeit("matmul-only layer fwd (x12 per step)", mm_layer, x2,
           flops=layer_flops)

    # ---- full single layer fwd --------------------------------------- #
    cfg = GPT2Config(n_positions=SEQ, bf16=True)
    model = GPT2Model(cfg)
    params = jax.tree.map(jnp.asarray, model.init_params(ks[9]))
    layer0 = jax.tree.map(lambda a: a[0], params["h"])
    layer0_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), layer0)

    lf = jax.jit(lambda p, x, r: model.layer(p, x, rng=r))
    timeit("full layer fwd fp32-params (x12 per step)", lf, layer0, x, ks[4])
    timeit("full layer fwd bf16-params (x12 per step)", lf, layer0_bf16, x,
           ks[4])
    lfd = jax.jit(lambda p, x: model.layer(p, x, deterministic=True))
    timeit("full layer fwd no-dropout (x12)", lfd, layer0, x)

    lb = jax.jit(jax.grad(
        lambda p, x, r: model.layer(p, x, rng=r).astype(jnp.float32).sum(),
        argnums=(0, 1)))
    timeit("full layer fwd+bwd fp32-params (x12)", lb, layer0, x, ks[4])
    timeit("full layer fwd+bwd bf16-params (x12)", lb, layer0_bf16, x, ks[4])

    # ---- body: scan vs unrolled -------------------------------------- #
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(BATCH, SEQ)), jnp.int32)

    body_fwd = jax.jit(lambda p, r: model.hidden_states(p, ids, r))
    timeit("body fwd scan (12 layers)", body_fwd, params, ks[4])

    params_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    timeit("body fwd scan bf16-params", body_fwd, params_bf16, ks[4])

    @jax.jit
    def body_unrolled(p, r):
        h = model.embed(p, ids)
        h = dropout(h, cfg.embd_dropout, r, False)
        for i in range(LAYERS):
            lp = jax.tree.map(lambda a: a[i], p["h"])
            h = model.layer(lp, h, rng=jax.random.fold_in(r, i))
        return h

    timeit("body fwd unrolled (12 layers)", body_unrolled, params, ks[4])

    bscan = jax.jit(jax.grad(
        lambda p, r: model.hidden_states(p, ids, r)
        .astype(jnp.float32).sum()))
    timeit("body fwd+bwd scan", bscan, params, ks[4])
    timeit("body fwd+bwd scan bf16-params", bscan, params_bf16, ks[4])

    bunroll = jax.jit(jax.grad(
        lambda p, r: body_unrolled.__wrapped__(p, r)
        .astype(jnp.float32).sum()))
    timeit("body fwd+bwd unrolled", bunroll, params, ks[4])

    # ---- embedding + head -------------------------------------------- #
    emb = jax.jit(lambda p: model.embed(p, ids))
    timeit("embed fwd", emb, params)

    from deepspeed_tpu.ops.fused_cross_entropy import (
        fused_linear_cross_entropy)
    hflat = x.reshape(-1, H)
    head_w = params["wte"].astype(jnp.bfloat16).T
    labels = ids.reshape(-1)
    ce_flops = 2 * BATCH * SEQ * H * cfg.vocab_size

    fce = jax.jit(lambda h, w: fused_linear_cross_entropy(h, w, labels, 8192))
    timeit("fused CE fwd (chunk 8192)", fce, hflat, head_w, flops=ce_flops)
    fceb = jax.jit(jax.grad(
        lambda h, w: fused_linear_cross_entropy(h, w, labels, 8192),
        argnums=(0, 1)))
    timeit("fused CE fwd+bwd (chunk 8192)", fceb, hflat, head_w,
           flops=3 * ce_flops)

    for chunk in (16384, 50304):
        fce2 = jax.jit(lambda h, w, c=chunk: fused_linear_cross_entropy(
            h, w, labels, c))
        timeit(f"fused CE fwd (chunk {chunk})", fce2, hflat, head_w,
               flops=ce_flops)
        fce2b = jax.jit(jax.grad(
            lambda h, w, c=chunk: fused_linear_cross_entropy(h, w, labels, c),
            argnums=(0, 1)))
        timeit(f"fused CE fwd+bwd (chunk {chunk})", fce2b, hflat, head_w,
               flops=3 * ce_flops)

    # unfused reference: full logits + optax CE
    import optax

    @jax.jit
    def unfused(h, w):
        logits = (h @ w).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    timeit("unfused CE fwd (full logits)", unfused, hflat, head_w,
           flops=ce_flops)
    ufb = jax.jit(jax.grad(unfused, argnums=(0, 1)))
    timeit("unfused CE fwd+bwd (full logits)", ufb, hflat, head_w,
           flops=3 * ce_flops)


if __name__ == "__main__":
    main()
