"""Single-chip GPT-2 step-time breakdown (round-2 MFU work).

Times isolated variants of the flagship bench to locate the bottleneck:
full engine step vs no-dropout vs no-LM-head vs matmul roofline.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import _harness  # noqa: F401 — compile cache
import jax
import jax.numpy as jnp
import numpy as np
import optax

from deepspeed_tpu.models import GPT2Config, GPT2Model

BATCH, SEQ = 8, 1024


def timeit(name, fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.time() - t0) / iters
    print(f"{name:45s} {dt * 1e3:9.2f} ms")
    return dt


def main():
    cfg = GPT2Config(n_positions=SEQ, bf16=True)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.tree.map(jnp.asarray, params)
    rng = jax.random.PRNGKey(1)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(BATCH, SEQ)), jnp.int32)

    tx = optax.adamw(6e-4, weight_decay=0.1)
    opt_state = tx.init(params)


    # --- full train step, with dropout (bench equivalent) -------------- #
    @jax.jit
    def step_full(params, opt_state, rng):
        def loss_fn(p):
            return model.loss(p, rng, ids)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # --- no dropout ---------------------------------------------------- #
    @jax.jit
    def step_nodrop(params, opt_state):
        def loss_fn(p):
            return model.loss(p, None, ids)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # --- fwd only ------------------------------------------------------ #
    @jax.jit
    def fwd_only(params, rng):
        return model.loss(params, rng, ids)

    # --- fwd+bwd only (no optimizer) ----------------------------------- #
    @jax.jit
    def fwdbwd(params, rng):
        def loss_fn(p):
            return model.loss(p, rng, ids)
        return jax.value_and_grad(loss_fn)(params)

    # --- body only (no head/CE), fwd+bwd ------------------------------- #
    @jax.jit
    def body_fwdbwd(params, rng):
        def loss_fn(p):
            h = model.hidden_states(p, ids, rng)
            return (h.astype(jnp.float32) ** 2).mean()
        return jax.value_and_grad(loss_fn)(params)

    # --- head+CE only, fwd+bwd ----------------------------------------- #
    h_fixed = jax.jit(
        lambda p, r: model.hidden_states(p, ids, r))(params, rng)

    @jax.jit
    def head_fwdbwd(params):
        def loss_fn(p):
            logits = model.head_logits(p, h_fixed)[:, :-1]
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, ids[:, 1:]).mean()
        return jax.value_and_grad(loss_fn)(params)

    # --- matmul roofline ------------------------------------------------ #
    a = jnp.ones((8192, 4096), jnp.bfloat16)
    b = jnp.ones((4096, 4096), jnp.bfloat16)

    @jax.jit
    def mm(a, b):
        for _ in range(8):
            a = jax.lax.dot(a, b)
        return a

    t = timeit("matmul roofline (8x 8192x4096x4096)", mm, a, b)
    tf = 8 * 2 * 8192 * 4096 * 4096 / t / 1e12
    print(f"    -> {tf:.1f} TFLOPS achievable")

    # flops_per_token() already includes the LM-head matmul (Megatron-style
    # accounting) — do not add it again
    flops = BATCH * SEQ * cfg.flops_per_token()
    print(f"step model-FLOPs (incl LM head): {flops/1e12:.2f} T")

    t = timeit("full step (dropout)", step_full, params, opt_state, rng)
    print(f"    -> {flops / t / 1e12:.1f} TFLOPS")
    t = timeit("full step (no dropout)", step_nodrop, params, opt_state)
    t = timeit("fwd only (dropout)", fwd_only, params, rng)
    t = timeit("fwd+bwd (dropout)", fwdbwd, params, rng)
    t = timeit("body fwd+bwd (no head)", body_fwdbwd, params, rng)
    t = timeit("head+CE fwd+bwd", head_fwdbwd, params)


if __name__ == "__main__":
    main()
