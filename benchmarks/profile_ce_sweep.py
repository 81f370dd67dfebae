"""CE chunk-size sweep at the full-step level (GPT-2 flagship shape).

50304 = 2^7 x 3 x 131, so divisor-friendly chunks are 12576 (x4),
16768 (x3), 25152 (x2), 50304 (x1); non-divisors pad the vocab up.
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
ITERS = int(os.environ.get("DS_PROFILE_ITERS", 15))


def main():
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, 50304, size=(BATCH, SEQ)), jnp.int32)
    tx = optax.adamw(6e-4, weight_decay=0.1)

    for chunk in (8192, 12576, 16768, 25152, 50304):
        cfg = GPT2Config(n_positions=SEQ, bf16=True, fused_loss_chunk=chunk)
        model = GPT2Model(cfg)
        params = jax.tree.map(jnp.asarray,
                              model.init_params(jax.random.PRNGKey(0)))
        flops = BATCH * SEQ * cfg.flops_per_token()
        state = (params, tx.init(params), jax.random.key(1, impl="rbg"))

        @jax.jit
        def step(state):
            p, o, r = state
            r, sub = jax.random.split(r)
            loss, grads = jax.value_and_grad(
                lambda pp: model.loss(pp, sub, ids))(p)
            updates, o = tx.update(grads, o, p)
            return (optax.apply_updates(p, updates), o, r)

        try:
            state = step(state)
            jax.block_until_ready(jax.tree.leaves(state)[0])
            t0 = time.time()
            for _ in range(ITERS):
                state = step(state)
            jax.block_until_ready(jax.tree.leaves(state)[0])
            dt = (time.time() - t0) / ITERS
            print(f"chunk {chunk:6d}: {dt*1e3:8.2f} ms "
                  f"({flops/dt/1e12:5.1f} TFLOPS)", flush=True)
        except Exception as e:
            print(f"chunk {chunk:6d}: FAILED {type(e).__name__}: "
                  f"{str(e)[:100]}", flush=True)
        finally:
            state = None
            jax.clear_caches()


if __name__ == "__main__":
    main()
