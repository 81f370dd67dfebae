"""Pallas flash-attention block-size sweep at long sequence (the regime
where flash is the dispatcher's chosen path).

State-feedback loop (inputs perturbed by the previous output) so nothing
between iterations can be cached; fwd+bwd per iteration.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import _harness  # noqa: F401 — compile cache
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.flash_attention import flash_attention, mha_reference

B, H, S, D = 2, 12, 4096, 64
ITERS = int(os.environ.get("DS_PROFILE_ITERS", 30))
# causal halves the work
FLOPS = 3.5 * 2 * 2 * B * H * S * S * D / 2  # fwd+bwd ~3.5x fwd matmuls


def sweep(name, attn):
    rng = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (B, H, S, D),
                                 jnp.bfloat16) for i in range(3))

    @jax.jit
    def step(q, k, v):
        def loss(q, k, v):
            return flashsum(attn(q, k, v))
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        # feed back so every iteration is fresh work
        return (q + 0.001 * dq.astype(q.dtype),
                k + 0.001 * dk.astype(k.dtype),
                v + 0.001 * dv.astype(v.dtype))

    def flashsum(o):
        return jnp.sum(o.astype(jnp.float32))

    try:
        q, k, v = step(q, k, v)
        float(jnp.sum(q))  # scalar fetch: waits for the whole chain
        t0 = time.time()
        for _ in range(ITERS):
            q, k, v = step(q, k, v)
        float(jnp.sum(q))
        dt = (time.time() - t0) / ITERS
        print(f"{name:40s} {dt * 1e3:9.2f} ms ({FLOPS / dt / 1e12:5.1f} "
              f"TFLOPS)", flush=True)
    except Exception as e:
        print(f"{name:40s} FAILED {type(e).__name__}: {str(e)[:100]}",
              flush=True)
    finally:
        jax.clear_caches()


def main():
    print(f"B={B} H={H} S={S} D={D}  fwd+bwd")
    for bq, bk in ((128, 128), (256, 256), (512, 512), (256, 1024),
                   (512, 1024), (1024, 1024), (2048, 512)):
        sweep(f"pallas block_q={bq} block_k={bk}",
              lambda q, k, v, bq=bq, bk=bk: flash_attention(
                  q, k, v, causal=True, block_q=bq, block_k=bk,
                  impl="pallas"))
    sweep("xla mha_reference",
          lambda q, k, v: mha_reference(q, k, v, causal=True))


if __name__ == "__main__":
    main()
