"""Shared timing harness for the step-level profiling scripts.

Full-train-step timing with state feedback: steps feed their output back
in, so nothing between iterations can be cached or elided, and the window
ends in ``block_until_ready``.

Importing this module turns on the persistent compilation cache
(deepspeed_tpu/utils/chip.py) — the profilers re-run often.
"""

import os
import sys
import time

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from deepspeed_tpu.utils.chip import enable_compile_cache  # noqa: E402

enable_compile_cache()


def time_step(name, make_step, params, flops, iters=15):
    """make_step(params) -> (jitted step, init_state); steps feed state
    back.  Prints one line; returns the per-step seconds (inf on failure).
    """
    try:
        step, state = make_step(params)
        state = step(state)  # compile
        jax.block_until_ready(jax.tree.leaves(state)[0])
        t0 = time.time()
        for _ in range(iters):
            state = step(state)
        jax.block_until_ready(jax.tree.leaves(state)[0])
        dt = (time.time() - t0) / iters
        print(f"{name:56s} {dt * 1e3:9.2f} ms  "
              f"({flops / dt / 1e12:6.1f} TFLOPS)", flush=True)
    except Exception as e:  # keep later variants running (e.g. one OOMs)
        print(f"{name:56s} FAILED: {type(e).__name__}: {str(e)[:120]}",
              flush=True)
        dt = float("inf")
    finally:
        # drop executables + their reserved HBM so variants don't accumulate
        state = step = None
        jax.clear_caches()
    return dt


def xla_attn(q, k, v, causal=False, sm_scale=None, bias=None, **kw):
    """flash_attention-compatible shim that always takes the XLA path
    (absorbs impl/block kwargs)."""
    from deepspeed_tpu.ops.flash_attention import mha_reference
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale, bias=bias)


def pallas_attn(q, k, v, causal=False, sm_scale=None, bias=None,
                block_q=128, block_k=128, **kw):
    """flash_attention-compatible shim that forces the Pallas kernel."""
    from deepspeed_tpu.ops.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                           bias=bias, block_q=block_q, block_k=block_k,
                           impl="pallas")
