"""A/B the flash-attention kernel layout at the full-step level:
attn_layout='bhsd' (classic, head transposes materialized around the
Pallas call) vs 'bshd' (transpose-free BlockSpec head indexing).

The bshd path's (1, rows, 1, d) block tiling is interpret-verified but its
compiled Mosaic cost is unknown — run THIS before flipping the default
(ops/transformer.py DeepSpeedTransformerConfig.attn_layout).

Full train steps with state feedback via the shared harness.  Also
times dropout-on vs off per
layout so the comparison holds on the production config.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from _harness import time_step
from deepspeed_tpu.models import GPT2Config, GPT2Model

SEQ = 1024
BATCH = 8
ITERS = int(os.environ.get("DS_PROFILE_ITERS", 15))


def main():
    tx = optax.adamw(6e-4, weight_decay=0.1)

    def build(**cfg_kw):
        cfg_kw.setdefault("scan_layers", False)
        cfg_kw.setdefault("fused_loss_chunk", 50304)
        cfg = GPT2Config(n_positions=SEQ, bf16=True, **cfg_kw)
        model = GPT2Model(cfg)
        params = jax.tree.map(jnp.asarray,
                              model.init_params(jax.random.PRNGKey(0)))
        ids = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, size=(BATCH, SEQ)), jnp.int32)
        flops = BATCH * SEQ * cfg.flops_per_token()
        return model, params, ids, flops

    def make(model, ids, deterministic):
        def factory(p):
            rng = None if deterministic else jax.random.key(1, impl="rbg")

            @jax.jit
            def step(state):
                params, opt = state

                def loss_fn(pp):
                    return model.loss(pp, rng, ids)

                g = jax.grad(loss_fn)(params)
                up, opt = tx.update(g, opt, params)
                return (optax.apply_updates(params, up), opt)

            return step, (p, tx.init(p))
        return factory

    for layout in ("bhsd", "bshd"):
        for drop, label in ((0.1, "dropout"), (0.0, "nodrop")):
            model, params, ids, flops = build(
                attn_layout=layout, embd_dropout=drop, attn_dropout=drop,
                hidden_dropout=drop)
            time_step(f"gpt2 step layout={layout} {label}",
                      make(model, ids, deterministic=(drop == 0.0)),
                      params, flops, iters=ITERS)


if __name__ == "__main__":
    main()
