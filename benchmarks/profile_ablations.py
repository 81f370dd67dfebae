"""Step-level ablations for the GPT-2 flagship bench (round-2 MFU work).

Each variant is a FULL train step (loss+grad+adamw, params fed back and
donated) — pure repeated-input microbenchmarks mis-time (dispatch-latency
floors and caching artifacts; see benchmarks/README.md).

Variants isolate: scan-vs-unrolled layer stack, dropout, Pallas-vs-XLA
attention, fused-CE chunk size, fp32-master-vs-bf16 params, optimizer cost.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import importlib

from _harness import time_step as _time_step, xla_attn

from deepspeed_tpu.models import GPT2Config, GPT2Model
from deepspeed_tpu.ops.activations import dropout
from deepspeed_tpu.ops.fused_cross_entropy import fused_linear_cross_entropy

fa_mod = importlib.import_module("deepspeed_tpu.ops.flash_attention")
nm_mod = importlib.import_module("deepspeed_tpu.ops.normalize")
tr_mod = importlib.import_module("deepspeed_tpu.ops.transformer")
gpt_mod = importlib.import_module("deepspeed_tpu.models.gpt2")

BATCH, SEQ = 8, 1024
ITERS = int(os.environ.get("DS_PROFILE_ITERS", 15))


def time_step(name, make_step, params, flops):
    return _time_step(name, make_step, params, flops, iters=ITERS)


def main():
    # Pin the ROUND-START configuration this script's recorded numbers used
    # (scan + 128x128-block pallas attention + CE chunk 8192) — the model
    # defaults have since moved to the measured winners (unrolled,
    # 512x1024 flash blocks, whole-vocab CE), so relying on defaults would
    # silently change every row's meaning.
    cfg = GPT2Config(n_positions=SEQ, bf16=True, scan_layers=True,
                     fused_loss_chunk=8192)
    model = GPT2Model(cfg)
    model.layer.config.attn_impl = "pallas"
    model.layer.config.block_q = 128
    model.layer.config.block_k = 128

    params0 = jax.tree.map(jnp.asarray,
                           model.init_params(jax.random.PRNGKey(0)))
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(BATCH, SEQ)), jnp.int32)
    flops = BATCH * SEQ * cfg.flops_per_token()
    print(f"step model-FLOPs: {flops / 1e12:.2f} T   iters={ITERS}")

    tx = optax.adamw(6e-4, weight_decay=0.1)

    def make(loss_fn, use_opt=True, params=None):
        def factory(p):
            state = (p, tx.init(p) if use_opt else None,
                     jax.random.PRNGKey(1))

            @jax.jit
            def step(state):
                p, o, r = state
                r, sub = jax.random.split(r)
                loss, grads = jax.value_and_grad(
                    lambda pp: loss_fn(pp, sub))(p)
                if use_opt:
                    updates, o = tx.update(grads, o, p)
                    p = optax.apply_updates(p, updates)
                else:
                    p = jax.tree.map(
                        lambda a, g: a - 1e-6 * g.astype(a.dtype), p, grads)
                return (p, o, r)

            return step, state
        return factory

    # -- baseline ------------------------------------------------------- #
    def loss_base(p, r):
        return model.loss(p, r, ids)

    time_step("round-start baseline (scan, dropout, pallas, CE8192)",
              make(loss_base), params0, flops)

    # -- no dropout ----------------------------------------------------- #
    def loss_nodrop(p, r):
        return model.loss(p, None, ids)

    time_step("no dropout", make(loss_nodrop), params0, flops)

    # -- unrolled body -------------------------------------------------- #
    def hidden_unrolled(p, r, deterministic=False):
        h = model.embed(p, ids)
        r_embd, r_layers = jax.random.split(r)
        h = dropout(h, cfg.embd_dropout, r_embd, deterministic)
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[i], p["h"])
            h = model.layer(lp, h, rng=jax.random.fold_in(r_layers, i),
                            deterministic=deterministic)
        return h

    from deepspeed_tpu.ops.normalize import fused_layer_norm

    def head_loss(p, h):
        h = fused_layer_norm(h, p["ln_f"]["w"], p["ln_f"]["b"],
                             cfg.layer_norm_eps)
        labels = ids[:, 1:]
        h = h[:, :-1]
        return fused_linear_cross_entropy(
            h.reshape(-1, cfg.hidden_size),
            p["wte"].astype(h.dtype).T,
            labels.reshape(-1).astype(jnp.int32), cfg.fused_loss_chunk)

    def loss_unrolled(p, r):
        return head_loss(p, hidden_unrolled(p, r))

    time_step("unrolled body", make(loss_unrolled), params0, flops)

    def loss_unrolled_nodrop(p, r):
        return head_loss(p, hidden_unrolled(p, r, deterministic=True))

    time_step("unrolled body + no dropout",
              make(loss_unrolled_nodrop), params0, flops)

    # -- XLA attention instead of Pallas -------------------------------- #
    orig_attn = tr_mod.flash_attention
    try:
        tr_mod.flash_attention = xla_attn
        time_step("XLA attention (mha_reference)",
                  make(loss_base), params0, flops)
    finally:
        tr_mod.flash_attention = orig_attn

    # -- plain-jnp LN instead of the Pallas custom-vjp LN ---------------- #
    orig_ln_tr = tr_mod.fused_layer_norm
    orig_ln_gpt = gpt_mod.fused_layer_norm
    try:
        tr_mod.fused_layer_norm = nm_mod.layer_norm_reference
        gpt_mod.fused_layer_norm = nm_mod.layer_norm_reference
        time_step("XLA LN (layer_norm_reference)",
                  make(loss_base), params0, flops)
        tr_mod.flash_attention = xla_attn
        time_step("XLA LN + XLA attention", make(loss_base), params0, flops)

        def loss_sink(p, r):
            return head_loss(p, hidden_unrolled(p, r, deterministic=True))

        time_step("XLA LN+attn, unrolled, no dropout",
                  make(loss_sink), params0, flops)
    finally:
        tr_mod.fused_layer_norm = orig_ln_tr
        gpt_mod.fused_layer_norm = orig_ln_gpt
        tr_mod.flash_attention = orig_attn

    # -- CE chunk sizes -------------------------------------------------- #
    for chunk in (16384, 50304):
        def loss_chunk(p, r, c=chunk):
            h = model.hidden_states(p, ids, r)
            h = fused_layer_norm(h, p["ln_f"]["w"], p["ln_f"]["b"],
                                 cfg.layer_norm_eps)
            return fused_linear_cross_entropy(
                h[:, :-1].reshape(-1, cfg.hidden_size),
                p["wte"].astype(h.dtype).T,
                ids[:, 1:].reshape(-1).astype(jnp.int32), c)

        time_step(f"CE chunk {chunk}", make(loss_chunk), params0, flops)

    # unfused CE (full logits)
    def loss_unfused(p, r):
        h = model.hidden_states(p, ids, r)
        logits = model.head_logits(p, h)[:, :-1]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, ids[:, 1:]).mean()

    time_step("unfused CE (full fp32 logits)",
              make(loss_unfused), params0, flops)

    # -- bf16 params end-to-end ----------------------------------------- #
    params_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params0)
    time_step("bf16 params (no fp32 master)",
              make(loss_base), params_bf16, flops)

    # -- optimizer cost -------------------------------------------------- #
    time_step("sgd-tiny instead of adamw (isolate opt)",
              make(loss_base, use_opt=False), params0, flops)


if __name__ == "__main__":
    main()
