"""bert_z2 end-to-end vs step-time gap probe (VERDICT r4 weak #2).

Round 4 measured 90.27 ms/step for BERT-large S=128 B=32 in the ablation
harness (profile_bert_ab.py — a bare optax.adamw loop) projecting ~354
samples/s, but the canonical bench row records 288.2 samples/s — a ~19%
gap.  Candidate explanations, each isolated here with full ENGINE steps
(the bench's own path, bench.py::bench_bert_z2):

  1. optimizer: the bench row trains with LAMB (per-param-group norms +
     trust ratios — runtime/optimizers.py:_lamb), the harness probe used
     AdamW.  This cell pair A/Bs exactly that, same engine/config
     otherwise.
  2. engine dispatch overhead: engine+AdamW vs the bare-optax harness
     number localizes anything the engine adds per step (GAS
     bookkeeping, overflow handling, loss-scale plumbing).

Emits one JSON line (metric bert_z2_gap_probe) with per-cell ms/step and
derived samples/s; appended to the ladder as a diagnostic row by the
session script.  Run on the real chip only.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import _harness  # noqa: F401,E402 — compile cache

import numpy as np


BATCH = 32
SEQ = 128
ITERS = int(os.environ.get("DS_PROFILE_ITERS", 30))


def engine_cell(opt_type):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import BertConfig, BertModel

    cfg = BertConfig(max_position_embeddings=SEQ, hidden_size=1024,
                     num_layers=24, num_heads=16, bf16=True)
    model = BertModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = ds.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": BATCH,
                "optimizer": {"type": opt_type, "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2},
                "steps_per_print": 10 ** 9})
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int32)
    labels = ids

    def step():
        loss = engine.forward(ids, labels)
        engine.backward(loss)
        engine.step()
        return loss

    for _ in range(3):
        loss = step()
    float(loss)
    t0 = time.time()
    for _ in range(ITERS):
        loss = step()
    float(loss)
    dt = (time.time() - t0) / ITERS
    print(f"[gap] engine {opt_type:6s}: {dt * 1e3:8.2f} ms/step "
          f"({BATCH / dt:6.1f} samples/s)", flush=True)
    del engine
    return dt


def main():
    import jax

    plat = os.environ.get("JAX_PLATFORMS")
    if plat:
        jax.config.update("jax_platforms", plat)

    dev = jax.devices()[0]
    dt_lamb = engine_cell("Lamb")
    dt_adamw = engine_cell("AdamW")
    out = {
        "metric": "bert_z2_gap_probe",
        "value": round(BATCH / dt_lamb, 1),
        "unit": "samples/s",
        "vs_baseline": 0.0,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "engine_lamb_ms": round(dt_lamb * 1e3, 2),
        "engine_adamw_ms": round(dt_adamw * 1e3, 2),
        "lamb_tax_pct": round(100 * (dt_lamb / dt_adamw - 1), 1),
        "harness_adamw_ms_r4": 90.27,
        "engine_overhead_vs_harness_pct":
            round(100 * (dt_adamw * 1e3 / 90.27 - 1), 1),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
