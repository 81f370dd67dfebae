"""The plain GPT-2 reference against the engine at a tiny size on the
CPU, in the layouts the cells use, and the command's refusal of any
platform but the TPU."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perf.families import gpt2, gpt2_reference
from perf.traffic import zipf_tokens

REPO = Path(__file__).resolve().parents[2]
TOY = {"family": "gpt2", "activation_function": "gelu_new",
       "attn_pdrop": 0.1, "embd_pdrop": 0.1, "resid_pdrop": 0.1,
       "initializer_range": 0.02, "layer_norm_epsilon": 1e-5, "n_embd": 64,
       "n_head": 2, "n_layer": 2, "n_positions": 64, "vocab_size": 250,
       "assumed": {"vocab_rows_padded": 256}}


def _job(stage):
    return {"gradient_accumulation_steps": 1, "activation_checkpointing": True,
            "batch_per_chip": 2, "seq": 64, "parity": {"layers": 2},
            "ds_config": {
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True, "grads_in_compute_dtype": True},
                # toy leaves all sit under ZeRO-3's default persistence
                # threshold, which would leave nothing to shard
                "zero_optimization": {
                    "stage": stage, "stage3_param_persistence_threshold": 0}}}


def _ids(rows):
    return zipf_tokens.make({"exponent": 1.0, "pool_steps": 1, "seq": 64},
                            rows, TOY["vocab_size"], seed=11)[0]


@pytest.mark.parametrize("chips, stage", [(1, 2), (4, 2), (4, 3)])
def test_engine_agrees_with_the_reference(chips, stage):
    import jax
    got = gpt2.parity(TOY, _job(stage), jax.devices()[:chips], 5,
                      _ids(2 * chips))
    assert got["ok"], got
    assert got["loss_rel"] <= gpt2.LOSS_RTOL
    assert got["grad_norm_rel"] <= gpt2.GRAD_NORM_RTOL
    assert got["grad_err_rel"] <= gpt2.GRAD_ERR_RTOL
    # an untrained model on 256 rows sits near ln 256
    assert abs(got["ref_loss"] - np.log(256)) < 0.2


def test_a_changed_term_fails_the_parity(monkeypatch):
    import jax
    monkeypatch.setattr(gpt2_reference, "gelu_new", jax.nn.relu)
    # a shape no other test uses, so that the reference is traced anew
    got = gpt2.parity(TOY, _job(2), jax.devices()[:1], 5, _ids(2)[:, :32])
    assert not got["ok"], got


def test_reference_loss_of_uniform_logits_is_ln_v():
    import jax
    import jax.numpy as jnp
    params = {"wte": jnp.zeros((16, 8)), "wpe": jnp.zeros((4, 8)),
              "ln_f": {"w": jnp.ones((8,)), "b": jnp.zeros((8,))}, "h": []}
    ids = jnp.zeros((2, 4), jnp.int32)
    loss, grads = jax.jit(gpt2_reference.loss_and_grads,
                          static_argnums=(2, 3))(params, ids, 2, 1e-5)
    assert float(loss) == pytest.approx(np.log(16), rel=1e-6)
    assert np.isfinite(float(gpt2_reference.global_norm(grads)))
    assert jax.tree.structure(grads) == jax.tree.structure(params)


def test_the_command_refuses_a_platform_that_is_not_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "gpt2-large.s1024",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "platform=cpu" in out.stderr
    # no result: the last line of stdout is not the JSON object
    assert '"metrics"' not in out.stdout
