"""Functionality matrix — the run_func_test.py:606 analog: train the same
tiny GPT-2 under every (zero stage x tensor parallel x offload) combination
on the simulated 8-device mesh and assert they all compute the SAME
optimization trajectory (ZeRO/TP/offload are memory/layout strategies, not
math changes)."""

import functools

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.models import GPT2Config, GPT2Model


GLOBAL_BATCH = 8  # fixed across every cell — tp changes dp, never the data


def _train(zero_stage: int, tp: int, offload: bool, steps: int = 3):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=-1, model=tp)
    cfg = GPT2Config(vocab_size=128, n_positions=32, hidden_size=64,
                     num_layers=2, num_heads=4, bf16=False, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    dp = mesh.data_parallel_world_size
    assert GLOBAL_BATCH % dp == 0
    conf = {
        # hold the GLOBAL batch constant so every matrix cell trains on
        # identical data (round-1 bug: per-chip batch was held fixed, so
        # tp=2 cells saw a different batch and diverged from the baseline)
        "train_micro_batch_size_per_gpu": GLOBAL_BATCH // dp,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10 ** 9,
    }
    if offload:
        conf["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, rng=jax.random.PRNGKey(42))
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                        (GLOBAL_BATCH, 32), 0, 128), np.int32)
    losses = []
    for _ in range(steps):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    final = jax.tree.map(np.asarray, engine.params)
    ds.reset_mesh_context()
    return losses, final


@functools.lru_cache(maxsize=None)
def _baseline():
    """The cell every other is compared with, trained once."""
    return _train(0, 1, False)


MATRIX = [
    (0, 1, False), (1, 1, False), (2, 1, False), (3, 1, False),
    (0, 2, False),  # pure TP vs TP=1 — validates TP is math-preserving
    (2, 2, False), (3, 2, False), (2, 1, True), (3, 2, True),
]


@pytest.mark.parametrize("stage,tp,offload", MATRIX,
                         ids=[f"z{s}-tp{t}{'-off' if o else ''}"
                              for s, t, o in MATRIX])
def test_matrix_matches_baseline(stage, tp, offload):
    base_losses, base_params = _baseline()
    losses, params = _train(stage, tp, offload)
    np.testing.assert_allclose(losses, base_losses, rtol=2e-4,
                               err_msg=f"z{stage} tp{tp} off={offload}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(base_params)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
