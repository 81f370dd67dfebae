"""3D composition matrix on the simulated 8-device mesh: pipeline × tensor
parallel × ZeRO × MoE, all at MATCHED GLOBAL BATCH, asserting trajectory
equality against the pipe=1/tp=1 baseline.

Reference: tests/model/run_func_test.py:606 (the Megatron-GPT2 mp × zero ×
ckpt functionality matrix).  Cells that cannot be supported must raise a
clear config error instead of silently computing something.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models import GPT2Config
from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

SEQ = 32
GLOBAL_BATCH = 8
MICRO_BATCHES = 4  # gradient_accumulation_steps


def _cfg():
    return GPT2Config(vocab_size=64, n_positions=SEQ, hidden_size=32,
                      num_layers=4, num_heads=4, bf16=False,
                      embd_dropout=0.0, attn_dropout=0.0,
                      hidden_dropout=0.0)


def _train_pipe(pipe, tp, zero_stage, steps=3, expert=1, seq=1):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(pipe=pipe, model=tp, expert=expert, seq=seq,
                              data=-1)
    dp = mesh.data_parallel_world_size
    module = gpt2_pipeline_module(_cfg(), num_stages=pipe)
    conf = {
        "train_batch_size": GLOBAL_BATCH * MICRO_BATCHES,
        "train_micro_batch_size_per_gpu": GLOBAL_BATCH // dp,
        "gradient_accumulation_steps": MICRO_BATCHES,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": zero_stage},
        "sequence_parallel": {"mode": "ring"},
        "steps_per_print": 10 ** 9,
    }
    engine = PipelineEngine(
        model=module, config=conf,
        example_input=jnp.zeros((GLOBAL_BATCH, SEQ), jnp.int32),
        rng=jax.random.PRNGKey(3))
    rs = np.random.RandomState(0)
    losses = []
    for step in range(steps):
        micro = []
        for _ in range(MICRO_BATCHES):
            ids = rs.randint(0, 64, size=(GLOBAL_BATCH, SEQ)).astype(
                np.int32)
            micro.append((ids, ids))
        losses.append(engine.train_batch(iter(micro)))
    params = jax.tree.map(np.asarray, engine.params)
    ds.reset_mesh_context()
    return losses, params


BASELINE = {}


def _baseline():
    if "v" not in BASELINE:
        BASELINE["v"] = _train_pipe(pipe=1, tp=1, zero_stage=0)
    return BASELINE["v"]


@pytest.mark.parametrize("pipe,tp,zero", [
    (4, 1, 0),   # pure pipeline
    (4, 1, 1),   # pipe × zero-1
    (2, 2, 0),   # pipe × tp
    (2, 2, 1),   # pipe × tp × zero — 3D
    (1, 2, 2),   # tp × zero-2 (pipeline module, no pipe axis)
    # pipe × zero-2/3: the reference RESTRICTS pipeline parallelism to
    # ZeRO-1 (grad/param partitioning fights its hook-based schedule);
    # sharding-as-policy composes them for free — trajectory-exact
    (4, 1, 2),   # pipe × zero-2 — beyond the reference
    (2, 2, 3),   # pipe × tp × zero-3 — beyond the reference
])
def test_composition_matches_baseline(pipe, tp, zero):
    base_losses, base_params = _baseline()
    losses, params = _train_pipe(pipe=pipe, tp=tp, zero_stage=zero)
    np.testing.assert_allclose(losses, base_losses, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(base_params)):
        # blocks are stacked [num_stages, layers_per_stage, ...] — flatten
        # the stage/layer dims (stage-major == global layer order) so cells
        # with different stage counts compare directly
        if a.shape != b.shape:
            a = a.reshape((-1,) + a.shape[2:])
            b = b.reshape((-1,) + b.shape[2:])
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=1e-5)


@pytest.mark.parametrize("pipe,tp,seq,zero", [
    (2, 1, 2, 0),   # pipe × seq (gated, allgather-KV attention)
    (2, 2, 2, 1),   # pipe × seq × tp × zero-1 — 4-axis composition
    (1, 1, 2, 0),   # seq-only through the same gated executor
])
def test_pipe_seq_matches_baseline(pipe, tp, seq, zero):
    """Gated sequence parallelism (round 5): the seq axis joins the
    manual region — seq peers share their pipe row's predicate; the body
    runs psum-allgather-KV attention (the divergent-branch-safe variant)
    and the seq-distributed aux chains slice their own chunk.  Must be
    trajectory-exact vs the pipe=1/seq=1 baseline."""
    base_losses, base_params = _baseline()
    losses, params = _train_pipe(pipe=pipe, tp=tp, zero_stage=zero, seq=seq)
    np.testing.assert_allclose(losses, base_losses, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(base_params)):
        if a.shape != b.shape:
            a = a.reshape((-1,) + a.shape[2:])
            b = b.reshape((-1,) + b.shape[2:])
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=1e-5)


def test_plain_body_pipe_expert_matches_baseline():
    """A PLAIN (dense GPT-2) body with an expert axis: the expert axis only
    shards the batch (expert-data parallelism), so the gated executor stays
    on and the trajectory must match — the silent-wrong-answer risk the old
    engine guard protected against, now asserted instead of forbidden."""
    base_losses, base_params = _baseline()
    losses, params = _train_pipe(pipe=2, tp=1, zero_stage=0, expert=2)
    np.testing.assert_allclose(losses, base_losses, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(base_params)):
        if a.shape != b.shape:
            a = a.reshape((-1,) + a.shape[2:])
            b = b.reshape((-1,) + b.shape[2:])
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=1e-5)


# ---------------------------------------------------------------------- #
# PP × EP cells (round 5): an MoE pipeline body with the expert axis —
# the composition the reference gets from running MoE under any engine
# (deepspeed/runtime/engine.py:1714-1727 per-group expert-grad reduction).
# ---------------------------------------------------------------------- #
# one config for every MoE-pipeline test in this file (the parity matrix
# and the checkpoint roundtrip must exercise the SAME model)
MOE_PIPE_CFG_KW = dict(
    vocab_size=64, n_positions=SEQ, hidden_size=32, num_layers=4,
    num_heads=4, bf16=False, num_experts=4, top_k=2,
    capacity_factor=2.0, min_capacity=4, moe_every=2,
    embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)


def _build_moe_pipe_engine(pipe, expert, zero_stage, tp=1):
    """Mesh + module + engine for the shared MoE-pipeline config
    (resets the mesh context; caller resets again when done)."""
    from deepspeed_tpu.models import GPTMoEConfig
    from deepspeed_tpu.models.gpt_moe_pipe import gpt_moe_pipeline_module

    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(pipe=pipe, expert=expert, model=tp, data=-1)
    dp = mesh.data_parallel_world_size
    module = gpt_moe_pipeline_module(GPTMoEConfig(**MOE_PIPE_CFG_KW),
                                     num_stages=pipe)
    conf = {
        "train_batch_size": GLOBAL_BATCH * MICRO_BATCHES,
        "train_micro_batch_size_per_gpu": GLOBAL_BATCH // dp,
        "gradient_accumulation_steps": MICRO_BATCHES,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10 ** 9,
    }
    return PipelineEngine(
        model=module, config=conf,
        example_input=jnp.zeros((GLOBAL_BATCH, SEQ), jnp.int32),
        rng=jax.random.PRNGKey(3))


def _train_moe_pipe(pipe, expert, zero_stage=0, steps=3, tp=1):
    engine = _build_moe_pipe_engine(pipe, expert, zero_stage, tp)
    rs = np.random.RandomState(0)
    losses = []
    for _ in range(steps):
        micro = []
        for _ in range(MICRO_BATCHES):
            ids = rs.randint(0, 64, size=(GLOBAL_BATCH, SEQ)).astype(
                np.int32)
            micro.append((ids, ids))
        losses.append(engine.train_batch(iter(micro)))
    params = jax.tree.map(np.asarray, engine.params)
    ds.reset_mesh_context()
    return losses, params


MOE_PIPE_BASELINE = {}


def _moe_pipe_baseline():
    if "v" not in MOE_PIPE_BASELINE:
        MOE_PIPE_BASELINE["v"] = _train_moe_pipe(pipe=1, expert=1)
    return MOE_PIPE_BASELINE["v"]


@pytest.mark.parametrize("pipe,expert,zero,tp", [
    (2, 2, 0, 1),   # pipe × expert (masked executor)
    (2, 2, 1, 1),   # pipe × expert × zero-1
    (1, 4, 0, 1),   # expert-only sanity on the same module
    (2, 1, 0, 1),   # MoE body under the GATED executor (expert=1: the aux
                    # channel's cond-gated accumulation + loss_scale vjp
                    # seed at S>1)
    (2, 1, 0, 2),   # gated MoE × manual TP: Megatron-split expert FFNs
                    # with explicit psums + replicated gate (round 5)
])
def test_pipe_expert_matches_baseline(pipe, expert, zero, tp):
    base_losses, base_params = _moe_pipe_baseline()
    losses, params = _train_moe_pipe(pipe=pipe, expert=expert,
                                     zero_stage=zero, tp=tp)
    np.testing.assert_allclose(losses, base_losses, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(base_params)):
        if a.shape != b.shape:
            a = a.reshape((-1,) + a.shape[2:])
            b = b.reshape((-1,) + b.shape[2:])
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=1e-5)


# ---------------------------------------------------------------------- #
# MoE × ZeRO cells (dense-equivalent MoE so trajectories are comparable)
# ---------------------------------------------------------------------- #
def _train_moe(zero_stage, steps=8):
    from deepspeed_tpu.moe import MoE

    ds.reset_mesh_context()
    ds.initialize_mesh(expert=4, data=-1)
    D = 32
    moe = MoE(hidden_size=D, num_experts=4, k=1, capacity_factor=4.0,
              min_capacity=64)
    rng = jax.random.PRNGKey(0)
    moe_params = moe.init_params(rng, jnp.zeros((16, D)))
    head = jax.random.normal(jax.random.PRNGKey(1), (D, D)) * 0.3
    params = {"moe": moe_params, "head": head}

    def model(p, rng, x, y):
        h, l_aux, _ = moe.apply(p["moe"], x, rng=rng)
        pred = h @ p["head"]
        return jnp.mean((pred - y) ** 2) + 0.01 * l_aux

    dp = ds.get_mesh_context().data_parallel_world_size
    conf = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 16 // dp,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=conf,
                                    model_parameters=params)
    rs = np.random.RandomState(0)
    w = rs.randn(D, D).astype(np.float32)
    xb = rs.randn(16, D).astype(np.float32)
    yb = xb @ w
    losses = []
    for _ in range(steps):
        loss = engine.forward(xb, yb)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    ds.reset_mesh_context()
    return losses


@pytest.mark.parametrize("zero", [2, 3])
def test_moe_zero_matches_zero0(zero):
    base = _train_moe(0)
    cell = _train_moe(zero)
    np.testing.assert_allclose(cell, base, rtol=2e-5)


def test_moe_pipe_checkpoint_roundtrip(tmp_path):
    """PP x EP checkpoint/resume: the MoE pipeline's stacked
    [stage, layer, expert, ...] leaves must survive save -> fresh-engine
    load -> continue, matching an uninterrupted run's trajectory.
    Same model as the parity matrix (_build_moe_pipe_engine)."""

    def build():
        return _build_moe_pipe_engine(pipe=2, expert=2, zero_stage=1)

    def batches(rs):
        return iter([(ids, ids) for ids in
                     (rs.randint(0, 64, (GLOBAL_BATCH, SEQ)).astype(np.int32)
                      for _ in range(MICRO_BATCHES))])

    # uninterrupted 3-step run
    ds.reset_mesh_context()
    ref = build()
    rs = np.random.RandomState(7)
    ref_losses = [ref.train_batch(batches(rs)) for _ in range(3)]

    # 2 steps -> save -> fresh engine -> load -> 1 more step
    ds.reset_mesh_context()
    eng = build()
    rs = np.random.RandomState(7)
    for _ in range(2):
        eng.train_batch(batches(rs))
    eng.save_checkpoint(str(tmp_path), tag="moe_pipe")

    ds.reset_mesh_context()
    eng2 = build()
    eng2.load_checkpoint(str(tmp_path), tag="moe_pipe")
    assert eng2.global_steps == 2
    loss3 = eng2.train_batch(batches(rs))
    np.testing.assert_allclose(loss3, ref_losses[2], rtol=2e-5)
    ds.reset_mesh_context()
