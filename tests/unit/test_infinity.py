"""ZeRO-Infinity layer-streaming engine (runtime/zero/infinity.py):
parameters paged from host/NVMe group by group, fp32 master + moments in
the host/NVMe optimizer tier, HBM never holding the full model.

Reference parity targets: stage3 + offload_param (stage3.py:932 NVMe param
swapping; partitioned_param_swapper.py:36), sub_group-wise optimizer sweep
(stage3.py:2777), "max model per device" (SURVEY.md: 40B on one V100).
"""

import numpy as np

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.models import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.zero.infinity import ZeroInfinityEngine

SEQ = 32
BATCH = 4


def _model():
    cfg = GPT2Config(vocab_size=128, n_positions=SEQ, hidden_size=32,
                     num_layers=4, num_heads=4, bf16=False, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    return GPT2Model(cfg)


def _data():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(5),
                                         (BATCH, SEQ), 0, 128), np.int32)


def _train_baseline(steps=4):
    """Reference trajectory: resident engine + the same host Adam tier."""
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=1, devices=jax.devices()[:1])
    model = _model()
    conf = {
        "train_micro_batch_size_per_gpu": BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2,
                              "offload_optimizer": {"device": "cpu"}},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, rng=jax.random.PRNGKey(9))
    ids = _data()
    losses = []
    for _ in range(steps):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    master = jax.tree.map(np.asarray, engine.optimizer.master_params)
    ds.reset_mesh_context()
    return losses, master


def _train_infinity(offload_param_device, tmp_path, steps=4,
                    opt_device="cpu"):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=1, devices=jax.devices()[:1])
    model = _model()
    zo = {
        "stage": 3,
        "offload_param": {"device": offload_param_device,
                          "nvme_path": str(tmp_path), "buffer_count": 2},
    }
    if opt_device == "nvme":
        zo["offload_optimizer"] = {"device": "nvme",
                                   "nvme_path": str(tmp_path)}
    conf = {
        "train_micro_batch_size_per_gpu": BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": zo,
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, rng=jax.random.PRNGKey(9))
    assert isinstance(engine, ZeroInfinityEngine)
    ids = _data()
    losses = []
    for _ in range(steps):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    ds.reset_mesh_context()
    return losses, engine


def test_host_param_streaming_matches_resident(tmp_path):
    base_losses, base_master = _train_baseline()
    losses, engine = _train_infinity("cpu", tmp_path)
    np.testing.assert_allclose(losses, base_losses, rtol=1e-5, atol=1e-6)
    master = jax.tree.map(np.asarray, engine.optimizer.master_params)
    # tied-wte grads accumulate in a different order (embed vjp + head vjp
    # vs one fused autodiff) — fp32 summation noise only
    for a, b in zip(jax.tree.leaves(master), jax.tree.leaves(base_master)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


def test_nvme_param_streaming_matches_resident(tmp_path):
    """Params AND optimizer states on NVMe files — the full Infinity tier.
    The CPU sim cannot enforce an HBM budget, so the 'never fully resident'
    claim is asserted via the engine's own residency accounting: at most 2
    parameter groups on device at any time, for a 6-group model."""
    base_losses, _ = _train_baseline()
    losses, engine = _train_infinity("nvme", tmp_path, opt_device="nvme")
    np.testing.assert_allclose(losses, base_losses, rtol=1e-5, atol=1e-6)
    assert engine.max_live_param_groups <= 2
    assert engine._swapper is not None
    # the host window never holds more groups than its buffer count
    assert len(engine._swapper.resident_groups) <= 2
    mem = engine.estimate_memory()
    assert mem["hbm_param_window"] < mem["host_or_nvme_params"]


def test_gradient_accumulation(tmp_path):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=1, devices=jax.devices()[:1])
    model = _model()
    conf = {
        "train_micro_batch_size_per_gpu": BATCH,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu"}},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, rng=jax.random.PRNGKey(9))
    ids = _data()
    for _ in range(2):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
    assert engine.global_steps == 1
    assert engine.micro_steps == 2
    ds.reset_mesh_context()


def test_legacy_cpu_offload_params_key_dispatches(tmp_path):
    """The v0.5-era flat key (zero/config.py cpu_offload_params back-compat)
    must reach the streaming engine exactly like the offload_param dict."""
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=1, devices=jax.devices()[:1])
    model = _model()
    conf = {
        "train_micro_batch_size_per_gpu": BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3, "cpu_offload_params": True},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, rng=jax.random.PRNGKey(9))
    assert isinstance(engine, ZeroInfinityEngine)
    loss = engine.forward(_data())
    engine.backward(loss)
    engine.step()
    assert engine.global_steps == 1
    ds.reset_mesh_context()


def test_checkpoint_roundtrip(tmp_path):
    losses, engine = _train_infinity("cpu", tmp_path, steps=2)
    ckpt_dir = str(tmp_path / "ckpt")
    engine.save_checkpoint(ckpt_dir)
    before = jax.tree.map(np.asarray, engine.module_state_dict())

    _, engine2 = _train_infinity("cpu", tmp_path / "other", steps=1)
    engine2.load_checkpoint(ckpt_dir)
    after = jax.tree.map(np.asarray, engine2.module_state_dict())
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)
    assert engine2.global_steps == 2


def test_join_consuming_matches_join_and_frees():
    """join_consuming must produce a tree EQUAL to join (same stacked
    layout the optimizer tier was built around) while consuming its
    input: every numpy layer-group leaf reference is dropped (set to
    None) once stacked — the r4 fix for the optimizer-boundary OOM at
    multi-B params (a full second copy of all layer grads)."""
    model = _model()
    api = model.layerwise_api()
    params = model.init_params(jax.random.PRNGKey(0))
    host = jax.tree.map(lambda a: np.asarray(a, np.float32), params)

    groups_a = api["split"](host)
    groups_b = api["split"](host)
    # split returns views of the SAME host arrays for both copies, so
    # value comparison below is against independent reconstructions
    joined = api["join"](groups_a)
    consumed = api["join_consuming"](groups_b)

    la = jax.tree.leaves(joined)
    lb = jax.tree.leaves(consumed)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the consuming join must have dropped every layer-group reference
    for i in range(api["num_layers"]):
        assert groups_b[f"layer{i}"] is None
