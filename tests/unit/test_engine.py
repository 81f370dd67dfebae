"""End-to-end engine tests on the 8-device CPU-sim mesh (role of reference
tests/unit/test_fp16.py + test_zero.py smoke paths)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from tests.unit.simple_model import (base_engine_config, random_dataloader,
                                     simple_model_apply, simple_model_params)

HIDDEN = 16


def make_engine(stage=0, gas=1, micro=8, dtype_cfg=None, **overrides):
    cfg = base_engine_config(micro_batch=micro, gas=gas, **(overrides or {}))
    if stage:
        cfg["zero_optimization"] = {"stage": stage}
    if dtype_cfg:
        cfg.update(dtype_cfg)
    params = simple_model_params(HIDDEN)
    engine, _, _, _ = ds.initialize(model=simple_model_apply, config=cfg,
                                    model_parameters=params)
    return engine


def train_steps(engine, n=10, micro=8, seed=5):
    # cycle a small fixed dataset so the loss decrease is deterministic
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    loader = random_dataloader(
        HIDDEN, total_samples=4 * micro * engine.gradient_accumulation_steps(),
        batch_size=micro, seed=seed)
    it = iter(RepeatingLoader(loader))
    losses = []
    for _ in range(n):
        for _ in range(engine.gradient_accumulation_steps()):
            x, y = next(it)
            loss = engine.forward(x, y)
            engine.backward(loss)
            engine.step()
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_loss_decreases_all_stages(stage):
    engine = make_engine(stage=stage)
    losses = train_steps(engine, n=15)
    assert losses[-1] < losses[0] * 0.9, f"no learning: {losses}"


def test_stage_parity():
    """All ZeRO stages must produce (near-)identical training trajectories —
    the sharding is a memory layout, not a math change (role of reference
    test_zero.py:233 correctness-vs-baseline)."""
    ref = None
    for stage in [0, 1, 2, 3]:
        engine = make_engine(stage=stage)
        losses = train_steps(engine, n=8, seed=77)
        if ref is None:
            ref = losses
        else:
            np.testing.assert_allclose(losses, ref, rtol=2e-4)


def test_gradient_accumulation_equivalence():
    """gas=2 with micro=4 must match gas=1 with micro=8 (same global batch):
    both consume the same 8 samples per optimizer step, so the parameter
    trajectories must agree."""
    e1 = make_engine(stage=0, gas=1, micro=8)
    e2 = make_engine(stage=0, gas=2, micro=4)
    train_steps(e1, n=6, micro=8, seed=9)
    train_steps(e2, n=6, micro=4, seed=9)
    p1 = jax.tree.map(np.asarray, e1.params)
    p2 = jax.tree.map(np.asarray, e2.params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 p1, p2)


def test_micro_step_boundary():
    engine = make_engine(stage=0, gas=4, micro=2)
    loader = random_dataloader(HIDDEN, 64, 2)
    it = iter(loader)
    for i in range(4):
        x, y = next(it)
        engine.backward(engine.forward(x, y))
        engine.step()
        if i < 3:
            assert engine.global_steps == 0
    assert engine.global_steps == 1


def test_fp16_dynamic_loss_scale_halves_on_overflow():
    """Overflow must skip the step and halve the scale (role of reference
    test_dynamic_loss_scale.py:315)."""
    cfg = {"fp16": {"enabled": True, "initial_scale_power": 4,
                    "loss_scale_window": 2, "hysteresis": 1,
                    "min_loss_scale": 0.25}}
    engine = make_engine(stage=0, dtype_cfg=cfg)
    assert engine.loss_scale == 16.0
    params_before = jax.tree.map(np.asarray, engine.params)

    x = np.full((8, HIDDEN), np.nan, np.float32)
    y = np.zeros((8,), np.float32)
    engine.backward(engine.forward(x, y))
    engine.step()
    assert engine.overflow
    assert engine.loss_scale == 8.0
    params_after = jax.tree.map(np.asarray, engine.params)
    jax.tree.map(np.testing.assert_array_equal, params_before, params_after)


def test_fp16_scale_doubles_after_window():
    cfg = {"fp16": {"enabled": True, "initial_scale_power": 4,
                    "loss_scale_window": 2, "hysteresis": 1}}
    engine = make_engine(stage=0, dtype_cfg=cfg)
    train_steps(engine, n=2)
    assert engine.loss_scale == 32.0  # 2 clean steps → doubled once


def test_fp16_hysteresis():
    cfg = {"fp16": {"enabled": True, "initial_scale_power": 4,
                    "loss_scale_window": 100, "hysteresis": 2}}
    engine = make_engine(stage=0, dtype_cfg=cfg)
    x = np.full((8, HIDDEN), np.nan, np.float32)
    y = np.zeros((8,), np.float32)
    engine.backward(engine.forward(x, y))
    engine.step()
    assert engine.loss_scale == 16.0  # first overflow burns hysteresis
    engine.backward(engine.forward(x, y))
    engine.step()
    assert engine.loss_scale == 8.0  # second halves


def test_bf16_training():
    engine = make_engine(stage=2, dtype_cfg={"bf16": {"enabled": True}})
    losses = train_steps(engine, n=20)
    assert np.mean(losses[-4:]) < np.mean(losses[:4]), losses


def test_bf16_grads_in_compute_dtype():
    """bf16 gradient buffers (the reference's fp16-grad-buffer analog):
    grads leave the grad program in bf16, training still converges, and
    the fp32 upcast lives in the apply program."""
    engine = make_engine(
        stage=2, dtype_cfg={"bf16": {"enabled": True,
                                     "grads_in_compute_dtype": True}})
    rng = np.random.RandomState(0)
    x = rng.standard_normal((8, HIDDEN)).astype(np.float32)
    y = rng.standard_normal((8,)).astype(np.float32)
    engine.backward(engine.forward(x, y))
    leaves = jax.tree.leaves(engine._grad_acc)
    assert leaves, "no accumulated grads cached"
    for g in leaves:
        assert g.dtype == jnp.bfloat16, g.dtype
    engine.step()
    losses = train_steps(engine, n=20)
    assert np.mean(losses[-4:]) < np.mean(losses[:4]), losses


def test_static_loss_scale():
    cfg = {"fp16": {"enabled": True, "loss_scale": 128.0}}
    engine = make_engine(stage=0, dtype_cfg=cfg)
    assert engine.loss_scale == 128.0
    train_steps(engine, n=3)
    assert engine.loss_scale == 128.0  # static never changes


def test_gradient_clipping_runs():
    engine = make_engine(stage=2, gradient_clipping=0.1)
    losses = train_steps(engine, n=10)
    assert np.isfinite(losses).all()


def test_lamb_optimizer():
    engine = make_engine(
        stage=1,
        optimizer={"type": "Lamb", "params": {"lr": 5e-2,
                                              "max_coeff": 0.3,
                                              "min_coeff": 0.01}})
    losses = train_steps(engine, n=24)
    # compare full cycles over the 4-batch dataset (phase-aligned)
    assert np.mean(losses[-4:]) < np.mean(losses[:4]), losses


def test_scheduler_integration():
    engine = make_engine(
        stage=0,
        scheduler={"type": "WarmupLR",
                   "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-2,
                              "warmup_num_steps": 10}})
    train_steps(engine, n=5)
    lr = engine.get_lr()[0]
    assert 0 < lr <= 1e-2


def test_zero3_params_are_sharded():
    engine = make_engine(
        stage=0,  # 0 = don't clobber the explicit zero_optimization override
        zero_optimization={"stage": 3, "stage3_param_persistence_threshold": 0})
    any_sharded = False
    for leaf in jax.tree.leaves(engine.params):
        spec = leaf.sharding.spec
        if any(p is not None for p in spec):
            any_sharded = True
    assert any_sharded, "stage 3 should shard at least the 16x16 weights"


def test_memory_estimator():
    engine0 = make_engine(stage=0)
    engine3 = make_engine(stage=3)
    m0 = engine0.estimate_memory()
    m3 = engine3.estimate_memory()
    assert m3["optimizer"] < m0["optimizer"]
    assert m3["params"] < m0["params"]


def test_train_batch_convenience():
    engine = make_engine(stage=2, gas=2, micro=4)
    loader = random_dataloader(HIDDEN, 128, 4)
    it = iter(loader)
    loss0 = engine.train_batch(it)
    for _ in range(8):
        loss = engine.train_batch(it)
    assert loss < loss0


def test_multi_output_model_uses_first_as_loss():
    """Models returning (loss, aux...) train on out[0] (the reference's
    multi_output_model.py coverage class)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu as ds

    w0 = jnp.ones((4,), jnp.float32)

    def model(p, rng, x, y):
        pred = x @ p["w"]
        loss = jnp.mean((pred - y) ** 2)
        return loss, pred.sum()  # aux output must be ignored by training

    engine, _, _, _ = ds.initialize(
        model=model, model_parameters={"w": w0},
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-1}},
                "steps_per_print": 10 ** 9})
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4).astype(np.float32)
    y = (x @ np.array([1., 2., 3., 4.], np.float32)).astype(np.float32)
    losses = []
    for _ in range(10):
        loss = engine.forward(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def _assert_fp16_export(engine, tmp_path):
    import jax
    import numpy as np
    path = engine.save_fp16_model(str(tmp_path))
    loaded = np.load(path)
    flat = jax.tree_util.tree_flatten_with_path(engine.params)[0]
    assert len(loaded.files) == len(flat)
    import jax.numpy as jnp
    for key_path, leaf in flat:
        name = jax.tree_util.keystr(key_path)
        arr = loaded[name]
        host = np.asarray(leaf)
        if jnp.issubdtype(host.dtype, jnp.floating):
            assert arr.dtype == np.float16, (name, arr.dtype)
            np.testing.assert_allclose(arr.astype(np.float32),
                                       host.astype(np.float32), rtol=1e-2,
                                       atol=1e-4)
        else:
            np.testing.assert_array_equal(arr, host)


def test_save_fp16_model_export(tmp_path):
    """Consolidated half-precision export (reference save_fp16_model):
    one npz of fp16 weights, loadable and matching the live params —
    including from a ZeRO-3 sharded engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=16,
                     num_layers=2, num_heads=2, bf16=True)
    model = GPT2Model(cfg)
    engine, _, _, _ = ds.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3},
                "steps_per_print": 10 ** 9})
    _assert_fp16_export(engine, tmp_path)


def test_save_fp16_model_export_bf16_offload(tmp_path):
    """ZeRO-Offload stores DEVICE params in the compute dtype (bf16) —
    the export must still emit readable fp16, not raw bf16 bytes (numpy
    would silently serialize ml_dtypes as void)."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=16,
                     num_layers=2, num_heads=2, bf16=True)
    model = GPT2Model(cfg)
    engine, _, _, _ = ds.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": 2, "offload_optimizer": {"device": "cpu"}},
                "steps_per_print": 10 ** 9})
    assert any(jnp.issubdtype(leaf.dtype, jnp.bfloat16) or
               leaf.dtype == jnp.bfloat16
               for leaf in jax.tree.leaves(engine.params)), \
        "offload engine should hold bf16 device params"
    _assert_fp16_export(engine, tmp_path)


# --------------------------------------------------------------------- #
# the step loop under accumulation: what it dispatches, what it reads
# --------------------------------------------------------------------- #
GAS = 4


def _accum_stream(n_steps, gas=GAS, micro=8, seed=3):
    """[(x, y)] covering n_steps optimizer steps."""
    rng = np.random.RandomState(seed)
    return [(rng.normal(0, 1, (micro, HIDDEN)).astype(np.float32),
             rng.normal(0, 1, (micro,)).astype(np.float32))
            for _ in range(n_steps * gas)]


def _run_loop(engine, batches):
    for x, y in batches:
        engine.backward(engine.forward(x, y))
        engine.step()


class _CountCalls:
    def __init__(self, fn):
        self.fn = fn
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.fn(*args, **kwargs)


def test_dispatch_count_of_the_step_loop_is_2n():
    """The loop launches 2N compiled programs an optimizer step at gas=N:
    N grad programs, N-1 accumulation adds — the first microbatch adopts
    the grad buffer directly — and 1 apply.  Wrapping the engine's
    compiled callables counts every dispatch the step loop can issue."""
    steps = 3
    engine = make_engine(gas=GAS)
    counters = {}
    for name in ("_grad_fn", "_acc_fn", "_apply_fn"):
        counters[name] = _CountCalls(getattr(engine, name))
        setattr(engine, name, counters[name])
    _run_loop(engine, _accum_stream(steps))
    assert counters["_grad_fn"].calls == steps * GAS
    assert counters["_acc_fn"].calls == steps * (GAS - 1)
    assert counters["_apply_fn"].calls == steps
    total = sum(c.calls for c in counters.values())
    assert total == steps * 2 * GAS                         # 2N per step


class _RecordingWriter:
    def __init__(self):
        self.scalars = []

    def add_scalar(self, tag, value, step):
        self.scalars.append((tag, value, step))


def test_summary_writer_and_lr_reads_only_at_boundaries():
    """step() used to call float(self._last_loss) + get_lr() for the
    writer on EVERY step, forcing a device sync each step; both must now
    run only at steps_per_print / tensorboard.write_interval boundaries."""
    engine = make_engine(gas=GAS, steps_per_print=3)
    writer = _RecordingWriter()
    engine._summary_writer = writer
    engine._tb_write_interval = 3
    lr_calls = []
    orig_get_lr = engine.get_lr
    engine.get_lr = lambda: (lr_calls.append(engine.global_steps)
                             or orig_get_lr())
    _run_loop(engine, _accum_stream(7, seed=31))
    written_steps = sorted({s for (tag, _, s) in writer.scalars
                            if tag == "Train/Samples/lr"})
    assert written_steps == [3, 6]
    assert sorted(set(lr_calls)) == [3, 6]


def test_tb_write_interval_config():
    engine = make_engine(gas=GAS, steps_per_print=100,
                         tensorboard={"enabled": False, "write_interval": 7})
    assert engine._tb_write_interval == 7
    ds.reset_mesh_context()
    engine = make_engine(gas=GAS, steps_per_print=100)
    assert engine._tb_write_interval == 100


# --------------------------------------------------------------------- #
# the paths a model may refuse
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("module, name", [
    ("ouro", "OuroModel"), ("keye_vl2", "KeyeVL2Model"),
    ("granite_hybrid", "GraniteHybridModel"),
    ("nemotron_h", "NemotronHModel")])
def test_every_key_of_refuses_is_a_path_the_engine_checks(module, name):
    """A key that is none of the engine's paths would refuse nothing."""
    import importlib
    from deepspeed_tpu.runtime.engine import REFUSABLE_PATHS
    refuses = getattr(importlib.import_module(
        f"deepspeed_tpu.models.{module}"), name).refuses
    assert refuses and set(refuses) <= set(REFUSABLE_PATHS)
    assert all(isinstance(why, str) and why for why in refuses.values())


def test_an_unknown_key_of_refuses_raises_at_construction():
    class Model:
        refuses = {"zero3_streaming": "it has not been run there",
                   "fused_step": "a path that is gone"}

        def __call__(self, params, rng, x, y):
            return simple_model_apply(params, rng, x, y)

    with pytest.raises(ValueError, match=r"\['fused_step'\].*knows "
                       r"\['zero3_streaming', 'pipeline'\]"):
        ds.initialize(model=Model(), config=base_engine_config(),
                      model_parameters=simple_model_params(HIDDEN))
