"""Fused whole-step train program (runtime/fused_step.py; docs/fused_step.md).

Covers the PR-3 acceptance surface:
  - numerical parity with the modular forward/backward/step loop over >=5
    optimizer steps at gas=4 for fp32, bf16, and fp16 dynamic scaling with
    a forced overflow (the skipped step must match on both paths);
  - a fused-path ZeRO-3 streaming case (scan-in-scan);
  - the dispatch-count regression: the fused path issues exactly ONE
    compiled-program invocation per optimizer step, the modular path 2N
    (N grad programs + N-1 accumulation adds + 1 apply);
  - the automatic-fallback matrix for host-interactive features;
  - in-program loss-only sentinel monitoring (skip policy rides the
    per-leaf select predicate);
  - the coalesced host reads of the async host loop (summary writer /
    get_lr only at boundaries).
"""

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.runtime.dataloader import stack_microbatches
from tests.unit.simple_model import (base_engine_config, simple_model_apply,
                                     simple_model_params)

HIDDEN = 16
MICRO = 8
GAS = 4


def make_engine(fused, gas=GAS, micro=MICRO, extra=None, model=None,
                params=None):
    ds.reset_mesh_context()
    cfg = base_engine_config(micro_batch=micro, gas=gas)
    cfg["fused_step"] = {"enabled": bool(fused)}
    if extra:
        cfg.update(extra)
    engine, _, _, _ = ds.initialize(
        model=model or simple_model_apply, config=cfg,
        model_parameters=params if params is not None
        else simple_model_params(HIDDEN))
    return engine


def data_stream(n_steps, gas=GAS, micro=MICRO, seed=3, poison=None,
                scale=1.0):
    """[(x, y)] covering n_steps optimizer steps; poison=(step, factor)
    multiplies ONE microbatch's inputs at that step."""
    rng = np.random.RandomState(seed)
    out = []
    for s in range(n_steps):
        for m in range(gas):
            x = rng.normal(0, 1, (micro, HIDDEN)).astype(np.float32) * scale
            y = rng.normal(0, 1, (micro,)).astype(np.float32)
            if poison is not None and s == poison[0] and m == 1:
                x = x * poison[1]
            out.append((x, y))
    return out


def run_modular(engine, batches, gas=GAS):
    it = iter(batches)
    losses = []
    for _ in range(len(batches) // gas):
        micro_losses = []
        for _ in range(gas):
            x, y = next(it)
            loss = engine.forward(x, y)
            engine.backward(loss)
            engine.step()
            micro_losses.append(np.asarray(loss).item())
        losses.append(float(np.mean(micro_losses)))
    return losses


def run_fused(engine, batches, gas=GAS):
    it = iter(batches)
    return [np.asarray(engine.train_batch(it)).item()
            for _ in range(len(batches) // gas)]


def assert_tree_close(a, b, atol):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                                atol=atol), a, b)


# --------------------------------------------------------------------- #
# parity: fused vs modular
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype_cfg, atol", [
    ({}, 1e-5),
    ({"bf16": {"enabled": True}}, 1e-2),
])
def test_fused_matches_modular(dtype_cfg, atol):
    batches = data_stream(6)
    e_mod = make_engine(False, extra=dtype_cfg)
    l_mod = run_modular(e_mod, batches)
    e_fus = make_engine(True, extra=dtype_cfg)
    assert e_fus._fused_step_fn is not None, e_fus.fused_step_reason
    l_fus = run_fused(e_fus, batches)
    np.testing.assert_allclose(l_mod, l_fus, atol=atol, rtol=1e-4)
    assert_tree_close(e_mod.params, e_fus.params, atol)
    assert_tree_close(e_mod.opt_state, e_fus.opt_state, atol)
    assert e_mod.global_steps == e_fus.global_steps == 6
    assert e_mod.micro_steps == e_fus.micro_steps == 6 * GAS


def test_fused_matches_modular_fp16_overflow_skip():
    """fp16 dynamic scaling with one poisoned microbatch: the overflow
    must skip the step (per-leaf selects) IDENTICALLY on both paths —
    same skipped_steps, same post-run loss scale, same params/opt
    trajectory through the skip."""
    fp16 = {"fp16": {"enabled": True, "initial_scale_power": 4,
                     "loss_scale_window": 100, "hysteresis": 1}}
    # 1e30 saturates the f16 cast -> inf activations -> NaN grads
    batches = data_stream(6, poison=(2, 1e30))
    e_mod = make_engine(False, extra=fp16)
    l_mod = run_modular(e_mod, batches)
    e_fus = make_engine(True, extra=fp16)
    assert e_fus._fused_step_fn is not None, e_fus.fused_step_reason
    l_fus = run_fused(e_fus, batches)
    assert e_mod.skipped_steps == e_fus.skipped_steps == 1
    assert e_mod.loss_scale == e_fus.loss_scale < 2.0 ** 4
    # the poisoned step's loss is NaN on both paths; compare the rest
    np.testing.assert_allclose(np.delete(l_mod, 2), np.delete(l_fus, 2),
                               atol=1e-3, rtol=1e-3)
    assert np.isnan(l_mod[2]) and np.isnan(l_fus[2])
    assert_tree_close(e_mod.params, e_fus.params, 1e-4)
    assert_tree_close(e_mod.opt_state, e_fus.opt_state, 1e-4)


@pytest.mark.parametrize("stream_cfg", [
    pytest.param({"stage3_max_live_parameters": 10_000,
                  "stage3_prefetch_bucket_size": 0}, id="at_use"),
    # carried double-buffer prefetch nested INSIDE the fused gas scan
    # (scan-in-scan-in-scan): the hand-written VJP's residuals are the
    # layers' input carries, so the outer scan never stacks gathered
    # groups across microbatches (ISSUE 7)
    pytest.param({"stage3_max_live_parameters": 100_000,
                  "stage3_prefetch_bucket_size": 100_000}, id="carried"),
])
def test_fused_zero3_streaming_parity(stream_cfg):
    """Scan-in-scan: the fused program's microbatch scan wraps the ZeRO-3
    streamed layer scan (at-use or carried prefetch) without changes."""
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    batch, seq, gas, steps = 8, 16, 2, 2
    zero3 = {"zero_optimization": dict({"stage": 3}, **stream_cfg)}

    def build(fused):
        ds.reset_mesh_context()
        mesh = ds.initialize_mesh(data=-1)
        cfg = GPT2Config(vocab_size=64, n_positions=seq, hidden_size=32,
                         num_layers=2, num_heads=2, bf16=False,
                         embd_dropout=0.0, attn_dropout=0.0,
                         hidden_dropout=0.0)
        model = GPT2Model(cfg)
        conf = {"train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9,
                "fused_step": {"enabled": fused}}
        conf.update(zero3)
        engine, _, _, _ = ds.initialize(
            model=model, config=conf,
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            mesh=mesh, rng=jax.random.PRNGKey(7))
        return engine

    rng = np.random.RandomState(0)
    batches = [(rng.randint(0, 64, size=(batch, seq)).astype(np.int32),)
               for _ in range(gas * steps)]
    e_mod = build(False)
    it = iter(batches)
    l_mod = []
    for _ in range(steps):
        micro = []
        for _ in range(gas):
            (ids,) = next(it)
            loss = e_mod.forward(ids)
            e_mod.backward(loss)
            e_mod.step()
            micro.append(np.asarray(loss).item())
        l_mod.append(float(np.mean(micro)))
    e_fus = build(True)
    assert e_fus._fused_step_fn is not None, e_fus.fused_step_reason
    l_fus = run_fused(e_fus, batches, gas=gas)
    # the plan is recorded when the fused program traces the scan
    assert e_fus._zero3_stream.last_plan.prefetch == bool(
        stream_cfg["stage3_prefetch_bucket_size"])
    np.testing.assert_allclose(l_mod, l_fus, rtol=2e-4)
    assert_tree_close(e_mod.params, e_fus.params, 2e-5)


# --------------------------------------------------------------------- #
# dispatch-count regression
# --------------------------------------------------------------------- #
class _CountCalls:
    def __init__(self, fn):
        self.fn = fn
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.fn(*args, **kwargs)


def _wrap_programs(engine):
    counters = {}
    for name in ("_grad_fn", "_acc_fn", "_apply_fn", "_fused_step_fn"):
        fn = getattr(engine, name, None)
        if fn is not None:
            counters[name] = _CountCalls(fn)
            setattr(engine, name, counters[name])
    return counters


def test_dispatch_count_fused_is_one_modular_is_2n():
    """The whole point of the fused path: 1 compiled-program invocation
    per optimizer step, vs the modular loop's 2N (N grad programs, N-1
    accumulation adds — the first microbatch adopts the grad buffer
    directly — and 1 apply).  Wrapping the engine's compiled callables
    counts every dispatch the step loop can issue, so the win cannot
    silently regress."""
    steps = 3
    batches = data_stream(steps)

    e_fus = make_engine(True)
    assert e_fus._fused_step_fn is not None, e_fus.fused_step_reason
    c_fus = _wrap_programs(e_fus)
    run_fused(e_fus, batches)
    assert c_fus["_fused_step_fn"].calls == steps          # exactly 1/step
    assert c_fus["_grad_fn"].calls == 0
    assert c_fus["_acc_fn"].calls == 0
    assert c_fus["_apply_fn"].calls == 0

    e_mod = make_engine(False)
    c_mod = _wrap_programs(e_mod)
    run_modular(e_mod, batches)
    assert c_mod["_grad_fn"].calls == steps * GAS
    assert c_mod["_acc_fn"].calls == steps * (GAS - 1)
    assert c_mod["_apply_fn"].calls == steps
    total = sum(c.calls for c in c_mod.values())
    assert total == steps * 2 * GAS                         # 2N per step


# --------------------------------------------------------------------- #
# config gating + fallback matrix
# --------------------------------------------------------------------- #
def test_fused_off_by_default():
    ds.reset_mesh_context()
    engine, _, _, _ = ds.initialize(
        model=simple_model_apply, config=base_engine_config(micro_batch=MICRO),
        model_parameters=simple_model_params(HIDDEN))
    assert engine._fused_step_fn is None
    assert engine.fused_step_reason is None  # off, not fallen back


@pytest.mark.parametrize("extra, marker", [
    ({"zero_optimization": {"stage": 2,
                            "offload_optimizer": {"device": "cpu"}}},
     "offload_optimizer"),
    ({"quantize_training": {"enabled": True, "quantize_groups": 1}},
     "quantize-training"),
    ({"progressive_layer_drop": {"enabled": True}}, "progressive_layer_drop"),
    ({"curriculum_learning": {"enabled": True,
                              "curriculum_type": "fixed_linear",
                              "min_difficulty": 4, "max_difficulty": 16,
                              "schedule_config": {"total_curriculum_step": 10,
                                                  "difficulty_step": 8}}},
     "curriculum_learning"),
    ({"resilience": {"enabled": True,
                     "sentinel": {"enabled": True, "policy": "rewind",
                                  "monitor_grad_norm": False}}},
     "rewind"),
    ({"resilience": {"enabled": True,
                     "sentinel": {"enabled": True, "policy": "skip_step",
                                  "monitor_grad_norm": True}}},
     "grad-norm"),
])
def test_fused_falls_back_for_host_interactive_features(extra, marker):
    def pld_model(params, rng, x, y, pld_theta=None):
        return simple_model_apply(params, rng, x, y)

    engine = make_engine(True, extra=extra, model=pld_model)
    assert engine._fused_step_fn is None
    assert engine.fused_step_reason is not None
    assert marker in engine.fused_step_reason


def test_fused_fallback_offload_still_trains():
    """The offload fallback must run the modular loop through the same
    train_batch API — and at gas>1 this exercises the host optimizer's
    grad scaling on read-only device-array views (fixed in this PR)."""
    extra = {"zero_optimization": {"stage": 2,
                                   "offload_optimizer": {"device": "cpu"}}}
    engine = make_engine(True, extra=extra)
    assert engine._fused_step_fn is None
    assert "offload_optimizer" in engine.fused_step_reason
    losses = [engine.train_batch(iter(data_stream(1, seed=40 + i)))
              for i in range(2)]
    assert all(np.isfinite(loss) for loss in losses)


# --------------------------------------------------------------------- #
# in-program loss-only sentinel
# --------------------------------------------------------------------- #
def test_fused_sentinel_skip_policy_skips_in_program():
    """A k-sigma loss anomaly with FINITE gradients must zero the update
    INSIDE the fused program (healthy rides the same per-leaf select as
    the overflow skip — the apply's own finite check would not fire).
    The EWMA state is rigged to a warmed, far-off baseline so the verdict
    is deterministic regardless of training noise."""
    import jax.numpy as jnp
    from deepspeed_tpu.runtime.fused_step import FusedSentinelState

    sent = {"resilience": {"enabled": True,
                           "sentinel": {"enabled": True,
                                        "policy": "skip_step",
                                        "monitor_grad_norm": False,
                                        "warmup_steps": 2, "k_sigma": 6.0,
                                        "anomaly_budget": 50}}}
    engine = make_engine(True, extra=sent)
    assert engine._fused_step_fn is not None, engine.fused_step_reason
    run_fused(engine, data_stream(2, seed=11))
    engine._drain_fused_sentinel()

    def rig(mean, var, count):
        engine._fused_sent_state = jax.device_put(
            FusedSentinelState(mean=jnp.asarray(mean, jnp.float32),
                               var=jnp.asarray(var, jnp.float32),
                               count=jnp.asarray(count, jnp.int32)),
            engine.mesh_ctx.replicated())

    pre_skipped = engine.skipped_steps
    rig(mean=1e6, var=1e-6, count=100)  # any real loss is >>6 sigma away
    before = jax.tree.map(np.asarray, engine.params)
    spike_loss = run_fused(engine, data_stream(1, seed=12))[0]
    assert np.isfinite(spike_loss)  # grads were finite — only the
    after = jax.tree.map(np.asarray, engine.params)  # sentinel skipped
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 before, after)
    engine._drain_fused_sentinel()
    assert engine.skipped_steps == pre_skipped + 1
    assert engine.sentinel.counters()["steps_skipped"] >= 1
    # a rigged-clean baseline lets training continue
    rig(mean=spike_loss, var=1e6, count=100)
    run_fused(engine, data_stream(1, seed=13))
    final = jax.tree.map(np.asarray, engine.params)
    assert any(
        not np.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(final)))


def test_fused_sentinel_skip_freezes_lr_scheduler_and_counts_once():
    """Parity with step()'s skip chain: a sentinel-skipped step must not
    advance the host lr scheduler, and a step that is BOTH an fp16
    overflow and a sentinel flag counts toward skipped_steps exactly
    once (the sentinel branch wins, like the modular if/elif)."""
    import jax.numpy as jnp
    from deepspeed_tpu.runtime.fused_step import FusedSentinelState

    extra = {"fp16": {"enabled": True, "initial_scale_power": 4,
                      "loss_scale_window": 100, "hysteresis": 2},
             "scheduler": {"type": "WarmupLR",
                           "params": {"warmup_min_lr": 0.0,
                                      "warmup_max_lr": 1e-2,
                                      "warmup_num_steps": 100}},
             "resilience": {"enabled": True,
                            "sentinel": {"enabled": True,
                                         "policy": "skip_step",
                                         "monitor_grad_norm": False,
                                         "warmup_steps": 2,
                                         "anomaly_budget": 50}}}
    engine = make_engine(True, extra=extra)
    assert engine._fused_step_fn is not None, engine.fused_step_reason
    run_fused(engine, data_stream(2, seed=50))
    sched_before = engine.lr_scheduler.last_batch_iteration
    # NaN loss: overflow AND nonfinite sentinel flag on the same step
    run_fused(engine, data_stream(1, seed=51, poison=(0, np.inf)))
    engine._drain_fused_sentinel()
    assert engine.skipped_steps == 1  # once, not twice
    assert engine.lr_scheduler.last_batch_iteration == sched_before
    # rigged finite k-sigma skip: scheduler still frozen
    engine._fused_sent_state = jax.device_put(
        FusedSentinelState(mean=jnp.asarray(1e6, jnp.float32),
                           var=jnp.asarray(1e-6, jnp.float32),
                           count=jnp.asarray(100, jnp.int32)),
        engine.mesh_ctx.replicated())
    run_fused(engine, data_stream(1, seed=52))
    assert engine.skipped_steps == 2
    assert engine.lr_scheduler.last_batch_iteration == sched_before


def test_fused_sentinel_warmup_zero_never_flags_first_step():
    """warmup_steps=0 must not flag the very first observation (the
    device EWMA mean is a placeholder until something is observed) —
    mirrors the host sentinel's mean-is-None guard."""
    sent = {"resilience": {"enabled": True,
                           "sentinel": {"enabled": True,
                                        "policy": "skip_step",
                                        "monitor_grad_norm": False,
                                        "warmup_steps": 0,
                                        "anomaly_budget": 50}}}
    engine = make_engine(True, extra=sent)
    assert engine._fused_step_fn is not None, engine.fused_step_reason
    before = jax.tree.map(np.asarray, engine.params)
    run_fused(engine, data_stream(1, seed=60))
    engine._drain_fused_sentinel()
    assert engine.skipped_steps == 0
    assert engine.sentinel.counters()["anomalies_seen"] == 0
    after = jax.tree.map(np.asarray, engine.params)
    assert any(not np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(before), jax.tree.leaves(after)))


def test_fused_sentinel_state_survives_checkpoint(tmp_path):
    """save_checkpoint folds the in-program EWMA back into the host
    sentinel; load re-seeds the device state."""
    sent = {"resilience": {"enabled": True,
                           "sentinel": {"enabled": True, "policy": "warn",
                                        "monitor_grad_norm": False,
                                        "warmup_steps": 2}}}
    engine = make_engine(True, extra=sent)
    assert engine._fused_step_fn is not None, engine.fused_step_reason
    run_fused(engine, data_stream(4, seed=21))
    engine.save_checkpoint(str(tmp_path), tag="t4")
    assert engine.sentinel.loss_stat.count == 4
    assert engine.sentinel.loss_stat.mean is not None
    engine2 = make_engine(True, extra=sent)
    engine2.load_checkpoint(str(tmp_path), tag="t4")
    assert int(np.asarray(engine2._fused_sent_state.count)) == 4
    np.testing.assert_allclose(np.asarray(engine2._fused_sent_state.mean),
                               engine.sentinel.loss_stat.mean, rtol=1e-6)


# --------------------------------------------------------------------- #
# microbatch stacking
# --------------------------------------------------------------------- #
def test_stack_microbatches():
    b = [(np.ones((2, 3)), {"y": np.zeros((2,))}) for _ in range(4)]
    stacked = stack_microbatches(b)
    assert stacked[0].shape == (4, 2, 3)
    assert stacked[1]["y"].shape == (4, 2)
    with pytest.raises(ValueError, match="tree structure"):
        stack_microbatches([(np.ones(2),), (np.ones(2), np.ones(2))])
    with pytest.raises(ValueError, match="at least one"):
        stack_microbatches([])


# --------------------------------------------------------------------- #
# async host loop: coalesced boundary reads (modular path satellite)
# --------------------------------------------------------------------- #
class _RecordingWriter:
    def __init__(self):
        self.scalars = []

    def add_scalar(self, tag, value, step):
        self.scalars.append((tag, value, step))


def test_summary_writer_and_lr_reads_only_at_boundaries():
    """step() used to call float(self._last_loss) + get_lr() for the
    writer on EVERY step, forcing a device sync each step; both must now
    run only at steps_per_print / tensorboard.write_interval boundaries."""
    engine = make_engine(False, extra={"steps_per_print": 3})
    writer = _RecordingWriter()
    engine._summary_writer = writer
    engine._tb_write_interval = 3
    lr_calls = []
    orig_get_lr = engine.get_lr
    engine.get_lr = lambda: (lr_calls.append(engine.global_steps)
                             or orig_get_lr())
    run_modular(engine, data_stream(7, seed=31))
    written_steps = sorted({s for (tag, _, s) in writer.scalars
                            if tag == "Train/Samples/lr"})
    assert written_steps == [3, 6]
    assert sorted(set(lr_calls)) == [3, 6]


def test_tb_write_interval_config():
    engine = make_engine(False, extra={"steps_per_print": 100,
                                       "tensorboard": {"enabled": False,
                                                       "write_interval": 7}})
    assert engine._tb_write_interval == 7
    engine = make_engine(False, extra={"steps_per_print": 100})
    assert engine._tb_write_interval == 100
