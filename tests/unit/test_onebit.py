"""1-bit optimizer wire tier (zero_optimization.low_bandwidth.onebit;
docs/onebit.md).

Covers the round-20 acceptance surface:
  - warmup identity: with the tier armed, every pre-freeze step is
    byte-identical to the same OneBit optimizer without the tier (the
    dense program IS the warmup program), and tracks a dense Adam twin;
  - the freeze-boundary phase switch is exactly ONE planned retrace
    (RecompileGuard.planned_retraces) and flips the engine's phase;
  - compression numerics: exact fp32 error-feedback round-trip on
    dyadic-rational inputs, packed-wire consensus + mean preservation
    under shard_map (flat and hierarchical), LAMB trust ratio computed
    on the raw (lr-normalised) step;
  - static pricing: the per-leaf wire-cost gate, the onebit_bytes
    breakout in collective_wire_bytes, and the >=4x jaxpr+HLO wire
    reduction of the compressed program vs its dense twin;
  - e2e: 6-step parity across the switch, fp16 forced-overflow skip
    leaves params/momentum/wire-error untouched, checkpoint/resume on
    both sides of freeze_step restores the phase as program identity;
  - config conflicts (config.py _validate_onebit).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from tests.unit.simple_model import (base_engine_config, simple_model_apply,
                                     simple_model_params)

HIDDEN = 16
MICRO = 8


# --------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------- #
def make_engine(tier=True, optimizer="OneBitAdam", freeze=3, lr=1e-3,
                stage=2, hidden=HIDDEN, gas=1, analysis=None,
                extra=None, opt_params=None):
    ds.reset_mesh_context()
    cfg = base_engine_config(micro_batch=MICRO, gas=gas)
    params = {"lr": lr}
    if optimizer.lower().startswith("onebit"):
        params["freeze_step"] = freeze
    if opt_params:
        params.update(opt_params)
    cfg["optimizer"] = {"type": optimizer, "params": params}
    if stage:
        cfg["zero_optimization"] = {"stage": stage}
    if tier:
        cfg.setdefault("zero_optimization", {})
        cfg["zero_optimization"]["low_bandwidth"] = {"onebit": True}
    if analysis:
        cfg["analysis"] = analysis
    if extra:
        cfg.update(extra)
    engine, _, _, _ = ds.initialize(model=simple_model_apply, config=cfg,
                                    model_parameters=simple_model_params(
                                        hidden))
    return engine


def batches(n, hidden=HIDDEN, seed=7):
    rng = np.random.RandomState(seed)
    return [(rng.normal(0, 1, (MICRO, hidden)).astype(np.float32),
             rng.normal(0, 1, (MICRO,)).astype(np.float32))
            for _ in range(n)]


def run_steps(engine, data):
    losses = []
    for x, y in data:
        loss = engine.forward(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(np.asarray(loss).item())
    return losses


def assert_tree_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def assert_tree_close(a, b, rtol=1e-5, atol=1e-6):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=rtol, atol=atol), a, b)


# --------------------------------------------------------------------- #
# warmup identity + phase switch
# --------------------------------------------------------------------- #
def test_warmup_bitwise_vs_numerics_only():
    """Before freeze_step the tier must be INERT: byte-identical params
    and optimizer state vs the same OneBitAdam without the wire tier."""
    data = batches(3)
    e_tier = make_engine(tier=True, freeze=4)
    run_steps(e_tier, data)
    e_plain = make_engine(tier=False, freeze=4)
    run_steps(e_plain, data)
    assert e_tier._onebit_phase == "warmup"
    assert_tree_equal(e_tier.params, e_plain.params)
    assert_tree_equal(e_tier.opt_state, e_plain.opt_state)


def test_warmup_tracks_dense_adam():
    data = batches(3)
    e_tier = make_engine(tier=True, freeze=4)
    l1 = run_steps(e_tier, data)
    e_adam = make_engine(tier=False, optimizer="Adam")
    l2 = run_steps(e_adam, data)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    assert_tree_close(e_tier.params, e_adam.params, rtol=1e-4, atol=1e-6)


def test_phase_switch_single_planned_retrace():
    """Crossing freeze_step re-derives the step programs exactly once,
    announced to the RecompileGuard as a PLANNED retrace — lockstep
    stays clean and max_retraces absorbs the switch."""
    e = make_engine(freeze=2, analysis={"mode": "warn"})
    assert e._onebit_phase == "warmup"
    run_steps(e, batches(4))
    assert e._onebit_phase == "compressed"
    c = e._recompile_guard.counters()
    assert c["planned_retraces"] == 1, c
    assert c["retraces_seen"] == 1, c


# --------------------------------------------------------------------- #
# compression numerics
# --------------------------------------------------------------------- #
def test_sign_compress_exact_fp32_roundtrip():
    """cm + residual must reconstruct the compensated momentum EXACTLY
    (bitwise) on dyadic-rational inputs — the error feedback loses
    nothing to the wire, it only defers it."""
    from deepspeed_tpu.runtime.comm.onebit import _sign_compress

    rs = np.random.RandomState(3)
    m = jnp.asarray(rs.randint(-8, 9, 256) * 0.25, jnp.float32)
    err = jnp.asarray(rs.randint(-8, 9, 256) * 0.25, jnp.float32)
    cm, resid = _sign_compress(m, err)
    # scale = mean|comp| of 256 dyadic values: exact in fp32, so the
    # round-trip is exact too
    np.testing.assert_array_equal(np.asarray(cm + resid),
                                  np.asarray(m + err))
    # the wire tensor really is 1-bit + scale: one magnitude everywhere
    mags = np.unique(np.abs(np.asarray(cm)))
    assert len(mags[mags > 0]) == 1


def test_packed_wire_consensus_and_mean_preservation():
    """wire="packed" (the int8-lane sign pack): every worker decodes the
    identical reduced tensor, and error feedback preserves the mean over
    rounds; group_size == world degenerates to the exact dense mean."""
    from deepspeed_tpu.parallel import initialize_mesh, reset_mesh_context
    from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce

    reset_mesh_context()
    mesh = initialize_mesh(data=-1)
    w = mesh.data_parallel_world_size
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(w, 64), jnp.float32)
    true_mean = np.asarray(x).mean(axis=0)

    red, err = compressed_allreduce(x, jnp.zeros_like(x), mesh_ctx=mesh,
                                    wire="packed", block=8)
    red = np.asarray(red)
    np.testing.assert_array_equal(red[0], red[-1])

    def avg_err(n, group_size=0):
        f = jax.jit(lambda a, e: compressed_allreduce(
            a, e, mesh_ctx=mesh, wire="packed", block=8,
            group_size=group_size))
        acc = np.zeros(64)
        e = jnp.zeros_like(x)
        for _ in range(n):
            red, e = f(x, e)
            acc += np.asarray(red)[0]
        return np.abs(acc / n - true_mean).max()

    # the two-stage scheme compensates the server-side residual only at
    # the owning worker, so per-round it is NOT conservative — but the
    # accumulated average still closes on the true mean, and beats a
    # single uncompensated round
    single = np.abs(red[0] - true_mean).max()
    e8, e128 = avg_err(8), avg_err(128)
    assert e128 < 0.75 * e8, (e8, e128)
    assert e128 < 0.35, e128
    assert e128 < single, (e128, single)
    # hierarchical (Frontier-style): intra-group dense, cross-group 1-bit
    assert avg_err(64, group_size=2) < 0.35
    # group covering the whole world -> pure dense mean, exact
    red, _ = compressed_allreduce(x, jnp.zeros_like(x), mesh_ctx=mesh,
                                  wire="packed", block=8, group_size=w)
    np.testing.assert_allclose(np.asarray(red)[0], true_mean, rtol=1e-6)
    reset_mesh_context()


def test_lamb_trust_on_raw_step():
    """The trust ratio is computed on the lr-NORMALISED step (the raw
    Adam direction), so scaling lr scales the update linearly instead of
    feeding back into the ratio; out-of-range ratios clip."""
    from deepspeed_tpu.runtime.comm.onebit import lamb_trust_math

    rs = np.random.RandomState(4)
    d = jnp.asarray(rs.randn(32), jnp.float32)
    p = jnp.asarray(rs.randn(32), jnp.float32)
    out_hi = np.asarray(lamb_trust_math(0.1 * d, p, 0.1, 0.01, 10.0))
    out_lo = np.asarray(lamb_trust_math(0.001 * d, p, 0.001, 0.01, 10.0))
    np.testing.assert_allclose(out_hi, 100.0 * out_lo, rtol=1e-4)

    # clip: a huge parameter norm vs a tiny step norm -> max_trust
    big_p = jnp.full((32,), 1e6, jnp.float32)
    out = np.asarray(lamb_trust_math(0.1 * d, big_p, 0.1, 0.01, 10.0))
    np.testing.assert_allclose(out, 10.0 * 0.1 * np.asarray(d), rtol=1e-5)
    # zero parameter norm -> ratio 1 (no trust scaling)
    out = np.asarray(lamb_trust_math(0.1 * d, jnp.zeros((32,)), 0.1,
                                     0.01, 10.0))
    np.testing.assert_allclose(out, 0.1 * np.asarray(d), rtol=1e-6)


def test_onebit_leaf_saves_bytes_gate():
    """Skinny leaves stay on the dense wire: chunk padding makes the
    packed transport COST bytes below ~world*block elements."""
    from deepspeed_tpu.runtime.comm.onebit import onebit_leaf_saves_bytes

    assert not onebit_leaf_saves_bytes((16,), jnp.float32, 8)
    assert not onebit_leaf_saves_bytes((64,), jnp.float32, 8)
    assert onebit_leaf_saves_bytes((64, 64), jnp.float32, 8)
    assert onebit_leaf_saves_bytes((1 << 20,), jnp.float32, 8)


def test_collective_wire_onebit_breakout():
    """collective_wire_bytes prices the packed sync under its own
    onebit_bytes attribution key (named_scope onebit_packed)."""
    from deepspeed_tpu.parallel import initialize_mesh, reset_mesh_context
    from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce
    from deepspeed_tpu.runtime.comm.low_bandwidth import \
        collective_wire_bytes

    reset_mesh_context()
    mesh = initialize_mesh(data=-1)
    w = mesh.data_parallel_world_size
    x = jnp.zeros((w, 64), jnp.float32)

    def wire(kind):
        jaxpr = jax.make_jaxpr(
            lambda a, e: compressed_allreduce(a, e, mesh_ctx=mesh,
                                              wire=kind, block=8))(
            x, jnp.zeros_like(x))
        return collective_wire_bytes(jaxpr.jaxpr)

    packed = wire("packed")
    assert packed["onebit_bytes"] > 0, packed
    full = wire("full")
    assert full["onebit_bytes"] == 0, full
    reset_mesh_context()


# --------------------------------------------------------------------- #
# static pricing: the compressed program's wire vs its dense twin
# --------------------------------------------------------------------- #
def test_compressed_wire_4x_reduction():
    """Round-20 acceptance: at hidden=64 the compressed-phase program
    moves <= 1/4 the bytes of the dense twin at BOTH the jaxpr and the
    compiled-HLO level, the two levels reconcile within
    spmd_match_tolerance, and the warmup program prices identically to
    the dense twin."""
    from deepspeed_tpu.analysis.auditor import audit_engine

    e = make_engine(freeze=1, hidden=64)
    run_steps(e, batches(3, hidden=64))
    assert e._onebit_phase == "compressed"
    warm = audit_engine(e, multihost=False, phase="warmup", hlo=True)
    comp = audit_engine(e, multihost=False, phase="compressed", hlo=True)

    e_dense = make_engine(tier=False, optimizer="Adam", hidden=64)
    run_steps(e_dense, batches(1, hidden=64))
    dense = audit_engine(e_dense, multihost=False, hlo=True)

    # warmup == dense twin on the wire (the tier is pure bookkeeping
    # until freeze_step).  Both dense programs have their grad reduction
    # GSPMD-inserted (jaxpr-invisible), so the dense side is priced at
    # the compiled-HLO level; the onebit optimizer adds a few scalar
    # collectives (count/freeze bookkeeping), hence the 1% band.
    assert warm.wire_bytes_per_step == dense.wire_bytes_per_step == 0
    assert dense.hlo_wire_bytes_per_step > 0
    assert abs(warm.hlo_wire_bytes_per_step -
               dense.hlo_wire_bytes_per_step) <= \
        0.01 * dense.hlo_wire_bytes_per_step
    # compressed phase: >= 4x reduction — the explicit (jaxpr-counted)
    # compressed wire AND its compiled-HLO twin against the dense
    # program's compiled wire
    assert comp.wire_bytes_per_step > 0
    assert comp.wire_bytes_per_step * 4 <= dense.hlo_wire_bytes_per_step, (
        comp.wire_bytes_per_step, dense.hlo_wire_bytes_per_step)
    assert comp.hlo_wire_bytes_per_step * 4 <= \
        dense.hlo_wire_bytes_per_step, (
        comp.hlo_wire_bytes_per_step, dense.hlo_wire_bytes_per_step)
    assert comp.hlo_wire_bytes_per_step * 4 <= \
        warm.hlo_wire_bytes_per_step
    # the jaxpr accounting and the compiled program agree
    assert abs(comp.hlo_divergence_ratio - 1.0) <= 0.05, \
        comp.hlo_divergence_ratio
    assert comp.hlo["n_silent_reshards"] == 0
    # phase is program identity: distinct lockstep signatures
    assert e.lockstep_signature("warmup") != \
        e.lockstep_signature("compressed")


# --------------------------------------------------------------------- #
# e2e parity, overflow-skip, checkpoint
# --------------------------------------------------------------------- #
def test_e2e_six_step_parity():
    """6 steps across freeze=3: the warmup half is bitwise vs the
    numerics-only twin; the compressed half stays inside the loss band
    of the dense Adam twin."""
    data = batches(6, seed=11)
    e = make_engine(freeze=3)
    l_tier = run_steps(e, data)
    assert e._onebit_phase == "compressed"

    e_plain = make_engine(tier=False, freeze=3)
    l_plain = run_steps(e_plain, data)
    np.testing.assert_array_equal(l_tier[:3], l_plain[:3])

    e_adam = make_engine(tier=False, optimizer="Adam")
    l_adam = run_steps(e_adam, data)
    for a, b in zip(l_tier, l_adam):
        assert abs(a - b) <= 0.10 * max(1.0, abs(b)), (l_tier, l_adam)


def test_fp16_overflow_skip_preserves_error_feedback():
    """A post-freeze overflow-skipped step must leave params, momentum
    AND the wire-error carry untouched — otherwise the compensation
    stream drifts on every skip."""
    fp16 = {"fp16": {"enabled": True, "initial_scale_power": 4,
                     "loss_scale_window": 100, "hysteresis": 1}}
    e = make_engine(freeze=2, extra=fp16)
    data = batches(3, seed=13)
    run_steps(e, data)
    assert e._onebit_phase == "compressed"
    assert e.skipped_steps == 0

    p0 = jax.tree.map(np.asarray, e.params)
    s0 = jax.tree.map(np.asarray, e.opt_state)
    w0 = jax.tree.map(np.asarray, e._onebit_wire_error)
    scale0 = e.loss_scale
    x, y = data[0]
    loss = e.forward(x * 1e30, y)
    e.backward(loss)
    e.step()
    assert e.skipped_steps == 1
    assert e.loss_scale < scale0
    assert_tree_equal(e.params, p0)
    assert_tree_equal(e.opt_state, s0)
    assert_tree_equal(e._onebit_wire_error, w0)
    # the next clean step proceeds normally
    run_steps(e, data[1:2])
    assert e.skipped_steps == 1
    assert any(np.any(np.asarray(a) != b) for a, b in
               zip(jax.tree.leaves(e.params), jax.tree.leaves(p0)))


def test_checkpoint_across_freeze_boundary(tmp_path):
    """Phase is program identity: a pre-freeze checkpoint resumes in
    warmup and replays bitwise; a post-freeze checkpoint resumes
    directly in the compressed phase (no spurious warmup program)."""
    data = batches(6, seed=17)
    e = make_engine(freeze=3)
    run_steps(e, data[:2])
    e.save_checkpoint(str(tmp_path), tag="pre")

    e2 = make_engine(freeze=3)
    e2.load_checkpoint(str(tmp_path), tag="pre")
    assert e2._onebit_phase == "warmup"
    run_steps(e, data[2:])       # crosses freeze at step 4
    run_steps(e2, data[2:])
    assert e._onebit_phase == e2._onebit_phase == "compressed"
    assert_tree_equal(e.params, e2.params)
    assert_tree_equal(e._onebit_wire_error, e2._onebit_wire_error)

    e.save_checkpoint(str(tmp_path), tag="post")
    e3 = make_engine(freeze=3)
    assert e3._onebit_phase == "warmup"
    e3.load_checkpoint(str(tmp_path), tag="post")
    assert e3._onebit_phase == "compressed"
    extra = batches(1, seed=18)
    run_steps(e, extra)
    run_steps(e3, extra)
    assert_tree_equal(e.params, e3.params)


# --------------------------------------------------------------------- #
# config conflicts
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("cfg_patch, msg", [
    ({"optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
     "requires a OneBitAdam or OneBitLamb"),
    ({"zero_optimization": {"stage": 3, "low_bandwidth": {"onebit": True}}},
     "stage"),
    ({"zero_optimization": {"stage": 2, "low_bandwidth": {"onebit": True},
                            "offload_optimizer": {"device": "cpu"}}},
     "offload"),
    ({"gradient_clipping": 1.0}, "gradient_clipping"),
    ({"sparse_gradients": True}, "sparse_gradients"),
    ({"optimizer": {"type": "OneBitAdam",
                    "params": {"lr": 1e-3, "freeze_step": 0}}},
     "freeze_step"),
    ({"optimizer": {"type": "OneBitAdam",
                    "params": {"lr": 1e-3, "freeze_step": 2,
                               "betas": [0.9, 1.5]}}},
     "betas"),
])
def test_onebit_config_conflicts(cfg_patch, msg):
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError

    cfg = {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "OneBitAdam",
                      "params": {"lr": 1e-3, "freeze_step": 2}},
        "zero_optimization": {"stage": 2, "low_bandwidth": {"onebit": True}},
    }
    for k, v in cfg_patch.items():
        cfg[k] = v
    with pytest.raises(DeepSpeedConfigError, match=msg):
        DeepSpeedConfig(cfg)
