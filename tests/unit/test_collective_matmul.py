"""Fused collective-matmul (ops/collective_matmul.py, ISSUE 13,
docs/fused_collective_matmul.md): T3-style per-tile fusion of the
qwZ/qgZ transports with their producer/consumer GEMMs.

Interpret-mode coverage on the 8-device CPU sim mesh — the per-tile GEMM
kernels run under ``pallas_call(interpret=True)`` with the remote-copy
ring mesh-simulated as ``lax.ppermute`` (the flash_attention.py
pattern); the in-kernel RDMA path is chip-only (ROADMAP item 1).

Pinned contracts: fused-vs-modular forward/backward numerics (qwZ gather
BITWISE, qgZ scatter bitwise via the shard-order accumulation contract),
error-feedback round-trip over 6 steps, grad flow through the fused
custom_vjp under the carried streaming scan, the Schedule Auditor's
fused/hidden classification with zero new host_sync/lockstep findings,
and config validation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu import constants as C
from deepspeed_tpu.ops import collective_matmul as cm
from deepspeed_tpu.runtime.comm import low_bandwidth as lb

from .test_zero3_streaming import _mode_cfg, _train_tiny


def _mesh(n=4, name="data"):
    devs = np.array(jax.devices()[:n]).reshape(n)
    return Mesh(devs, (name,))


def _sm(f, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


# --------------------------------------------------------------------- #
# transport drop-ins: fused vs modular numerics
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("qwz,qgz", [(8, 8), (8, 0), (4, 4), (0, 0)])
def test_fcm_all_gather_forward_bitwise(dtype, qwz, qgz):
    """The fused gather is BITWISE-identical to the modular qwZ path at
    every width (the same quantization runs once at the source, the
    same dequant math per tile) — only the transport schedule differs."""
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 24)).astype(dtype)

    def fused(a):
        return cm.fcm_all_gather(a, ("data",), 0, qwz, qgz, 16)

    def modular(a):
        if qwz or qgz:
            return lb.low_bandwidth_all_gather(a, ("data",), 0, qwz,
                                               qgz, 16)
        return lax.all_gather(a, ("data",), axis=0, tiled=True)

    of = _sm(fused, mesh, P("data"), P("data"))(x)
    om = _sm(modular, mesh, P("data"), P("data"))(x)
    assert of.dtype == om.dtype == dtype
    assert (np.asarray(of) == np.asarray(om)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("qwz,qgz", [(8, 8), (4, 4)])
def test_fcm_all_gather_backward_bitwise(dtype, qwz, qgz):
    """With qgZ on, the fused custom_vjp's transpose keeps the modular
    accumulation-order contract (dequantized source table summed in
    shard-index order) — grads are bitwise-equal."""
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 24)).astype(dtype)

    def g_of(fn):
        def loss(a):
            y = fn(a)
            return jnp.sum((y.astype(jnp.float32)) ** 2)
        return _sm(jax.grad(loss), mesh, P("data"), P("data"))(x)

    gf = g_of(lambda a: cm.fcm_all_gather(a, ("data",), 0, qwz, qgz, 16))
    gm = g_of(lambda a: lb.low_bandwidth_all_gather(a, ("data",), 0,
                                                    qwz, qgz, 16))
    assert (np.asarray(gf) == np.asarray(gm)).all()


def test_fcm_all_gather_backward_f32_fallback_close():
    """qgz_bits=0: the fused transpose reduces through the per-tile
    table in fp32 with a FIXED shard-index order; the modular
    psum_scatter leaves the order to XLA — equal up to reassociation."""
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 24))

    def g_of(fn):
        def loss(a):
            return jnp.sum(fn(a) ** 2)
        return _sm(jax.grad(loss), mesh, P("data"), P("data"))(x)

    gf = g_of(lambda a: cm.fcm_all_gather(a, ("data",), 0, 8, 0, 16))
    gm = g_of(lambda a: lb.low_bandwidth_all_gather(a, ("data",), 0,
                                                    8, 0, 16))
    np.testing.assert_allclose(gf, gm, rtol=1e-6, atol=1e-6)


def test_fcm_reduce_scatter_matches_modular_bitwise():
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 8, 12))

    def fused(a):
        return cm.fcm_reduce_scatter(a, ("data",), 0, bits=8, block=16)

    def modular(a):
        return lb.quantized_psum_scatter(a, ("data",), 0, bits=8,
                                         block=16)

    of = _sm(fused, mesh, P("data"), P("data"))(x)
    om = _sm(modular, mesh, P("data"), P("data"))(x)
    assert (np.asarray(of) == np.asarray(om)).all()


def _parent_fcm_scatter_bits0(x, axis_name, dim):
    """``fcm_reduce_scatter(bits=0)`` as the parent of PR 59 computed one
    axis of it: the chunk table widened to fp32 BEFORE the wire, moved by
    ``_scatter_tiles``, summed over the source index."""
    world = lax.axis_size(axis_name)
    xt = jnp.moveaxis(x, dim, 0)
    chunks = xt.reshape((world, xt.shape[0] // world) + xt.shape[1:])
    red, _ = cm._quantize_scatter_reduce(chunks, axis_name, 0, 16)
    return jnp.moveaxis(red.astype(x.dtype), 0, dim)


@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("world", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_fcm_reduce_scatter_bits0_is_the_dense_path_bitwise(dtype, world,
                                                            dim):
    """The knob's ``bits=0`` scatter and the default dense path are one
    function, and its result is bit for bit what the parent's fp32 table
    gave: widening commutes with the wire."""
    mesh = _mesh(world)
    x = (jax.random.normal(jax.random.PRNGKey(5), (world, 16, 24)) * 3.7
         ).astype(dtype)

    def on(fn):
        return np.asarray(_sm(lambda a: fn(a[0])[None], mesh, P("data"),
                              P("data"))(x).astype(jnp.float32))

    now = on(lambda a: cm.fcm_reduce_scatter(a, ("data",), dim, bits=0))
    dense = on(lambda a: lb.dense_psum_scatter(a, ("data",), dim))
    parent = on(lambda a: _parent_fcm_scatter_bits0(a, "data", dim))
    np.testing.assert_array_equal(now, dense)
    np.testing.assert_array_equal(now, parent)


def test_fcm_multi_axis_gather_matches_joint():
    """Nested per-axis rings reproduce the joint tiled all_gather's
    axis-major index order (the modular path gathers both axes in one
    collective)."""
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("data", "expert"))
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 6))

    def fused(a):
        return cm.fcm_all_gather(a, ("data", "expert"), 0, 8, 0, 8)

    def modular(a):
        return lb.low_bandwidth_all_gather(a, ("data", "expert"), 0,
                                           8, 0, 8)

    spec = P(("data", "expert"))
    of = _sm(fused, mesh, spec, spec)(x)
    om = _sm(modular, mesh, spec, spec)(x)
    assert (np.asarray(of) == np.asarray(om)).all()


# --------------------------------------------------------------------- #
# error feedback
# --------------------------------------------------------------------- #
def test_error_feedback_round_trip_six_steps():
    """The fused qgZ scatter carries the identical error-feedback
    residual as the modular variant: over 6 steps of a persistent
    signal, reduced chunks AND error buffers stay bitwise-equal, and
    the accumulated mean converges on the exact value (the telescoping
    argument both implementations share)."""
    mesh = _mesh()
    world = 4
    signal = jax.random.normal(jax.random.PRNGKey(5), (world, 16, 8))

    def one(fn, a, e):
        r, ne = fn(a[0], e[0], "data", 0, 4, 8)
        return r[None], ne[None]

    run_f = _sm(lambda a, e: one(cm.fcm_qgz_reduce_scatter_inner, a, e),
                mesh, (P("data"), P("data")), (P("data"), P("data")))
    run_m = _sm(lambda a, e: one(lb.qgz_reduce_scatter_inner, a, e),
                mesh, (P("data"), P("data")), (P("data"), P("data")))

    ef = em = jnp.zeros_like(signal)
    acc_f = None
    for step in range(6):
        rf, ef = run_f(signal, ef)
        rm, em = run_m(signal, em)
        assert (np.asarray(rf) == np.asarray(rm)).all(), f"step {step}"
        assert (np.asarray(ef) == np.asarray(em)).all(), f"step {step}"
        acc_f = rf if acc_f is None else acc_f + rf
    # persistent-signal convergence: the 6-step average of the int4
    # quantized reduction approaches the exact sum far beyond one
    # step's quantization error
    exact = jnp.stack([signal[:, 4 * p:4 * (p + 1)].sum(0)[None]
                       for p in range(world)])[:, 0]
    exact = exact.reshape(acc_f.shape)
    err6 = float(jnp.max(jnp.abs(acc_f / 6 - exact)))
    r1, _ = run_f(signal, jnp.zeros_like(signal))
    err1 = float(jnp.max(jnp.abs(r1 - exact)))
    assert err6 < err1 / 2, (err6, err1)


# --------------------------------------------------------------------- #
# GEMM-fused kernels (layer 1), interpret mode
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("qwz", [8, 4, 0])
def test_fused_allgather_matmul_matches_reference(qwz):
    """y = x @ dequant(all_gather(w)): the ring-fused kernel against
    the unfused quantize -> gather -> dequant -> matmul reference
    (qwz=0: native-width tiles ride the ring, no dequant)."""
    mesh = _mesh()
    W, M, K, N = 4, 8, 32, 16
    x = jax.random.normal(jax.random.PRNGKey(6), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(7), (W, K // W, N)) / 4

    def fused(xr, wr):
        return cm.fused_allgather_matmul(xr, wr[0], "data", qwz, 0, 8,
                                         True)[None]

    y = _sm(fused, mesh, (P(), P("data")), P("data"))(x, w)
    if qwz:
        wq = jnp.concatenate([
            lb.blockwise_dequantize(*lb.blockwise_quantize(
                w[i], dim=0, bits=qwz, block=8), w[i].shape, dim=0,
                bits=qwz)
            for i in range(W)], axis=0)
    else:
        wq = w.reshape(K, N)
    np.testing.assert_allclose(y[0], x @ wq, rtol=1e-5, atol=1e-5)


def test_fused_allgather_matmul_grads():
    """The fused custom_vjp: dx re-rings the quantized shards through
    the transposed tile GEMM; dW is the fused matmul-reduce-scatter
    epilogue (straight-through quantizer at qgz_bits=0)."""
    mesh = _mesh()
    W, M, K, N = 4, 8, 32, 16
    x = jax.random.normal(jax.random.PRNGKey(8), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(9), (W, K // W, N)) / 4

    def loss(xr, wr):
        return jnp.sum(cm.fused_allgather_matmul(
            xr, wr[0], "data", 8, 0, 8, True) ** 2)

    gx, gw = _sm(jax.grad(loss, argnums=(0, 1)), mesh,
                 (P(), P("data")), (P(), P("data")))(x, w)
    wq = jnp.concatenate([
        lb.blockwise_dequantize(*lb.blockwise_quantize(
            w[i], dim=0, bits=8, block=8), w[i].shape, dim=0)
        for i in range(W)], axis=0)
    rx, rw = jax.grad(lambda a, b: jnp.sum((a @ b) ** 2),
                      argnums=(0, 1))(x, wq)
    # dx is computed per shard-region replica (x enters replicated)
    np.testing.assert_allclose(gx, rx, rtol=1e-4, atol=1e-4)
    # dW: every replica contributed the same x^T@dy, reduce-scattered —
    # chunk p = W * rows p of the reference grad
    np.testing.assert_allclose(gw.reshape(K, N), W * rw,
                               rtol=1e-3, atol=1e-3)


def test_fused_matmul_reduce_scatter_with_error_feedback():
    """dW = lhs^T @ rhs reduce-scattered per tile, error residual
    intact: new_error == compensated - deq(quant(compensated))."""
    mesh = _mesh()
    W, B, K, N = 4, 16, 32, 12
    lhs = jax.random.normal(jax.random.PRNGKey(10), (B, K))
    rhs = jax.random.normal(jax.random.PRNGKey(11), (B, N))
    err0 = jnp.zeros((K, N))

    def fused(lhs, r, e):
        c, ne = cm.fused_matmul_reduce_scatter(lhs, r, e[0], "data", 8,
                                               16, True)
        return c[None], ne[None]

    chunk, new_err = _sm(fused, mesh, (P(), P(), P("data")),
                         (P("data"), P("data")))(
        lhs, rhs, jnp.broadcast_to(err0, (W,) + err0.shape))
    dw = np.asarray(lhs.T @ rhs)
    tab = dw.reshape(W, K // W, N)
    q, s = lb.blockwise_quantize(jnp.asarray(tab), dim=0, bits=8,
                                 block=16)
    deq = lb.blockwise_dequantize(q, s, tab.shape, dim=0)
    # all W replicas send identical tiles: chunk p sums W copies
    np.testing.assert_allclose(chunk[0], W * np.asarray(deq)[0],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        new_err[0], dw - np.asarray(deq).reshape(K, N),
        rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------- #
# grad flow through the fused custom_vjp under the carried scan
# --------------------------------------------------------------------- #
_FCM_LB = {"low_bandwidth": {"qwz_bits": 8, "qgz_bits": 8,
                             "fused_collective_matmul": True}}
_MOD_LB = {"low_bandwidth": {"qwz_bits": 8, "qgz_bits": 8}}


def test_fcm_carried_scan_training_parity():
    """End-to-end: the carried streamed engine with fused transports
    trains identically to the modular qwZ/qgZ engine — same
    quantization, same accumulation contract, grads flow through the
    fused custom_vjp inside the hand-written carried VJP's forward AND
    backward re-gather sweeps."""
    l_mod, p_mod, _ = _train_tiny(_mode_cfg("carried", _MOD_LB))
    l_fcm, p_fcm, plan = _train_tiny(_mode_cfg("carried", _FCM_LB))
    assert plan.prefetch
    np.testing.assert_allclose(l_fcm, l_mod, rtol=1e-6)
    # wide leaves are bitwise (qwZ gather + qgZ shard-order scatter);
    # skinny leaves (biases/LN) fall back dense in BOTH modes but reduce
    # through psum_scatter (modular) vs the fixed-order fp32 table
    # (fused) — fp reassociation at the 1e-7 scale, nothing structural
    for a, b in zip(jax.tree.leaves(p_fcm), jax.tree.leaves(p_mod)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-6)
    assert l_fcm[-1] < l_fcm[0]  # still actually training


def test_fcm_at_use_mode_training_parity():
    """fcm composes with prefetch off (at-use gathers through the scan
    VJP, exercising fcm_all_gather's own custom_vjp under lax.scan
    differentiation)."""
    l_mod, p_mod, _ = _train_tiny(_mode_cfg("off", _MOD_LB))
    l_fcm, p_fcm, plan = _train_tiny(_mode_cfg("off", _FCM_LB))
    assert not plan.prefetch
    np.testing.assert_allclose(l_fcm, l_mod, rtol=1e-6)
    # same skinny-leaf dense-fallback reassociation note as the carried
    # parity above
    for a, b in zip(jax.tree.leaves(p_fcm), jax.tree.leaves(p_mod)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-6)


# --------------------------------------------------------------------- #
# Schedule Auditor classification
# --------------------------------------------------------------------- #
def _fcm_engine():
    ds.reset_mesh_context()
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    mesh = ds.initialize_mesh(data=-1)
    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                     num_layers=4, num_heads=4, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    conf = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": _mode_cfg("carried", _FCM_LB),
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh)
    return engine


def test_auditor_classifies_fcm_transports_fused_hidden():
    """ISSUE 13 acceptance: on the fused streamed config, every
    hot-loop qwZ/qgZ wire-mover classifies fused/hidden — zero
    serialized hot-loop collectives, zero exposed hot-loop wire bytes
    (the exposed-comm lane's hot-loop share is 0), and the fused bytes
    price into the hidden-comm lane.  No new host_sync or lockstep
    findings ride along."""
    from deepspeed_tpu.analysis import audit_engine
    engine = _fcm_engine()
    try:
        report = audit_engine(engine, multihost=False)
        ov = report.overlap
        assert ov["n_fused"] > 0
        assert ov["n_serialized_hot_loop"] == 0
        fused_recs = [r for r in ov["records"] if r["fused"]]
        assert fused_recs and all(r["hidden_fraction"] == 1.0
                                  and not r["serialized"]
                                  for r in fused_recs)
        assert all(r["prim"] == "ppermute" for r in fused_recs)
        exposed_hot = sum(
            r["wire_bytes"] * r["mult"] * (1.0 - r["hidden_fraction"])
            for r in ov["records"] if r["loop_depth"] > 0)
        assert exposed_hot == 0
        assert report.step_time["wire_bytes_fused"] > 0
        # the fused wire rides the hidden lane in the lower bound
        assert (report.step_time["wire_bytes_hidden"]
                >= report.step_time["wire_bytes_fused"])
        # zero new host_sync / lockstep findings on the fused program
        assert [f for f in report.findings
                if f.rule in ("host_sync", "lockstep")] == []
        # require_overlap strict posture stays green
        from deepspeed_tpu.config import AnalysisConfig
        from deepspeed_tpu.analysis import ProgramAuditor
        from deepspeed_tpu.analysis.auditor import engine_targets
        strict = AnalysisConfig.from_dict(
            {"mode": "warn", "require_overlap": True})
        strict_report = ProgramAuditor(strict).run(
            engine_targets(engine),
            gas=engine.gradient_accumulation_steps())
        assert [f for f in strict_report.findings
                if f.rule == "overlap"] == []
    finally:
        ds.reset_mesh_context()


def test_fcm_wire_accounted_not_zero():
    """The fused ring hops are ACCOUNTED (step_wire_bytes counts
    FCM-scoped ppermutes; collective_wire_bytes reports them under
    fcm_bytes) — a fused config must not report zero wire."""
    mesh = _mesh()
    x = jnp.ones((8, 24), jnp.float32)

    def fused(a):
        return cm.fcm_all_gather(a, ("data",), 0, 8, 0, 16)

    jx = jax.make_jaxpr(
        jax.shard_map(fused, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False))(x)
    from deepspeed_tpu.analysis.rules import step_wire_bytes
    total, contributors = step_wire_bytes(jx)
    assert total > 0
    assert any("ppermute" in name for name, _ in contributors)
    wire = lb.collective_wire_bytes(jx)
    assert wire["fcm_bytes"] > 0
    assert wire["gather_bytes"] == 0  # no monolithic gather remains

    # a generic (non-fcm) ppermute stays lockstep-only — unchanged,
    # and a USER scope that merely CONTAINS the marker as a prefix must
    # not hijack the fused classification (component matching, not
    # substring: scope_has_component)
    def plain(a):
        world = 4
        perm = [(i, (i + 1) % world) for i in range(world)]
        with jax.named_scope("fcm_fused_block"):
            return lax.ppermute(a, "data", perm)

    jx2 = jax.make_jaxpr(
        jax.shard_map(plain, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False))(x)
    assert step_wire_bytes(jx2)[0] == 0
    assert lb.collective_wire_bytes(jx2)["fcm_bytes"] == 0
    from deepspeed_tpu.analysis import analyze_overlap
    from deepspeed_tpu.config import AnalysisConfig
    recs = analyze_overlap(jx2, AnalysisConfig.from_dict({"mode": "warn"}))
    assert all(not r.fused for r in recs)


# --------------------------------------------------------------------- #
# config validation
# --------------------------------------------------------------------- #
def test_fcm_config_validation():
    from deepspeed_tpu.config import (DeepSpeedConfigError,
                                      ZeroLowBandwidthConfig)
    cfg = ZeroLowBandwidthConfig.from_dict(
        {"fused_collective_matmul": True})
    assert cfg.fused_collective_matmul is True
    # fcm alone engages the low-bandwidth context (native-width rings)
    assert cfg.enabled
    assert not ZeroLowBandwidthConfig.from_dict({}).fused_collective_matmul
    assert not ZeroLowBandwidthConfig.from_dict({}).enabled
    with pytest.raises(DeepSpeedConfigError,
                       match="fused_collective_matmul"):
        ZeroLowBandwidthConfig.from_dict(
            {"fused_collective_matmul": "yes"})
    # constants single-source the knob and the scope marker
    assert C.LOW_BANDWIDTH_FCM == "fused_collective_matmul"
    assert cm.FCM_SCOPE == C.FCM_SCOPE


def test_fcm_autotuning_axis_config():
    from deepspeed_tpu.config import AutotuningConfig
    cfg = AutotuningConfig.from_dict(
        {"chips": 8, "fused_collective_matmul": [False, True]})
    assert cfg.fused_collective_matmul == (False, True)
    assert AutotuningConfig.from_dict(
        {"chips": 8}).fused_collective_matmul == (False,)


def test_fcm_reduce_scatter_rejects_indivisible_dim():
    mesh = _mesh()
    x = jnp.ones((6, 4), jnp.float32)  # 6 rows over a 4-way axis

    def bad(a):
        return cm.fcm_reduce_scatter(a, ("data",), 0, bits=8, block=16)

    with pytest.raises(ValueError, match="divisible"):
        _sm(bad, mesh, P("data"), P("data"))(x)
