"""Program Auditor (deepspeed_tpu/analysis/, docs/program_auditor.md).

One deliberately-broken fixture per lint rule — host callback in a scan
body, undonated grad carry, divergent collective order, forced fp32
upcast, wire-budget blowup, retrace storm — asserting rule id, severity,
and provenance; plus clean-program zero-findings runs over the gpt2
train step, the shared jaxpr-walk regression pins
(remat2/shard_map/while-cond gaps, custom_vjp-bwd wire bytes), the
golden lockstep signature, the CLI exit-code contract, and the
checkpoint round-trip of the audit counters.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.analysis import (
    ArgInfo, AuditTarget, ProgramAuditor, ProgramAuditError,
    RecompileGuard, RULE_COMM_BUDGET, RULE_DONATION, RULE_DTYPE_HAZARD,
    RULE_HBM_BUDGET, RULE_HOST_SYNC, RULE_LOCKSTEP, RULE_OVERLAP,
    RULE_RECOMPILE, analyze_overlap, compare_lockstep, estimate_liveness,
    iter_eqns, lockstep_signature, overlap_efficiency, sub_jaxprs)
from deepspeed_tpu.config import AnalysisConfig, DeepSpeedConfigError

REPO = Path(__file__).resolve().parents[2]
GOLDEN = REPO / "tests" / "unit" / "golden" / "gpt2_lockstep_signature.json"
GOLDEN_STREAM = (REPO / "tests" / "unit" / "golden" /
                 "gpt2_zero3_stream_schedule.json")
GOLDEN_STREAM_SERIALIZED = (REPO / "tests" / "unit" / "golden" /
                            "gpt2_zero3_stream_schedule_serialized.json")
GOLDEN_STREAM_FCM = (REPO / "tests" / "unit" / "golden" /
                     "gpt2_zero3_stream_fcm_schedule.json")
GOLDEN_HLO_AUDIT = (REPO / "tests" / "unit" / "golden" /
                    "gpt2_hlo_audit.json")
EXAMPLE_CFG = REPO / "docs" / "examples" / "gpt2_analysis.json"
EXAMPLE_STREAM_CFG = (REPO / "docs" / "examples" /
                      "gpt2_zero3_stream_analysis.json")
EXAMPLE_FCM_CFG = (REPO / "docs" / "examples" /
                   "gpt2_zero3_stream_fcm.json")
EXAMPLE_HLO_CFG = REPO / "docs" / "examples" / "gpt2_hlo_audit.json"


def _cfg(**kw) -> AnalysisConfig:
    return AnalysisConfig.from_dict(dict({"mode": "warn"}, **kw))


def _target(fn, *args, label="fixture", args_info=None,
            **target_kw) -> AuditTarget:
    return AuditTarget(label, jax.make_jaxpr(fn)(*args),
                       args=args_info or [], **target_kw)


def _findings(target, cfg=None):
    return ProgramAuditor(cfg or _cfg()).run([target]).findings


# --------------------------------------------------------------------- #
# shared jaxpr walker (satellite: the unified sub-jaxpr dispatch)
# --------------------------------------------------------------------- #
def test_sub_jaxprs_dispatch_covers_higher_order_prims():
    jx = jax.make_jaxpr(
        jax.grad(lambda x: jax.checkpoint(
            lambda a: jnp.dot(a, a).sum())(x)))(jnp.ones((4, 4)))
    names = {c.eqn.primitive.name for c in iter_eqns(jx)}
    assert "remat2" in names and "dot_general" in names

    def wf(x):
        return lax.while_loop(lambda c: jnp.dot(c, c).sum() < 100,
                              lambda c: c + jnp.dot(c, c), x)
    jw = jax.make_jaxpr(wf)(jnp.ones((4, 4)))
    eqn = next(e for e in jw.jaxpr.eqns if e.primitive.name == "while")
    kinds = [s.kind for s in sub_jaxprs(eqn)]
    assert kinds == ["while_cond", "while_body"]
    # the cond jaxpr's dot is visible to the flat iterator (the old
    # flops walk missed while_cond entirely)
    dots = [c for c in iter_eqns(jw)
            if c.eqn.primitive.name == "dot_general"]
    assert len(dots) == 2


def test_flops_counts_remat_and_shard_map_regions():
    """Unification gap fix: jax.checkpoint emits `remat2` (the old
    dispatch listed only 'remat'/'checkpoint' and counted the region as
    1 flop/element), and shard_map regions were skipped entirely."""
    from deepspeed_tpu.profiling.flops_profiler import count_jaxpr_flops
    n = 32
    dot_flops = 2 * n * n * n

    plain = jax.make_jaxpr(lambda x: jnp.dot(x, x))(jnp.ones((n, n)))
    remat = jax.make_jaxpr(
        jax.checkpoint(lambda x: jnp.dot(x, x)))(jnp.ones((n, n)))
    bd_plain, bd_remat = {}, {}
    count_jaxpr_flops(plain, bd_plain)
    count_jaxpr_flops(remat, bd_remat)
    assert bd_plain["dot_general"] == dot_flops
    assert bd_remat["dot_general"] == dot_flops

    mesh = ds.initialize_mesh(data=-1)

    def region(x):
        return jnp.dot(x, x)

    sm = jax.make_jaxpr(jax.shard_map(
        region, mesh=mesh.mesh, in_specs=P(), out_specs=P()))(
        jnp.ones((n, n)))
    bd_sm = {}
    count_jaxpr_flops(sm, bd_sm)
    assert bd_sm.get("dot_general", 0) == dot_flops
    ds.reset_mesh_context()


def test_wire_bytes_counts_custom_vjp_bwd_under_shard_map():
    """Satellite regression: a two-collective program — custom_vjp whose
    forward all-gathers and whose backward reduce-scatters, inside
    shard_map, traced under grad — pins both directions' counted bytes.
    The sparse-gradients/low-bandwidth paths have exactly this shape."""
    from deepspeed_tpu.runtime.comm.low_bandwidth import (
        collective_wire_bytes)
    mesh = ds.initialize_mesh(data=-1)  # 8 simulated devices

    @jax.custom_vjp
    def gather(x):
        return lax.all_gather(x, "data", axis=0, tiled=True)

    def fwd(x):
        return gather(x), None

    def bwd(_, g):
        return (lax.psum_scatter(g, "data", scatter_dimension=0,
                                 tiled=True),)

    gather.defvjp(fwd, bwd)

    def region(x):
        y = gather(x)
        return (y * y).sum()

    def loss(x):
        return jax.shard_map(region, mesh=mesh.mesh, in_specs=P("data"),
                             out_specs=P(), check_vma=False)(x).sum()

    jx = jax.make_jaxpr(jax.grad(loss))(jnp.ones((8, 4), jnp.float32))
    wire = collective_wire_bytes(jx)
    # fwd: all_gather output [8, 4] fp32 inside the region = 128 B —
    # nested under custom_vjp fun_jaxpr under shard_map
    assert wire["gather_bytes"] == 8 * 4 * 4
    # bwd: the custom-vjp reduce_scatter operand [8, 4] fp32 = 128 B
    # (+ 16 B from the axes=() psum jax's shard_map transpose inserts on
    # the [1, 4] output — pinned so a walker regression is loud)
    assert wire["reduce_bytes"] == 8 * 4 * 4 + 16, wire
    ds.reset_mesh_context()


# --------------------------------------------------------------------- #
# rule fixtures — one deliberately-broken program per rule
# --------------------------------------------------------------------- #
def test_host_sync_fires_on_callback_in_scan_body():
    def body(c, x):
        with jax.named_scope("hot_region"):
            jax.debug.print("loss={}", x)
            return c + x, None

    def f(xs):
        return lax.scan(body, 0.0, xs)[0]

    target = _target(f, jnp.ones(4), label="grad_step")
    hits = [f for f in _findings(target) if f.rule == RULE_HOST_SYNC]
    assert len(hits) == 1
    assert hits[0].severity == "error"
    assert "debug_print" in hits[0].message
    assert hits[0].target == "grad_step"
    # name-stack provenance into the scan body survives
    assert "hot_region" in hits[0].scope


def test_host_sync_warns_at_top_level():
    def f(x):
        jax.debug.print("x={}", x)
        return x * 2

    hits = [f for f in _findings(_target(f, jnp.ones(4)))
            if f.rule == RULE_HOST_SYNC]
    assert len(hits) == 1 and hits[0].severity == "warning"


def test_host_sync_silent_on_clean_scan():
    def f(xs):
        return lax.scan(lambda c, x: (c + x, None), 0.0, xs)[0]

    assert not [f for f in _findings(_target(f, jnp.ones(4)))
                if f.rule == RULE_HOST_SYNC]


def test_donation_audit_flags_undonated_consumed_arg():
    mb = 1024 * 1024

    def f(p, g):
        return jax.tree.map(lambda a, b: a - b, p, g)

    p = {"w": jnp.ones((512, 512))}  # 1 MiB
    target = _target(
        f, p, p, label="apply_step",
        args_info=[ArgInfo("params", mb, donated=False, consumed=True),
                   ArgInfo("grads", mb, donated=True, consumed=True)])
    hits = [f for f in _findings(target) if f.rule == RULE_DONATION]
    assert len(hits) == 1
    assert hits[0].severity == "error"
    assert "params" in hits[0].message and "1.0 MiB" in hits[0].message
    # donated and sub-floor args stay silent; waste estimate = the miss
    report = ProgramAuditor(_cfg()).run([target])
    assert report.donation_waste_bytes == mb


def test_lockstep_divergent_collective_order_between_configs():
    mesh = ds.initialize_mesh(data=-1)

    def order_a(x):
        g = lax.all_gather(x, "data", axis=0, tiled=True)
        return lax.psum_scatter(g, "data", scatter_dimension=0,
                                tiled=True).sum()

    def order_b(x):  # reduces BEFORE gathering — diverges at position 0
        s = lax.psum(x, "data")
        g = lax.all_gather(s, "data", axis=0, tiled=True)
        return g.sum()

    def shmap(f):
        return jax.make_jaxpr(jax.shard_map(
            f, mesh=mesh.mesh, in_specs=P("data"), out_specs=P(),
            check_vma=False))(jnp.ones((8, 4)))

    jx_a, jx_b = shmap(order_a), shmap(order_b)
    same = compare_lockstep(jx_a, jx_a)
    assert same is None
    finding = compare_lockstep(jx_a, jx_b, "host0", "host1")
    assert finding is not None and finding.rule == RULE_LOCKSTEP
    assert finding.severity == "error"
    assert "position 0" in finding.message  # first divergence named
    # signatures themselves are order-sensitive and stable
    assert lockstep_signature(jx_a)[0] != lockstep_signature(jx_b)[0]
    assert lockstep_signature(jx_a)[0] == lockstep_signature(jx_a)[0]
    ds.reset_mesh_context()


def test_lockstep_expected_signature_mismatch_is_error():
    target = _target(lambda x: x + 1, jnp.ones(4), label="grad_step")
    report = ProgramAuditor(
        _cfg(expected_signature="deadbeef")).run([target])
    hits = [f for f in report.findings if f.rule == RULE_LOCKSTEP]
    assert len(hits) == 1 and hits[0].severity == "error"
    # pinning the real combined signature passes clean
    report2 = ProgramAuditor(
        _cfg(expected_signature=report.signature)).run(
        [_target(lambda x: x + 1, jnp.ones(4), label="grad_step")])
    assert not report2.findings


def test_dtype_hazard_forced_fp32_upcast_feeding_matmul():
    def bad(x):  # bf16 wire upcast then matmul at fp32
        return jnp.dot(x.astype(jnp.float32), x.astype(jnp.float32))

    def good(x):  # matmul stays bf16; scalar loss upcast is intended
        return jnp.dot(x, x).sum().astype(jnp.float32)

    cfg = _cfg(dtype_min_elements=1)
    x = jnp.ones((8, 8), jnp.bfloat16)
    hits = [f for f in _findings(_target(bad, x), cfg)
            if f.rule == RULE_DTYPE_HAZARD]
    assert hits and hits[0].severity == "error"
    assert "bfloat16" in hits[0].message and "fp32" in hits[0].message
    assert not [f for f in _findings(_target(good, x), cfg)
                if f.rule == RULE_DTYPE_HAZARD]


def test_dtype_hazard_upcast_wire_into_collective():
    mesh = ds.initialize_mesh(data=-1)

    def region(x):
        return lax.all_gather(x.astype(jnp.float32), "data", axis=0,
                              tiled=True).sum()

    jx = jax.make_jaxpr(jax.shard_map(
        region, mesh=mesh.mesh, in_specs=P("data"), out_specs=P(),
        check_vma=False))(jnp.ones((8, 16), jnp.bfloat16))
    hits = [f for f in _findings(
        AuditTarget("grad_step", jx), _cfg(dtype_min_elements=1))
        if f.rule == RULE_DTYPE_HAZARD]
    assert hits and hits[0].severity == "error"
    assert "all_gather" in hits[0].message
    ds.reset_mesh_context()


def test_comm_budget_dense_blowup_flagged():
    mesh = ds.initialize_mesh(data=-1)

    def region(x):
        return lax.all_gather(x, "data", axis=0, tiled=True).sum()

    jx = jax.make_jaxpr(jax.shard_map(
        region, mesh=mesh.mesh, in_specs=P("data"), out_specs=P(),
        check_vma=False))(jnp.ones((8, 1024), jnp.float32))
    target = AuditTarget("grad_step", jx)
    # gather moves 8*1024*4 B = 32 KiB; budget of 1 KiB trips
    hits = [f for f in _findings(target, _cfg(comm_budget_mb=1 / 1024))
            if f.rule == RULE_COMM_BUDGET]
    assert len(hits) == 1 and hits[0].severity == "error"
    assert "all_gather" in hits[0].message  # top contributor named
    # a budget that fits stays silent; None disables
    assert not [f for f in _findings(target, _cfg(comm_budget_mb=1.0))
                if f.rule == RULE_COMM_BUDGET]
    assert not [f for f in _findings(target, _cfg())
                if f.rule == RULE_COMM_BUDGET]
    ds.reset_mesh_context()


def test_comm_budget_is_gas_weighted_per_optimizer_step():
    """The budget must compare against the same gas-weighted per-step
    total the report (and bench rows) publish: the modular grad program
    dispatches gas times per optimizer step."""
    mesh = ds.initialize_mesh(data=-1)

    def region(x):
        return lax.all_gather(x, "data", axis=0, tiled=True).sum()

    jx = jax.make_jaxpr(jax.shard_map(
        region, mesh=mesh.mesh, in_specs=P("data"), out_specs=P(),
        check_vma=False))(jnp.ones((8, 1024), jnp.float32))
    target = AuditTarget("grad_step", jx)
    one_dispatch = 8 * 1024 * 4  # 32 KiB
    # budget sits between 1 dispatch and the gas=8 per-step total
    cfg = _cfg(comm_budget_mb=(4 * one_dispatch) / (1024 * 1024))
    report = ProgramAuditor(cfg).run([target], gas=8)
    assert report.wire_bytes_per_step == 8 * one_dispatch
    hits = [f for f in report.findings if f.rule == RULE_COMM_BUDGET]
    assert len(hits) == 1 and hits[0].severity == "error"
    # at gas=1 the same budget fits
    assert not [f for f in ProgramAuditor(cfg).run([target]).findings
                if f.rule == RULE_COMM_BUDGET]
    ds.reset_mesh_context()


def test_step_wire_bytes_counts_max_cond_branch_only():
    """Only one cond branch executes, so wire volume counts the most
    expensive branch (the flops counter's semantics) — and ppermute is
    lockstep-relevant but excluded from wire volume."""
    from deepspeed_tpu.analysis import step_wire_bytes
    mesh = ds.initialize_mesh(data=-1)

    def region(pred, x):
        big = lambda a: lax.all_gather(a, "data", axis=0, tiled=True).sum()
        small = lambda a: a.sum()
        return lax.cond(pred, big, small, x)

    jx = jax.make_jaxpr(jax.shard_map(
        region, mesh=mesh.mesh, in_specs=(P(), P("data")), out_specs=P(),
        check_vma=False))(jnp.array(True), jnp.ones((8, 64), jnp.float32))
    total, contributors = step_wire_bytes(jx)
    assert total == 8 * 64 * 4  # the gather branch, counted once
    assert len(contributors) == 1

    def perm(x):
        return lax.ppermute(x, "data",
                            perm=[(i, (i + 1) % 8) for i in range(8)])

    jp = jax.make_jaxpr(jax.shard_map(
        perm, mesh=mesh.mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(jnp.ones((8, 64), jnp.float32))
    assert step_wire_bytes(jp)[0] == 0  # ppermute: lockstep-only
    from deepspeed_tpu.analysis import collective_sequence
    assert any("ppermute" in s for s in collective_sequence(jp))
    ds.reset_mesh_context()


def test_recompile_guard_retrace_storm():
    guard = RecompileGuard(max_retraces=2)
    assert guard.observe((np.zeros((4, 16), np.int32),)) is None
    assert guard.observe((np.zeros((4, 16), np.int32),)) is None  # cached
    assert guard.observe((np.zeros((4, 12), np.int32),)) is None  # 1st
    assert guard.observe((np.zeros((4, 8), np.int32),)) is None   # 2nd
    finding = guard.observe((np.zeros((4, 4), np.int32),))        # 3rd
    assert finding is not None and finding.rule == RULE_RECOMPILE
    assert finding.severity == "error"
    assert "(4, 8)" in finding.message and "(4, 4)" in finding.message
    assert guard.retraces_seen == 3
    # dtype flap is also a retrace
    g2 = RecompileGuard(max_retraces=1)
    g2.observe((np.zeros(4, np.int32),))
    g2.observe((np.zeros(4, np.float32),))
    f2 = g2.observe((np.zeros(4, np.int64),))
    assert f2 is not None and "int64" in f2.message


# --------------------------------------------------------------------- #
# schedule rules (ISSUE 6): overlap + HBM liveness fixtures
# --------------------------------------------------------------------- #
def _serialized_gather_scan_jaxpr(mesh):
    """A layer scan that gathers each layer's weights ON the critical
    path (first consumer is the very next matmul) — the shape of the
    current streamed-ZeRO-3 schedule."""
    def region(x, w):
        def body(c, wi):
            full = lax.all_gather(wi, "data", axis=0, tiled=True)
            return c @ full, None
        c, _ = lax.scan(body, x, w)
        return c

    return jax.make_jaxpr(jax.shard_map(
        region, mesh=mesh.mesh, in_specs=(P(), P(None, "data")),
        out_specs=P(), check_vma=False))(
        jnp.ones((16, 64)), jnp.ones((4, 64, 64)))


def test_overlap_serialized_gather_in_scan_flagged():
    mesh = ds.initialize_mesh(data=-1)
    jx = _serialized_gather_scan_jaxpr(mesh)
    target = AuditTarget("grad_step", jx)
    hits = [f for f in _findings(target) if f.rule == RULE_OVERLAP]
    assert len(hits) == 1
    assert hits[0].severity == "warning"  # error once require_overlap
    assert "all_gather" in hits[0].message
    assert "critical path" in hits[0].message
    assert hits[0].target == "grad_step"
    # analysis.require_overlap escalates to error (the prefetch CI gate)
    hits_err = [f for f in _findings(target, _cfg(require_overlap=True))
                if f.rule == RULE_OVERLAP]
    assert hits_err and hits_err[0].severity == "error"
    # the record carries the schedule facts
    recs = analyze_overlap(jx, _cfg(), "grad_step")
    gathers = [r for r in recs if r.prim == "all_gather"]
    assert len(gathers) == 1
    r = gathers[0]
    assert r.serialized and not r.carried
    assert r.loop_depth == 1 and r.mult == 4  # inside the 4-layer scan
    assert r.distance_eqns == 0 and r.slack_flops == 0
    report = ProgramAuditor(_cfg()).run([target])
    assert report.overlap_efficiency < 0.5
    ds.reset_mesh_context()


def test_overlap_carried_gather_verifies_double_buffer():
    """The double-buffered prefetch shape (ROADMAP item 1): layer i+1's
    gather is issued into the scan carry under layer i's compute — the
    overlap rule must verify it statically and stay silent."""
    mesh = ds.initialize_mesh(data=-1)

    def region(x, w):
        def body(carry, wi):
            c, pref = carry
            nxt = lax.all_gather(wi, "data", axis=0, tiled=True)
            return (c @ pref, nxt), None
        first = lax.all_gather(w[0], "data", axis=0, tiled=True)
        (c, _), _ = lax.scan(body, (x, first), w)
        return c

    jx = jax.make_jaxpr(jax.shard_map(
        region, mesh=mesh.mesh, in_specs=(P(), P(None, "data")),
        out_specs=P(), check_vma=False))(
        jnp.ones((16, 64)), jnp.ones((4, 64, 64)))
    assert not [f for f in _findings(AuditTarget("grad_step", jx))
                if f.rule == RULE_OVERLAP]
    recs = analyze_overlap(jx, _cfg(), "grad_step")
    in_loop = [r for r in recs if r.prim == "all_gather"
               and r.loop_depth == 1]
    assert in_loop and all(r.carried and not r.serialized
                           for r in in_loop)
    ds.reset_mesh_context()


def test_overlap_top_level_collective_not_flagged():
    """A one-shot top-level gather is serialized by the dispatch anyway
    — recorded (it feeds overlap_efficiency and the step-time model) but
    never a finding."""
    mesh = ds.initialize_mesh(data=-1)

    def region(x):
        return lax.all_gather(x, "data", axis=0, tiled=True).sum()

    jx = jax.make_jaxpr(jax.shard_map(
        region, mesh=mesh.mesh, in_specs=P("data"), out_specs=P(),
        check_vma=False))(jnp.ones((8, 64), jnp.float32))
    assert not [f for f in _findings(AuditTarget("grad_step", jx))
                if f.rule == RULE_OVERLAP]
    recs = analyze_overlap(jx, _cfg(), "grad_step")
    assert len(recs) == 1 and recs[0].loop_depth == 0
    assert overlap_efficiency([]) == 1.0
    ds.reset_mesh_context()


def test_hbm_budget_undonated_blowup_over_budget():
    """An undonated param/grad update doubles its HBM; the liveness
    estimator sees it and the hbm_budget rule names the contributors."""
    def f(p, g):
        return jax.tree.map(lambda a, b: a - 0.1 * b, p, g)

    p = {"w": jnp.ones((512, 512))}  # 1 MiB
    jx = jax.make_jaxpr(f)(p, p)
    undonated = estimate_liveness(jx, [False, False],
                                  ["params[0]", "grads[0]"])
    donated = estimate_liveness(jx, [True, True],
                                ["params[0]", "grads[0]"])
    mb = 1024 * 1024
    assert undonated.peak_bytes == 3 * mb  # params + grads + new params
    assert donated.peak_bytes == 2 * mb    # output aliases a dying input
    assert any("params[0]" in k for k, _ in undonated.contributors)

    target = AuditTarget("apply_step", jx,
                         donated_invars=[False, False],
                         invar_labels=["params[0]", "grads[0]"])
    hits = [f for f in _findings(target, _cfg(hbm_budget_mb=2.5))
            if f.rule == RULE_HBM_BUDGET]
    assert len(hits) == 1 and hits[0].severity == "error"
    assert "params[0]" in hits[0].message
    # a budget that fits stays silent; None disables the lint
    assert not [f for f in _findings(target, _cfg(hbm_budget_mb=4.0))
                if f.rule == RULE_HBM_BUDGET]
    assert not [f for f in _findings(target, _cfg())
                if f.rule == RULE_HBM_BUDGET]


def test_liveness_counts_scan_body_internals():
    """The streamed gather materializes the full layer INSIDE the scan
    body — the estimator must count the body's transient peak, not just
    the top-level live set."""
    def f(xs):
        def body(c, x):
            big = jnp.tile(x, (64, 1))        # transient [64, 256]
            return c + big.sum(), None
        return lax.scan(body, 0.0, xs)[0]

    jx = jax.make_jaxpr(f)(jnp.ones((4, 256), jnp.float32))
    rep = estimate_liveness(jx)
    assert rep.peak_bytes >= 64 * 256 * 4  # the body transient counts


def test_step_time_model_fields_and_bound():
    mesh = ds.initialize_mesh(data=-1)
    jx = _serialized_gather_scan_jaxpr(mesh)
    report = ProgramAuditor(_cfg()).run([AuditTarget("grad_step", jx)])
    st = report.step_time
    assert st["predicted_step_time_lb_s"] > 0
    assert st["bound"] in ("compute", "memory", "hidden_comm")
    assert st["flops_per_step"] > 0 and st["io_bytes_per_step"] > 0
    # serialized wire is exposed: the lower bound must include it
    assert st["wire_bytes_exposed"] > 0
    assert (st["predicted_step_time_lb_s"]
            >= st["t_comm_exposed_s"] > 0)
    # gas weighting: the modular grad program dispatches gas times
    report4 = ProgramAuditor(_cfg()).run(
        [AuditTarget("grad_step", jx)], gas=4)
    assert (report4.step_time["flops_per_step"]
            == 4 * st["flops_per_step"])
    ds.reset_mesh_context()


# --------------------------------------------------------------------- #
# clean programs: gpt2 train steps audit to zero
# --------------------------------------------------------------------- #
def _tiny_engine(extra_config=None, bf16=False, gas=1,
                 num_layers=2):
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    ds.reset_mesh_context()
    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                     num_layers=num_layers, num_heads=4, bf16=bf16,
                     embd_dropout=0.0, attn_dropout=0.0,
                     hidden_dropout=0.0)
    model = GPT2Model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "analysis": {"mode": "warn"},
        "steps_per_print": 10 ** 9,
    }
    if bf16:
        config["bf16"] = {"enabled": True}
    config.update(extra_config or {})
    engine, _, _, _ = ds.initialize(
        model=model, config=config,
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    return engine


@pytest.mark.parametrize("bf16, gas", [(False, 1), (True, 2)],
                         ids=["fp32-gas1", "bf16-gas2"])
def test_clean_gpt2_modular_step_zero_findings(bf16, gas):
    engine = _tiny_engine(bf16=bf16, gas=gas)
    report = engine.program_audit
    assert report is not None
    assert report.findings == [], [f.format() for f in report.findings]
    assert report.targets == ["grad_step", "apply_step"]
    assert report.signature is not None


def test_zero3_streaming_gather_on_critical_path_pinned():
    """The negative fixture of the overlap gate: with prefetch off (the
    pre-carried schedule, frozen in golden/gpt2_zero3_stream_schedule_
    serialized.json) the streamed stage-3 program gathers each group at
    use, and the overlap rule must flag the serialized hot-loop gathers
    with the plan's provenance.  ISSUE 7's carried prefetch flips this to
    zero findings — pinned by test_zero3_streaming_carried_flips_
    overlap_gate_green."""
    engine = _tiny_engine(extra_config={"zero_optimization": {
        "stage": 3, "stage3_param_persistence_threshold": 0,
        "stage3_max_live_parameters": 1,
        "stage3_prefetch_bucket_size": 0}})
    assert not engine._zero3_stream.last_plan.prefetch
    report = engine.program_audit
    assert report.wire_bytes_per_step > 0
    assert any("all_gather" in s for s in report.collective_sequence)
    # every finding is the overlap rule (the other five rules stay
    # clean) and at least one names a hot-loop serialized gather with
    # the streamed plan's provenance
    assert report.findings, "streamed gathers should be flagged"
    assert all(f.rule == RULE_OVERLAP and f.severity == "warning"
               for f in report.findings), [
        f.format() for f in report.findings]
    gather_hits = [f for f in report.findings
                   if "all_gather" in f.message]
    assert gather_hits
    assert any("streamed ZeRO-3 plan" in f.message for f in gather_hits)
    assert any("prefetch=False" in f.message for f in gather_hits)
    assert report.overlap["n_serialized_hot_loop"] > 0
    assert report.overlap_efficiency < 1.0


def _stream_engine(layers=2, bucket=200_000, max_live=200_000):
    cfg = {"stage": 3, "stage3_param_persistence_threshold": 0,
           "stage3_max_live_parameters": max_live,
           "stage3_prefetch_bucket_size": bucket}
    return _tiny_engine(extra_config={"zero_optimization": cfg},
                        num_layers=layers)


def test_zero3_streaming_carried_flips_overlap_gate_green():
    """ISSUE 7 tentpole pin: with a prefetch bucket that covers a layer
    group the hot-loop weight gathers ride the scan carry — the
    overlap rule verifies the double buffer statically (zero findings
    even under require_overlap), every hot-loop gather record is
    ``carried``, and the bytes-weighted efficiency beats the frozen
    serialized baseline."""
    from deepspeed_tpu.analysis import audit_engine
    engine = _stream_engine()
    plan = engine._zero3_stream.last_plan
    assert plan.prefetch
    report = engine.program_audit
    assert report.findings == [], [f.format() for f in report.findings]
    assert report.overlap["n_serialized_hot_loop"] == 0
    hot_gathers = [r for r in report.overlap["records"]
                   if r["prim"] == "all_gather" and r["loop_depth"] > 0]
    assert hot_gathers and all(r["carried"] for r in hot_gathers)
    # the carried records carry real slack: a full group of compute sits
    # between issue and first consume
    assert all(r["slack_flops"] > 0 for r in hot_gathers)
    # the backward's gradient wire (the re-gather sweep's transposes) is
    # no reduce_scatter any more: shifted permutes a layer, classified
    # as the hidden transport they are
    wire = [r for r in report.overlap["records"]
            if r["prim"] in ("ppermute", "reduce_scatter", "psum_scatter")]
    assert wire and all(r["prim"] == "ppermute" and r["fused"]
                        and not r["serialized"] for r in wire)
    assert report.overlap["n_fused"] == len(wire)
    serialized = json.loads(GOLDEN_STREAM_SERIALIZED.read_text())
    assert (report.overlap_efficiency
            > serialized["overlap"]["overlap_efficiency"])
    # require_overlap (the CI posture) stays green on the carried
    # schedule: zero findings at error severity
    strict = audit_engine(engine, cfg=AnalysisConfig.from_dict(
        {"mode": "error", "require_overlap": True}), multihost=False)
    assert strict.findings == [], [f.format() for f in strict.findings]


def test_zero3_streaming_carried_liveness_within_plan_bound():
    """The carried buffer must NOT become a stacked scan residual (the
    naive carried structure saves steps x group = the full unsharded
    model).  Pin: the carried program's static peak stays within the
    at-use program's peak plus the plan's 2x-group live-parameter bound
    — a full-model stacking regression would blow past it by
    (num_layers - 2) x group."""
    carried = _stream_engine()
    at_use = _stream_engine(bucket=0)
    plan = carried._zero3_stream.last_plan
    assert plan.prefetch and not at_use._zero3_stream.last_plan.prefetch
    group_bytes = plan.layers_per_step * plan.params_per_layer * 4
    peak_carried = carried.program_audit.peak_hbm_bytes
    peak_at_use = at_use.program_audit.peak_hbm_bytes
    assert peak_carried <= peak_at_use + 2 * group_bytes, (
        peak_carried, peak_at_use, group_bytes)


def test_zero3_streaming_forfeited_prefetch_surfaced():
    """plan_layer_streaming forfeits a requested prefetch when no legal
    group split exists (a single layer cannot form two groups) — the
    auditor must surface the forfeit as a warning finding instead of
    silently falling back to serialized gathers."""
    engine = _stream_engine(layers=1)
    plan = engine._zero3_stream.last_plan
    assert not plan.prefetch and plan.forfeited is not None
    report = engine.program_audit
    forfeits = [f for f in report.findings
                if f.rule == RULE_OVERLAP and "FORFEITED" in f.message]
    assert len(forfeits) >= 1
    # plan_layer_streaming's reason rides into the finding
    assert ">= 2 groups" in forfeits[0].message
    # the serialized gathers themselves are still flagged alongside
    assert any("critical path" in f.message for f in report.findings)


def test_overlap_chase_flows_through_dequant_epilogue():
    """A quantized gather's dequant (payload * scales) must not count as
    the first consumer: the payload-preserving elementwise op flows the
    chase through, so a dequantized-then-carried gather still verifies
    as carried, while a dequantized-then-matmul'd gather stays
    serialized."""
    mesh = ds.initialize_mesh(data=-1)

    def make(carried):
        def region(x, w, s):
            def body(carry, xs):
                c, pref = carry
                wi, si = xs
                q = lax.all_gather(wi, "data", axis=0, tiled=True)
                deq = q * si          # same-shape dequant epilogue
                if carried:
                    return (c @ pref, deq), None
                return (c @ deq, pref), None
            first = jnp.zeros((64, 64))
            (c, _), _ = lax.scan(body, (x, first), (w, s))
            return c

        return jax.make_jaxpr(jax.shard_map(
            region, mesh=mesh.mesh, in_specs=(P(), P(None, "data"), P()),
            out_specs=P(), check_vma=False))(
            jnp.ones((16, 64)), jnp.ones((4, 64, 64)),
            jnp.ones((4, 64, 64)))

    recs = analyze_overlap(make(carried=True), _cfg(), "grad_step")
    in_loop = [r for r in recs if r.prim == "all_gather"
               and r.loop_depth == 1]
    assert in_loop and all(r.carried for r in in_loop)
    recs = analyze_overlap(make(carried=False), _cfg(), "grad_step")
    in_loop = [r for r in recs if r.prim == "all_gather"
               and r.loop_depth == 1]
    assert in_loop and all(not r.carried and r.serialized
                           for r in in_loop)
    ds.reset_mesh_context()


def test_peak_hbm_default_gpt2_within_sanity_band():
    """The donation-aware static peak for the default gpt2 config must
    sit in a sane band: at least the resident state (params + Adam
    moments live through the grad program), at most a small multiple of
    state + activations (the estimator is pre-fusion, so it may
    overcount transients — but never by orders of magnitude)."""
    import jax as _jax
    engine = _tiny_engine()
    report = engine.program_audit
    param_bytes = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in _jax.tree.leaves(engine.params))
    state_bytes = param_bytes + sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in _jax.tree.leaves(engine.opt_state)
        if hasattr(leaf, "shape"))
    assert report.peak_hbm_bytes >= state_bytes
    assert report.peak_hbm_bytes <= 50 * state_bytes, (
        report.peak_hbm_bytes, state_bytes,
        report.peak_hbm_contributors)
    assert report.peak_hbm_contributors
    # engine exposes the static step-time bound for the monitor
    assert engine.predicted_step_time_lb_s == (
        report.step_time["predicted_step_time_lb_s"])
    assert engine.predicted_step_time_lb_s > 0


def test_engine_error_mode_raises_on_retrace_storm():
    engine = _tiny_engine(extra_config={
        "analysis": {"mode": "error", "max_retraces": 1}})
    ids16 = np.zeros((8, 16), np.int32)
    ids12 = np.zeros((8, 12), np.int32)
    ids8 = np.zeros((8, 8), np.int32)
    engine.forward(ids16)
    engine.backward()
    engine.step()
    engine.forward(ids12)  # 1st retrace: within budget
    engine.backward()
    engine.step()
    with pytest.raises(ProgramAuditError) as ei:
        engine.forward(ids8)  # 2nd retrace: over budget
    assert "retraced" in str(ei.value)


def test_audit_counters_round_trip_through_checkpoint(tmp_path):
    engine = _tiny_engine(extra_config={
        "analysis": {"mode": "warn", "max_retraces": 8}})
    engine.forward(np.zeros((8, 16), np.int32))
    engine.backward()
    engine.step()
    engine.forward(np.zeros((8, 12), np.int32))  # one retrace
    engine.backward()
    engine.step()
    assert engine._recompile_guard.retraces_seen == 1
    engine.save_checkpoint(str(tmp_path), tag="t1")
    meta = json.loads(
        (tmp_path / "t1" / "ds_meta.json").read_text())
    audit = meta["client_state"]["program_audit"]
    assert audit["retraces_seen"] == 1
    assert audit["lockstep_signature"] == engine.program_audit.signature
    assert "findings_by_severity" in audit

    engine2 = _tiny_engine(extra_config={
        "analysis": {"mode": "warn", "max_retraces": 8}})
    engine2.load_checkpoint(str(tmp_path), tag="t1")
    assert engine2._recompile_guard.retraces_seen >= 1


def test_analysis_off_by_default_no_auditor_state():
    engine = _tiny_engine(extra_config={"analysis": None})
    assert engine.program_audit is None
    assert engine._recompile_guard is None


def test_analysis_config_validation():
    assert not AnalysisConfig.from_dict(None).enabled
    with pytest.raises(DeepSpeedConfigError):
        AnalysisConfig.from_dict({"mode": "loud"})
    with pytest.raises(DeepSpeedConfigError):
        AnalysisConfig.from_dict({"mode": "warn", "max_retraces": 0})
    with pytest.raises(DeepSpeedConfigError):
        AnalysisConfig.from_dict({"mode": "warn", "comm_budget_mb": -1})


# --------------------------------------------------------------------- #
# golden lockstep signature + CLI contract (CI satellites)
# --------------------------------------------------------------------- #
def test_golden_lockstep_signature_of_default_gpt2_config():
    """Drift in the default gpt2 config's collective sequence must be an
    explicit diff of the golden file, not a silent change."""
    golden = json.loads(GOLDEN.read_text())
    engine = _tiny_engine()  # stage 2 — the example config's shape
    report = engine.program_audit
    assert report.signature == golden["signature"], (
        "the default gpt2 step program's collective sequence changed — "
        "if intended, update tests/unit/golden/gpt2_lockstep_signature"
        f".json (traced {len(report.collective_sequence)} collectives: "
        f"{report.collective_sequence[:5]}...)")
    assert len(report.collective_sequence) == golden["collective_count"]


def _run_cli(config_path, *extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis",
         "--config", str(config_path), *extra],
        cwd=str(REPO), capture_output=True, text=True, timeout=300,
        env=env)


def test_cli_warn_mode_exits_zero_on_example_config():
    out = _run_cli(EXAMPLE_CFG, "--json")
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(
        out.stdout[out.stdout.index("{\n"):])
    golden = json.loads(GOLDEN.read_text())
    assert payload["signature"] == golden["signature"]
    assert payload["findings"] == []


def test_cli_error_mode_exits_nonzero_on_error_findings(tmp_path):
    bad = dict(json.loads(EXAMPLE_CFG.read_text()))
    bad["analysis"] = {"mode": "error", "expected_signature": "deadbeef"}
    cfg_path = tmp_path / "bad.json"
    cfg_path.write_text(json.dumps(bad))
    out = _run_cli(cfg_path)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "lockstep" in out.stdout
    assert "FAILED" in out.stderr


def test_cli_error_mode_hbm_budget_exits_nonzero(tmp_path, capsys):
    """Acceptance criterion (ISSUE 6): an over-budget
    analysis.hbm_budget_mb run exits nonzero via the CLI in error
    mode, naming the live buffers.  Runs cli.main in-process — its
    return value IS the process exit code (__main__ sys.exits it); the
    true subprocess path is pinned by the neighboring CLI tests."""
    from deepspeed_tpu.analysis.cli import main as cli_main
    bad = dict(json.loads(EXAMPLE_CFG.read_text()))
    bad["analysis"] = {"mode": "error", "hbm_budget_mb": 0.001}
    cfg_path = tmp_path / "hbm.json"
    cfg_path.write_text(json.dumps(bad))
    ds.reset_mesh_context()
    rc = cli_main(["--config", str(cfg_path)])
    out = capsys.readouterr()
    assert rc == 1, out.out + out.err
    assert "hbm_budget" in out.out
    assert "FAILED" in out.err


# --------------------------------------------------------------------- #
# CI gate (satellite, ISSUE 6): every docs/examples config must lint
# clean under --mode error — a schedule regression (serialized gather
# escalated via require_overlap, budget breach, signature drift) fails
# the suite here before it can burn a pod.  The same gate runs as a
# workflow step (.github/workflows/tier1.yml) via the real CLI.
# --------------------------------------------------------------------- #
def test_ci_gate_examples_error_mode(capsys, request):
    from deepspeed_tpu.analysis.cli import main as cli_main
    examples = sorted((REPO / "docs" / "examples").glob("*.json"))
    assert EXAMPLE_CFG in examples and EXAMPLE_STREAM_CFG in examples
    assert EXAMPLE_FCM_CFG in examples and EXAMPLE_HLO_CFG in examples
    golden_stream = json.loads(GOLDEN_STREAM.read_text())
    # gpt2_chaos.json installs the process-global chaos plane at engine
    # init; its faults are at_step-triggered (audits never step, so none
    # can fire here) but the plane must not outlive this gate
    from deepspeed_tpu.runtime.resilience import chaos as _chaos
    request.addfinalizer(_chaos.uninstall)
    for cfg_path in examples:
        ds.reset_mesh_context()
        rc = cli_main(["--config", str(cfg_path), "--mode", "error",
                       "--json"])
        stdout = capsys.readouterr().out
        assert rc == 0, (
            f"{cfg_path.name} failed the error-mode analysis gate:\n"
            + stdout)
        payload = json.loads(stdout[stdout.index("{\n"):])
        # a 1-bit-tier config is TWO audited programs: the CLI emits
        # one payload per phase, and each must clear the same gate
        phases = ([payload["phase_warmup"], payload["phase_compressed"]]
                  if "phase_warmup" in payload else [payload])
        for ph in phases:
            errors = [f for f in ph["findings"]
                      if f["severity"] == "error"]
            assert errors == [], f"{cfg_path.name}: {errors}"
        if cfg_path == EXAMPLE_STREAM_CFG:
            # the streamed config's CARRIED schedule is pinned by its
            # golden: signature, collective count, zero serialized
            # hot-loop gathers, carried records present (regenerate with
            # --update-golden).  The config sets require_overlap +
            # mode=error, so a serialized regression fails the rc==0
            # assert above before these pins even run.
            assert payload["signature"] == golden_stream["signature"]
            assert (len(payload["collective_sequence"])
                    == golden_stream["collective_count"])
            ov = golden_stream["overlap"]
            assert payload["overlap"]["n_serialized_hot_loop"] == 0
            assert (payload["overlap"]["n_serialized_hot_loop"]
                    == ov["n_serialized_hot_loop"])
            assert payload["overlap"]["n_carried"] == ov["n_carried"] > 0
            assert abs(payload["overlap_efficiency"]
                       - ov["overlap_efficiency"]) < 0.1
            # the carried schedule must beat the frozen pre-carried
            # serialized baseline on bytes-weighted efficiency — the
            # ISSUE 7 acceptance bar
            serialized = json.loads(GOLDEN_STREAM_SERIALIZED.read_text())
            assert (payload["overlap_efficiency"]
                    > serialized["overlap"]["overlap_efficiency"])
            assert payload["findings"] == []
        if cfg_path == EXAMPLE_FCM_CFG:
            # the fused-collective-matmul schedule is pinned by its
            # golden: every hot-loop qwZ/qgZ wire-mover classifies
            # fused/hidden, ZERO exposed hot-loop bytes — the ISSUE 13
            # acceptance bar (exposed-comm lane ~ 0), enforced here
            # under the config's own require_overlap + mode=error
            golden_fcm = json.loads(GOLDEN_STREAM_FCM.read_text())
            assert payload["signature"] == golden_fcm["signature"]
            assert (len(payload["collective_sequence"])
                    == golden_fcm["collective_count"])
            ovf = golden_fcm["overlap"]
            assert payload["overlap"]["n_serialized_hot_loop"] == 0
            assert (payload["overlap"]["n_fused"]
                    == ovf["n_fused"] > 0)
            exposed_hot = sum(
                int(r["wire_bytes"] * r["mult"]
                    * (1.0 - r["hidden_fraction"]))
                for r in payload["overlap"]["records"]
                if r["loop_depth"] > 0)
            assert exposed_hot == 0
            assert golden_fcm["wire_bytes_exposed_hot_loop"] == 0
            assert (payload["step_time"]["wire_bytes_fused"]
                    == golden_fcm["wire_bytes_fused"] > 0)
            assert payload["findings"] == []
        if cfg_path == EXAMPLE_HLO_CFG:
            # the HLO-level SPMD cross-check config runs the compiled-
            # view audit via its own analysis.hlo_audit knob (no CLI
            # flag needed) under require_spmd_match + mode=error; its
            # golden pins the clean compiled wire story — zero silent
            # reshards, jaxpr/HLO accountings in agreement (ISSUE 14
            # acceptance bar).  Regenerate with --update-golden.
            golden_hlo = json.loads(GOLDEN_HLO_AUDIT.read_text())
            assert payload["signature"] == golden_hlo["signature"]
            hlo = payload["hlo"]
            assert (hlo["n_silent_reshards"]
                    == golden_hlo["n_silent_reshards"] == 0)
            assert hlo["reshard_bytes_per_step"] == 0
            assert (hlo["hlo_wire_bytes_per_step"]
                    == golden_hlo["hlo_wire_bytes_per_step"] > 0)
            assert (hlo["hlo_collective_count"]
                    == golden_hlo["hlo_collective_count"] > 0)
            assert (round(hlo["divergence_ratio"], 4)
                    == golden_hlo["divergence_ratio"] == 1.0)
            # the compiled-view-only wire is priced in the exposed lane
            assert (payload["step_time"]["wire_bytes_hlo_only"]
                    == hlo["hlo_only_wire_bytes_per_step"] > 0)
            assert payload["findings"] == []


@pytest.mark.slow
def test_cli_update_golden_regenerates_checked_in_files(tmp_path):
    """--update-golden must reproduce the checked-in goldens exactly —
    the files are CLI output, never hand-edited.  One loop covers all
    four golden files (lockstep, streamed schedule, FCM schedule, HLO
    cross-check) so stale-golden drift fails in one place."""
    env_dir = str(tmp_path / "golden")
    for cfg_path, golden_path, extra in (
            (EXAMPLE_CFG, GOLDEN, ()),
            (EXAMPLE_STREAM_CFG, GOLDEN_STREAM, ("--devices", "8")),
            (EXAMPLE_FCM_CFG, GOLDEN_STREAM_FCM, ("--devices", "8")),
            (EXAMPLE_HLO_CFG, GOLDEN_HLO_AUDIT, ("--devices", "8"))):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["DS_ANALYSIS_GOLDEN_DIR"] = env_dir
        out = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.analysis",
             "--config", str(cfg_path), "--update-golden", *extra],
            cwd=str(REPO), capture_output=True, text=True, timeout=300,
            env=env)
        assert out.returncode == 0, out.stdout + out.stderr
        regenerated = json.loads(
            (Path(env_dir) / golden_path.name).read_text())
        assert regenerated == json.loads(golden_path.read_text()), (
            f"{golden_path.name} drifted from CLI output — regenerate "
            "with --update-golden")


def test_cli_update_golden_unknown_config_errors(tmp_path):
    from deepspeed_tpu.analysis.cli import GOLDEN_MAP, _golden_payload
    assert "gpt2_analysis.json" in GOLDEN_MAP
    assert "gpt2_zero3_stream_analysis.json" in GOLDEN_MAP
    # payload shape for the lockstep golden matches the checked-in file
    from deepspeed_tpu.analysis import AuditReport
    rep = AuditReport(signature="ab" * 32)
    payload = _golden_payload("gpt2_lockstep_signature.json", rep)
    assert set(payload) == {"_comment", "signature", "collective_count"}
    payload2 = _golden_payload("gpt2_zero3_stream_schedule.json", rep)
    assert set(payload2) == {"_comment", "signature", "collective_count",
                             "overlap"}
    payload3 = _golden_payload("gpt2_zero3_stream_fcm_schedule.json",
                               rep)
    assert set(payload3) == {"_comment", "signature", "collective_count",
                             "overlap", "wire_bytes_exposed_hot_loop",
                             "wire_bytes_fused"}
    assert "n_fused" in payload3["overlap"]
    # the HLO cross-check golden (ISSUE 14): its config must be in the
    # regen map and its payload must pin the clean compiled wire story
    assert "gpt2_hlo_audit.json" in GOLDEN_MAP
    payload4 = _golden_payload("gpt2_hlo_audit.json", rep)
    assert {"signature", "hlo_wire_bytes_per_step",
            "hlo_collective_count", "divergence_ratio",
            "n_silent_reshards", "waivers"} <= set(payload4)
