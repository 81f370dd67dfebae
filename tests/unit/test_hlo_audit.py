"""HLO-level SPMD audit (deepspeed_tpu/analysis/hlo_audit.py; ISSUE 14).

Three layers of coverage:

  * parser fixtures over synthetic optimized-HLO text — replica-group
    forms (explicit + iota), async start/done dedup, while trip-count
    weighting, conditional worst-branch accounting;
  * real-XLA fixtures that PROVOKE silent resharding — a mis-annotated
    pjit out_sharding forcing a compiler-inserted all-gather, a weight
    annotated sharded while the consumer needs it replicated — asserting
    the `silent_reshard` finding fires with source provenance (warning
    by default, error under analysis.require_spmd_match), plus clean
    traced-collective programs reconciling at divergence_ratio 1.0;
  * the cross-accounting regression over every docs/examples config:
    jaxpr-predicted wire within a tolerance band of the HLO-measured
    bytes, or carrying a named, asserted waiver — so future transports
    cannot silently fork the two accountings.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.analysis import (
    AuditTarget, ProgramAuditError, RULE_SILENT_RESHARD,
    RULE_SPMD_DIVERGENCE, SpmdWaiver, audit_target_hlo, step_wire_bytes,
    walk_hlo_collectives)
from deepspeed_tpu.analysis.hlo_audit import HloProgram
from deepspeed_tpu.config import AnalysisConfig

REPO = Path(__file__).resolve().parents[2]
EXAMPLES = REPO / "docs" / "examples"
GOLDEN_HLO = REPO / "tests" / "unit" / "golden" / "gpt2_hlo_audit.json"


def _cfg(**kw) -> AnalysisConfig:
    return AnalysisConfig.from_dict(dict({"mode": "warn"}, **kw))


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("data",))


def _target(fn, *args, label="fixture", jit_kw=None, **target_kw):
    jit_kw = jit_kw or {}
    return AuditTarget(
        label, jax.make_jaxpr(fn)(*args),
        lower=lambda: jax.jit(fn, **jit_kw).lower(
            *args).compile().as_text(),
        **target_kw)


# --------------------------------------------------------------------- #
# parser fixtures: synthetic optimized-HLO text
# --------------------------------------------------------------------- #
_SYNTH_HLO = """\
HloModule jit_f, is_scheduled=true, num_partitions=8

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

%body.1 (p: (s32[], f32[16,32])) -> (s32[], f32[16,32]) {
  %p = (s32[], f32[16,32]) parameter(0)
  %gte = f32[16,32]{1,0} get-tuple-element((s32[], f32[16,32]) %p), index=1
  %ag = f32[128,32]{1,0} all-gather(f32[16,32]{1,0} %gte), channel_id=1, replica_groups=[1,8]<=[8], dimensions={0}, metadata={op_name="jit(f)/jit(main)/while/body/all_gather" source_file="a.py" source_line=3}
  %ar = f32[16,32]{1,0} all-reduce(f32[16,32]{1,0} %gte), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%region_add, metadata={op_name="jit(f)/jit(main)/while/body/psum" source_file="a.py" source_line=4}
  %c = s32[] constant(1)
  %i = s32[] get-tuple-element((s32[], f32[16,32]) %p), index=0
  %ip = s32[] add(s32[] %i, s32[] %c)
  ROOT %tup = (s32[], f32[16,32]) tuple(s32[] %ip, f32[16,32] %ar)
}

%cond.1 (p: (s32[], f32[16,32])) -> pred[] {
  %p = (s32[], f32[16,32]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[16,32]) %p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

ENTRY %main_spmd (param: f32[16,32]) -> f32[16,32] {
  %param = f32[16,32]{1,0} parameter(0)
  %ags = (f32[16,32]{1,0}, f32[128,32]{1,0}) all-gather-start(f32[16,32]{1,0} %param), channel_id=3, replica_groups=[1,8]<=[8], dimensions={0}
  %agd = f32[128,32]{1,0} all-gather-done((f32[16,32]{1,0}, f32[128,32]{1,0}) %ags)
  %deg = f32[16,32]{1,0} all-reduce(f32[16,32]{1,0} %param), channel_id=4, replica_groups={{0},{1},{2},{3},{4},{5},{6},{7}}, to_apply=%region_add
  %tup = (s32[], f32[16,32]) tuple(s32[] %deg, f32[16,32] %param)
  %w = (s32[], f32[16,32]) while((s32[], f32[16,32]) %tup), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[16,32]{1,0} get-tuple-element((s32[], f32[16,32]) %w), index=1
}
"""


def test_parser_walks_synthetic_module():
    prog = HloProgram(_SYNTH_HLO)
    assert prog.num_partitions == 8
    assert prog.entry == "main_spmd"
    recs = walk_hlo_collectives(prog, "synth")
    by_name = {r.name: r for r in recs}
    # async pair deduped to the start; gather priced at group-sized
    # output (operand 16*32*4 = 2048 B x 8 participants)
    assert "agd" not in by_name
    start = by_name["ags"]
    assert start.opcode == "all-gather" and start.wire_bytes == 2048 * 8
    assert start.mult == 1 and not start.traced
    # while body collectives trip-weighted by known_trip_count
    ag = by_name["ag"]
    assert ag.mult == 5 and ag.traced and ag.counted
    assert ag.wire_bytes == 2048 * 8
    assert ag.source == "a.py:3"
    # explicit replica groups: 2 groups of 4
    ar = by_name["ar"]
    assert (ar.group_size, ar.n_groups) == (4, 2)
    assert ar.traced and ar.counted and ar.wire_bytes == 2048
    # degenerate single-participant groups move no wire
    deg = by_name["deg"]
    assert deg.degenerate and deg.wire_bytes == 0


# One sharded reduction (jnp.sum over the data-sharded axis of an
# f32[1024,128], 8 partitions), as two releases of the compiler print
# it: jax 0.4.x puts each operand's shape in the operand list and the
# source in the metadata; jax 0.9.0 prints operands by name only and a
# stack_frame_id that the module's header tables resolve.
_REDUCE_HLO_INLINE_SHAPES = """\
HloModule jit_g, is_scheduled=true, num_partitions=8

%region_0.0.clone (reduce_sum.6: f32[], reduce_sum.10: f32[]) -> f32[] {
  %reduce_sum.6 = f32[] parameter(0)
  %reduce_sum.10 = f32[] parameter(1)
  ROOT %reduce_sum.11 = f32[] add(f32[] %reduce_sum.6, f32[] %reduce_sum.10)
}

ENTRY %main.0_spmd (param: f32[128,128]) -> f32[128] {
  %param = f32[128,128]{1,0} parameter(0), sharding={devices=[8,1]<=[8]}
  %constant.2 = f32[] constant(0)
  %wrapped_reduce = f32[128]{0} fusion(f32[128,128]{1,0} %param, f32[] %constant.2), kind=kLoop, calls=%wrapped_reduce_computation
  ROOT %all-reduce = f32[128]{0} all-reduce(f32[128]{0} %wrapped_reduce), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, to_apply=%region_0.0.clone, metadata={op_name="jit(g)/reduce_sum" source_file="model.py" source_line=12}
}
"""

_REDUCE_HLO_OPERANDS_BY_NAME = """\
HloModule jit_g, is_scheduled=true, entry_computation_layout={(f32[128,128]{1,0})->f32[128]{0}}, num_partitions=8

FileNames
1 "model.py"

FunctionNames
1 "<module>"
2 "g"

FileLocations
1 {file_name_id=1 function_name_id=1 line=15 end_line=15 column=6 end_column=6}
2 {file_name_id=1 function_name_id=2 line=12 end_line=12 column=17 end_column=17}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

%region_0.0.clone (reduce_sum.6: f32[], reduce_sum.10: f32[]) -> f32[] {
  %reduce_sum.6 = f32[] parameter(0), metadata={op_name="jit(g)/reduce_sum"}
  %reduce_sum.10 = f32[] parameter(1), metadata={op_name="jit(g)/reduce_sum"}
  ROOT %reduce_sum.11 = f32[] add(%reduce_sum.6, %reduce_sum.10), metadata={op_name="jit(g)/reduce_sum" stack_frame_id=2}
}

ENTRY %main.0_spmd (param: f32[128,128]) -> f32[128] {
  %param = f32[128,128]{1,0} parameter(0), sharding={devices=[8,1]<=[8]}, metadata={op_name="x"}
  %constant.2 = f32[] constant(0)
  %wrapped_reduce = f32[128]{0} fusion(%param, %constant.2), kind=kLoop, calls=%wrapped_reduce_computation, metadata={op_name="jit(g)/reduce_sum" stack_frame_id=2}
  ROOT %all-reduce = f32[128]{0} all-reduce(%wrapped_reduce), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, to_apply=%region_0.0.clone, metadata={op_name="jit(g)/reduce_sum" stack_frame_id=2}
}
"""


@pytest.mark.parametrize("text", [
    pytest.param(_REDUCE_HLO_INLINE_SHAPES, id="operands_with_shapes"),
    pytest.param(_REDUCE_HLO_OPERANDS_BY_NAME, id="operands_by_name")])
def test_reader_prices_both_printed_forms_alike(text):
    """An operand printed as a bare %name is priced from the
    instruction that defines it, and a stack_frame_id is resolved to
    the file and line the older form printed in place."""
    (rec,) = walk_hlo_collectives(HloProgram(text), "g")
    assert rec.opcode == "all-reduce" and rec.name == "all-reduce"
    assert rec.wire_bytes == 128 * 4
    assert (rec.group_size, rec.n_groups) == (8, 1)
    assert rec.op_name == "jit(g)/reduce_sum"
    assert rec.source == "model.py:12"
    assert not rec.traced and not rec.degenerate


def test_parser_conditional_takes_worst_branch():
    text = """\
HloModule jit_c, num_partitions=4

%region_add (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

%true.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %ar = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={{0,1,2,3}}, to_apply=%region_add, metadata={op_name="jit(c)/psum"}
}

%false.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %ag = f32[32]{0} all-gather(f32[8]{0} %p), replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(c)/all_gather"}
  ROOT %sl = f32[8]{0} slice(f32[32]{0} %ag), slice={[0:8]}
}

ENTRY %main (pr: pred[], p: f32[8]) -> f32[8] {
  %pr = pred[] parameter(0)
  %p = f32[8]{0} parameter(1)
  ROOT %c = f32[8]{0} conditional(pred[] %pr, f32[8]{0} %p, f32[8]{0} %p), true_computation=%true.1, false_computation=%false.1
}
"""
    recs = walk_hlo_collectives(HloProgram(text), "cond")
    assert {r.opcode for r in recs} == {"all-reduce", "all-gather"}
    # the gather branch is the worst (operand 8 elems * 4 B, output-
    # priced x4 participants = 128 B vs the reduce's 32 B): only it is
    # charged into the totals; the other branch keeps its TRUE wire
    # (the reshard classifier must still see it) but charged=False
    by_op = {r.opcode: r for r in recs}
    assert by_op["all-gather"].wire_bytes == 8 * 4 * 4
    assert by_op["all-gather"].charged
    assert by_op["all-reduce"].wire_bytes == 8 * 4
    assert not by_op["all-reduce"].charged
    assert all(r.in_branch for r in recs)


def test_uncharged_branch_reshard_still_flags():
    """A compiler-inserted gather in the CHEAPER conditional branch
    must still produce a silent_reshard finding — only one branch
    executes per step, but both are real code that can run."""
    text = """\
HloModule jit_c, num_partitions=4

%region_add (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

%true.1 (p: f32[65536]) -> f32[65536] {
  %p = f32[65536]{0} parameter(0)
  ROOT %ar = f32[65536]{0} all-reduce(f32[65536]{0} %p), replica_groups={{0,1,2,3}}, to_apply=%region_add, metadata={op_name="jit(c)/psum"}
}

%false.1 (p: f32[65536]) -> f32[65536] {
  %p = f32[65536]{0} parameter(0)
  %sl0 = f32[4096]{0} slice(f32[65536]{0} %p), slice={[0:4096]}
  %ag = f32[16384]{0} all-gather(f32[4096]{0} %sl0), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %pd = f32[65536]{0} pad(f32[16384]{0} %ag, f32[] %p), padding=0_49152
}

ENTRY %main (pr: pred[], p: f32[65536]) -> f32[65536] {
  %pr = pred[] parameter(0)
  %p = f32[65536]{0} parameter(1)
  ROOT %c = f32[65536]{0} conditional(pred[] %pr, f32[65536]{0} %p, f32[65536]{0} %p), true_computation=%true.1, false_computation=%false.1
}
"""
    target = AuditTarget("cond", jax.make_jaxpr(lambda x: x + 1)(1.0),
                         lower=lambda: text)
    # traced psum in the worst branch is charged; the inserted gather
    # in the cheaper branch is uncharged but still classified
    cfg = _cfg(spmd_reshard_min_mb=0.0001, require_spmd_match=True)
    audit, findings = audit_target_hlo(target, cfg, jaxpr_wire_bytes=0)
    reshards = [f for f in findings if f.rule == RULE_SILENT_RESHARD]
    assert reshards and reshards[0].severity == "error"
    assert audit.n_silent_reshards == 1
    # ...without contaminating the charged byte totals: only the worst
    # branch's traced psum (65536 f32 operand) is charged
    assert audit.reshard_bytes == 0
    assert audit.matched_wire_bytes == 65536 * 4
    assert audit.hlo_wire_bytes == 65536 * 4


def test_unverified_targets_do_not_skew_divergence():
    """An errored/skipped target's jaxpr wire must not drag the summary
    divergence ratio below 1 — unverified is its own state, not
    'XLA optimized the wire away'."""
    from deepspeed_tpu.analysis import summarize_hlo
    from deepspeed_tpu.analysis.hlo_audit import HloTargetAudit
    ok = HloTargetAudit(target="good", jaxpr_wire_bytes=1000,
                        matched_wire_bytes=1000)
    bad = HloTargetAudit(target="doomed", jaxpr_wire_bytes=1000,
                         error="XlaRuntimeError: UNIMPLEMENTED")
    payload = summarize_hlo([(ok, 1), (bad, 1)])
    assert payload["divergence_ratio"] == 1.0
    assert payload["n_unverified_targets"] == 1
    assert payload["targets"]["doomed"]["verified"] is False
    assert payload["targets"]["doomed"]["divergence_ratio"] is None
    assert bad.divergence_ratio is None


def test_compile_failure_escalates_under_require_spmd_match():
    """The gate posture must FAIL when a target cannot be
    cross-checked, not pass with the audit silently disabled."""
    def boom():
        raise RuntimeError("UNIMPLEMENTED: PartitionId")
    target = AuditTarget("doomed", jax.make_jaxpr(lambda x: x + 1)(1.0),
                         lower=boom)
    _audit, findings = audit_target_hlo(
        target, _cfg(require_spmd_match=True), 0)
    assert findings and findings[0].severity == "error"
    # a wire-carrying target with NO lowering hook is equally unverified
    hookless = AuditTarget("bare", jax.make_jaxpr(lambda x: x + 1)(1.0))
    audit2, findings2 = audit_target_hlo(
        hookless, _cfg(require_spmd_match=True), 4096)
    assert audit2.skipped and findings2
    assert "no lowering hook" in findings2[0].message
    # ...but fixture targets under the default posture stay silent
    _a, none = audit_target_hlo(hookless, _cfg(), 4096)
    assert none == []


# --------------------------------------------------------------------- #
# real-XLA fixtures: silent reshards provoked and caught
# --------------------------------------------------------------------- #
def test_misannotated_out_sharding_flags_silent_reshard():
    """The ISSUE 14 acceptance fixture: a pjit out_sharding demanding
    replication of data-sharded compute makes GSPMD insert an
    all-gather AFTER tracing — the jaxpr sees zero collectives, the
    compiled program moves the whole tensor.  warning by default,
    error-severity under require_spmd_match."""
    mesh = _mesh()
    sh = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    def f(x, w):
        return x @ w

    xs = jax.ShapeDtypeStruct((64, 128), jnp.float32, sharding=sh)
    ws = jax.ShapeDtypeStruct((128, 256), jnp.float32, sharding=rep)
    target = _target(f, xs, ws, jit_kw={"out_shardings": rep})
    # the jaxpr-level story is empty — that is the blind spot
    assert step_wire_bytes(target.closed_jaxpr)[0] == 0

    cfg = _cfg(spmd_reshard_min_mb=0.001)
    audit, findings = audit_target_hlo(target, cfg, jaxpr_wire_bytes=0)
    reshards = [f for f in findings if f.rule == RULE_SILENT_RESHARD]
    assert reshards, [f.format() for f in findings]
    assert all(f.severity == "warning" for f in reshards)
    assert audit.n_silent_reshards > 0 and audit.reshard_bytes > 0
    assert "all-gather" in reshards[0].message
    assert "jaxpr-level wire accounting never saw" in reshards[0].message

    # escalation: the CI posture
    cfg_err = _cfg(spmd_reshard_min_mb=0.001, require_spmd_match=True)
    _audit, findings_err = audit_target_hlo(target, cfg_err,
                                            jaxpr_wire_bytes=0)
    assert any(f.rule == RULE_SILENT_RESHARD and f.severity == "error"
               for f in findings_err)
    with pytest.raises(ProgramAuditError):
        from deepspeed_tpu.analysis import enforce, AuditReport
        enforce(AuditReport(findings=findings_err), "error")


def test_layout_flip_on_replicated_weight_matmul_flags_reshard():
    """Second fixture class: a replicated-weight matmul whose output
    annotation disagrees with the layout the math produces (row-sharded
    activations in, column-sharded output demanded) — GSPMD inserts a
    layout-flip transport (all-to-all / collective-permute /
    all-gather) the jaxpr never traced.  Every finding names a cause:
    the inserted op's own metadata, or the sharding-boundary wording."""
    mesh = _mesh()
    rows = NamedSharding(mesh, P("data", None))
    cols = NamedSharding(mesh, P(None, "data"))
    rep = NamedSharding(mesh, P())

    def f(x, w):
        return jnp.tanh(x) @ w

    xs = jax.ShapeDtypeStruct((64, 512), jnp.float32, sharding=rows)
    ws = jax.ShapeDtypeStruct((512, 512), jnp.float32, sharding=rep)
    target = _target(f, xs, ws, jit_kw={"out_shardings": cols})
    cfg = _cfg(spmd_reshard_min_mb=0.0001)
    audit, findings = audit_target_hlo(target, cfg, jaxpr_wire_bytes=0)
    reshards = [f for f in findings if f.rule == RULE_SILENT_RESHARD]
    assert reshards and audit.reshard_bytes > 0, \
        [(r.opcode, r.wire_bytes, r.op_name) for r in audit.collectives]
    # provenance: either the causing op's name or the sharding-boundary
    # wording — never a bare unexplained hit
    assert any(("inserted for" in f.message)
               or ("sharding boundary" in f.message) for f in reshards)


def test_named_waiver_absorbs_expected_resharding():
    """A declared sharding-contract waiver (the ZeRO param re-gather
    path) absorbs inserted gathers up to its byte budget — and is
    reported by name so tests can pin WHY the config is clean."""
    mesh = _mesh()
    sh = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    def f(x):
        return x * 2.0

    xs = jax.ShapeDtypeStruct((64, 128), jnp.float32, sharding=sh)
    budget = 64 * 128 * 4 * 2
    target = _target(f, xs, jit_kw={"out_shardings": rep},
                     spmd_waivers=(SpmdWaiver("declared_regather",
                                              budget),))
    cfg = _cfg(spmd_reshard_min_mb=0.0, require_spmd_match=True)
    audit, findings = audit_target_hlo(target, cfg, jaxpr_wire_bytes=0)
    assert not [f for f in findings if f.rule == RULE_SILENT_RESHARD]
    assert audit.n_silent_reshards == 0
    assert audit.waived_reshard_bytes > 0
    assert audit.waivers and audit.waivers[0]["name"] == "declared_regather"
    assert audit.waivers[0]["absorbed_bytes"] == audit.waived_reshard_bytes


def test_traced_collectives_reconcile_at_ratio_one():
    """Clean program: explicit shard_map collectives inside a scan —
    the jaxpr wire accounting and the compiled program agree exactly
    (trip counts included), so no divergence finding fires."""
    mesh = _mesh()
    sh = NamedSharding(mesh, P("data"))

    def region(x):
        g = jax.lax.all_gather(x, "data", tiled=True)
        return (x + g.sum(axis=0, keepdims=True)[:x.shape[0]]) * 0.5

    def f(x):
        def body(c, _):
            r = shard_map(region, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_vma=False)(c)
            return r, None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    xs = jax.ShapeDtypeStruct((64, 128), jnp.float32, sharding=sh)
    target = _target(f, xs)
    jaxpr_wire, _ = step_wire_bytes(target.closed_jaxpr)
    assert jaxpr_wire > 0
    cfg = _cfg(require_spmd_match=True)
    audit, findings = audit_target_hlo(target, cfg,
                                       jaxpr_wire_bytes=jaxpr_wire)
    assert findings == [], [f.format() for f in findings]
    assert audit.matched_wire_bytes == jaxpr_wire
    assert audit.divergence_ratio == pytest.approx(1.0)
    # the scan survived as a while loop: trip weighting engaged
    assert any(r.mult == 5 for r in audit.collectives)


def test_divergence_finding_names_direction():
    """A target whose jaxpr claims wire the compiled program does not
    move trips the divergence rule and names the overprediction."""
    mesh = _mesh()
    sh = NamedSharding(mesh, P("data"))

    def f(x):
        return x + 1.0

    xs = jax.ShapeDtypeStruct((64, 128), jnp.float32, sharding=sh)
    target = _target(f, xs)
    cfg = _cfg()
    audit, findings = audit_target_hlo(
        target, cfg, jaxpr_wire_bytes=10_000_000)
    div = [f for f in findings if f.rule == RULE_SPMD_DIVERGENCE]
    assert div and "OVERPREDICTION" in div[0].message
    assert audit.divergence_ratio == 0.0


def test_compile_failure_is_surfaced_not_fatal():
    """XLA refusing a program (the PartitionId seed-xfail class) must
    produce a warning finding naming the failure, never crash."""
    def boom():
        raise RuntimeError("UNIMPLEMENTED: PartitionId instruction is "
                           "not supported for SPMD partitioning")
    target = AuditTarget("doomed", jax.make_jaxpr(lambda x: x + 1)(1.0),
                         lower=boom)
    audit, findings = audit_target_hlo(target, _cfg(), 0)
    assert "PartitionId" in audit.error
    assert len(findings) == 1
    assert findings[0].severity == "warning"
    assert "UNVERIFIED" in findings[0].message


def test_hlo_only_wire_prices_into_exposed_lane():
    """The undercount fix: HLO-only wire raises the step-time lower
    bound through the exposed-comm lane."""
    from deepspeed_tpu.analysis import build_step_time_model
    cfg = _cfg()
    base = build_step_time_model(10 ** 9, 10 ** 6, [], cfg)
    with_hlo = build_step_time_model(10 ** 9, 10 ** 6, [], cfg,
                                     hlo_only_wire_bytes=10 ** 8)
    assert with_hlo["wire_bytes_hlo_only"] == 10 ** 8
    extra = 10 ** 8 / (cfg.hw_ici_gbps * 1e9)
    assert with_hlo["predicted_step_time_lb_s"] == pytest.approx(
        base["predicted_step_time_lb_s"] + extra)
    assert with_hlo["t_comm_exposed_s"] > base["t_comm_exposed_s"]


# --------------------------------------------------------------------- #
# engine-level: the audited programs the engine actually dispatches
# --------------------------------------------------------------------- #
def _tiny_engine(config_overrides=None):
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    raw = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
        "zero_optimization": {"stage": 2},
        "analysis": {"mode": "off"},
        "steps_per_print": 10 ** 9,
    }
    raw.update(config_overrides or {})
    cfg = GPT2Config(hidden_size=64, num_layers=2, num_heads=4,
                     n_positions=64, vocab_size=256)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = ds.initialize(model=model, config=raw,
                                    model_parameters=params)
    return engine


def test_engine_hlo_audit_clean_and_priced():
    """A clean stage-2 engine cross-checks with zero silent reshards;
    the compiled program's GSPMD wire (DP grad combine + ZeRO param
    re-gather) is surfaced and priced into the exposed lane, raising
    the lower bound vs the jaxpr-only model."""
    from deepspeed_tpu.analysis import audit_engine
    engine = _tiny_engine()
    cfg = _cfg(require_spmd_match=True)
    without = audit_engine(engine, cfg=cfg, multihost=False, hlo=False)
    report = audit_engine(engine, cfg=cfg, multihost=False, hlo=True)
    assert report.hlo["n_silent_reshards"] == 0
    assert not [f for f in report.findings
                if f.rule in (RULE_SILENT_RESHARD, RULE_SPMD_DIVERGENCE)]
    assert report.hlo_wire_bytes_per_step > 0
    assert report.hlo_collective_count > 0
    # stage-2: no explicit collectives traced, everything is HLO-only
    assert report.wire_bytes_per_step == 0
    assert report.hlo_divergence_ratio == 1.0
    assert (report.step_time["wire_bytes_hlo_only"]
            == report.hlo["hlo_only_wire_bytes_per_step"] > 0)
    assert (report.predicted_step_time_lb_s
            > without.predicted_step_time_lb_s)
    # the ZeRO param re-gather is absorbed by its NAMED waiver
    apply_audit = report.hlo["targets"]["apply_step"]
    assert any(w["name"] == "zero_param_regather"
               for w in apply_audit["waivers"])


def test_engine_init_runs_hlo_audit_from_config():
    """analysis.hlo_audit in the engine config runs the cross-check at
    init (the same surface CI's error mode gates)."""
    engine = _tiny_engine({"analysis": {
        "mode": "warn", "hlo_audit": True, "require_spmd_match": True}})
    assert engine.program_audit is not None
    assert engine.program_audit.hlo, "init audit must carry hlo payload"
    assert engine.program_audit.hlo["n_silent_reshards"] == 0


# --------------------------------------------------------------------- #
# cross-accounting regression (ISSUE 14 satellite): every example
# config's jaxpr wire within a tolerance band of the HLO-measured
# bytes — or carrying a NAMED, asserted waiver.  Future transports
# cannot silently fork the two accountings.
# --------------------------------------------------------------------- #
# config name -> (ratio_band, reason).  A waived config must land
# INSIDE its band — the waiver is itself an assertion, not an opt-out.
WIRE_WAIVERS = {
    # XLA unrolls the 2-group streamed layer scan on this tiny trace
    # model and CSEs the carried reverse-scan re-gathers; replicated
    # psums strength-reduce to multiplies.  The compiled program moves
    # LESS traced wire than the jaxpr predicts — overprediction, never
    # under.
    "gpt2_zero3_stream_analysis.json": ((0.55, 1.0), "xla_cse_regathers"),
    "gpt2_zero3_stream_fcm.json": ((0.55, 1.0), "xla_cse_regathers"),
}
WIRE_TOLERANCE = 0.05


@pytest.mark.slow
def test_examples_jaxpr_vs_hlo_wire_within_band(capsys):
    """Error-mode gate with the HLO cross-check enabled over every
    example config (the in-process twin of tier1.yml's workflow step),
    plus the wire-accounting band: zero unexplained divergence."""
    from deepspeed_tpu.analysis.cli import main as cli_main
    examples = sorted(EXAMPLES.glob("*.json"))
    assert (EXAMPLES / "gpt2_hlo_audit.json") in examples
    golden = json.loads(GOLDEN_HLO.read_text())
    for cfg_path in examples:
        ds.reset_mesh_context()
        rc = cli_main(["--config", str(cfg_path), "--mode", "error",
                       "--hlo-audit", "--json"])
        stdout = capsys.readouterr().out
        assert rc == 0, (f"{cfg_path.name} failed the error-mode "
                         f"HLO-audit gate:\n{stdout}")
        payload = json.loads(stdout[stdout.index("{\n"):])
        # a 1-bit-tier config is TWO audited programs (warmup +
        # compressed, cli.py); the wire band gates each phase
        phases = ([payload["phase_warmup"], payload["phase_compressed"]]
                  if "phase_warmup" in payload else [payload])
        for ph in phases:
            hlo = ph["hlo"]
            # zero UNEXPLAINED divergence: no silent reshards anywhere
            assert hlo["n_silent_reshards"] == 0, (cfg_path.name, hlo)
            assert hlo["reshard_bytes_per_step"] == 0
            ratio = hlo["divergence_ratio"]
            waiver = WIRE_WAIVERS.get(cfg_path.name)
            if waiver is not None:
                (lo, hi), reason = waiver
                assert lo <= ratio <= hi, (
                    f"{cfg_path.name} waived as {reason!r} but ratio "
                    f"{ratio} left its asserted band [{lo}, {hi}]")
            else:
                assert abs(ratio - 1.0) <= WIRE_TOLERANCE, (
                    f"{cfg_path.name}: jaxpr and HLO wire accountings "
                    f"forked (ratio {ratio}) with no named waiver")
        if cfg_path.name == "gpt2_hlo_audit.json":
            # the golden pins the clean compiled wire story exactly
            assert payload["signature"] == golden["signature"]
            assert (hlo["hlo_wire_bytes_per_step"]
                    == golden["hlo_wire_bytes_per_step"])
            assert (hlo["hlo_collective_count"]
                    == golden["hlo_collective_count"])
            assert golden["n_silent_reshards"] == 0
            assert golden["divergence_ratio"] == 1.0


def test_config_validation():
    from deepspeed_tpu.config import DeepSpeedConfigError
    cfg = _cfg(hlo_audit=True, require_spmd_match=True,
               spmd_reshard_min_mb=0.5, spmd_match_tolerance=0.1)
    assert cfg.hlo_audit and cfg.require_spmd_match
    assert cfg.spmd_reshard_min_mb == 0.5
    assert cfg.spmd_match_tolerance == 0.1
    with pytest.raises(DeepSpeedConfigError):
        _cfg(spmd_reshard_min_mb=-1)
    with pytest.raises(DeepSpeedConfigError):
        _cfg(spmd_match_tolerance=-0.1)
