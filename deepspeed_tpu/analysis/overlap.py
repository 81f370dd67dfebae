"""Overlap analysis — is each collective hidden under compute, or is it
serialized on the critical path?

T3 (arXiv:2401.16677) shows compute/collective overlap is a property of
the program graph, not of the runtime: a collective whose first consumer
follows immediately has nothing to hide behind, no matter how clever the
scheduler, while a collective whose result is carried to the next scan
iteration (the double-buffered prefetch shape, ROADMAP item 1) has the
whole iteration's compute as slack.  Both facts are readable off the
traced jaxpr, so the streamed-ZeRO-3 prefetch can be *verified
statically* and gated in CI before it ever touches hardware.

For every explicit collective (the same wire-moving surface the comm
budget accounts) this module computes:

  distance         equations between issue and first consume at the
                   collective's nesting level (transparent shape-only
                   ops and payload-preserving elementwise epilogues —
                   a quantized gather's dequant — extend the wire, they
                   don't consume it)
  slack_flops      flop-weighted independent work inside that window —
                   everything between issue and first consume is
                   provably independent of the collective's result.
                   A carried collective's window is the FULL iteration
                   (its result is consumed next time around), so its
                   slack is bounded below by one body's flops
  carried          the result escapes the enclosing body (scan carry /
                   region output) instead of being consumed in-body:
                   the double-buffer property, verified
  fused            the collective is a per-tile transport of a fused
                   collective-matmul (ops/collective_matmul.py, traced
                   under the ``constants.FCM_SCOPE`` name scope): the
                   wire is interleaved tile-by-tile with the producer/
                   consumer GEMM by construction, so it is hidden as a
                   STATIC property — the carried-like classification T3
                   fusion earns, gateable via ``require_overlap``
  hidden_fraction  min(1, slack_time / wire_time) under the configured
                   hardware model — how much of the wire the scheduler
                   CAN hide, which upper-bounds what it will

A collective inside a scan/while body whose hidden fraction falls below
``analysis.overlap_min_hidden_fraction`` is serialized on the hot loop's
critical path — a warning finding (error with
``analysis.require_overlap``, the CI posture once prefetch lands).
Top-level collectives are recorded (they feed ``overlap_efficiency`` and
the step-time model) but not flagged: the dispatch boundary serializes
them anyway.
"""

from dataclasses import asdict, dataclass
from typing import Any, Dict, List

from .. import constants as C
from .findings import Finding, RULE_OVERLAP
from .jaxpr_walk import (as_jaxpr, aval_bytes, eqn_scope,
                         scope_has_component, sub_jaxprs)
from .rules import _WIRE_GATHER_PRIMS, _WIRE_REDUCE_PRIMS

_WIRE_PRIMS = _WIRE_GATHER_PRIMS + _WIRE_REDUCE_PRIMS

# ppermute is deliberately NOT a generic wire-mover (ring attention uses
# it for lockstep-relevant but overlap-managed hops; see rules.py) —
# EXCEPT inside the fused-collective-matmul scope, where the per-tile
# ring permutes ARE the qwZ/qgZ payload movers and must be priced
_FCM_TRANSPORT_PRIMS = ("ppermute",)

# shape-only ops a collective result flows through unchanged — following
# the dtype-hazard rule's provenance convention, plus the convert a
# quantized gather's dequant epilogue emits and the `name` tag
# checkpoint_name wraps the streamed gathers in
_TRANSPARENT_PRIMS = ("reshape", "transpose", "broadcast_in_dim",
                      "squeeze", "rev", "slice", "copy",
                      "convert_element_type", "name")

# payload-preserving elementwise ops: when the output keeps the tracked
# operand's shape, the wire flows THROUGH (a quantized gather's dequant
# `payload * scales`, a bias add) rather than being consumed — the
# compute the collective is actually waiting for is the contraction /
# loop boundary further on.  Shape equality is the gate: a reduction or
# contraction changes shape and still counts as the first consumer.
_ELEMENTWISE_FLOWTHROUGH = ("mul", "add", "sub", "div", "max", "min")


def _flows_through(eqn, tracked: set) -> bool:
    name = eqn.primitive.name
    if name in _TRANSPARENT_PRIMS:
        return True
    if name not in _ELEMENTWISE_FLOWTHROUGH or len(eqn.outvars) != 1:
        return False
    out_aval = getattr(eqn.outvars[0], "aval", None)
    if out_aval is None or not hasattr(out_aval, "shape"):
        return False
    for v in eqn.invars:
        if id(v) in tracked:
            aval = getattr(v, "aval", None)
            if (aval is not None and hasattr(aval, "shape")
                    and tuple(aval.shape) == tuple(out_aval.shape)):
                return True
    return False


@dataclass
class CollectiveOverlap:
    """One collective equation's schedule facts."""
    prim: str
    target: str             # traced program ("grad_step", ...)
    scope: str              # name-stack provenance
    loop_depth: int         # enclosing scan/while bodies (0 = top level)
    mult: int               # static trip-count multiplier
    wire_bytes: int         # one issue's wire (gather: out, reduce: in)
    distance_eqns: int      # eqns between issue and first consume
    slack_flops: int        # independent flops inside the window
    carried: bool           # escapes the body (double-buffered prefetch)
    wire_time_s: float
    hidden_fraction: float  # min(1, slack_time / wire_time)
    serialized: bool        # on the critical path (per configured floor)
    fused: bool = False     # per-tile fused collective-matmul transport


def _eqn_wire_bytes(eqn) -> int:
    name = eqn.primitive.name
    if name in _WIRE_GATHER_PRIMS:
        return sum(aval_bytes(v) for v in eqn.outvars)
    return sum(aval_bytes(v) for v in eqn.invars)


class _Chase:
    """One collective result being chased toward its first consumer —
    possibly across call-kind sub-jaxpr boundaries (a custom_vjp gather's
    own jaxpr ends AT the gather; consumption happens in the caller)."""

    __slots__ = ("rec", "tracked")

    def __init__(self, rec: CollectiveOverlap, tracked: set):
        self.rec = rec
        self.tracked = tracked


def _finalize(rec: CollectiveOverlap, cfg, carried: bool) -> None:
    peak_flops_s = cfg.hw_peak_tflops * 1e12
    wire_time = (rec.wire_bytes / (cfg.hw_ici_gbps * 1e9)
                 if cfg.hw_ici_gbps > 0 else 0.0)
    slack_time = (rec.slack_flops / peak_flops_s
                  if peak_flops_s > 0 else 0.0)
    rec.carried = carried
    rec.wire_time_s = wire_time
    rec.hidden_fraction = (1.0 if wire_time <= 0.0
                           else min(1.0, slack_time / wire_time))
    # a carried result is consumed next iteration, under this
    # iteration's remaining compute — the double-buffer property
    rec.serialized = ((not carried) and
                      rec.hidden_fraction < cfg.overlap_min_hidden_fraction)


def _finalize_fused(rec: CollectiveOverlap, cfg) -> None:
    """A fused transport's hiddenness is structural (per-tile under the
    GEMM), not slack-derived: full hidden fraction, never serialized.
    The wire time still feeds the cost model's hidden-comm lane."""
    rec.wire_time_s = (rec.wire_bytes / (cfg.hw_ici_gbps * 1e9)
                       if cfg.hw_ici_gbps > 0 else 0.0)
    rec.hidden_fraction = 1.0
    rec.serialized = False


def _analyze(jaxpr, cfg, target_label, _scope, _mult, _loop_depth):
    """Walk one jaxpr level.  Returns (records, escaped) where escaped
    chases reached this jaxpr's outvars unconsumed, as
    (chase, outvar_positions) pairs for the caller to continue."""
    from ..profiling.flops_profiler import eqn_flops
    jx = as_jaxpr(jaxpr)
    records: List[CollectiveOverlap] = []
    eqns = list(jx.eqns)
    active: List[_Chase] = []

    for i, eqn in enumerate(eqns):
        scope = eqn_scope(eqn, _scope)
        started_here: List[_Chase] = []
        for sub in sub_jaxprs(eqn):
            is_loop = sub.kind in ("scan", "while_body", "while_cond")
            sub_records, sub_escaped = _analyze(
                sub.jaxpr, cfg, target_label, scope,
                _mult * (sub.trip_count or 1),
                _loop_depth + (1 if is_loop else 0))
            records.extend(sub_records)
            outs = list(eqn.outvars)
            sub_outs = list(as_jaxpr(sub.jaxpr).outvars)
            body_flops = None  # one body iteration, computed lazily
            for chase, positions in sub_escaped:
                if is_loop:
                    # escaping a scan/while body = the result rides the
                    # carry into the next iteration: double-buffered.
                    # The schedule window of a carried collective is the
                    # FULL iteration — everything the wire does not feed
                    # (it feeds nothing in-body, it escaped) can hide it,
                    # regardless of where partial eval placed the issue
                    # in the body's eqn order — so the slack is bounded
                    # below by one body's flops.
                    if body_flops is None:
                        from ..profiling.flops_profiler import (
                            count_jaxpr_flops)
                        body_flops = count_jaxpr_flops(sub.jaxpr)
                    chase.rec.slack_flops = max(chase.rec.slack_flops,
                                                body_flops)
                    _finalize(chase.rec, cfg, carried=True)
                elif len(outs) == len(sub_outs):
                    # call-kind boundary (pjit/remat/custom_vjp/
                    # shard_map/branch): 1:1 outvar mapping — keep
                    # chasing in this frame from the call site on
                    chase.tracked = {id(outs[p]) for p in positions
                                     if p < len(outs)}
                    started_here.append(chase)
                else:
                    # unknown outvar mapping: classify with the slack
                    # accumulated so far
                    _finalize(chase.rec, cfg, carried=False)
        # consumption checks against everything issued BEFORE this eqn
        still_active: List[_Chase] = []
        flops = None  # computed once per eqn, shared across chases
        for chase in active:
            touches = any(id(v) in chase.tracked for v in eqn.invars)
            if touches and _flows_through(eqn, chase.tracked):
                chase.tracked.update(id(v) for v in eqn.outvars)
                still_active.append(chase)
            elif touches:
                _finalize(chase.rec, cfg, carried=False)
            else:
                # per-issue slack: eqn_flops already trip-weights its
                # own inner scans, which repeat per issue — the
                # enclosing mult does not (it repeats the ISSUE too)
                if flops is None:
                    flops = eqn_flops(eqn)
                chase.rec.distance_eqns += 1
                chase.rec.slack_flops += flops
                still_active.append(chase)
        active = still_active + started_here
        prim = eqn.primitive.name
        in_fcm = scope_has_component(scope, C.FCM_SCOPE)
        if in_fcm and (prim in _WIRE_PRIMS
                       or prim in _FCM_TRANSPORT_PRIMS):
            # fused collective-matmul transport: the tile's wire is
            # interleaved with the producer/consumer GEMM by
            # construction (the op traces it per tile), so it is hidden
            # as a static property — no chase; classified like carried
            rec = CollectiveOverlap(
                prim=prim, target=target_label,
                scope=scope, loop_depth=_loop_depth, mult=_mult,
                wire_bytes=_eqn_wire_bytes(eqn), distance_eqns=0,
                slack_flops=0, carried=False, wire_time_s=0.0,
                hidden_fraction=0.0, serialized=False, fused=True)
            _finalize_fused(rec, cfg)
            records.append(rec)
        elif prim in _WIRE_PRIMS:
            rec = CollectiveOverlap(
                prim=prim, target=target_label,
                scope=scope, loop_depth=_loop_depth, mult=_mult,
                wire_bytes=_eqn_wire_bytes(eqn), distance_eqns=0,
                slack_flops=0, carried=False, wire_time_s=0.0,
                hidden_fraction=0.0, serialized=False)
            records.append(rec)
            active.append(_Chase(rec, {id(v) for v in eqn.outvars}))

    outvar_pos = {}
    for p, v in enumerate(jx.outvars):
        outvar_pos.setdefault(id(v), []).append(p)
    escaped = []
    for chase in active:
        positions = [p for vid in chase.tracked
                     for p in outvar_pos.get(vid, [])]
        if positions:
            escaped.append((chase, positions))
        else:
            # result is dead at this level (dce leftovers): classify
            # with the slack accumulated
            _finalize(chase.rec, cfg, carried=False)
    return records, escaped


def analyze_overlap(jaxpr, cfg, target_label: str = ""
                    ) -> List[CollectiveOverlap]:
    """Walk a traced program and classify every wire-moving collective."""
    records, escaped = _analyze(jaxpr, cfg, target_label, "", 1, 0)
    for chase, _positions in escaped:
        # reached the program outputs: the dispatch boundary is the
        # consumer; everything after issue was slack
        _finalize(chase.rec, cfg, carried=False)
    return records


def overlap_efficiency(records: List[CollectiveOverlap]) -> float:
    """Bytes-weighted hidden fraction across every collective issue
    (trip counts multiplied in).  1.0 when no explicit collectives —
    there is nothing to serialize."""
    total = sum(r.wire_bytes * r.mult for r in records)
    if total <= 0:
        return 1.0
    hidden = sum(r.wire_bytes * r.mult * r.hidden_fraction
                 for r in records)
    return hidden / total


def summarize_overlap(records: List[CollectiveOverlap]) -> Dict[str, Any]:
    """Report payload: aggregate counts + the per-collective records."""
    return {
        "n_collectives": len(records),
        "n_serialized_hot_loop": sum(
            1 for r in records if r.serialized and r.loop_depth > 0),
        "n_serialized_top_level": sum(
            1 for r in records if r.serialized and r.loop_depth == 0),
        "n_carried": sum(1 for r in records if r.carried),
        "n_fused": sum(1 for r in records if r.fused),
        "records": [asdict(r) for r in records],
    }


def overlap_rule_findings(records: List[CollectiveOverlap], cfg,
                          scan_info: Dict[str, Any] = None
                          ) -> List[Finding]:
    """One finding per serialized collective inside a hot-loop body,
    plus a warning when the streamed-ZeRO-3 plan FORFEITED a requested
    prefetch (the fallback would otherwise be silent).

    With prefetch (``stage3_prefetch_bucket_size`` covering a layer
    group, the default) the streamed layer scan issues group i+1's
    gather into the scan carry under group i's compute — in both
    directions — so its hot-loop gathers classify as ``carried`` and
    this rule stays silent; the serialized shape survives where the
    groups are gathered at use and is what ``require_overlap`` gates in
    CI."""
    out: List[Finding] = []
    severity = "error" if cfg.require_overlap else "warning"
    plan = (scan_info or {}).get("zero3_streaming")
    hot_gathers = any(r.loop_depth > 0 and r.prim in _WIRE_GATHER_PRIMS
                      for r in records)
    if plan is not None and plan.get("forfeited") and hot_gathers:
        out.append(Finding(
            rule=RULE_OVERLAP, severity="warning",
            message=("streamed ZeRO-3 prefetch was FORFEITED: "
                     f"{plan['forfeited']} — the layer gathers run "
                     "serialized at use"),
            target=next(r.target for r in records
                        if r.loop_depth > 0
                        and r.prim in _WIRE_GATHER_PRIMS),
            # the forfeit reason itself names the failed constraint; the
            # hint covers the budget levers
            fix_hint=("raise stage3_max_live_parameters / "
                      "stage3_prefetch_bucket_size until a double-buffer "
                      "budget fits — the finding names the constraint "
                      "that failed")))
    for r in records:
        if not (r.serialized and r.loop_depth > 0):
            continue
        plan_note = ""
        if plan is not None and r.prim in _WIRE_GATHER_PRIMS:
            plan_note = (f" (streamed ZeRO-3 plan: groups of "
                         f"{plan['layers_per_step']}, "
                         f"prefetch={plan['prefetch']})")
        out.append(Finding(
            rule=RULE_OVERLAP, severity=severity,
            message=(f"collective `{r.prim}` ({r.wire_bytes} B x{r.mult}) "
                     "is serialized on a hot-loop critical path: first "
                     f"consumer is {r.distance_eqns} eqn(s) away with "
                     f"{r.slack_flops} independent flops — only "
                     f"{r.hidden_fraction * 100:.0f}% of its "
                     f"{r.wire_time_s * 1e6:.1f} us wire time can hide"
                     + plan_note),
            target=r.target, scope=r.scope,
            fix_hint=("issue the gather for iteration i+1 under "
                      "iteration i's compute (a stage3_prefetch_bucket_"
                      "size that covers a layer group: the double-"
                      "buffered carry prefetch), or "
                      "shrink the wire (qwZ/hpZ) until the slack "
                      "covers it")))
    return out
