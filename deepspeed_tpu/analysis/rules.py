"""Lint rules over traced train-step programs.

Each rule is ``rule(target, cfg) -> list[Finding]`` over an
``AuditTarget`` (a closed jaxpr plus argument metadata); the registry at
the bottom is what the auditor iterates.  Rules are *static* — they read
program structure, never execute it.  The sixth rule (recompile guard) is
a runtime counter and lives in analysis/recompile.py.
"""

from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

from .. import constants as C
from .findings import (Finding, RULE_COMM_BUDGET, RULE_DONATION,
                       RULE_DTYPE_HAZARD, RULE_HOST_SYNC, RULE_LOCKSTEP)
from .jaxpr_walk import aval_bytes, as_jaxpr, iter_eqns
from .signature import (GATHER_PRIMS, REDUCE_PRIMS, first_divergence,
                        lockstep_signature)


@dataclass
class ArgInfo:
    """One top-level argument of a traced program (a whole pytree
    subtree, e.g. "params"), with the facts the donation rule needs."""
    label: str
    nbytes: int
    donated: bool
    # consumed = the program produces a replacement output for it (the
    # old buffer is dead after the step) — the donation candidates
    consumed: bool


@dataclass
class AuditTarget:
    """One traced program under audit."""
    label: str                      # "grad_step" | "apply_step" | ...
    closed_jaxpr: Any
    args: List[ArgInfo] = field(default_factory=list)
    # per-flattened-invar donation flags + labels (the liveness
    # estimator's aliasing facts); None = conservative all-False
    donated_invars: Optional[List[bool]] = None
    invar_labels: Optional[List[str]] = None
    # engine state resident during this program but not among its args
    # (the modular grad program runs while opt_state sits in HBM)
    resident_extra_bytes: int = 0
    # scan-structure provenance the engine records at build time (the
    # streamed-ZeRO-3 plan) — named in overlap findings
    scan_info: dict = field(default_factory=dict)
    # HLO-level SPMD audit hooks (analysis/hlo_audit.py).  ``lower`` is
    # a zero-arg thunk returning the OPTIMIZED post-SPMD HLO text of
    # the program as the engine actually dispatches it (compile-only,
    # never executed); None = the cross-check skips this target.
    # ``spmd_waivers`` are (name, byte_budget, opcodes) expectations
    # for compiler-inserted gather-family wire the sharding contract
    # predicts (ZeRO's param re-gather in the apply program).
    lower: Optional[Any] = None
    spmd_waivers: Tuple = ()


# --------------------------------------------------------------------- #
# rule 1: host-sync lint
# --------------------------------------------------------------------- #
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "debug_print", "callback")
# primitives that force a device->host transfer when they appear inside
# a compiled program with a host destination
_TRANSFER_PRIMS = ("device_put",)


def host_sync_rule(target: AuditTarget, cfg) -> List[Finding]:
    """Host callbacks / transfers inside the step program.  Inside a
    scan/while body they fence every iteration of the hot loop (error);
    at the top level they still sync the step's dispatch (warning)."""
    out = []
    for ctx in iter_eqns(target.closed_jaxpr):
        name = ctx.eqn.primitive.name
        if name in _CALLBACK_PRIMS:
            in_hot_loop = ctx.loop_depth > 0
            sev = "error" if in_hot_loop else "warning"
            where = ("inside a scan/while body — it fences EVERY "
                     "iteration" if in_hot_loop
                     else "in the step program — it fences the dispatch")
            out.append(Finding(
                rule=RULE_HOST_SYNC, severity=sev,
                message=f"host callback `{name}` {where}",
                target=target.label, scope=ctx.scope,
                fix_hint=("move the callback out of the compiled step "
                          "(drain device flags at logging boundaries "
                          "like the fused sentinel) or gate it off the "
                          "hot path")))
        elif name in _TRANSFER_PRIMS:
            devices = ctx.eqn.params.get("devices", ())
            # a device_put staying on-device is a sharding hint, not a
            # transfer; only flag explicit host destinations
            if any("host" in str(d).lower() for d in devices):
                out.append(Finding(
                    rule=RULE_HOST_SYNC, severity="warning",
                    message=("`device_put` to a host memory kind inside "
                             "the step program"),
                    target=target.label, scope=ctx.scope,
                    fix_hint="keep step state device-resident; offload "
                             "belongs in the host optimizer path"))
    return out


# --------------------------------------------------------------------- #
# rule 2: donation audit
# --------------------------------------------------------------------- #
def donation_rule(target: AuditTarget, cfg) -> List[Finding]:
    """Large consumed arguments that are not donated double their HBM for
    the life of the program (old buffer pinned + new output allocated) —
    the exact failure that resurrects OOMs after an innocent refactor
    drops a donate_argnums."""
    out = []
    floor = int(cfg.donation_min_mb * 1024 * 1024)
    for arg in target.args:
        if arg.consumed and not arg.donated and arg.nbytes >= floor:
            mb = arg.nbytes / (1024 * 1024)
            out.append(Finding(
                rule=RULE_DONATION, severity="error",
                message=(f"argument `{arg.label}` ({mb:.1f} MiB) is "
                         "consumed by the step but not donated — its old "
                         "buffer stays pinned alongside the new output, "
                         f"wasting ~{mb:.1f} MiB of HBM"),
                target=target.label,
                fix_hint=(f"add `{arg.label}`'s argnum to donate_argnums "
                          "(the engine's apply program donates "
                          "params/opt_state)")))
    return out


def donation_waste_bytes(targets: List[AuditTarget], cfg) -> int:
    floor = int(cfg.donation_min_mb * 1024 * 1024)
    return sum(a.nbytes for t in targets for a in t.args
               if a.consumed and not a.donated and a.nbytes >= floor)


# --------------------------------------------------------------------- #
# rule 3: collective-lockstep signature
# --------------------------------------------------------------------- #
def lockstep_expectation_finding(signature: str, n_collectives: int,
                                 cfg) -> List[Finding]:
    """Report-level check of the COMBINED engine signature (what the CLI
    prints and users pin in analysis.expected_signature)
    against the configured expectation — per-target signatures feed into
    it but are not individually pinnable."""
    if not cfg.expected_signature or signature is None:
        return []
    if signature.startswith(cfg.expected_signature):
        return []
    return [Finding(
        rule=RULE_LOCKSTEP, severity="error",
        message=(f"collective lockstep signature {signature[:12]} does "
                 f"not match analysis.expected_signature "
                 f"{cfg.expected_signature[:12]} — on a multihost pod a "
                 "config diverging like this would issue a different "
                 "collective sequence and hang "
                 f"({n_collectives} collectives traced)"),
        target="combined",
        fix_hint=("diff the collective sequences (CLI --dump-sequence) "
                  "and re-pin expected_signature only if the change is "
                  "intended on EVERY host"))]


def compare_lockstep(jaxpr_a, jaxpr_b, label_a: str = "a",
                     label_b: str = "b") -> Optional[Finding]:
    """Cross-config / cross-host comparison helper: None when in
    lockstep, else an error finding naming the first divergence."""
    sig_a, seq_a = lockstep_signature(jaxpr_a)
    sig_b, seq_b = lockstep_signature(jaxpr_b)
    if sig_a == sig_b:
        return None
    return Finding(
        rule=RULE_LOCKSTEP, severity="error",
        message=(f"collective sequences diverge between {label_a} "
                 f"({sig_a[:12]}, {len(seq_a)} collectives) and "
                 f"{label_b} ({sig_b[:12]}, {len(seq_b)}): "
                 f"{first_divergence(seq_a, seq_b)}"),
        target=f"{label_a} vs {label_b}",
        fix_hint="align the configs (zero stage, low_bandwidth bits, "
                 "hpz group, mesh axes) before launching a pod")


# --------------------------------------------------------------------- #
# rule 4: dtype-hazard lint
# --------------------------------------------------------------------- #
_HALF_DTYPES = ("bfloat16", "float16")
# shape-only ops a value flows through unchanged — provenance tracking
# follows the upcast wire through these
_TRANSPARENT_PRIMS = ("reshape", "transpose", "broadcast_in_dim",
                      "squeeze", "rev", "slice", "copy")


def dtype_hazard_rule(target: AuditTarget, cfg) -> List[Finding]:
    """Unintended fp32 upcasts on half wires.

    Two hazards, both read off `convert_element_type` provenance:
      (a) a half->fp32 convert feeding a dot/conv — the matmul silently
          runs at fp32 (4x MXU cost on TPU) on data that was deliberately
          half-width;
      (b) a half->fp32 convert feeding a collective — the wire moves 4
          bytes where 2 were intended (the qwZ/qgZ savings silently
          undone).  The engine's OWN fp32 promotions (scalar loss upcast,
          grad unscale into optimizer math, f32_psum_scatter's documented
          promote-reduce-demote) either are scalar, feed elementwise
          optimizer math, or convert straight back — none trip (a)/(b).

    Weak-type promotions surface the same way: jax materializes the
    promotion as a convert_element_type on the wide operand, so an
    accidental `0.1 * bf16_tensor` in fp32 shows up here when it feeds
    compute that matters.
    """
    out = []
    min_elems = int(cfg.dtype_min_elements)
    jaxpr = target.closed_jaxpr

    def scan_jaxpr(jx, scope_prefix=""):
        # provenance: var -> originating half->f32 convert scope, traced
        # through shape-only ops.  Per-subjaxpr (vars don't cross jaxpr
        # boundaries except via invars, which is conservative enough).
        upcast_from: dict = {}
        from .jaxpr_walk import eqn_scope, sub_jaxprs
        for eqn in as_jaxpr(jx).eqns:
            name = eqn.primitive.name
            if name == "convert_element_type":
                src = eqn.invars[0]
                src_aval = getattr(src, "aval", None)
                dst_aval = getattr(eqn.outvars[0], "aval", None)
                if (src_aval is not None and dst_aval is not None
                        and str(src_aval.dtype) in _HALF_DTYPES
                        and str(dst_aval.dtype) == "float32"
                        and _n_elems(dst_aval) >= min_elems):
                    upcast_from[id(eqn.outvars[0])] = (
                        eqn_scope(eqn, scope_prefix), str(src_aval.dtype))
            elif name in _TRANSPARENT_PRIMS:
                src = next((v for v in eqn.invars
                            if id(v) in upcast_from), None)
                if src is not None:
                    upcast_from[id(eqn.outvars[0])] = upcast_from[id(src)]
            elif name in ("dot_general", "conv_general_dilated"):
                for v in eqn.invars:
                    if id(v) in upcast_from:
                        scope, half = upcast_from[id(v)]
                        out.append(Finding(
                            rule=RULE_DTYPE_HAZARD, severity="error",
                            message=(f"`{name}` consumes an operand "
                                     f"upcast from {half} to float32 — "
                                     "the matmul runs at fp32 width on a "
                                     "half wire (silent 4x MXU cost)"),
                            target=target.label,
                            scope=scope or eqn_scope(eqn, scope_prefix),
                            fix_hint=("keep the operand in its compute "
                                      "dtype (check for a stray "
                                      ".astype(float32) or a weak-typed "
                                      "fp32 scalar promoting the wire)")))
                        break
            elif name in GATHER_PRIMS + REDUCE_PRIMS:
                for v in eqn.invars:
                    if id(v) in upcast_from:
                        scope, half = upcast_from[id(v)]
                        # f32_psum_scatter's promote-reduce-demote is the
                        # documented exception: the convert feeds ONLY
                        # the reduction and converts straight back.  A
                        # psum_scatter/reduce_scatter/psum (psum2 inside
                        # shard_map on jax 0.4.x) of an upcast wire is
                        # therefore warning-grade; gathers and
                        # all_to_alls of an upcast wire are real waste.
                        sev = ("warning" if name in
                               ("reduce_scatter", "psum_scatter", "psum",
                                "psum2")
                               else "error")
                        out.append(Finding(
                            rule=RULE_DTYPE_HAZARD, severity=sev,
                            message=(f"collective `{name}` moves a wire "
                                     f"upcast from {half} to float32 — "
                                     "4 bytes/elem where 2 were "
                                     "intended"),
                            target=target.label,
                            scope=scope or eqn_scope(eqn, scope_prefix),
                            fix_hint=("collect in the half dtype, or "
                                      "route through the quantized "
                                      "low-bandwidth collectives "
                                      "(qwZ/qgZ)")))
                        break
            for sub in sub_jaxprs(eqn):
                scan_jaxpr(sub.jaxpr, eqn_scope(eqn, scope_prefix))

    scan_jaxpr(jaxpr)
    return out


def _n_elems(aval) -> int:
    import numpy as np
    return int(np.prod(aval.shape, initial=1))


# --------------------------------------------------------------------- #
# rule 5: comm-budget lint
# --------------------------------------------------------------------- #
# wire-moving families for the BUDGET accounting: collective_wire_bytes'
# families plus psum2 (what a psum traces to inside shard_map on jax
# 0.4.x).  NOT signature.REDUCE_PRIMS: ppermute/pmax/pmin matter for
# lockstep ordering but are excluded from wire volume, keeping this
# comparable with collective_wire_bytes A/B numbers.  One exception:
# a ppermute traced inside the fused-collective-matmul scope
# (constants.FCM_SCOPE, ops/collective_matmul.py) IS the qwZ/qgZ
# payload riding a per-tile ring — those count operand bytes, so a
# fused config's wire volume stays comparable with its modular twin
# instead of reading as zero.
_WIRE_GATHER_PRIMS = GATHER_PRIMS
_WIRE_REDUCE_PRIMS = ("psum_scatter", "reduce_scatter", "all_to_all",
                      "psum", "psum2")


def step_wire_bytes(jaxpr) -> Tuple[int, List[Tuple[str, int]]]:
    """Trip-count-weighted wire bytes of one program: output bytes for
    gathers, operand bytes for reductions, each multiplied by the static
    trip count of its enclosing scans (unlike `collective_wire_bytes`,
    which stays unweighted for same-structure A/B ratios).  cond
    branches contribute their MOST EXPENSIVE branch (only one executes),
    mirroring the flops counter."""
    from .jaxpr_walk import (as_jaxpr, eqn_scope, scope_has_component,
                             sub_jaxprs)
    contributors: List[Tuple[str, int]] = []

    def walk(jx, scope, mult, out):
        total = 0
        for eqn in as_jaxpr(jx).eqns:
            name = eqn.primitive.name
            if name in _WIRE_GATHER_PRIMS:
                b = sum(aval_bytes(v) for v in eqn.outvars) * mult
            elif name in _WIRE_REDUCE_PRIMS:
                b = sum(aval_bytes(v) for v in eqn.invars) * mult
            elif (name == "ppermute" and scope_has_component(
                    eqn_scope(eqn, scope), C.FCM_SCOPE)):
                # fused collective-matmul ring hop: the quantized
                # payload tile on the wire
                b = sum(aval_bytes(v) for v in eqn.invars) * mult
            elif name == "cond":
                probes = []
                for sub in sub_jaxprs(eqn):
                    branch_out: List[Tuple[str, int]] = []
                    probes.append((walk(sub.jaxpr, eqn_scope(eqn, scope),
                                        mult, branch_out), branch_out))
                if probes:
                    cost, branch_contrib = max(probes,
                                               key=lambda p: p[0])
                    total += cost
                    out.extend(branch_contrib)
                continue
            else:
                for sub in sub_jaxprs(eqn):
                    total += walk(sub.jaxpr, eqn_scope(eqn, scope),
                                  mult * (sub.trip_count or 1), out)
                continue
            total += b
            out.append((f"{name}@{eqn_scope(eqn, scope) or '<top>'}", b))
        return total

    total = walk(jaxpr, "", 1, contributors)
    contributors.sort(key=lambda kv: -kv[1])
    return total, contributors


def comm_budget_finding(total_bytes: int,
                        contributors: List[Tuple[str, int]],
                        cfg) -> List[Finding]:
    """Report-level per-OPTIMIZER-STEP wire volume vs the configured
    budget — the total is gas-weighted across every dispatched program
    (the modular grad program counts gas times, scan trip counts are
    multiplied in), matching the report's ``wire_bytes_per_step``.
    Catches dense fallbacks silently reappearing (a skinny-leaf gate
    regression turns one int8 gather back into fp32 and nothing else
    changes)."""
    if cfg.comm_budget_mb is None:
        return []
    budget = int(cfg.comm_budget_mb * 1024 * 1024)
    if total_bytes <= budget:
        return []
    top = "; ".join(f"{k}={v} B" for k, v in contributors[:3])
    return [Finding(
        rule=RULE_COMM_BUDGET, severity="error",
        message=(f"step moves {total_bytes} wire bytes, over the "
                 f"{cfg.comm_budget_mb} MiB budget "
                 f"({budget} B) — top contributors: {top}"),
        target="combined",
        fix_hint=("check for a dense-fallback regression (qwZ skinny-"
                  "leaf gate, hpZ axes) or raise analysis."
                  "comm_budget_mb if the new traffic is intended"))]


# --------------------------------------------------------------------- #
# registry of per-target rules (lockstep expectation and comm budget
# are report-level — lockstep_expectation_finding /
# comm_budget_finding; rule 6, the recompile guard, is runtime:
# recompile.py)
# --------------------------------------------------------------------- #
STATIC_RULES = (
    (RULE_HOST_SYNC, host_sync_rule),
    (RULE_DONATION, donation_rule),
    (RULE_DTYPE_HAZARD, dtype_hazard_rule),
)
