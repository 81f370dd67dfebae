"""Structured lint findings + the audit report container.

Every rule emits ``Finding`` records instead of log lines so that CI, the
engine init summary and the CLI all consume the same data —
the reference DeepSpeed has no analog (its failure modes surface as hung
pods and OOMs at runtime; see ISSUE 5 / docs/program_auditor.md).
"""

import json
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional

SEVERITIES = ("error", "warning", "info")

# rule ids (stable: tests, golden files, and docs key off these)
RULE_HOST_SYNC = "host_sync"
RULE_DONATION = "donation"
RULE_LOCKSTEP = "lockstep"
RULE_DTYPE_HAZARD = "dtype_hazard"
RULE_COMM_BUDGET = "comm_budget"
RULE_RECOMPILE = "recompile"
# schedule-level rules (overlap / liveness / step-time; ISSUE 6)
RULE_OVERLAP = "overlap"
RULE_HBM_BUDGET = "hbm_budget"
# HLO-level SPMD cross-check (analysis/hlo_audit.py; ISSUE 14):
# compiler-inserted gather-family collectives the jaxpr never saw, and
# jaxpr-predicted vs HLO-measured wire drift on the traced ones
RULE_SILENT_RESHARD = "silent_reshard"
RULE_SPMD_DIVERGENCE = "spmd_divergence"

ALL_RULES = (RULE_HOST_SYNC, RULE_DONATION, RULE_LOCKSTEP,
             RULE_DTYPE_HAZARD, RULE_COMM_BUDGET, RULE_RECOMPILE,
             RULE_OVERLAP, RULE_HBM_BUDGET, RULE_SILENT_RESHARD,
             RULE_SPMD_DIVERGENCE)


@dataclass
class Finding:
    """One lint hit: what rule fired, how bad, where in the program, and
    what to do about it."""
    rule: str                 # one of ALL_RULES
    severity: str             # "error" | "warning" | "info"
    message: str              # human-readable defect statement
    target: str = ""          # which traced program ("grad_step", ...)
    scope: str = ""           # eqn name-stack provenance inside the target
    fix_hint: str = ""        # one actionable sentence

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"severity {self.severity!r} not in "
                             f"{SEVERITIES}")
        if self.rule not in ALL_RULES:
            raise ValueError(f"unknown rule id {self.rule!r}")

    def format(self) -> str:
        where = self.target + (f" @ {self.scope}" if self.scope else "")
        hint = f"  hint: {self.fix_hint}" if self.fix_hint else ""
        return (f"[{self.severity.upper():7s}] {self.rule}: {self.message}"
                f" ({where}){hint}")


@dataclass
class AuditReport:
    """Everything one audit pass learned about the program(s)."""
    findings: List[Finding] = field(default_factory=list)
    # collective-lockstep signature of the step program (hex digest) and
    # the human-readable sequence it hashes
    signature: Optional[str] = None
    collective_sequence: List[str] = field(default_factory=list)
    # trip-count-weighted wire bytes per optimizer step
    wire_bytes_per_step: int = 0
    # HBM the donation rule estimates is being wasted (0 when clean)
    donation_waste_bytes: int = 0
    targets: List[str] = field(default_factory=list)
    # ---- schedule-level analyses (overlap / liveness / step-time) ---- #
    # bytes-weighted fraction of collective wire time hidden under
    # independent compute (1.0 when there are no explicit collectives)
    overlap_efficiency: float = 1.0
    # per-collective overlap records + summary (analysis/overlap.py)
    overlap: Dict[str, Any] = field(default_factory=dict)
    # donation-aware static peak HBM estimate across targets, with the
    # top live-buffer contributors at the peak point
    peak_hbm_bytes: int = 0
    peak_hbm_contributors: List[Any] = field(default_factory=list)
    # static step-time lower bound (analysis/cost_model.py)
    step_time: Dict[str, Any] = field(default_factory=dict)
    # HLO-level SPMD cross-check payload (analysis/hlo_audit.py):
    # per-target compiled-program wire accounting, matched vs
    # compiler-inserted, divergence ratio — empty when the audit did
    # not run (analysis.hlo_audit off and no --hlo-audit)
    hlo: Dict[str, Any] = field(default_factory=dict)

    def counts(self) -> Dict[str, int]:
        out = {s: 0 for s in SEVERITIES}
        for f in self.findings:
            out[f.severity] += 1
        return out

    @property
    def has_errors(self) -> bool:
        return any(f.severity == "error" for f in self.findings)

    @property
    def predicted_step_time_lb_s(self) -> Optional[float]:
        return self.step_time.get("predicted_step_time_lb_s")

    # ---- HLO cross-check conveniences (None when the audit is off) -- #
    @property
    def hlo_wire_bytes_per_step(self) -> Optional[int]:
        return self.hlo.get("hlo_wire_bytes_per_step")

    @property
    def hlo_collective_count(self) -> Optional[int]:
        return self.hlo.get("hlo_collective_count")

    @property
    def hlo_divergence_ratio(self) -> Optional[float]:
        return self.hlo.get("divergence_ratio")

    def summary_line(self) -> str:
        c = self.counts()
        sig = (self.signature or "")[:12] or "n/a"
        lb = self.predicted_step_time_lb_s
        lb_ms = f"{lb * 1e3:.2f}" if lb is not None else "n/a"
        return (f"program audit: {c['error']} error(s), "
                f"{c['warning']} warning(s), {c['info']} info over "
                f"{len(self.targets)} program(s); "
                f"wire={self.wire_bytes_per_step} B/step, "
                f"donation_waste={self.donation_waste_bytes} B, "
                f"overlap={self.overlap_efficiency:.2f}, "
                f"peak_hbm={self.peak_hbm_bytes / (1024 * 1024):.1f} MiB, "
                f"step_lb={lb_ms} ms, "
                f"lockstep={sig}")

    def counters(self) -> Dict[str, Any]:
        """Checkpoint-client-state payload (mirrors the sentinel-counter
        round-trip: plain JSON-serializable scalars only)."""
        return {
            "findings_by_severity": self.counts(),
            "wire_bytes_per_step": int(self.wire_bytes_per_step),
            "donation_waste_bytes": int(self.donation_waste_bytes),
            "lockstep_signature": self.signature,
            "overlap_efficiency": float(self.overlap_efficiency),
            "peak_hbm_bytes": int(self.peak_hbm_bytes),
            "predicted_step_time_lb_s": self.predicted_step_time_lb_s,
        }

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps({
            "findings": [asdict(f) for f in self.findings],
            "signature": self.signature,
            "collective_sequence": self.collective_sequence,
            "wire_bytes_per_step": self.wire_bytes_per_step,
            "donation_waste_bytes": self.donation_waste_bytes,
            "targets": self.targets,
            "overlap_efficiency": self.overlap_efficiency,
            "overlap": self.overlap,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "peak_hbm_contributors": [
                list(c) for c in self.peak_hbm_contributors],
            "step_time": self.step_time,
            "hlo": self.hlo,
        }, indent=indent)


class ProgramAuditError(RuntimeError):
    """Raised in ``analysis.mode == "error"`` when error-severity findings
    exist; carries the report for structured handling."""

    def __init__(self, report: AuditReport):
        self.report = report
        errors = [f.format() for f in report.findings
                  if f.severity == "error"]
        super().__init__(
            "program audit failed with error-severity findings "
            "(analysis.mode = \"error\"):\n" + "\n".join(errors))
