"""HLO-level SPMD audit — cross-check the jaxpr wire story against the
program XLA actually compiled.

Every number the repo stakes its honesty on — ``collective_wire_bytes``,
the lockstep signature, ``predicted_step_time_lb``, the
``require_overlap`` CI gate — is computed from the **jaxpr**.  But under
pjit/GSPMD, XLA inserts collectives *after* tracing: the partitioner
adds the data-parallel partial-sum reductions, re-gathers ZeRO-sharded
params at the optimizer boundary, and — when a sharding annotation is
wrong — silently reshards tensors with all-gathers the entire
jaxpr-level analysis stack never sees (the exact failure mode the T3
paper, arXiv:2401.16677, fuses kernels to avoid).  This module closes
the blind spot:

  1. lower each ``AuditTarget`` through XLA's SPMD partitioner on the
     simulated mesh (compile-only on CPU, never executed — the same
     contract as the rest of the auditor),
  2. walk the optimized post-SPMD HLO for collective ops (all-gather /
     all-reduce / reduce-scatter / collective-permute / all-to-all;
     async ``-start``/``-done`` pairs deduped to the start),
  3. price each collective with replica-group-aware sizing and while-
     loop trip-count weighting (``known_trip_count`` backend config),
  4. reconcile against the jaxpr-level prediction: collectives whose op
     metadata names a traced jax collective primitive confirm the
     accounting; compiler-inserted reductions are the partial-sum
     combine GSPMD must insert (explained, priced); compiler-inserted
     GATHER-family collectives are resharding — waived when a declared
     sharding contract predicts them (ZeRO's param re-gather) or when
     below the configured floor, otherwise a ``silent_reshard`` finding
     with op-metadata source provenance (warning by default, error
     under ``analysis.require_spmd_match``).

The HLO-only wire (everything the jaxpr never counted) feeds the cost
model's exposed-comm lane so ``predicted_step_time_lb`` stops
undercounting — see ``cost_model.build_step_time_model``.

Parsing note: the walk reads the optimized HLO **text**
(``lowered.compile().as_text()``), the one stable surface jax exposes
across jaxlib versions for the post-optimization program.  The parser
is deliberately structural — computations, instructions, called
computations, replica groups — and every quantity it extracts is pinned
by fixture tests against real XLA output (tests/unit/test_hlo_audit.py).
Two printed forms are read alike: operands with their shapes inline and
``source_file=`` / ``source_line=`` metadata (jax 0.4.x), and operands
by bare ``%name`` with ``stack_frame_id=`` metadata resolved through the
module's FileNames / FileLocations / StackFrames tables (jax 0.9.0).
"""

import re
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from .. import constants as C
from .findings import Finding, RULE_SILENT_RESHARD, RULE_SPMD_DIVERGENCE
from .jaxpr_walk import scope_has_component

# HLO collective opcodes (sync + async-start forms).  ``-done`` halves
# of async pairs are skipped: the wire moves once per start.
GATHER_OPCODES = ("all-gather",)
REDUCE_OPCODES = ("all-reduce", "reduce-scatter")
PERMUTE_OPCODES = ("collective-permute", "all-to-all")
COLLECTIVE_OPCODES = GATHER_OPCODES + REDUCE_OPCODES + PERMUTE_OPCODES
# gather-family = compiler-inserted instances are resharding, not the
# mathematically-required partial-sum combine
RESHARD_OPCODES = GATHER_OPCODES + PERMUTE_OPCODES

# traced jax collective primitives an HLO op's metadata op_name ends in
# when the collective came from the traced program (signature.py's
# COLLECTIVE_PRIMS vocabulary).  GSPMD-inserted collectives carry the
# CAUSING op's metadata (dot_general, scatter-add) or none at all.
_TRACED_PRIMS = ("all_gather", "psum_scatter", "reduce_scatter",
                 "all_to_all", "ppermute", "psum2", "psum", "pmax",
                 "pmin")
# the subset whose wire the jaxpr accounting (rules.step_wire_bytes)
# actually counts — ppermute only inside the fused-collective-matmul
# scope, pmax/pmin never (lockstep-relevant, wire-irrelevant)
_COUNTED_PRIMS = ("all_gather", "psum_scatter", "reduce_scatter",
                  "all_to_all", "psum2", "psum")

_DTYPE_BITS = {
    "pred": 8, "token": 0, "opaque": 0,
    "s2": 2, "u2": 2, "s4": 4, "u4": 4,
    "s8": 8, "u8": 8, "s16": 16, "u16": 16, "s32": 32, "u32": 32,
    "s64": 64, "u64": 64,
    "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3b11fnuz": 8, "f8e4m3fnuz": 8,
    "f8e5m2fnuz": 8, "f8e3m4": 8, "f8e4m3": 8, "f8e8m0fnu": 8,
    "bf16": 16, "f16": 16, "f32": 32, "f64": 64,
    "c64": 64, "c128": 128,
}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\](?:\{[^}]*\})?")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s*=\s*"
    r"(?P<shape>\([^)]*\)|\S+)\s+(?P<opcode>[\w\-]+)\(")
_COMP_RE = re.compile(r"^\s*(?P<entry>ENTRY\s+)?%(?P<name>[^\s(]+)\s*\(")
_METADATA_RE = re.compile(
    r'metadata=\{[^}]*?op_name="(?P<op_name>[^"]*)"'
    r'(?:[^}]*?source_file="(?P<file>[^"]*)")?'
    r'(?:[^}]*?source_line=(?P<line>\d+))?'
    r'(?:[^}]*?stack_frame_id=(?P<frame>\d+))?')
_OPERAND_NAME_RE = re.compile(r"%([^\s,()]+)")
# the module header's source tables (stack_frame_id -> file:line)
_TABLE_RE = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\s*$", re.M)
_FILE_NAME_ROW_RE = re.compile(r'^(\d+)\s+"(.*)"\s*$', re.M)
_FILE_LOCATION_ROW_RE = re.compile(
    r"^(\d+)\s+\{file_name_id=(\d+)\b.*?\bline=(\d+)", re.M)
_STACK_FRAME_ROW_RE = re.compile(
    r"^(\d+)\s+\{file_location_id=(\d+)", re.M)
_TRIP_RE = re.compile(r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"(\d+)"')
# greedy digits/braces body (the lazy form would stop at the FIRST
# inner '}' of {{0,1},{2,3}}); the [^a-z=] class halts at the next
# lowercase attribute name either way
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(?P<body>[^a-z=]*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(?P<ngroups>\d+),(?P<size>\d+)\]<=\[")
_CALLED_RE = {
    "body": re.compile(r"body=%(\S+?)(?=[,)\s]|$)"),
    "condition": re.compile(r"condition=%(\S+?)(?=[,)\s]|$)"),
    "calls": re.compile(r"calls=%(\S+?)(?=[,)\s]|$)"),
    "to_apply": re.compile(r"to_apply=%(\S+?)(?=[,)\s]|$)"),
    "true": re.compile(r"true_computation=%(\S+?)(?=[,)\s]|$)"),
    "false": re.compile(r"false_computation=%(\S+?)(?=[,)\s]|$)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
}
_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")


def _shape_bytes(text: str) -> int:
    """Total bytes of every shape token in ``text`` (sub-byte dtypes
    round up per array, matching numpy's int4 itemsize convention)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        bits = _DTYPE_BITS.get(dtype)
        if bits is None or bits == 0:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += max(1, (n * bits) // 8) if n else 0
    return total


def _paren_operands(line: str, opcode: str) -> str:
    """The operand list of the instruction call: text between the
    opcode's '(' and its matching ')'."""
    start = line.index(opcode + "(") + len(opcode)
    depth = 0
    for i in range(start, len(line)):
        ch = line[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i]
    return line[start + 1:]


def _split_operands(text: str) -> List[str]:
    """Top-level comma split of an operand list (commas inside a
    shape's dims, layout or tuple belong to that operand)."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(text[start:i])
            start = i + 1
    out.append(text[start:])
    return [o for o in out if o.strip()]


def _operand_bytes(text: str, shapes: Dict[str, str]) -> int:
    """Bytes of an operand list.  An operand printed with its shape
    (``f32[16,32]{1,0} %x``) is read off the line; one printed as a bare
    ``%x`` takes the shape of the instruction (or parameter) of the same
    computation that defines it, ``shapes``."""
    total = 0
    for operand in _split_operands(text):
        name = _OPERAND_NAME_RE.search(operand)
        if name is not None and not _SHAPE_RE.search(operand):
            operand = shapes.get(name.group(1), "")
        total += _shape_bytes(operand)
    return total


def _frame_sources(text: str) -> Dict[str, str]:
    """stack_frame_id -> "file:line", from the tables a module printed
    with ``stack_frame_id=`` metadata carries in its header (empty for
    text that names the source in each instruction's metadata)."""
    parts = _TABLE_RE.split(text)
    tables = dict(zip(parts[1::2], parts[2::2]))
    files = dict(_FILE_NAME_ROW_RE.findall(tables.get("FileNames", "")))
    locations = {
        loc: f"{files[name]}:{line}"
        for loc, name, line in _FILE_LOCATION_ROW_RE.findall(
            tables.get("FileLocations", "")) if name in files}
    return {frame: locations[loc]
            for frame, loc in _STACK_FRAME_ROW_RE.findall(
                tables.get("StackFrames", "")) if loc in locations}


class HloInstr(NamedTuple):
    name: str
    opcode: str
    shape: str
    line: str


@dataclass
class HloCollective:
    """One collective instruction of the optimized post-SPMD program."""
    opcode: str             # canonical (async -start folded in)
    name: str               # HLO instruction name
    target: str             # audited program label
    wire_bytes: int         # one execution's wire (gather: group-sized
                            # output, reduce/permute: operand bytes)
    mult: int               # enclosing while-loop trip multiplier
    group_size: int         # replica-group participant count
    n_groups: int
    op_name: str            # metadata op_name ("" when absent)
    source: str             # "file:line" provenance ("" when absent)
    traced: bool            # produced by a traced jax collective prim
    counted: bool           # traced AND in the jaxpr wire accounting
    degenerate: bool        # single-participant group: no wire moves
    in_branch: bool = False  # under a conditional (may not execute)
    # False for records in a non-worst conditional branch: excluded
    # from every byte total (only one branch executes; totals take the
    # worst branch, mirroring the jaxpr-side walkers) but still
    # CLASSIFIED — a silent reshard in the cheaper branch must flag
    charged: bool = True
    waived_by: str = ""     # waiver name for inserted gathers ("" = none)


class HloProgram:
    """Parsed optimized-HLO module: computations, entry, partitions."""

    def __init__(self, text: str):
        self.computations: Dict[str, List[HloInstr]] = {}
        self.entry: Optional[str] = None
        self.frame_sources = _frame_sources(text)
        m = _NUM_PARTITIONS_RE.search(text)
        self.num_partitions = int(m.group(1)) if m else 1
        current: Optional[List[HloInstr]] = None
        for raw in text.splitlines():
            instr = _INSTR_RE.match(raw)
            if instr is not None and current is not None:
                current.append(HloInstr(instr.group("name"),
                                        instr.group("opcode"),
                                        instr.group("shape"), raw))
                continue
            comp = _COMP_RE.match(raw)
            if comp is not None and "->" in raw and raw.rstrip().endswith("{"):
                current = []
                self.computations[comp.group("name")] = current
                if comp.group("entry"):
                    self.entry = comp.group("name")


def _replica_group(line: str, num_partitions: int) -> Tuple[int, int]:
    """(group_size, n_groups) of a collective instruction."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group("size")), int(m.group("ngroups"))
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        groups = [g for g in m.group("body").split("},{") if g.strip()]
        if not groups:
            # replica_groups={} — all participants in one group
            return num_partitions, 1
        first = groups[0].strip("{} ")
        size = len([x for x in first.split(",") if x.strip()])
        return max(1, size), len(groups)
    return num_partitions, 1


def _canonical_opcode(opcode: str) -> Optional[str]:
    """Map sync/async spellings onto the canonical collective opcode;
    None for non-collectives and for the -done halves of async pairs."""
    if opcode.endswith("-done") or opcode.endswith("-update"):
        return None
    base = opcode[:-6] if opcode.endswith("-start") else opcode
    return base if base in COLLECTIVE_OPCODES else None


def _collective_wire_bytes(instr: HloInstr, opcode: str,
                           group_size: int,
                           shapes: Dict[str, str]) -> int:
    """Replica-group-aware wire bytes, on the jaxpr accounting's
    conventions: a gather is priced at its group-sized OUTPUT (operand
    bytes x participants — matches `step_wire_bytes` counting gather
    outvars), reductions/permutes at their operand bytes.  ``shapes``
    names the shapes of the enclosing computation's instructions."""
    operands = _operand_bytes(_paren_operands(instr.line, instr.opcode),
                              shapes)
    if opcode in GATHER_OPCODES:
        return operands * group_size
    return operands


def walk_hlo_collectives(program: HloProgram,
                         target_label: str = "") -> List[HloCollective]:
    """Trip-count-weighted collective records of one compiled program.

    Walks from ENTRY through while bodies (mult x known_trip_count),
    conditional branches (marked ``in_branch``; totals take the worst
    branch like the jaxpr-side walkers), and call/async computations.
    Fusion computations are skipped — XLA never fuses collectives.
    """
    out: List[HloCollective] = []
    visiting: List[str] = []

    def visit(comp_name: str, mult: int, in_branch: bool,
              sink: List[HloCollective]) -> None:
        comp = program.computations.get(comp_name)
        if comp is None or comp_name in visiting:
            return
        visiting.append(comp_name)
        shapes = {i.name: i.shape for i in comp}
        for instr in comp:
            opcode = _canonical_opcode(instr.opcode)
            if opcode is not None:
                size, n_groups = _replica_group(instr.line,
                                                program.num_partitions)
                meta = _METADATA_RE.search(instr.line)
                op_name = meta.group("op_name") if meta else ""
                source = ""
                if meta and meta.group("file"):
                    source = meta.group("file")
                    if meta.group("line"):
                        source += f":{meta.group('line')}"
                elif meta and meta.group("frame"):
                    source = program.frame_sources.get(
                        meta.group("frame"), "")
                last = op_name.rsplit("/", 1)[-1]
                prim = next((p for p in _TRACED_PRIMS
                             if re.search(rf"\b{p}\b", last)), None)
                counted = prim in _COUNTED_PRIMS
                if prim == "ppermute":
                    # the jaxpr accounting prices ppermute only as a
                    # fused-collective-matmul transport (rules.py)
                    counted = scope_has_component(op_name, C.FCM_SCOPE)
                degenerate = size <= 1
                sink.append(HloCollective(
                    opcode=opcode, name=instr.name, target=target_label,
                    wire_bytes=(0 if degenerate else
                                _collective_wire_bytes(instr, opcode,
                                                       size, shapes)),
                    mult=mult, group_size=size, n_groups=n_groups,
                    op_name=op_name, source=source,
                    traced=prim is not None, counted=counted,
                    degenerate=degenerate, in_branch=in_branch))
                continue
            if instr.opcode == "while":
                trip = _TRIP_RE.search(instr.line)
                n = int(trip.group(1)) if trip else 1
                for key in ("body", "condition"):
                    m = _CALLED_RE[key].search(instr.line)
                    if m:
                        visit(m.group(1), mult * n, in_branch, sink)
            elif instr.opcode == "conditional":
                branches = []
                m = _CALLED_RE["branches"].search(instr.line)
                if m:
                    branches = [b.strip().lstrip("%")
                                for b in m.group(1).split(",") if b.strip()]
                else:
                    for key in ("true", "false"):
                        mm = _CALLED_RE[key].search(instr.line)
                        if mm:
                            branches.append(mm.group(1))
                probes: List[List[HloCollective]] = []
                for b in branches:
                    probe: List[HloCollective] = []
                    visit(b, mult, True, probe)
                    probes.append(probe)
                if probes:
                    # worst branch feeds the totals (only one executes);
                    # every branch's records are kept for findings —
                    # uncharged, wire intact, so the reshard classifier
                    # still sees their true bytes
                    best = max(probes, key=lambda p: sum(
                        r.wire_bytes * r.mult for r in p))
                    for p in probes:
                        for r in p:
                            if p is not best:
                                r.charged = False
                            sink.append(r)
            elif instr.opcode in ("call", "async-start"):
                for key in ("to_apply", "calls"):
                    m = _CALLED_RE[key].search(instr.line)
                    if m:
                        visit(m.group(1), mult, in_branch, sink)
        visiting.pop()

    if program.entry is not None:
        visit(program.entry, 1, False, out)
    return out


@dataclass
class SpmdWaiver:
    """A declared expectation for compiler-inserted gather-family wire:
    the sharding contract predicts up to ``byte_budget`` bytes/step of
    ``opcodes`` resharding (ZeRO stage >= 1 re-gathers the updated
    params at the optimizer boundary).  Absorbed bytes are reported per
    waiver so tests can pin WHY a config's divergence is explained."""
    name: str
    byte_budget: int
    opcodes: Tuple[str, ...] = RESHARD_OPCODES
    absorbed_bytes: int = 0


@dataclass
class HloTargetAudit:
    """Reconciliation of one compiled program against its jaxpr."""
    target: str
    collectives: List[HloCollective] = field(default_factory=list)
    error: str = ""             # lowering/compile failure (audit skipped)
    skipped: bool = False       # target had no lowering hook
    # accounting (all trip-count weighted, one dispatch of the program)
    jaxpr_wire_bytes: int = 0   # rules.step_wire_bytes prediction
    matched_wire_bytes: int = 0  # traced+counted collectives, HLO-sized
    uncounted_traced_bytes: int = 0  # traced but outside jaxpr accounting
    reduction_bytes: int = 0    # inserted all-reduce/reduce-scatter
    waived_reshard_bytes: int = 0
    reshard_bytes: int = 0      # inserted, unwaived — the finding bytes
    n_silent_reshards: int = 0
    waivers: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def hlo_wire_bytes(self) -> int:
        return (self.matched_wire_bytes + self.uncounted_traced_bytes
                + self.reduction_bytes + self.waived_reshard_bytes
                + self.reshard_bytes)

    @property
    def hlo_only_bytes(self) -> int:
        """COMPILER-INSERTED wire the jaxpr accounting never saw —
        priced fully exposed by the cost model (no overlap record
        exists for it).  Traced-but-uncounted wire (a ring attention's
        ppermute, pmax/pmin) is deliberately NOT here: the jaxpr side
        excludes it because it is overlap-managed by construction, and
        pricing it exposed would push the 'lower bound' above
        achievable step time."""
        return (self.reduction_bytes + self.waived_reshard_bytes
                + self.reshard_bytes)

    @property
    def verified(self) -> bool:
        """The cross-check actually ran for this target."""
        return not self.error and not self.skipped

    @property
    def divergence_ratio(self) -> Optional[float]:
        """None when the target was never cross-checked — an errored
        target must not masquerade as a measured-zero-wire one."""
        if not self.verified:
            return None
        if self.jaxpr_wire_bytes <= 0:
            return 1.0 if self.matched_wire_bytes == 0 else float("inf")
        return self.matched_wire_bytes / self.jaxpr_wire_bytes


def audit_target_hlo(target, cfg, jaxpr_wire_bytes: int
                     ) -> Tuple[HloTargetAudit, List[Finding]]:
    """Lower one AuditTarget through the SPMD partitioner and reconcile
    (compile-only; returns an error-carrying audit when XLA refuses —
    the PartitionId seed-xfail class must not crash the auditor)."""
    audit = HloTargetAudit(target=target.label,
                           jaxpr_wire_bytes=int(jaxpr_wire_bytes))
    severity = "error" if cfg.require_spmd_match else "warning"
    if target.lower is None:
        audit.skipped = True
        if cfg.require_spmd_match and jaxpr_wire_bytes > 0:
            # under the gate posture, a wire-carrying target that
            # cannot be cross-checked must not silently pass
            return audit, [Finding(
                rule=RULE_SPMD_DIVERGENCE, severity=severity,
                message=(f"target carries {jaxpr_wire_bytes} B of "
                         "traced wire but has no lowering hook — its "
                         "compiled wire story is UNVERIFIED under "
                         "require_spmd_match"),
                target=target.label,
                fix_hint="give the AuditTarget a `lower` thunk (the "
                         "engine targets wire theirs automatically)")]
        return audit, []
    try:
        text = target.lower()
    except Exception as e:  # noqa: BLE001 — surface, never crash
        audit.error = f"{type(e).__name__}: {e}"
        # escalates with require_spmd_match: the gate must fail rather
        # than pass with a target's cross-check silently disabled
        return audit, [Finding(
            rule=RULE_SPMD_DIVERGENCE, severity=severity,
            message=("HLO audit could not compile the program through "
                     f"the SPMD partitioner: {audit.error[:200]} — the "
                     "compiled wire story is UNVERIFIED for this target"),
            target=target.label,
            fix_hint="see the seed-xfail ledger (docs/COVERAGE.md) for "
                     "known partitioner rejections on this backend")]

    program = HloProgram(text)
    records = walk_hlo_collectives(program, target.label)
    audit.collectives = records

    # fresh copies: absorbed_bytes accumulates per audit run
    waivers = [SpmdWaiver(w.name, int(w.byte_budget), tuple(w.opcodes))
               for w in target.spmd_waivers]
    floor = int(cfg.spmd_reshard_min_mb * 1024 * 1024)
    floor_waiver = SpmdWaiver("below_floor", 0)
    findings: List[Finding] = []
    flagged: set = set()
    for rec in records:
        weighted = rec.wire_bytes * rec.mult
        if rec.degenerate:
            continue
        if rec.traced:
            if not rec.charged:
                continue
            if rec.counted:
                audit.matched_wire_bytes += weighted
            else:
                audit.uncounted_traced_bytes += weighted
            continue
        if rec.opcode in REDUCE_OPCODES:
            if rec.charged:
                audit.reduction_bytes += weighted
            continue
        # compiler-inserted gather-family: resharding.  Named waivers
        # (largest budget first) absorb the wire the sharding contract
        # predicts; the configured floor absorbs small indexed-update
        # gathers; the remainder is a silent reshard.  Records in a
        # non-worst conditional branch (charged=False) go through the
        # SAME classification — a reshard there still flags — but
        # consume no waiver budget and add to no byte total.
        waiver = next(
            (w for w in sorted(waivers, key=lambda w: -w.byte_budget)
             if rec.opcode in w.opcodes
             and w.absorbed_bytes + weighted <= w.byte_budget), None)
        if waiver is None and weighted < floor:
            waiver = floor_waiver
        if waiver is not None:
            if rec.charged:
                waiver.absorbed_bytes += weighted
                audit.waived_reshard_bytes += weighted
            rec.waived_by = waiver.name
            continue
        if rec.charged:
            audit.reshard_bytes += weighted
        audit.n_silent_reshards += 1
        key = (rec.opcode, rec.op_name, rec.wire_bytes)
        if key in flagged:
            continue
        flagged.add(key)
        cause = (f"inserted for `{rec.op_name.rsplit('/', 1)[-1]}`"
                 if rec.op_name else
                 "inserted at a sharding boundary (no causing op — an "
                 "in/out sharding annotation disagrees with the data's "
                 "actual placement)")
        findings.append(Finding(
            rule=RULE_SILENT_RESHARD, severity=severity,
            message=(f"compiler-inserted `{rec.opcode}` moves "
                     f"{rec.wire_bytes} B x{rec.mult} "
                     f"(groups of {rec.group_size}) that the jaxpr-level "
                     f"wire accounting never saw — {cause}"),
            target=target.label,
            scope=rec.source or rec.op_name,
            fix_hint=("align the sharding annotation with the intended "
                      "layout (pjit out_shardings / NamedSharding on "
                      "the weight), or declare the wire with an "
                      "explicit collective so every analysis layer "
                      "prices it; raise analysis.spmd_reshard_min_mb "
                      "only if this gather is intended")))

    audit.waivers = [{"name": w.name, "byte_budget": int(w.byte_budget),
                      "absorbed_bytes": int(w.absorbed_bytes)}
                     for w in waivers + [floor_waiver]
                     if w.absorbed_bytes > 0]

    ratio = audit.divergence_ratio
    if (audit.jaxpr_wire_bytes > 0 or audit.matched_wire_bytes > 0) \
            and abs(ratio - 1.0) > cfg.spmd_match_tolerance:
        direction = (
            "the compiled program moves LESS traced wire than the "
            "jaxpr predicts (an OVERPREDICTION: XLA CSE'd duplicate "
            "gathers or strength-reduced an all-reduce of replicated "
            "data to a multiply)" if ratio < 1.0 else
            "the compiled program moves MORE traced wire than the "
            "jaxpr predicts (an UNDERPREDICTION — the honesty gap "
            "this audit exists to catch)")
        findings.append(Finding(
            rule=RULE_SPMD_DIVERGENCE, severity=severity,
            message=(f"jaxpr-predicted wire ({audit.jaxpr_wire_bytes} B) "
                     f"and HLO-measured wire of the SAME traced "
                     f"collectives ({audit.matched_wire_bytes} B) "
                     f"diverge by {abs(ratio - 1.0) * 100:.1f}% "
                     f"(tolerance {cfg.spmd_match_tolerance * 100:.0f}%)"
                     f" — {direction}"),
            target=target.label,
            fix_hint=("diff the collective lists (--json reports both "
                      "sides per target); re-pin analysis."
                      "spmd_match_tolerance (or waive the config in the "
                      "cross-check regression) only once the gap is "
                      "understood and named")))
    return audit, findings


def summarize_hlo(audits: List[Tuple[HloTargetAudit, int]]
                  ) -> Dict[str, Any]:
    """Report payload over every audited target.  ``audits`` pairs each
    target's reconciliation with its per-step repeat count (the modular
    grad program dispatches gas times, matching the jaxpr accounting).
    """
    total_hlo = sum(a.hlo_wire_bytes * rep for a, rep in audits)
    total_jaxpr = sum(a.jaxpr_wire_bytes * rep for a, rep in audits)
    total_matched = sum(a.matched_wire_bytes * rep for a, rep in audits)
    n_coll = sum(sum(r.mult for r in a.collectives
                     if not r.degenerate and r.charged) * rep
                 for a, rep in audits)
    # the divergence ratio compares VERIFIED targets only: an errored
    # or skipped target contributed no matched bytes, and folding its
    # jaxpr wire into the denominator would read as "XLA optimized it
    # away" when the truth is "never cross-checked" (its own finding
    # carries that)
    v_jaxpr = sum(a.jaxpr_wire_bytes * rep for a, rep in audits
                  if a.verified)
    if v_jaxpr > 0:
        ratio = total_matched / v_jaxpr
    else:
        ratio = 1.0 if total_matched == 0 else float("inf")
    return {
        "hlo_wire_bytes_per_step": int(total_hlo),
        "hlo_collective_count": int(n_coll),
        "jaxpr_wire_bytes_per_step": int(total_jaxpr),
        "matched_wire_bytes_per_step": int(total_matched),
        "hlo_only_wire_bytes_per_step": int(
            sum(a.hlo_only_bytes * rep for a, rep in audits)),
        "reshard_bytes_per_step": int(
            sum(a.reshard_bytes * rep for a, rep in audits)),
        "n_silent_reshards": int(
            sum(a.n_silent_reshards for a, _ in audits)),
        "divergence_ratio": ratio,
        "n_unverified_targets": sum(
            1 for a, _ in audits if not a.verified),
        "targets": {
            a.target: {
                "error": a.error,
                "verified": a.verified,
                "jaxpr_wire_bytes": a.jaxpr_wire_bytes,
                "hlo_wire_bytes": a.hlo_wire_bytes,
                "matched_wire_bytes": a.matched_wire_bytes,
                "uncounted_traced_bytes": a.uncounted_traced_bytes,
                "reduction_bytes": a.reduction_bytes,
                "waived_reshard_bytes": a.waived_reshard_bytes,
                "reshard_bytes": a.reshard_bytes,
                "n_silent_reshards": a.n_silent_reshards,
                "divergence_ratio": a.divergence_ratio,
                "waivers": a.waivers,
                "collectives": [asdict(r) for r in a.collectives],
            } for a, _ in audits},
    }
