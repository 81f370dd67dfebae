"""Cost-model-driven config autotuner — search offline, validate on
chip once (ROADMAP item 5).

Chip time is the scarcest resource: every config this repo has ever
run was hand-picked, and the acceptance sweeps wedge when they try to
cover the space.  The Schedule Auditor already computes everything a
search needs to rank a config WITHOUT executing it — roofline step-time
lower bound, donation-aware peak-HBM liveness, trip-weighted wire
bytes, overlap efficiency — in seconds per candidate on CPU.  This
module closes the loop:

  enumerate   the real decision space (analysis/search_space.py)
  prune       hard constraints BEFORE tracing: batch-triple validity
              (elasticity solver reuse), a sound static HBM floor
              (param + optimizer residency under the ZeRO stage) vs the
              budget
  trace       each survivor's step program on the simulated mesh (the
              --devices machinery) and drop candidates the auditor
              rejects (error findings: liveness over budget, serialized
              hot-loop collectives under require_overlap, lockstep
              drift, ...)
  rank        by predicted_step_time_lb with per-lane attribution
              (compute / memory / hidden-comm / exposed-comm / swap) so
              the report says WHY each winner wins
  emit        the top-K as engine-ready config JSONs — each must pass
              the same `cli.main --mode error` gate CI runs before it
              is written — plus a machine-readable leaderboard
              (autotune_results.json; nothing in the repo reads it back
              since the ladder script that did was deleted — running a
              ranked config on the chip is a benchmark cell's job)
  calibrate   fit the hw_{peak_tflops,hbm_gbps,ici_gbps} constants from
              measured-vs-predicted reconciliation windows (the
              monitor's records, or any JSON row that embeds such a
              summary), so the next search ranks with THIS hardware's
              numbers

Mirrors the reference DeepSpeed's config-sweep culture and the
interconnect-aware partitioning search of arXiv:2501.04266, applied to
the ZeRO++-style transport knobs (arXiv:2306.10209) this repo
implements.  An empty search FAILS LOUDLY naming the binding
constraint — never an empty leaderboard with exit 0.
"""

import copy
import json
import os
import statistics
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .. import constants as C
from ..config import AnalysisConfig, validate_hw_constants
from .cost_model import hw_constants, per_lane_predictions
from .search_space import (AutotuneError, Candidate, Pruned, SearchSpace,
                           enumerate_candidates, nearest_divisor_worlds)

RESULTS_FILENAME = "autotune_results.json"

# the tiny trace model: the lint is about PROGRAM STRUCTURE (which the
# config decides), not model scale — same defaults as the lint CLI
DEFAULT_MODEL_KW = {"hidden": 64, "layers": 2, "heads": 4,
                    "seq": 64, "vocab": 256}

_LANE_KEYS = ("compute", "memory", "hidden_comm", "exposed_comm", "swap")


class AutotuneEmptySearch(AutotuneError):
    """Every candidate was pruned; the message names the binding
    constraint (the CLI exits nonzero with it)."""


@dataclass
class RankedCandidate:
    """A survivor with its audit evidence."""
    candidate: Candidate
    report: Any  # AuditReport

    @property
    def predicted_step_time_lb_s(self) -> float:
        return float(self.report.predicted_step_time_lb_s)


@dataclass
class SearchOutcome:
    """Everything one search run learned."""
    space: SearchSpace
    ranked: List[RankedCandidate]
    analysis_cfg: AnalysisConfig
    chips: int
    global_batch: int
    hbm_budget_mb: Optional[float]
    model_kw: Dict[str, int]
    calibration_file: Optional[str] = None
    base_config_path: Optional[str] = None
    # (name, floor_bytes) of hbm_floor prunes — empty-search diagnosis
    floor_prunes: List[Tuple[str, int]] = field(default_factory=list)
    # (name, liveness_bytes) of auditor hbm_budget prunes
    liveness_prunes: List[Tuple[str, int]] = field(default_factory=list)


# --------------------------------------------------------------------- #
# pre-trace pruning
# --------------------------------------------------------------------- #
def static_hbm_floor_bytes(knobs: Dict[str, Any], param_bytes: int,
                           opt_state_bytes: int, dp_world: int) -> int:
    """A SOUND lower bound on any step program's resident HBM for this
    candidate: parameter + optimizer-state residency under the ZeRO
    stage and offload tier, ignoring activations/grads entirely.  It can
    only prune true budget violations — the traced liveness estimate is
    the authoritative (and larger) number for survivors."""
    stage = int(knobs.get("zero_stage") or 0)
    offload = knobs.get("offload") or C.AUTOTUNING_OFFLOAD_TIER_NONE
    p = param_bytes
    if offload == C.AUTOTUNING_OFFLOAD_TIER_NVME:
        p = 0  # window buffers only
    elif stage >= 3:
        p //= max(1, dp_world)
    o = opt_state_bytes
    if offload != C.AUTOTUNING_OFFLOAD_TIER_NONE:
        o = 0  # host / NVMe resident
    elif stage >= 1:
        o //= max(1, dp_world)
    return p + o


def _optimizer_moments(opt_name: str) -> int:
    """Per-param moment count the configured optimizer MUST carry — a
    sound floor may only assume state the step cannot avoid (Adam
    family: two moments; momentum-SGD: one; plain SGD: none)."""
    opt_name = (opt_name or "").lower()
    if "adam" in opt_name:
        return 2
    if "momentum" in opt_name:
        return 1
    return 0


def _model_param_bytes(model_kw: Dict[str, int]) -> int:
    """Byte size of the tiny trace model's param tree, computed
    abstractly (eval_shape — no allocation).  Master params are fp32
    regardless of bf16 compute (GPT2Model casts at use), so this IS the
    resident size."""
    import jax
    from ..models import GPT2Config, GPT2Model
    cfg = GPT2Config(hidden_size=model_kw["hidden"],
                     num_layers=model_kw["layers"],
                     num_heads=model_kw["heads"],
                     n_positions=model_kw["seq"],
                     vocab_size=model_kw["vocab"])
    model = GPT2Model(cfg)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    from .auditor import _tree_bytes
    return _tree_bytes(shapes)


# --------------------------------------------------------------------- #
# per-candidate trace + audit
# --------------------------------------------------------------------- #
def _auditable_config(raw: Dict[str, Any]) -> Tuple[Dict[str, Any], bool]:
    """The config the auditor can trace.  NVMe-param candidates route to
    the ZeRO-Infinity layer-streaming engine, which the static auditor
    cannot trace — audit their RESIDENT TWIN (offload stripped) for the
    on-chip program shape, and charge the disk trips via the swap lane
    instead.  Returns (config, is_twin)."""
    zo = raw.get(C.ZERO_OPTIMIZATION) or {}
    op = zo.get(C.ZERO_OPTIMIZATION_OFFLOAD_PARAM) or {}
    if (op.get(C.OFFLOAD_PARAM_DEVICE) or "none") == "none":
        return raw, False
    twin = copy.deepcopy(raw)
    tzo = twin[C.ZERO_OPTIMIZATION]
    tzo.pop(C.ZERO_OPTIMIZATION_OFFLOAD_PARAM, None)
    tzo.pop(C.ZERO_OPTIMIZATION_OFFLOAD_OPTIMIZER, None)
    return twin, True


def audit_candidate(candidate: Candidate, model_kw: Dict[str, int],
                    analysis_cfg: AnalysisConfig):
    """Build the candidate's engine on the simulated mesh, trace its
    step program(s) abstractly, and return the full AuditReport (never
    executes a step).  NVMe candidates audit their resident twin with
    the swap lane folded in."""
    import jax
    import deepspeed_tpu as ds
    from ..models import GPT2Config, GPT2Model
    from .auditor import _tree_bytes, audit_engine

    raw = copy.deepcopy(candidate.config)
    # the engine is built with analysis off so an error-mode base config
    # cannot raise mid-build; the search applies findings itself
    raw[C.ANALYSIS] = dict(raw.get(C.ANALYSIS) or {},
                           **{C.ANALYSIS_MODE: "off"})
    traced_raw, is_twin = _auditable_config(raw)

    mcfg = GPT2Config(
        hidden_size=model_kw["hidden"], num_layers=model_kw["layers"],
        num_heads=model_kw["heads"], n_positions=model_kw["seq"],
        vocab_size=model_kw["vocab"],
        bf16=bool(raw.get(C.BF16, {}).get(C.BF16_ENABLED, False)))
    model = GPT2Model(mcfg)
    params = model.init_params(jax.random.PRNGKey(0))

    ds.reset_mesh_context()
    engine = None
    try:
        engine, _, _, _ = ds.initialize(model=model, config=traced_raw,
                                        model_parameters=params)
        swap = None
        if is_twin:
            from ..config import ZeroConfig
            from .cost_model import swap_lane
            orig_zero = ZeroConfig.from_dict(
                candidate.config.get(C.ZERO_OPTIMIZATION))
            swap = swap_lane(orig_zero, engine.config.aio_config,
                             param_bytes=_tree_bytes(engine.params),
                             opt_state_bytes=_tree_bytes(engine.opt_state))
        # 1-bit candidates are ranked on their STEADY-STATE program: the
        # post-freeze compressed phase is what the run spends its life
        # in (the warmup program is the dense twin, already enumerated)
        lb = (traced_raw.get(C.ZERO_OPTIMIZATION) or {}).get(
            C.ZERO_OPTIMIZATION_LOW_BANDWIDTH) or {}
        phase = ("compressed" if lb.get(C.LOW_BANDWIDTH_ONEBIT)
                 else None)
        return audit_engine(engine, cfg=analysis_cfg, multihost=False,
                            swap=swap, phase=phase)
    finally:
        if engine is not None and getattr(engine, "_preemption",
                                          None) is not None:
            engine._preemption.uninstall()
        ds.reset_mesh_context()


# --------------------------------------------------------------------- #
# the search
# --------------------------------------------------------------------- #
def run_search(base_raw: Dict[str, Any], tune_cfg=None, *,
               chips: Optional[int] = None,
               global_batch: Optional[int] = None,
               hbm_budget_mb: Optional[float] = None,
               model_kw: Optional[Dict[str, int]] = None,
               calibration: Optional[Any] = None,
               base_config_path: Optional[str] = None) -> SearchOutcome:
    """Run the full offline search.  CLI flags (the keyword args) win
    over the config's ``autotuning`` block; ``calibration`` is a path or
    an already-loaded hw mapping.  Raises AutotuneEmptySearch when
    pruning eliminates every candidate."""
    import jax
    from ..config import AutotuningConfig

    if tune_cfg is None:
        tune_cfg = AutotuningConfig.from_dict(base_raw.get(C.AUTOTUNING))
    chips = chips if chips is not None else tune_cfg.chips
    if chips is None:
        raise AutotuneError(
            "the chip count is required: set autotuning.chips or pass "
            "--chips")
    if jax.device_count() != chips:
        raise AutotuneError(
            f"search wants a {chips}-device mesh but jax initialized "
            f"{jax.device_count()} device(s) — the tune CLI sets "
            "xla_force_host_platform_device_count before jax import; "
            "unset any conflicting XLA_FLAGS and rerun")
    if global_batch is None:
        global_batch = tune_cfg.global_batch
    if global_batch is None:
        global_batch = base_raw.get(C.TRAIN_BATCH_SIZE)
    if global_batch is None:
        micro = int(base_raw.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU) or 1)
        gas = int(base_raw.get(C.GRADIENT_ACCUMULATION_STEPS) or 1)
        global_batch = micro * gas * chips
    global_batch = int(global_batch)
    model_kw = dict(DEFAULT_MODEL_KW, **(model_kw or {}))

    analysis_raw = dict(base_raw.get(C.ANALYSIS) or {})
    analysis_raw[C.ANALYSIS_MODE] = "off"  # search applies findings
    if hbm_budget_mb is None:
        hbm_budget_mb = tune_cfg.hbm_budget_mb
    if hbm_budget_mb is None:
        b = analysis_raw.get(C.ANALYSIS_HBM_BUDGET_MB)
        hbm_budget_mb = None if b is None else float(b)
    if hbm_budget_mb is not None:
        analysis_raw[C.ANALYSIS_HBM_BUDGET_MB] = hbm_budget_mb
    analysis_cfg = AnalysisConfig.from_dict(analysis_raw)

    calibration_file = None
    if calibration is None:
        calibration = tune_cfg.calibration_file
    if isinstance(calibration, str):
        calibration_file = calibration
        calibration = load_calibration(calibration)
    if calibration:
        analysis_cfg = analysis_cfg.hw_overridden(calibration)

    space = enumerate_candidates(base_raw, tune_cfg, chips, global_batch)
    outcome = SearchOutcome(
        space=space, ranked=[], analysis_cfg=analysis_cfg, chips=chips,
        global_batch=global_batch, hbm_budget_mb=hbm_budget_mb,
        model_kw=model_kw, calibration_file=calibration_file,
        base_config_path=base_config_path)

    # ---- pre-trace HBM-floor prune -------------------------------- #
    survivors: List[Candidate] = []
    if hbm_budget_mb is not None:
        budget_bytes = int(hbm_budget_mb * 1024 * 1024)
        param_bytes = _model_param_bytes(model_kw)
        # moment count from the CONFIGURED optimizer — a sound floor
        # may only assume state the step cannot avoid (the old
        # hardcoded Adam 2x over-pruned SGD searches)
        opt_bytes = _optimizer_moments(
            (base_raw.get(C.OPTIMIZER) or {}).get("type")) * param_bytes
        for cand in space.candidates:
            mesh = cand.knobs["mesh"]
            dp = mesh["data"] * mesh["expert"]
            floor = static_hbm_floor_bytes(cand.knobs, param_bytes,
                                           opt_bytes, dp)
            if floor > budget_bytes:
                space.pruned.append(Pruned(
                    name=cand.name, stage="hbm_floor",
                    reason=(f"static param+optimizer residency floor "
                            f"{floor} B exceeds hbm_budget_mb="
                            f"{hbm_budget_mb} ({budget_bytes} B) before "
                            "tracing")))
                outcome.floor_prunes.append((cand.name, floor))
            else:
                survivors.append(cand)
    else:
        survivors = list(space.candidates)

    # ---- trace + audit + rank ------------------------------------- #
    for cand in survivors:
        try:
            report = audit_candidate(cand, model_kw, analysis_cfg)
        except Exception as e:  # noqa: BLE001 — a candidate that cannot
            # even build/trace is pruned with provenance, not fatal
            space.pruned.append(Pruned(
                name=cand.name, stage="trace",
                reason=f"{type(e).__name__}: {e}"[:300]))
            continue
        if report.has_errors:
            first = next(f for f in report.findings
                         if f.severity == "error")
            space.pruned.append(Pruned(
                name=cand.name, stage="auditor",
                reason=f"[{first.rule}] {first.message}"[:300]))
            if first.rule == "hbm_budget":
                outcome.liveness_prunes.append(
                    (cand.name, int(report.peak_hbm_bytes)))
            continue
        outcome.ranked.append(RankedCandidate(cand, report))

    if not outcome.ranked:
        raise AutotuneEmptySearch(_empty_search_message(outcome))
    outcome.ranked.sort(
        key=lambda r: (r.predicted_step_time_lb_s, r.candidate.name))
    return outcome


def _empty_search_message(outcome: SearchOutcome) -> str:
    """Name the binding constraint instead of printing an empty
    leaderboard."""
    space = outcome.space
    stages = [p.stage for p in space.pruned]
    header = (f"autotune search pruned all {space.n_enumerated} "
              "enumerated candidate(s): ")
    if stages and all(s == "batch" for s in stages):
        worlds = nearest_divisor_worlds(outcome.global_batch,
                                        outcome.chips)
        return (header + "batch-triple infeasibility — global batch "
                f"{outcome.global_batch} admits no (micro, gas) split "
                f"on any enumerated mesh of {outcome.chips} chips. "
                f"Nearest chip counts whose data world divides the "
                f"batch: {worlds}. First reason: "
                f"{space.pruned[0].reason}")
    hbm_prunes = outcome.floor_prunes + outcome.liveness_prunes
    # the HBM diagnosis may only fire when every traced prune actually
    # WAS an hbm_budget finding — an auditor prune for a different rule
    # (overlap, lockstep, ...) would survive any budget raise
    hbm_auditor_names = {name for name, _ in outcome.liveness_prunes}
    if hbm_prunes and all(
            p.stage in ("hbm_floor", "batch")
            or (p.stage == "auditor" and p.name in hbm_auditor_names)
            for p in space.pruned):
        name, smallest = min(hbm_prunes, key=lambda kv: kv[1])
        mib = smallest / (1024 * 1024)
        return (header + "HBM budget is the binding constraint — "
                f"hbm_budget_mb={outcome.hbm_budget_mb} is below the "
                f"smallest feasible estimate {mib:.1f} MiB (candidate "
                f"{name}). Raise the budget, stream params (zero stage "
                "3 + streamed variant), or add an offload tier to the "
                "search axes")
    lines = "; ".join(f"{p.name}[{p.stage}]: {p.reason}"
                      for p in space.pruned[:5])
    return header + f"first reasons: {lines}"


# --------------------------------------------------------------------- #
# emission: engine-ready configs + machine-readable leaderboard
# --------------------------------------------------------------------- #
def _leaderboard_entry(rank: int, rc: RankedCandidate,
                       config_file: Optional[str]) -> Dict[str, Any]:
    report = rc.report
    st = report.step_time
    lanes = {k: round(float(v), 9)
             for k, v in per_lane_predictions(st).items()
             if isinstance(v, (int, float))}
    entry = {
        "rank": rank,
        "name": rc.candidate.name,
        "predicted_step_time_lb_s": round(
            rc.predicted_step_time_lb_s, 9),
        "bound": st["bound"],
        "lanes": lanes,
        "wire_bytes_per_step": int(report.wire_bytes_per_step),
        "peak_hbm_bytes": int(report.peak_hbm_bytes),
        "overlap_efficiency": round(float(report.overlap_efficiency), 4),
        "findings": report.counts(),
        "knobs": rc.candidate.knobs,
        "config_file": config_file,
    }
    if st.get("swap") is not None:
        entry["swap"] = st["swap"]
    return entry


def results_payload(outcome: SearchOutcome, top_k: int,
                    entries: List[Dict[str, Any]]) -> Dict[str, Any]:
    return {
        "schema": C.AUTOTUNE_RESULTS_SCHEMA,
        "base_config": outcome.base_config_path,
        "chips": outcome.chips,
        "global_batch": outcome.global_batch,
        "hbm_budget_mb": outcome.hbm_budget_mb,
        "top_k": top_k,
        "model": dict(outcome.model_kw),
        "hw": hw_constants(outcome.analysis_cfg),
        "calibration_file": outcome.calibration_file,
        "n_enumerated": outcome.space.n_enumerated,
        "n_candidates": len(outcome.space.candidates),
        "n_survivors": len(outcome.ranked),
        "pruned": [{"name": p.name, "stage": p.stage,
                    "reason": p.reason} for p in outcome.space.pruned],
        "leaderboard": entries,
    }


def emit_results(outcome: SearchOutcome, out_dir: str,
                 top_k: int) -> Dict[str, Any]:
    """Write the top-K engine-ready configs plus autotune_results.json.

    Every emitted config must itself pass the SAME ``cli.main --mode
    error`` gate CI runs over docs/examples — a config the auditor
    rejects is never written (it is recorded as an ``emit_gate`` prune
    and the next ranked candidate is promoted)."""
    os.makedirs(out_dir, exist_ok=True)
    entries: List[Dict[str, Any]] = []
    for rc in outcome.ranked:
        if len(entries) >= top_k:
            break
        rank = len(entries) + 1
        cfg = copy.deepcopy(rc.candidate.config)
        # the emitted config self-enforces the search's HBM budget
        analysis = dict(cfg.get(C.ANALYSIS) or {})
        if outcome.hbm_budget_mb is not None:
            analysis[C.ANALYSIS_HBM_BUDGET_MB] = outcome.hbm_budget_mb
        if analysis:
            cfg[C.ANALYSIS] = analysis
        cfg["_autotune"] = {
            "rank": rank, "name": rc.candidate.name,
            "predicted_step_time_lb_s": round(
                rc.predicted_step_time_lb_s, 9),
            "chips": outcome.chips,
            "global_batch": outcome.global_batch,
            "base_config": outcome.base_config_path,
            "model": dict(outcome.model_kw),
        }
        fname = f"autotune_rank{rank}_{rc.candidate.name}.json"
        ok, gate_tail = _emit_gate(cfg, outcome, out_dir)
        if not ok:
            outcome.space.pruned.append(Pruned(
                name=rc.candidate.name, stage="emit_gate",
                reason=("emitted config failed cli.main --mode error — "
                        "never emitting a config the auditor rejects: "
                        + gate_tail)[:300]))
            continue
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(cfg, f, indent=2)
            f.write("\n")
        entries.append(_leaderboard_entry(rank, rc, fname))
    if not entries:
        raise AutotuneEmptySearch(
            "every ranked candidate failed the emit gate "
            "(cli.main --mode error) — the search and the gate disagree; "
            "rerun with --json and inspect the pruned records")
    payload = results_payload(outcome, top_k, entries)
    validate_results(payload)
    with open(os.path.join(out_dir, RESULTS_FILENAME), "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return payload


def _emit_gate(cfg: Dict[str, Any], outcome: SearchOutcome,
               out_dir: str) -> Tuple[bool, str]:
    """Run the literal CI lint (cli.main --mode error) over the
    candidate config before it is written.  NVMe candidates gate their
    resident twin — the exact program the search ranked.  The lint's
    own stdout/stderr is captured (the tune CLI's --json contract keeps
    stdout parseable); the tail rides the prune reason on failure."""
    import contextlib
    import io

    import deepspeed_tpu as ds
    from .cli import main as cli_main
    gated, is_twin = _auditable_config(cfg)
    if is_twin:
        gated = copy.deepcopy(gated)
        gated.setdefault("_autotune", {})["emit_gate"] = "resident_twin"
    pending = os.path.join(out_dir, ".pending_emit_gate.json")
    with open(pending, "w") as f:
        json.dump(gated, f)
    buf = io.StringIO()
    try:
        argv = ["--config", pending, "--mode", "error",
                "--hidden", str(outcome.model_kw["hidden"]),
                "--layers", str(outcome.model_kw["layers"]),
                "--heads", str(outcome.model_kw["heads"]),
                "--seq", str(outcome.model_kw["seq"]),
                "--vocab", str(outcome.model_kw["vocab"])]
        if outcome.chips > 1:
            argv += ["--devices", str(outcome.chips)]
        ds.reset_mesh_context()
        try:
            with contextlib.redirect_stdout(buf), \
                    contextlib.redirect_stderr(buf):
                ok = cli_main(argv) == 0
            return ok, buf.getvalue()[-200:]
        finally:
            ds.reset_mesh_context()
    finally:
        try:
            os.remove(pending)
        except OSError:
            pass


def validate_results(payload: Dict[str, Any]) -> None:
    """Schema check for autotune_results.json — shared by the writer
    and the CI smoke test, so a malformed
    artifact fails at the boundary with a named defect."""
    def _fail(msg):
        raise AutotuneError(f"invalid autotune results: {msg}")

    if not isinstance(payload, dict):
        _fail(f"payload must be a dict, got {type(payload).__name__}")
    if payload.get("schema") != C.AUTOTUNE_RESULTS_SCHEMA:
        _fail(f"schema tag {payload.get('schema')!r} != "
              f"{C.AUTOTUNE_RESULTS_SCHEMA!r}")
    for key in ("chips", "global_batch", "model", "hw", "leaderboard",
                "pruned", "n_enumerated", "n_candidates", "n_survivors"):
        if key not in payload:
            _fail(f"missing key {key!r}")
    board = payload["leaderboard"]
    if not isinstance(board, list) or not board:
        _fail("leaderboard must be a non-empty list")
    for i, entry in enumerate(board):
        if entry.get("rank") != i + 1:
            _fail(f"leaderboard ranks must be consecutive from 1, got "
                  f"{entry.get('rank')} at index {i}")
        for key in ("name", "predicted_step_time_lb_s", "bound",
                    "lanes", "knobs", "config_file"):
            if key not in entry:
                _fail(f"leaderboard[{i}] missing {key!r}")
        if not (isinstance(entry["predicted_step_time_lb_s"],
                           (int, float))
                and entry["predicted_step_time_lb_s"] > 0):
            _fail(f"leaderboard[{i}].predicted_step_time_lb_s must be "
                  f"> 0, got {entry['predicted_step_time_lb_s']}")
        missing = [k for k in _LANE_KEYS if k not in entry["lanes"]]
        if missing:
            _fail(f"leaderboard[{i}].lanes missing {missing}")
    lbs = [e["predicted_step_time_lb_s"] for e in board]
    if lbs != sorted(lbs):
        _fail("leaderboard is not sorted by predicted_step_time_lb_s")
    for key in ("hw",):
        hw = payload[key]
        if not all(k in hw for k in C.ANALYSIS_HW_KEYS):
            _fail(f"hw block missing canonical keys "
                  f"{list(C.ANALYSIS_HW_KEYS)}")


# --------------------------------------------------------------------- #
# calibration: reconciliation windows -> fitted hardware constants
# --------------------------------------------------------------------- #
def extract_reconciliation_windows(path: str) -> List[Dict[str, Any]]:
    """Pull (measured step time, predicted lanes) pairs out of a
    records artifact: a monitor JSONL stream (kind == "reconcile"
    records), a JSON line/file with an embedded "reconciliation"
    summary (no script of the repo writes such rows any longer: only
    tests exercise this form, ROADMAP D5), or a bare list of window
    dicts."""
    objs: List[Any] = []
    with open(path) as f:
        text = f.read()
    try:
        top = json.loads(text)
        objs = top if isinstance(top, list) else [top]
    except ValueError:
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                objs.append(json.loads(line))
            except ValueError:
                continue
    windows = []
    for obj in objs:
        if not isinstance(obj, dict):
            continue
        if isinstance(obj.get("reconciliation"), dict):
            obj = obj["reconciliation"]
        m = obj.get("measured_step_time_s")
        lanes = obj.get("lanes")
        if m is None or not isinstance(lanes, dict):
            continue
        windows.append({"measured_step_time_s": float(m),
                        "lanes": lanes})
    return windows


_BINDING_TO_HW = {"compute": C.ANALYSIS_HW_PEAK_TFLOPS,
                  "memory": C.ANALYSIS_HW_HBM_GBPS,
                  "hidden_comm": C.ANALYSIS_HW_ICI_GBPS}


def fit_hw_calibration(windows: List[Dict[str, Any]],
                       base_hw: Dict[str, float],
                       source: Optional[str] = None) -> Dict[str, Any]:
    """Fit the canonical hardware constants from measured windows.

    Per window: the binding roofline lane (largest of compute / memory /
    hidden_comm) absorbs the measured time net of exposed comm —
    ``scale = (measured - exposed) / t_binding`` — and its constant is
    divided by the median scale across windows (t = work / constant).
    Comm-exposed windows (exposed > binding) fit the ICI constant from
    the exposed term instead.  Swap-tier windows (a nonzero ``swap``
    lane) are SKIPPED entirely: the disk time is already priced at the
    measured aio sweep ceiling, and a summary window cannot separate it
    back out of the measured step — attributing it to a roofline lane
    would corrupt that lane's constant (an NVMe row's serialized disk
    seconds would read as "compute is 6x slower").  Constants with no
    evidence keep their base values and are marked unfitted."""
    scales: Dict[str, List[float]] = {k: [] for k in C.ANALYSIS_HW_KEYS}
    used = skipped = 0
    for w in windows:
        m = float(w.get("measured_step_time_s") or 0.0)
        lanes = w.get("lanes") or {}
        if m <= 0 or not lanes:
            skipped += 1
            continue
        if float(lanes.get("swap") or 0.0) > 0.0:
            skipped += 1
            continue
        binding = max(_BINDING_TO_HW,
                      key=lambda k: float(lanes.get(k) or 0.0))
        t_b = float(lanes.get(binding) or 0.0)
        exposed = float(lanes.get("exposed_comm") or 0.0)
        if exposed > t_b and exposed > 0:
            scale = (m - t_b) / exposed
            key = C.ANALYSIS_HW_ICI_GBPS
        elif t_b > 0:
            scale = (m - exposed) / t_b
            key = _BINDING_TO_HW[binding]
        else:
            skipped += 1
            continue
        if scale <= 0:
            skipped += 1
            continue
        scales[key].append(scale)
        used += 1
    hw = {k: float(base_hw[k]) for k in C.ANALYSIS_HW_KEYS}
    fitted = {k: False for k in C.ANALYSIS_HW_KEYS}
    for key, ss in scales.items():
        if ss:
            hw[key] = float(base_hw[key]) / statistics.median(ss)
            fitted[key] = True
    validate_hw_constants(hw, context="calibration")
    return {
        "schema": C.HW_CALIBRATION_SCHEMA,
        "hw": hw,
        "fitted": fitted,
        "base_hw": {k: float(base_hw[k]) for k in C.ANALYSIS_HW_KEYS},
        "windows_used": used,
        "windows_skipped": skipped,
        "source": source,
    }


def load_calibration(path: str) -> Dict[str, float]:
    """Load + validate a calibration file written by ``calibrate`` —
    returns the hw mapping under the canonical names."""
    with open(path) as f:
        payload = json.load(f)
    if not isinstance(payload, dict) or \
            payload.get("schema") != C.HW_CALIBRATION_SCHEMA:
        raise AutotuneError(
            f"{path}: not a calibration file (expected schema "
            f"{C.HW_CALIBRATION_SCHEMA!r}, got "
            f"{payload.get('schema') if isinstance(payload, dict) else type(payload).__name__!r})")
    hw = payload.get("hw") or {}
    missing = [k for k in C.ANALYSIS_HW_KEYS if k not in hw]
    if missing:
        raise AutotuneError(
            f"{path}: calibration hw block missing {missing}")
    return validate_hw_constants(hw, context="calibration")
