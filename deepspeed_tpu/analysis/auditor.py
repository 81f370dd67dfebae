"""Program Auditor — trace the engine's train step to closed jaxprs
(without executing them) and run the static lint registry.

The engine's failure modes stopped being Python bugs when the whole
optimizer step became one XLA program (PR 3) and params started streaming
through quantized collectives (PR 1): a stray host callback fencing the
gas scan, a dropped donate_argnums doubling HBM, a collective sequence
that diverges across hosts and hangs the pod, a silent fp32 upcast on a
bf16 wire.  All of those are *program-shape* properties readable off the
jaxpr — so they are linted here, statically, at engine init / in CI,
instead of being discovered on a burning pod.

Entry points:
  ``audit_engine(engine)``            — full report for a built engine
  ``ProgramAuditor(cfg).run(targets)``— rule registry over explicit
                                        targets (tests, CLI fixtures)
"""

from typing import Any, List, Optional, Tuple

import numpy as np

from .cost_model import build_step_time_model, program_io_bytes
from .findings import AuditReport, Finding, ProgramAuditError
from .hlo_audit import SpmdWaiver, audit_target_hlo, summarize_hlo
from .liveness import estimate_liveness, hbm_budget_finding
from .overlap import (analyze_overlap, overlap_efficiency,
                      overlap_rule_findings, summarize_overlap)
from .rules import (ArgInfo, AuditTarget, STATIC_RULES,
                    comm_budget_finding, donation_waste_bytes,
                    lockstep_expectation_finding, step_wire_bytes)
from .signature import combine_signatures, lockstep_signature


def _tree_bytes(tree) -> int:
    import jax
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            total += int(np.prod(shape, initial=1)) * np.dtype(dtype).itemsize
        except TypeError:
            # extended dtypes (PRNG keys): count the key payload
            total += int(np.prod(shape, initial=1)) * 4
    return total


def _leaf_count(tree) -> int:
    import jax
    return len(jax.tree.leaves(tree))


def _expand_invars(arg_trees, donated_labels):
    """Flattened per-invar (donated, label) lists for a traced call:
    make_jaxpr flattens the arguments in order, so each argument
    subtree's flags expand across its leaf count."""
    donated, labels = [], []
    for tree, (is_donated, label) in zip(arg_trees, donated_labels):
        n = _leaf_count(tree)
        donated.extend([is_donated] * n)
        labels.extend([f"{label}[{k}]" for k in range(n)])
    return donated, labels


def _engine_scan_info(engine) -> dict:
    """Scan-structure provenance: the streamed-ZeRO-3 layer plan
    (runtime/zero/stage3_streaming.py, populated during tracing)."""
    info = {}
    stream = getattr(engine, "_zero3_stream", None)
    plan = getattr(stream, "last_plan", None)
    if plan is not None:
        info["zero3_streaming"] = {
            "layers_per_step": plan.layers_per_step,
            "prefetch": plan.prefetch,
            "forfeited": plan.forfeited,
            "num_layers": plan.num_layers,
            "params_per_layer": plan.params_per_layer,
        }
    return info


def _grads_template(engine):
    """ShapeDtypeStructs of the accumulated-grad tree (the apply
    program's 4th argument) without running a grad step."""
    import jax
    import jax.numpy as jnp
    grads_half = (engine.config.bf16.enabled
                  and engine.config.bf16.grads_in_compute_dtype)

    def one(p):
        dtype = p.dtype
        if grads_half and jnp.issubdtype(p.dtype, jnp.floating):
            dtype = engine.compute_dtype
        return jax.ShapeDtypeStruct(p.shape, dtype)

    return jax.tree.map(one, engine.params)


def synthesize_sample_batch(engine) -> Optional[Tuple]:
    """A ShapeDtypeStruct batch for tracing the grad program, derived
    from the model's declared shapes (GPT2/BERT-style configs expose
    n_positions + vocab_size).  None when the model's input contract is
    unknown — the auditor then audits the apply program only."""
    import jax
    mcfg = getattr(engine.module, "config", None)
    seq = getattr(mcfg, "n_positions", None)
    if seq is None:
        seq = getattr(mcfg, "max_position_embeddings", None)
    if seq is None or getattr(mcfg, "vocab_size", None) is None:
        return None
    # the dispatched batch is GLOBAL (micro x dp_world): _shard_batch
    # places a full cross-host array, and program structure depends on it
    # (the ZeRO-3 streamed scan only engages when the batch divides the
    # ZeRO world — a micro-batch-sized probe would audit the fallback
    # program instead of the one training dispatches)
    batch = engine.train_micro_batch_size_per_gpu() * engine.world_size
    return (jax.ShapeDtypeStruct((batch, int(seq)), np.int32),)


def _sharded_batch_structs(engine, sample_batch):
    """ShapeDtypeStructs carrying the shardings ``_shard_batch`` would
    place — the HLO audit must compile the program TRAINING dispatches,
    and in/out shardings are part of what the SPMD partitioner sees (an
    unsharded probe batch would audit a different partitioning)."""
    import jax
    dp = engine.world_size
    data = (engine.mesh_ctx.sharding(("data", "expert")) if dp > 1
            else engine.mesh_ctx.replicated())
    rep = engine.mesh_ctx.replicated()

    def place(s):
        fits = len(s.shape) > 0 and s.shape[0] % dp == 0
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=data if fits else rep)
    return tuple(place(s) for s in sample_batch)


def _engine_spmd_waivers(engine, kind: str) -> Tuple[SpmdWaiver, ...]:
    """Compiler-inserted gather wire the engine's sharding contract
    PREDICTS, so the HLO cross-check can tell it from a silent reshard:
    ZeRO stage >= 1 re-gathers the updated params at the optimizer
    boundary (the apply program's GSPMD all-gathers ARE the DeepSpeed
    wire model), and stage-3 leaves outside the explicit streamed path
    are gathered at use in forward and backward."""
    stage = engine.config.zero_config.stage
    pbytes = _tree_bytes(engine.params)
    slack = pbytes // 4 + (1 << 20)
    waivers = []
    if kind == "apply" and stage >= 1:
        waivers.append(SpmdWaiver("zero_param_regather", pbytes + slack,
                                  ("all-gather",)))
    if kind == "grad" and stage >= 3:
        waivers.append(SpmdWaiver("zero3_param_gather_at_use",
                                  2 * pbytes + slack, ("all-gather",)))
    return tuple(waivers)


def _onebit_wire_template(engine):
    """ShapeDtypeStructs of the worker-stacked wire-error state — the
    compressed-phase programs carry it even when the engine itself is
    still in warmup (the auditor prices both phases at init)."""
    import jax
    W = engine._onebit["world"]
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((W,) + tuple(p.shape), np.float32),
        engine.params)


def _onebit_engine_targets(engine, sample_batch) -> List[AuditTarget]:
    """Compressed-phase (post-freeze) audit targets for the onebit wire
    tier (docs/onebit.md).  Program identity differs from warmup — the
    dense DP grad allreduce is gone from the grad program and the
    momentum sync rides the apply program's packed wire — so the phase
    is part of what gets traced, priced, and lockstep-pinned."""
    import jax
    progs = engine._onebit_get_programs()
    wire_tmpl = _onebit_wire_template(engine)
    wire_sharding = progs["wire_sharding"]
    wire_sharded = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=wire_sharding), wire_tmpl)
    targets: List[AuditTarget] = []

    if sample_batch is not None:
        closed = jax.make_jaxpr(
            lambda p, s, r, *b: progs["loss_and_grads"](p, s, r, *b))(
            engine.params, engine.scaler_state, engine._rng,
            *sample_batch)
        args = [
            ArgInfo("params", _tree_bytes(engine.params), False, False),
            ArgInfo("scaler_state", _tree_bytes(engine.scaler_state),
                    False, False),
            ArgInfo("batch", _tree_bytes(sample_batch), False, False),
        ]
        donated_invars, labels = _expand_invars(
            (engine.params, engine.scaler_state, engine._rng,
             list(sample_batch)),
            [(False, "params"), (False, "scaler_state"),
             (False, "rng"), (False, "batch")])
        sharded_batch = _sharded_batch_structs(engine, sample_batch)
        targets.append(AuditTarget(
            "grad_step", closed, args,
            donated_invars=donated_invars, invar_labels=labels,
            resident_extra_bytes=(_tree_bytes(engine.opt_state) +
                                  _tree_bytes(wire_tmpl)),
            scan_info=_engine_scan_info(engine),
            lower=lambda: progs["grad_fn"].lower(
                engine.params, engine.scaler_state, engine._rng,
                *sharded_batch).compile().as_text(),
            spmd_waivers=_engine_spmd_waivers(engine, "grad")))

    grads = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            (engine._onebit["world"],) + tuple(s.shape), s.dtype),
        _grads_template(engine))
    healthy = jax.ShapeDtypeStruct((), np.bool_)
    closed = jax.make_jaxpr(
        lambda p, o, s, g, e, h: progs["apply_core"](p, o, s, g, e, h))(
        engine.params, engine.opt_state, engine.scaler_state, grads,
        wire_tmpl, healthy)
    donated = progs["apply_donate_argnums"]
    args = [
        ArgInfo("params", _tree_bytes(engine.params), 0 in donated, True),
        ArgInfo("opt_state", _tree_bytes(engine.opt_state), 1 in donated,
                True),
        ArgInfo("scaler_state", _tree_bytes(engine.scaler_state),
                2 in donated, True),
        ArgInfo("grads", _tree_bytes(grads), 3 in donated, True),
        ArgInfo("wire_error", _tree_bytes(wire_tmpl), 4 in donated, True),
    ]
    donated_invars, labels = _expand_invars(
        (engine.params, engine.opt_state, engine.scaler_state, grads,
         wire_tmpl, healthy),
        [(0 in donated, "params"), (1 in donated, "opt_state"),
         (2 in donated, "scaler_state"), (3 in donated, "grads"),
         (4 in donated, "wire_error"), (False, "healthy")])
    grads_sharded = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=wire_sharding), grads)
    healthy_arr = jax.ShapeDtypeStruct(
        (), np.bool_, sharding=engine.mesh_ctx.replicated())
    targets.append(AuditTarget(
        "apply_step", closed, args,
        donated_invars=donated_invars, invar_labels=labels,
        scan_info=_engine_scan_info(engine),
        lower=lambda: progs["apply_fn"].lower(
            engine.params, engine.opt_state, engine.scaler_state,
            grads_sharded, wire_sharded,
            healthy_arr).compile().as_text(),
        spmd_waivers=_engine_spmd_waivers(engine, "apply")))
    return targets


def engine_targets(engine, sample_batch: Optional[Tuple] = None,
                   phase: Optional[str] = None) -> List[AuditTarget]:
    """Trace the engine's step program(s) abstractly.

    The grad program (dispatched gas times per step) and the apply
    program.  Donation facts come from the argnum tuple the engine
    recorded next to its jit call (`_apply_donate_argnums`) so the
    audit reflects what is actually dispatched.

    ``phase`` selects which of an onebit engine's two step programs to
    trace ("warmup" / "compressed" — docs/onebit.md); None follows the
    engine's current phase.  Non-onebit engines ignore it.
    """
    import jax
    targets: List[AuditTarget] = []
    if sample_batch is None:
        sample_batch = synthesize_sample_batch(engine)

    onebit = getattr(engine, "_onebit", None)
    if onebit is not None:
        if phase is None:
            phase = getattr(engine, "_onebit_phase", "warmup")
        if phase == "compressed":
            return _onebit_engine_targets(engine, sample_batch)

    if sample_batch is not None:
        closed = jax.make_jaxpr(
            lambda p, s, r, *b: engine._loss_and_grads(p, s, r, *b))(
            engine.params, engine.scaler_state, engine._rng,
            *sample_batch)
        args = [
            ArgInfo("params", _tree_bytes(engine.params), False, False),
            ArgInfo("scaler_state", _tree_bytes(engine.scaler_state),
                    False, False),
            ArgInfo("batch", _tree_bytes(sample_batch), False, False),
        ]
        donated_invars, labels = _expand_invars(
            (engine.params, engine.scaler_state, engine._rng,
             list(sample_batch)),
            [(False, "params"), (False, "scaler_state"),
             (False, "rng"), (False, "batch")])
        # opt_state sits in HBM while the grad program runs
        sharded_batch = _sharded_batch_structs(engine, sample_batch)
        targets.append(AuditTarget(
            "grad_step", closed, args,
            donated_invars=donated_invars, invar_labels=labels,
            resident_extra_bytes=_tree_bytes(engine.opt_state),
            scan_info=_engine_scan_info(engine),
            lower=lambda: engine._grad_fn.lower(
                engine.params, engine.scaler_state, engine._rng,
                *sharded_batch).compile().as_text(),
            spmd_waivers=_engine_spmd_waivers(engine, "grad")))

    if engine._apply_core is not None:
        grads = _grads_template(engine)
        arg_trees = (engine.params, engine.opt_state, engine.scaler_state,
                     grads)
        apply = engine._apply_core
        if getattr(engine, "_weights", None) is not None:
            # the dispatched apply also writes the weights' compute-dtype
            # copy, into the old copy's donated buffers: audit that one
            from ..runtime.engine import _masked_leaves
            apply = engine._apply_fn.__wrapped__
            arg_trees += (_masked_leaves(engine._weights,
                                         engine._copy_mask),)
        closed = jax.make_jaxpr(lambda *a: apply(*a))(*arg_trees)
        donated = getattr(engine, "_apply_donate_argnums", (0, 1, 3))
        names = ("params", "opt_state", "scaler_state", "grads",
                 "weight_copy")
        args = [ArgInfo(name, _tree_bytes(tree), k in donated, True)
                for k, (name, tree) in enumerate(zip(names, arg_trees))]
        donated_invars, labels = _expand_invars(
            arg_trees, [(k in donated, name)
                        for k, name in enumerate(names[:len(arg_trees)])])
        grads_sharded = None
        if engine._apply_fn is not None and engine.grad_shardings is not None:
            grads_sharded = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                grads, engine.grad_shardings)
        targets.append(AuditTarget(
            "apply_step", closed, args,
            donated_invars=donated_invars, invar_labels=labels,
            scan_info=_engine_scan_info(engine),
            lower=(None if grads_sharded is None else
                   lambda: engine._apply_fn.lower(
                       engine.params, engine.opt_state,
                       engine.scaler_state, grads_sharded,
                       *arg_trees[4:]).compile().as_text()),
            spmd_waivers=_engine_spmd_waivers(engine, "apply")))
    return targets


class ProgramAuditor:
    """Run the static rule registry over audit targets."""

    def __init__(self, cfg):
        self.cfg = cfg

    def run(self, targets: List[AuditTarget], gas: int = 1,
            swap=None, hlo: bool = False) -> AuditReport:
        """``swap`` is an optional offload-tier traffic model
        (cost_model.swap_lane) folded into the step-time lower bound —
        a config streaming params/optimizer state from NVMe must not
        rank as if they were HBM-resident.  ``hlo`` additionally lowers
        each target through XLA's SPMD partitioner (compile-only) and
        cross-checks the jaxpr wire story against the compiled program
        (analysis/hlo_audit.py)."""
        report = AuditReport(targets=[t.label for t in targets])
        hlo_audits = []
        for target in targets:
            for _rule_id, rule in STATIC_RULES:
                report.findings.extend(rule(target, self.cfg))
        sigs = []
        contributors = []
        all_records = []
        total_flops = 0
        io_bytes = 0
        peak_liveness = None
        from ..profiling.flops_profiler import count_jaxpr_flops
        for target in targets:
            sig, seq = lockstep_signature(target.closed_jaxpr)
            sigs.append(sig)
            # the grad program is dispatched gas times per optimizer
            # step — its collectives (and wire bytes) repeat in lockstep
            repeat = gas if target.label == "grad_step" else 1
            report.collective_sequence.extend(seq * repeat)
            total, contrib = step_wire_bytes(target.closed_jaxpr)
            report.wire_bytes_per_step += total * repeat
            contributors.extend((f"{target.label}:{k}", v * repeat)
                                for k, v in contrib)
            if hlo:
                hlo_audit, hlo_findings = audit_target_hlo(
                    target, self.cfg, jaxpr_wire_bytes=total)
                hlo_audits.append((hlo_audit, repeat))
                report.findings.extend(hlo_findings)
            # ---- schedule-level analyses -------------------------- #
            records = analyze_overlap(target.closed_jaxpr, self.cfg,
                                      target_label=target.label)
            report.findings.extend(overlap_rule_findings(
                records, self.cfg, target.scan_info))
            all_records.extend(records * repeat)
            total_flops += count_jaxpr_flops(target.closed_jaxpr) * repeat
            io_bytes += program_io_bytes(target.closed_jaxpr) * repeat
            liveness = estimate_liveness(
                target.closed_jaxpr, target.donated_invars,
                target.invar_labels, target.resident_extra_bytes)
            if (peak_liveness is None or
                    liveness.total_bytes > peak_liveness[1].total_bytes):
                peak_liveness = (target.label, liveness)
        report.signature = (combine_signatures(sigs) if sigs else None)
        report.findings.extend(lockstep_expectation_finding(
            report.signature, len(report.collective_sequence), self.cfg))
        contributors.sort(key=lambda kv: -kv[1])
        # budget is checked against the same gas-weighted per-step total
        # the report publishes
        report.findings.extend(comm_budget_finding(
            report.wire_bytes_per_step, contributors, self.cfg))
        report.donation_waste_bytes = donation_waste_bytes(targets,
                                                           self.cfg)
        # peak HBM = the worst single program (programs run one at a
        # time; each target already counts its resident-but-unreferenced
        # engine state)
        report.overlap_efficiency = overlap_efficiency(all_records)
        report.overlap = summarize_overlap(all_records)
        if peak_liveness is not None:
            label, liveness = peak_liveness
            report.peak_hbm_bytes = liveness.total_bytes
            report.peak_hbm_contributors = list(liveness.contributors)
            if liveness.resident_extra_bytes > 0:
                report.peak_hbm_contributors.append(
                    ("<resident engine state>",
                     liveness.resident_extra_bytes))
            report.findings.extend(hbm_budget_finding(
                liveness.total_bytes, label,
                report.peak_hbm_contributors, self.cfg))
        if hlo_audits:
            report.hlo = summarize_hlo(hlo_audits)
        # HLO-only wire (compiler-inserted collectives plus traced wire
        # outside the jaxpr accounting) prices into the exposed-comm
        # lane: predicted_step_time_lb must not undercount what the
        # compiled program actually moves
        report.step_time = build_step_time_model(
            total_flops, io_bytes, all_records, self.cfg, swap=swap,
            hlo_only_wire_bytes=report.hlo.get(
                "hlo_only_wire_bytes_per_step", 0))
        return report


def verify_multihost_lockstep(report: AuditReport) -> List[Finding]:
    """On a multihost pod, allgather the signature digests and flag any
    divergence BEFORE the first collective dispatch can hang it.
    Single-process: no-op."""
    import jax
    if jax.process_count() <= 1 or report.signature is None:
        return []
    import hashlib
    from jax.experimental import multihost_utils
    digest = np.frombuffer(
        hashlib.sha256(report.signature.encode()).digest()[:8],
        dtype=np.int64)
    all_digests = np.asarray(multihost_utils.process_allgather(digest))
    if (all_digests == digest.reshape(1, -1)).all():
        return []
    return [Finding(
        rule="lockstep", severity="error",
        message=(f"collective lockstep signature "
                 f"{report.signature[:12]} differs across hosts — the "
                 "pod WOULD deadlock at the first diverged collective"),
        target="multihost",
        fix_hint="diff each host's config (CLI --dump-sequence) — "
                 "every process must trace the identical step program")]


def engine_swap_lane(engine, swap=None):
    """Offload-tier traffic model for a built engine: when the config
    targets NVMe for the optimizer sweep, the step-time bound must pay
    the disk trips at the measured sweep ceiling.  An explicit ``swap``
    (the autotuner's resident-twin path for offload_param candidates)
    wins; returns None for purely HBM/host-resident configs."""
    if swap is not None:
        return swap
    from .cost_model import swap_lane
    try:
        return swap_lane(engine.config.zero_config,
                         engine.config.aio_config,
                         param_bytes=_tree_bytes(engine.params),
                         opt_state_bytes=_tree_bytes(engine.opt_state))
    except Exception:  # noqa: BLE001 — the lane is provenance, never fatal
        return None


def audit_engine(engine, sample_batch: Optional[Tuple] = None,
                 cfg=None, multihost: bool = True,
                 swap=None, hlo: Optional[bool] = None,
                 phase: Optional[str] = None) -> AuditReport:
    """Full static audit of a built engine.  Never executes the step.

    ``hlo`` forces the HLO-level SPMD cross-check on (True) or off
    (False); None follows ``analysis.hlo_audit``.  The cross-check
    compiles each program through the SPMD partitioner — meaningful
    extra init cost, so it stays opt-in.  ``phase`` audits an onebit
    engine's warmup or compressed step program (docs/onebit.md); None
    follows the engine's current phase."""
    cfg = cfg if cfg is not None else engine.config.analysis_config
    targets = engine_targets(engine, sample_batch, phase=phase)
    report = ProgramAuditor(cfg).run(
        targets, gas=engine.gradient_accumulation_steps(),
        swap=engine_swap_lane(engine, swap),
        hlo=cfg.hlo_audit if hlo is None else hlo)
    if multihost:
        report.findings.extend(verify_multihost_lockstep(report))
    return report


def enforce(report: AuditReport, mode: str, logger: Any = None) -> None:
    """Apply the configured reaction: warn logs every finding, error
    raises ProgramAuditError when error-severity findings exist."""
    if mode == "off" or not report.findings:
        return
    if logger is not None:
        for f in report.findings:
            log = (logger.error if f.severity == "error"
                   else logger.warning)
            log(f.format())
    if mode == "error" and report.has_errors:
        raise ProgramAuditError(report)
