"""Declared repo invariants the source-lint rules check against.

This file is the single place where the repo says OUT LOUD which files
form the deterministic planes, which cross-thread attributes are
intentionally lock-free, which except-and-continue ladders are waived
from degradation-registry coverage (and WHY — a reason string is
mandatory for every waiver, same contract as the per-file suppression
comments), which modules count as config validators, and which classes
carry checkpointed counters.  Rules read these tables; humans review
them in diffs — adding a waiver is a visible act.
"""

# ---------------------------------------------------------------- #
# determinism rule: the deterministic planes
# ---------------------------------------------------------------- #
# The chaos plane's bitwise-fired-log contract (docs/resilience.md):
# same seed + same schedule => byte-identical fired logs, so these
# files may not read wall clocks or the process-global random state.
# Seeded ``random.Random(seed)`` instances and ``time.sleep`` (which
# delays but never *decides*) are allowed.
DETERMINISTIC_PLANES = (
    "deepspeed_tpu/runtime/resilience/chaos.py",
    "deepspeed_tpu/runtime/resilience/retry.py",
    "deepspeed_tpu/monitor/health.py",
)

# ---------------------------------------------------------------- #
# thread-discipline rule: the declared lock map
# ---------------------------------------------------------------- #
# (path, ClassName) -> {attr: reason}.  Attributes written inside a
# thread target and read outside it must appear here — the reason
# documents why the access is safe without a lock (GIL-atomic store of
# an immutable value, or ordered by a join/Event).
LOCK_MAP = {
    ("deepspeed_tpu/runtime/resilience/preemption.py",
     "PreemptionHandler"): {
        "deadline_fired": (
            "grace-deadline timer callback stores an immutable bool; "
            "the step loop only polls it (GIL-atomic, one writer)"),
        "forced_tag": (
            "set once by the timer callback before deadline_fired, "
            "read only after deadline_fired observes True"),
    },
    ("deepspeed_tpu/monitor/writers.py", "WriterThread"): {
        "_errored": (
            "one-shot failure latch stored by the writer thread; "
            "readers only poll the immutable bool (GIL-atomic)"),
    },
}

# ---------------------------------------------------------------- #
# degradation-coverage rule: waived except-and-continue ladders
# ---------------------------------------------------------------- #
# (path, enclosing-qualname) -> reason.  A broad except that swallows
# without registering in resilience/degradation.py is only legal when
# listed here; the reason must say why the registry is the wrong tool
# (per-window transient, best-effort cleanup, or the registry itself).
DEGRADATION_WAIVERS = {
    ("deepspeed_tpu/runtime/resilience/degradation.py",
     "record"): "the registry's own never-raise guard cannot recurse "
                "into itself",
    ("deepspeed_tpu/analysis/auditor.py", "engine_swap_lane"):
        "the swap lane is optional provenance; a None lane is visible "
        "in the audit report, not a silent tier change",
    ("deepspeed_tpu/analysis/autotuner.py", "run_search"):
        "an untraceable candidate is pruned WITH provenance into "
        "space.pruned and shows up in the leaderboard output",
    ("deepspeed_tpu/analysis/hlo_audit.py", "audit_target_hlo"):
        "the compile failure becomes an audit Finding that escalates "
        "under require_spmd_match — louder than the registry",
    ("deepspeed_tpu/config.py", "PreemptionConfig.from_dict"):
        "jax import probe at config-parse time; the guarded multihost "
        "path RAISES DeepSpeedConfigError, it never falls back",
    ("deepspeed_tpu/launcher/runner.py", "_pump_lines"):
        "a garbled worker output line is per-line transient; the "
        "worker's exit code is still collected and aggregated",
    ("deepspeed_tpu/launcher/runner.py", "launch_and_collect"):
        "the --watch status render retries next interval and says so; "
        "rc aggregation is unaffected",
    ("deepspeed_tpu/moe/sharded_moe.py", "sum_routing_stats"):
        "one-shot-warned inner-scan tracer case; missing moe records "
        "are visible in the monitor stream",
    ("deepspeed_tpu/monitor/capture.py", "ProfileCapture.disarm"):
        "stop_trace cleanup is best-effort teardown; the persistent "
        "case (arm failure) registers in the handler above it",
    ("deepspeed_tpu/monitor/fleet.py", "FleetAggregator._missing_hosts"):
        "heartbeat attribution is advisory diagnosis inside an "
        "already-raising ExchangeTimeout path",
    ("deepspeed_tpu/monitor/fleet.py", "FleetAggregator._gather_window"):
        "guarded chaos-plane import probe (partial install): chaos off "
        "means no injection, not a tier change",
    ("deepspeed_tpu/monitor/fleet.py",
     "FleetAggregator._gather_under_deadline.work"):
        "the worker catches only to RETHROW on the calling thread via "
        "box['exc'] — nothing is swallowed",
    ("deepspeed_tpu/monitor/heartbeat.py", "HeartbeatWriter._chaos_fire"):
        "guarded chaos-plane import probe (partial install)",
    ("deepspeed_tpu/monitor/heartbeat.py", "read_heartbeats"):
        "a torn/unreadable beat file is per-read transient; staleness "
        "math treats it as missing and the watch table shows it",
    ("deepspeed_tpu/monitor/monitor.py", "_batched_loss_fetch"):
        "per-window device fetch; the window record visibly carries "
        "whatever was fetched",
    ("deepspeed_tpu/monitor/monitor.py", "MetricsStream.flush"):
        "per-window best-effort reads (loss/memory/fleet); the next "
        "window retries — no persistent tier change",
    ("deepspeed_tpu/monitor/monitor.py", "TrainingMonitor._fleet_window"):
        "fleet exchange failures feed the supervisor/eviction path, "
        "which owns the loud reporting",
    ("deepspeed_tpu/monitor/monitor.py", "TrainingMonitor.close"):
        "teardown is best-effort; after close there is nothing left "
        "to degrade",
    ("deepspeed_tpu/monitor/record.py", "device_memory"):
        "backend memory_stats probe, per-call; records carry nulls "
        "visibly when it fails",
    ("deepspeed_tpu/monitor/record.py", "identity"):
        "hostname/pid label probes — cosmetic record fields",
    ("deepspeed_tpu/monitor/writers.py", "TensorBoardWriter.flush"):
        "per-call flush cleanup; write failures latch _warned in the "
        "write handler, which registers",
    ("deepspeed_tpu/monitor/writers.py", "_json_default"):
        "repr() fallback for one unserializable record field",
    ("deepspeed_tpu/monitor/writers.py", "WriterThread._run"):
        "per-batch flush is best-effort; a failing WRITER registers "
        "via the _errored latch in the write loop above",
    ("deepspeed_tpu/monitor/writers.py", "WriterThread.close"):
        "teardown close after drain (or after the loud drain-timeout "
        "warning) is best-effort",
    ("deepspeed_tpu/runtime/engine.py",
     "DeepSpeedEngine._configure_tensorboard"):
        "these handlers only probe importability down the tb ladder; "
        "the chosen tier is registered via degrade() at the ladder "
        "foot in the same method",
    ("deepspeed_tpu/runtime/engine.py",
     "DeepSpeedEngine._monitor_boundary_reads"):
        "per-step telemetry read; next boundary retries",
    ("deepspeed_tpu/runtime/engine.py",
     "DeepSpeedEngine._moe_local_expert_slice"):
        "optional moe expert-slice probe; absence is visible as "
        "missing moe records",
    ("deepspeed_tpu/runtime/engine.py",
     "DeepSpeedEngine._monitor_moe_stats"):
        "per-window moe stat fetch; next window retries",
    ("deepspeed_tpu/runtime/engine.py",
     "DeepSpeedEngine._resolve_verified_tag"):
        "an unreadable latest file falls through to the directory "
        "scan; a truly broken checkpoint raises on load",
    ("deepspeed_tpu/runtime/engine.py",
     "DeepSpeedEngine._maybe_handle_preemption"):
        "emergency save on the signal path: failure is logged loudly "
        "and the run is already ending — the registry summary would "
        "never be read",
    ("deepspeed_tpu/runtime/engine.py",
     "DeepSpeedEngine._forced_emergency_save"):
        "forced save during teardown; loud log, process is dying",
    ("deepspeed_tpu/runtime/engine.py", "DeepSpeedEngine.load_checkpoint"):
        "engine_rng restore from an old/foreign checkpoint is skipped "
        "with a per-rank log; training state itself loaded fine",
    ("deepspeed_tpu/runtime/resilience/preemption.py",
     "PreemptionHandler._deadline_expired"):
        "forced-save failure on the timer thread is logged at error "
        "level mid-teardown; the process is being preempted",
    ("deepspeed_tpu/runtime/resilience/retry.py", "RetryPolicy.run"):
        "stamping retry_attempts on a foreign (possibly slotted) "
        "exception is diagnostic garnish; the original error re-raises",
    ("deepspeed_tpu/runtime/swap_tensor/aio_handle.py", "_chaos_fire"):
        "guarded chaos-plane import probe (partial install)",
    ("deepspeed_tpu/runtime/swap_tensor/aio_handle.py", "_degraded"):
        "this IS the registry shim: a guarded import of degradation "
        "itself cannot register its own absence",
    ("deepspeed_tpu/runtime/swap_tensor/aio_handle.py",
     "AsyncIOHandle.__del__"):
        "interpreter-teardown destructor; modules may already be gone",
    ("deepspeed_tpu/runtime/utils.py", "see_memory_usage"):
        "debug memory-print probes; output says n/a when they fail",
    ("deepspeed_tpu/runtime/zero/infinity.py",
     "ZeroInfinityEngine._monitor_boundary_reads"):
        "per-step telemetry read; next boundary retries",
    ("deepspeed_tpu/runtime/zero/infinity.py",
     "ZeroInfinityEngine.load_checkpoint"):
        "engine_rng restore from an old/foreign checkpoint is skipped "
        "with a per-rank log; training state itself loaded fine",
    ("deepspeed_tpu/runtime/zero/stage3_streaming.py", "_body_uses_pallas"):
        "static jaxpr probe; an unprobeable body is treated as "
        "pallas-free, which only affects a log line",
    ("deepspeed_tpu/runtime/zero/stage3_streaming.py",
     "_body_closes_over_tracers.has_tracer"):
        "static closure probe during trace-error diagnosis",
    ("deepspeed_tpu/runtime/zero/stage3_streaming.py",
     "Zero3StreamContext.scan"):
        "the guarded import protects the degrade() call itself "
        "(partial install) — the fallback IS being registered there",
    ("deepspeed_tpu/utils/logging.py", "_process_index"):
        "jax absent or uninitialized at log-format time; rank label "
        "defaults to 0",
}

# ---------------------------------------------------------------- #
# knob tri-sourcing rule
# ---------------------------------------------------------------- #
# modules (repo-relative) that count as the validation surface for
# constants.py keys — a knob referenced by none of them is an orphan
VALIDATOR_MODULES = (
    "deepspeed_tpu/config.py",
    "deepspeed_tpu/elasticity.py",
)

# constant-name prefixes reserved for upstream-parity surfaces that are
# intentionally accepted-but-unvalidated (config blocks we parse for
# upstream config compatibility but do not yet act on) -> reason
RESERVED_KNOB_PREFIXES = {
    "SPARSE_": (
        "sparse-attention block: upstream-DeepSpeed config parity "
        "surface; no TPU sparse-attention kernels exist yet, so the "
        "keys are declared but deliberately unvalidated (ROADMAP)"),
    "PIPELINE_": (
        "pipeline-parallel block: reserved for the ROADMAP pipeline "
        "direction; the engine does not consume these keys yet"),
}

# ---------------------------------------------------------------- #
# checkpoint-state coverage rule
# ---------------------------------------------------------------- #
# Classes whose counter/state attributes must round-trip through the
# declared save/load pair (the PR 16 onebit_phase bug class).
# Candidate attrs: public attributes initialized in __init__ to an int
# or dict literal AND mutated outside __init__/save/load; extra_attrs
# forces private attrs into the candidate set; exempt_attrs documents
# deliberate non-persistence (reason per attr).
STATE_CLASSES = (
    {
        "path": "deepspeed_tpu/runtime/resilience/sentinel.py",
        "cls": "TrainingSentinel",
        "save": "state_dict",
        "load": "load_state_dict",
        "extra_attrs": (),
        "exempt_attrs": {},
    },
    {
        "path": "deepspeed_tpu/runtime/resilience/retry.py",
        "cls": "RetryPolicy",
        "save": "snapshot",
        "load": "restore",
        "extra_attrs": (),
        "exempt_attrs": {},
    },
    {
        "path": "deepspeed_tpu/analysis/recompile.py",
        "cls": "RecompileGuard",
        "save": "counters",
        "load": "load_counters",
        "extra_attrs": (),
        "exempt_attrs": {},
    },
    {
        "path": "deepspeed_tpu/runtime/engine.py",
        "cls": "DeepSpeedEngine",
        "save": "save_checkpoint",
        "load": "load_checkpoint",
        "extra_attrs": ("_onebit_phase",),
        "exempt_attrs": {},
    },
)
