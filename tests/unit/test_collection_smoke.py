"""Collect-only smoke check: the tier-1 command runs with
``--continue-on-collection-errors``, so an ImportError in one test module
silently shrinks the suite instead of failing it.  This test makes any
collection error loud: it re-collects the unit suite WITHOUT that flag
(collection errors -> nonzero rc) and sanity-checks the collected count
so a mass-deselection regression can't hide either.  ~5 s, fast lane."""

import os
import re
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

# floor well under the current count (458 at introduction) but high
# enough that losing a whole module to an import error trips it
MIN_COLLECTED = 400


def test_resilience_package_imports_cleanly():
    """Lazily-imported engine modules (resilience: only when the config
    block is on) would not surface a syntax/import error in most tests —
    and an ImportError in their test modules would just shrink the suite under
    --continue-on-collection-errors.  Import each explicitly, in a
    subprocess, so it fails loudly."""
    mods = ("deepspeed_tpu.runtime.resilience",
            "deepspeed_tpu.runtime.resilience.atomic",
            "deepspeed_tpu.runtime.resilience.recovery",
            "deepspeed_tpu.runtime.resilience.preemption",
            "deepspeed_tpu.runtime.resilience.sentinel",
            # chaos plane + retry/degradation (round 21): fired lazily
            # from guarded imports at every injection surface — a broken
            # standalone import would silently disable fault injection
            "deepspeed_tpu.runtime.resilience.chaos",
            "deepspeed_tpu.runtime.resilience.retry",
            "deepspeed_tpu.runtime.resilience.degradation",
            # elastic self-healing layer: reshard validation is lazily
            # imported inside save/load_checkpoint; the supervisor is
            # jax-free and imported by controller-side scripts only
            "deepspeed_tpu.runtime.resilience.reshard",
            "deepspeed_tpu.runtime.resilience.supervisor",
            # program auditor: lazily imported by the engine (only when
            # the analysis block is on) and by the CLI entry point
            "deepspeed_tpu.analysis",
            "deepspeed_tpu.analysis.cli",
            "deepspeed_tpu.analysis.__main__",
            # HLO-level SPMD cross-check: lazily reachable through the
            # auditor's hlo path and the CLI's --hlo-audit
            "deepspeed_tpu.analysis.hlo_audit",
            # config autotuner: lazily imported by the tune/calibrate
            # subcommands
            "deepspeed_tpu.analysis.search_space",
            "deepspeed_tpu.analysis.autotuner",
            # source-invariant lint (round 22): lazily imported by the
            # lint-source subcommand; jax-free by design, so nothing
            # else in the suite would catch a break in it
            "deepspeed_tpu.analysis.source_lint",
            "deepspeed_tpu.analysis.source_lint.core",
            "deepspeed_tpu.analysis.source_lint.manifest",
            "deepspeed_tpu.analysis.source_lint.runner",
            "deepspeed_tpu.analysis.source_lint.rules_thread",
            "deepspeed_tpu.analysis.source_lint.rules_determinism",
            "deepspeed_tpu.analysis.source_lint.rules_degradation",
            "deepspeed_tpu.analysis.source_lint.rules_knobs",
            "deepspeed_tpu.analysis.source_lint.rules_checkpoint",
            # fused collective-matmul kernels: lazily reachable through
            # the streaming context's fcm routing
            "deepspeed_tpu.ops.collective_matmul",
            # 1-bit optimizer wire tier: the compressed transport and
            # wire accounting are lazily imported by the engine (only
            # when low_bandwidth.onebit is on)
            "deepspeed_tpu.runtime.comm.onebit",
            "deepspeed_tpu.runtime.comm.compressed",
            "deepspeed_tpu.runtime.comm.low_bandwidth",
            # telemetry monitor: lazily imported by the engines (only
            # when the monitor block is on)
            "deepspeed_tpu.monitor",
            "deepspeed_tpu.monitor.record",
            "deepspeed_tpu.monitor.writers",
            "deepspeed_tpu.monitor.trace",
            "deepspeed_tpu.monitor.reconcile",
            "deepspeed_tpu.monitor.monitor",
            # fleet observability layer (monitor.fleet is lazily
            # reachable through the launcher's --watch too)
            "deepspeed_tpu.monitor.fleet",
            "deepspeed_tpu.monitor.health",
            "deepspeed_tpu.monitor.heartbeat",
            "deepspeed_tpu.monitor.capture",
            # MoE routing observability (monitor.moe is lazily reachable
            # through TrainingMonitor)
            "deepspeed_tpu.monitor.moe")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         "import importlib\n"
         + "\n".join(f"importlib.import_module({m!r})" for m in mods)],
        cwd=str(REPO), capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, (
        f"resilience package import failed:\n{out.stderr[-2000:]}")


def test_unit_suite_collects_cleanly():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/unit", "--collect-only",
         "-q", "-p", "no:cacheprovider"],
        cwd=str(REPO), capture_output=True, text=True, timeout=300,
        env=env)
    tail = "\n".join(out.stdout.splitlines()[-25:])
    assert out.returncode == 0, (
        f"unit-suite collection failed (rc={out.returncode}) — a test "
        f"module no longer imports:\n{tail}\n{out.stderr[-2000:]}")
    m = re.search(r"(\d+) tests? collected", out.stdout)
    assert m, f"no collection summary in output:\n{tail}"
    count = int(m.group(1))
    assert count >= MIN_COLLECTED, (
        f"only {count} tests collected (expected >= {MIN_COLLECTED}) — "
        "did a module or parametrization silently vanish?")


# the guards of the one step loop on the streamed ZeRO-3 scan, and the
# loop at every ZeRO stage against the baseline
FAST_LANE_GUARDS = (
    "test_zero3_streaming.py::test_carried_mode_parity_fp32",
    "test_zero3_streaming.py::test_carried_mode_parity_bf16",
    "test_zero3_streaming.py::test_streaming_matches_baseline",
    "test_zero3_streaming.py::test_carried_low_bandwidth_parity",
    "test_zero3_streaming.py::test_carried_hpz_parity",
    "test_zero3_streaming.py::test_zero3_bf16_streams_on_cpu",
    "test_zero3_streaming.py::test_streaming_with_tensor_parallel",
    "test_functionality_matrix.py::test_matrix_matches_baseline",
)


def test_step_loop_guards_run_in_fast_lane():
    """Fast-lane marker audit: the parities of the streamed ZeRO-3 scan
    against the unstreamed baseline and the functionality matrix are what
    guards the forward / backward / step loop end to end, so they must run
    in tier-1, i.e. survive the `-m "not slow"` deselection — a conftest
    _SLOW_PREFIXES entry or a stray marker would silently drop them from
    the gate."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "pytest",
         "tests/unit/test_zero3_streaming.py",
         "tests/unit/test_functionality_matrix.py",
         "--collect-only", "-q", "-m", "not slow", "-p", "no:cacheprovider"],
        cwd=str(REPO), capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, (
        f"collection failed:\n{out.stdout[-1500:]}\n{out.stderr[-1500:]}")
    missing = [name for name in FAST_LANE_GUARDS if name not in out.stdout]
    assert not missing, (
        f"the tier-1 gate no longer runs {missing}: marked slow?")
