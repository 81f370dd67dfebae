"""Chaos plane tests: deterministic fault injection across every
registered failure surface (runtime/resilience/chaos.py), the bounded
retry policy (resilience/retry.py), the fleet-exchange watchdog
(monitor/fleet.py), and the degradation registry
(resilience/degradation.py).  All fast-lane: faults are seeded and
call/step-triggered — no wall clock anywhere in the assertions."""

import errno
import json
import os

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.runtime.resilience import chaos, degradation
from deepspeed_tpu.runtime.resilience.chaos import (ChaosFault, ChaosPlane,
                                                    InjectedCrash,
                                                    InjectedFault)
from deepspeed_tpu.runtime.resilience.retry import (CorruptionError,
                                                    RetryPolicy,
                                                    is_transient)
from tests.unit.simple_model import (base_engine_config, random_dataloader,
                                     simple_model_apply, simple_model_params)

HIDDEN = 16


@pytest.fixture(autouse=True)
def _chaos_clean():
    """Every test leaves the process-global plane and the degradation
    registry clean — a leaked plane would fire into unrelated tests."""
    yield
    chaos.uninstall()
    degradation.get_registry().clear()


def make_engine(**overrides):
    cfg = base_engine_config(micro_batch=8, gas=1, **(overrides or {}))
    params = simple_model_params(HIDDEN)
    engine, _, _, _ = ds.initialize(model=simple_model_apply, config=cfg,
                                    model_parameters=params)
    return engine


def run_steps(engine, n, seed=3):
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    it = iter(RepeatingLoader(random_dataloader(HIDDEN, 32, 8, seed=seed)))
    for _ in range(n):
        x, y = next(it)
        engine.backward(engine.forward(x, y))
        engine.step()
    return it


# --------------------------------------------------------------------- #
# schedule validation (parse-time, not silently-never-fires)
# --------------------------------------------------------------------- #
def test_fault_rejects_unknown_point():
    with pytest.raises(ValueError, match="unknown injection point"):
        ChaosFault(point="aio.prad", kind="eio", at_call=1)


def test_fault_rejects_kind_invalid_at_point():
    with pytest.raises(ValueError, match="not valid at point"):
        ChaosFault(point="heartbeat.beat", kind="eio", at_call=1)


def test_fault_requires_exactly_one_trigger():
    with pytest.raises(ValueError, match="exactly one trigger"):
        ChaosFault(point="aio.pread", kind="eio")
    with pytest.raises(ValueError, match="exactly one trigger"):
        ChaosFault(point="aio.pread", kind="eio", at_call=1, at_step=2)


def test_fault_spec_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown keys"):
        ChaosFault.from_dict({"point": "aio.pread", "kind": "eio",
                              "at_cal": 1})


def test_chaos_config_block_validates_specs():
    base = {"train_micro_batch_size_per_gpu": 8}
    ok = DeepSpeedConfig({**base, "resilience": {"chaos": {
        "enabled": True, "seed": 7,
        "faults": [{"point": "batch.next", "kind": "poison",
                    "at_step": 3}]}}})
    cc = ok.resilience_config.chaos
    assert cc.enabled and cc.seed == 7 and len(cc.faults) == 1
    with pytest.raises(DeepSpeedConfigError, match="not valid at point"):
        DeepSpeedConfig({**base, "resilience": {"chaos": {
            "faults": [{"point": "batch.next", "kind": "eio",
                        "at_step": 3}]}}})


def test_chaos_off_by_default():
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 8})
    assert not cfg.resilience_config.chaos.enabled
    assert chaos.active() is None
    assert chaos.maybe_fire(chaos.POINT_AIO_PREAD) is None


# --------------------------------------------------------------------- #
# determinism: same seed + schedule => bitwise-identical fired log
# --------------------------------------------------------------------- #
def _drive(plane):
    with chaos.installed(plane):
        for step in range(1, 6):
            for point in (chaos.POINT_AIO_PREAD, chaos.POINT_HEARTBEAT,
                          chaos.POINT_BATCH):
                try:
                    chaos.maybe_fire(point, step=step)
                except OSError:
                    pass
    return plane.fired


def _schedule():
    return [ChaosFault(point="aio.pread", kind="eio", at_call=4, repeat=2),
            ChaosFault(point="heartbeat.beat", kind="stale", at_call=2),
            ChaosFault(point="batch.next", kind="poison", at_step=3)]


def test_same_seed_same_schedule_identical_fired_log():
    log_a = _drive(ChaosPlane(_schedule(), seed=11))
    log_b = _drive(ChaosPlane(_schedule(), seed=11))
    assert log_a == log_b
    assert [e["kind"] for e in log_a] == ["stale", "poison", "eio", "eio"]
    # the log is timestamp-free by contract (what makes it comparable)
    assert all(set(e) == {"seq", "point", "kind", "call", "step", "detail"}
               for e in log_a)
    assert json.dumps(log_a, sort_keys=True) == \
        json.dumps(log_b, sort_keys=True)


def test_repeat_budget_bounds_firings():
    plane = ChaosPlane([ChaosFault(point="heartbeat.beat", kind="stale",
                                   at_call=1, repeat=3)])
    with chaos.installed(plane):
        fired = [chaos.maybe_fire(chaos.POINT_HEARTBEAT) is not None
                 for _ in range(6)]
    assert fired == [True, True, True, False, False, False]


def test_fired_faults_become_chaos_monitor_records():
    from deepspeed_tpu.monitor import record as R
    plane = ChaosPlane([ChaosFault(point="heartbeat.beat", kind="stale",
                                   at_call=1)])
    with chaos.installed(plane):
        chaos.maybe_fire(chaos.POINT_HEARTBEAT)
    recs = plane.drain_records()
    assert len(recs) == 1
    assert recs[0][R.F_KIND] == R.KIND_CHAOS
    assert recs[0]["fault_kind"] == "stale"
    assert recs[0]["point"] == "heartbeat.beat"
    assert plane.drain_records() == []  # drained means drained


# --------------------------------------------------------------------- #
# retry policy unit cells
# --------------------------------------------------------------------- #
def _policy(**kw):
    kw.setdefault("sleep", lambda s: None)
    return RetryPolicy(**kw)


def test_retry_transient_eio_then_success():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError(errno.EIO, "transient")
        return "ok"

    p = _policy(retries=3)
    assert p.run(flaky, what="cell") == "ok"
    assert calls["n"] == 3
    assert p.counters["retries"] == 2
    assert p.counters["recovered"] == 1
    assert p.counters["gave_up"] == 0


def test_retry_budget_exhaustion_raises_original_with_attempt_count():
    boom = OSError(errno.EIO, "persistent EIO")

    def always():
        raise boom

    p = _policy(retries=2)
    with pytest.raises(OSError) as ei:
        p.run(always, what="cell")
    assert ei.value is boom            # the ORIGINAL error, not a wrapper
    assert ei.value.retry_attempts == 3  # 1 initial + 2 retries
    assert p.counters["gave_up"] == 1


def test_retry_never_retries_corruption():
    calls = {"n": 0}

    def corrupt():
        calls["n"] += 1
        raise CorruptionError("crc mismatch / torn manifest")

    p = _policy(retries=5)
    with pytest.raises(CorruptionError):
        p.run(corrupt)
    assert calls["n"] == 1             # exactly one attempt, no retry
    assert p.counters["retries"] == 0


def test_retry_never_retries_injected_crash():
    calls = {"n": 0}

    def crash():
        calls["n"] += 1
        raise InjectedCrash("simulated kill")

    p = _policy(retries=5)
    with pytest.raises(InjectedCrash):
        p.run(crash)
    assert calls["n"] == 1


def test_is_transient_classification():
    assert is_transient(OSError(errno.EIO, "x"))
    assert is_transient(OSError(errno.ENOSPC, "x"))
    assert is_transient(OSError("errno-less"))
    assert not is_transient(CorruptionError("crc"))
    assert not is_transient(ValueError("x"))
    assert not is_transient(OSError(errno.ENOENT, "missing"))


def test_backoff_deterministic_under_fixed_seed():
    def delays(seed):
        slept = []
        p = RetryPolicy(retries=4, backoff_s=0.5, max_backoff_s=2.0,
                        jitter=0.25, seed=seed, sleep=slept.append)
        with pytest.raises(OSError):
            p.run(lambda: (_ for _ in ()).throw(OSError(errno.EIO, "x")))
        return slept

    a, b = delays(9), delays(9)
    assert a == b and len(a) == 4
    # exponential base under the cap, jitter bounded
    for k, d in enumerate(a, start=1):
        base = min(0.5 * 2 ** (k - 1), 2.0)
        assert base <= d <= base * 1.25
    assert delays(10) != a  # the jitter stream really is seed-keyed


def test_retry_counters_snapshot_restore_roundtrip():
    p = _policy(retries=1)
    p.run(lambda: "ok", what="a")
    with pytest.raises(OSError):
        p.run(lambda: (_ for _ in ()).throw(OSError(errno.EIO, "x")),
              what="b")
    snap = p.snapshot()
    q = _policy(retries=1)
    q.restore(snap)
    assert q.snapshot() == snap
    q.restore(None)  # tolerated (old checkpoints)


def test_build_retry_policy_from_config():
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 8,
        "resilience": {"enabled": True, "io_retries": 4,
                       "retry_jitter": 0.5, "retry_seed": 3,
                       "retry_max_backoff_seconds": 7.0}})
    p = cfg.resilience_config.build_retry_policy(sleep=lambda s: None)
    assert p.retries == 4 and p.jitter == 0.5 and p.max_backoff_s == 7.0
    off = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 8})
    assert off.resilience_config.build_retry_policy() is None


# --------------------------------------------------------------------- #
# satellite bugfix: grace_s forced saves are single-process only
# --------------------------------------------------------------------- #
def test_grace_s_rejected_on_multihost_with_actionable_message(monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    with pytest.raises(DeepSpeedConfigError) as ei:
        DeepSpeedConfig({
            "train_micro_batch_size_per_gpu": 8,
            "resilience": {"preemption": {"enabled": True,
                                          "grace_s": 30}}})
    msg = str(ei.value)
    assert "single-process only" in msg          # names the limitation
    assert "step-boundary emergency save" in msg  # names the alternative
    assert "4 processes" in msg


def test_grace_s_accepted_single_process():
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 8,
        "resilience": {"preemption": {"enabled": True, "grace_s": 30}}})
    assert cfg.resilience_config.preemption.grace_s == 30


# --------------------------------------------------------------------- #
# degradation registry
# --------------------------------------------------------------------- #
def test_degradation_dedups_and_drains_once():
    from deepspeed_tpu.monitor import record as R
    reg = degradation.get_registry()
    degradation.record("aio", "io_uring", "python", "probe failed")
    degradation.record("aio", "io_uring", "python", "probe failed again")
    degradation.record("tensorboard", "torch", "jsonl", "torch absent")
    evs = reg.events()
    assert len(evs) == 2
    assert evs[0]["count"] == 2        # repeats counted, not re-warned
    assert "aio:io_uring->python" in reg.summary()
    recs = reg.drain_records()
    assert {r[R.F_KIND] for r in recs} == {R.KIND_DEGRADATION}
    assert len(recs) == 2 and reg.drain_records() == []


# --------------------------------------------------------------------- #
# exchange watchdog: a rigged hang becomes an attributed eviction
# --------------------------------------------------------------------- #
def _hung_aggregator(arrival_ages):
    from deepspeed_tpu.monitor.fleet import FleetAggregator

    def gather(arr):
        return np.stack([arr, arr])    # 2-host fake fleet

    return FleetAggregator(process_index=0, process_count=2,
                           host="host-a", gather_fn=gather,
                           deadline_s=0.2,
                           arrival_fn=lambda: arrival_ages)


def test_watchdog_converts_hang_into_timeout_naming_missing_host():
    from deepspeed_tpu.monitor.fleet import ExchangeTimeout
    agg = _hung_aggregator({0: 0.0, 1: 500.0})  # peer 1 went dark
    plane = ChaosPlane([ChaosFault(point="fleet.exchange", kind="hang",
                                   at_call=1, args={"seconds": 30.0})])
    summary = {"step": 1, "steps": 1, "loss_mean": 0.0}
    with chaos.installed(plane):
        with pytest.raises(ExchangeTimeout) as ei:
            agg.exchange(summary)
    t = ei.value
    assert t.missing == [(1, "host-a")]
    assert "p1:host-a" in str(t) and "deadline" in str(t)
    assert agg.timeouts == 1
    # fault-free exchanges proceed normally under the same deadline
    assert agg.exchange(summary).shape[0] == 2


def test_watchdog_timeout_feeds_supervisor_eviction():
    from deepspeed_tpu.monitor.fleet import ExchangeTimeout
    from deepspeed_tpu.runtime.resilience.supervisor import SupervisorPolicy
    timeout = ExchangeTimeout("exchange missed 5.0s deadline",
                              missing=[(1, "host-b")], deadline_s=5.0)
    pol = SupervisorPolicy(min_world_size=1)
    pol.observe_exchange_timeout(timeout)
    decision = pol.decide(world_size=4)
    assert decision.action == "reshape"
    assert 1 in decision.drop
    assert "dead worker 1" in decision.reason


def test_watchdog_exception_fault_propagates_not_times_out():
    agg = _hung_aggregator({0: 0.0, 1: 0.0})
    plane = ChaosPlane([ChaosFault(point="fleet.exchange",
                                   kind="exception", at_call=1)])
    with chaos.installed(plane):
        with pytest.raises(InjectedFault):
            agg.exchange({"step": 1, "steps": 1})


# --------------------------------------------------------------------- #
# chaos matrix: every (fault kind x subsystem) cell either recovers
# with parity or fails loudly naming the injected fault
# --------------------------------------------------------------------- #
def _swapper(tmp_path, retry_policy=None):
    from deepspeed_tpu.runtime.swap_tensor.partitioned_param_swapper \
        import PartitionedParamSwapper
    tree = {"w": np.arange(64, dtype=np.float32)}
    sw = PartitionedParamSwapper(str(tmp_path / "swap"), {"g0": tree},
                                 buffer_count=2,
                                 retry_policy=retry_policy)
    return sw, tree


def test_matrix_aio_pread_eio_recovers_with_retry_and_parity(tmp_path):
    sw, tree = _swapper(tmp_path, _policy(retries=3))
    sw.write("g0", tree)
    sw.release("g0")
    plane = ChaosPlane([ChaosFault(point="aio.pread", kind="eio",
                                   at_call=1, repeat=2)])
    with chaos.installed(plane):
        got = sw.get("g0")             # 2 injected EIOs, then success
    np.testing.assert_array_equal(got["w"], tree["w"])  # parity
    assert sw.retry_policy.counters["recovered"] == 1
    assert [e["kind"] for e in plane.fired] == ["eio", "eio"]


def test_matrix_aio_pwrite_enospc_exhausts_budget_names_fault(tmp_path):
    sw, tree = _swapper(tmp_path, _policy(retries=1))
    plane = ChaosPlane([ChaosFault(point="aio.pwrite", kind="enospc",
                                   at_call=1, repeat=5)])
    with chaos.installed(plane):
        with pytest.raises(OSError) as ei:
            sw.write("g0", tree)
    assert ei.value.errno == errno.ENOSPC
    assert "chaos-injected enospc" in str(ei.value)   # names the fault
    assert ei.value.retry_attempts == 2
    assert sw.retry_policy.counters["gave_up"] == 1


def test_matrix_aio_without_retry_fails_on_first_injected_eio(tmp_path):
    sw, tree = _swapper(tmp_path, retry_policy=None)
    sw.write("g0", tree)
    sw.release("g0")
    plane = ChaosPlane([ChaosFault(point="aio.pread", kind="eio",
                                   at_call=1)])
    with chaos.installed(plane):
        with pytest.raises(OSError) as ei:
            sw.get("g0")
    assert "chaos-injected eio at aio.pread" in str(ei.value)


def test_matrix_manifest_torn_detected_never_retried(tmp_path):
    from deepspeed_tpu.runtime.resilience import atomic
    good = tmp_path / "good"
    good.mkdir()
    (good / "data.bin").write_bytes(b"payload")
    plane = ChaosPlane([ChaosFault(point="checkpoint.manifest",
                                   kind="torn_manifest", at_call=1)])
    with chaos.installed(plane):
        atomic.write_manifest(str(good))
    # the torn manifest is not valid JSON: verification must fail
    # loudly (CorruptionError family), and the retry policy must not
    # absorb it
    with pytest.raises(Exception) as ei:
        problems = atomic.verify_manifest(str(good))
        assert problems  # either raises or reports problems
        raise CorruptionError("; ".join(problems))
    assert not is_transient(ei.value)
    assert [e["kind"] for e in plane.fired] == ["torn_manifest"]


def test_matrix_commit_crash_leaves_no_final_dir_then_recovers(tmp_path):
    from deepspeed_tpu.runtime.resilience import atomic
    tmp_dir = atomic.tmp_tag_dir(str(tmp_path), "tag1")
    with open(os.path.join(tmp_dir, "data.bin"), "wb") as f:
        f.write(b"payload")
    plane = ChaosPlane([ChaosFault(point="checkpoint.commit",
                                   kind="crash", at_call=1)])
    with chaos.installed(plane):
        with pytest.raises(InjectedCrash):
            atomic.commit_tag_dir(str(tmp_path), "tag1", tmp_dir)
        # crash landed between stage and rename: no torn final dir
        assert not os.path.isdir(tmp_path / "tag1")
        # the "restarted process" re-commits; budget spent, so it lands
        final = atomic.commit_tag_dir(str(tmp_path), "tag1", tmp_dir)
    assert os.path.isdir(final)
    assert (tmp_path / "tag1" / "data.bin").read_bytes() == b"payload"


def test_matrix_heartbeat_stale_and_corrupt_surfaced(tmp_path):
    from deepspeed_tpu.monitor.heartbeat import (HeartbeatWriter,
                                                 read_heartbeats)
    hb_dir = str(tmp_path / "hb")
    w = HeartbeatWriter(hb_dir, process_index=0, world_size=1)
    w.beat(step=1)
    first = read_heartbeats(hb_dir)[0]
    plane = ChaosPlane([
        ChaosFault(point="heartbeat.beat", kind="stale", at_call=1),
        ChaosFault(point="heartbeat.beat", kind="corrupt", at_call=2)])
    with chaos.installed(plane):
        w.beat(step=2)                 # stale: write silently skipped
        assert read_heartbeats(hb_dir)[0]["step"] == first["step"]
        w.beat(step=3)                 # corrupt: torn garbage on disk
    rows = read_heartbeats(hb_dir)
    assert rows[0]["status"] == "corrupt"
    assert rows[0]["process_index"] == 0


def test_matrix_batch_poison_sentinel_skips_and_records(tmp_path):
    cfg = {"resilience": {"enabled": True,
                          "sentinel": {"enabled": True,
                                       "policy": "skip_step",
                                       "warmup_steps": 3}},
           "monitor": {"enabled": False}}
    e = make_engine(**cfg)
    plane = ChaosPlane([ChaosFault(point="batch.next", kind="poison",
                                   at_step=4)])
    with chaos.installed(plane):
        run_steps(e, 5)
        # the chaos record names the injected fault for post-mortem
        recs = e._drain_resilience_records()
    # the poisoned step was skipped (the recovery), training continued
    assert e.sentinel.counters()["steps_skipped"] == 1
    assert e.global_steps == 5
    kinds = [(r["fault_kind"], r["point"]) for r in recs
             if r.get("fault_kind")]
    assert ("poison", "batch.next") in kinds


def test_matrix_ckpt_stage_eio_retried_save_load_parity(tmp_path):
    cfg = {"resilience": {"enabled": True, "io_retries": 3,
                          "io_backoff_seconds": 0.0}}
    e = make_engine(**cfg)
    e._retry_policy._sleep = lambda s: None
    run_steps(e, 2)
    plane = ChaosPlane([ChaosFault(point="checkpoint.stage", kind="eio",
                                   at_call=1, repeat=2)])
    with chaos.installed(plane):
        e.save_checkpoint(str(tmp_path), tag="chaosed")
    assert [f["kind"] for f in plane.fired] == ["eio", "eio"]
    assert e._retry_policy.counters["recovered"] >= 1
    # the tally is snapshotted into client state at the NEXT save (the
    # current save's own I/O happens after its client dict is sealed) —
    # same boundary semantics as the sentinel counters
    e.save_checkpoint(str(tmp_path), tag="final")

    e2 = make_engine(**cfg)
    _, client = e2.load_checkpoint(str(tmp_path), tag="final")
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, e.params),
                 jax.tree.map(np.asarray, e2.params))
    # the retry tally rode client state (sentinel-counter pattern)
    assert e2._retry_policy.counters["recovered"] >= 1
    assert client["retry_counters"]["recovered"] >= 1


def test_matrix_step_boundary_sigterm_emergency_save_and_resume(tmp_path):
    from deepspeed_tpu.runtime.resilience.preemption import \
        TrainingInterrupted
    cfg = {"resilience": {
        "enabled": True,
        "preemption": {"enabled": True, "reraise": False,
                       "save_dir": str(tmp_path)},
        "chaos": {"enabled": True,
                  "faults": [{"point": "step.boundary", "kind": "sigterm",
                              "at_step": 2}]}}}
    e = make_engine(**cfg)
    try:
        assert chaos.active() is not None  # engine installed the plane
        it = run_steps(e, 1)
        x, y = next(it)
        e.backward(e.forward(x, y))
        with pytest.raises(TrainingInterrupted) as ei:
            e.step()               # chaos delivers SIGTERM at step 2
        tag = ei.value.emergency_tag
        assert tag == "emergency_step2"
        assert os.path.isdir(tmp_path / tag)
        chaos.uninstall()

        e2 = make_engine()
        e2.load_checkpoint(str(tmp_path), tag=tag)
        assert e2.global_steps == 2
        jax.tree.map(np.testing.assert_array_equal,
                     jax.tree.map(np.asarray, e.params),
                     jax.tree.map(np.asarray, e2.params))
    finally:
        if e._preemption is not None:
            e._preemption.uninstall()


def test_matrix_step_boundary_crash_raises_injected_crash():
    cfg = {"resilience": {"chaos": {
        "enabled": True, "seed": 5,
        "faults": [{"point": "step.boundary", "kind": "crash",
                    "at_step": 2}]}}}
    e = make_engine(**cfg)
    run_steps(e, 1)
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    it = iter(RepeatingLoader(random_dataloader(HIDDEN, 32, 8, seed=3)))
    x, y = next(it)
    e.backward(e.forward(x, y))
    with pytest.raises(InjectedCrash, match="step.boundary"):
        e.step()
    # the "killed" process's plane still knows exactly what it did
    assert chaos.active().fired[0]["step"] == 2


def test_engine_drains_degradation_records():
    from deepspeed_tpu.monitor import record as R
    e = make_engine()
    degradation.record("aio", "io_uring", "python", "probe failed")
    recs = e._drain_resilience_records()
    deg = [r for r in recs if r[R.F_KIND] == R.KIND_DEGRADATION]
    assert len(deg) == 1 and deg[0]["subsystem"] == "aio"
