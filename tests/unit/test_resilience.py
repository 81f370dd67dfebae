"""Resilience subsystem tests: atomic checksummed checkpoints,
crash-mid-save recovery (both checkpoint layouts), manifest verification
+ fallback, retention GC, the preemption handler, and the
training-health sentinel.  All deterministic via the fault-injection
harness (runtime/resilience/chaos.py) — fast lane."""

import json
import os
import signal

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.runtime.resilience import atomic, recovery
from deepspeed_tpu.runtime.resilience.chaos import (
    InjectedCrash, crash_after_bytes, measure_save_bytes, poison_batch)
from deepspeed_tpu.runtime.resilience.preemption import TrainingInterrupted
from deepspeed_tpu.runtime.resilience.sentinel import (SentinelAbort,
                                                       TrainingSentinel)
from tests.unit.simple_model import (base_engine_config, random_dataloader,
                                     simple_model_apply, simple_model_params)

HIDDEN = 16
RES_ON = {"enabled": True}


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


def np_params(engine):
    return jax.tree.map(np.asarray, engine.params)


def assert_params_equal(a, b):
    jax.tree.map(np.testing.assert_array_equal, a, b)


# --------------------------------------------------------------------- #
# package export sanity (the per-module import smoke lives in
# test_collection_smoke.py, which owns the module list)
# --------------------------------------------------------------------- #
def test_resilience_package_exports():
    from deepspeed_tpu.runtime import resilience
    for name in resilience.__all__:
        assert getattr(resilience, name) is not None


# --------------------------------------------------------------------- #
# atomic commit primitives
# --------------------------------------------------------------------- #
def test_write_latest_atomic_and_manifest_roundtrip(tmp_path):
    d = str(tmp_path)
    atomic.write_latest_atomic(d, "tagA")
    with open(os.path.join(d, "latest")) as f:
        assert f.read() == "tagA"
    atomic.write_latest_atomic(d, "tagB")
    with open(os.path.join(d, "latest")) as f:
        assert f.read() == "tagB"
    # no stray tmp files left behind
    assert os.listdir(d) == ["latest"]

    ck = tmp_path / "tag1"
    ck.mkdir()
    (ck / "a.bin").write_bytes(b"hello world")
    (ck / "b.bin").write_bytes(b"x" * 1000)
    atomic.write_manifest(str(ck))
    assert atomic.verify_manifest(str(ck)) == []
    # flip one byte -> CRC mismatch reported
    raw = bytearray((ck / "b.bin").read_bytes())
    raw[500] ^= 0xFF
    (ck / "b.bin").write_bytes(bytes(raw))
    problems = atomic.verify_manifest(str(ck))
    assert problems and "CRC32 mismatch" in problems[0]
    # truncate -> size mismatch
    (ck / "a.bin").write_bytes(b"hell")
    assert any("size mismatch" in p for p in atomic.verify_manifest(str(ck)))


def test_commit_tag_dir_replaces_existing(tmp_path):
    d = str(tmp_path)
    old = tmp_path / "tag"
    old.mkdir()
    (old / "stale.bin").write_bytes(b"old")
    tmp = atomic.tmp_tag_dir(d, "tag")
    with open(os.path.join(tmp, "fresh.bin"), "wb") as f:
        f.write(b"new")
    final = atomic.commit_tag_dir(d, "tag", tmp)
    assert sorted(os.listdir(final)) == ["fresh.bin", "manifest.json"]
    assert not any(atomic.is_tmp_dir(n) for n in os.listdir(d))


def test_retry_io_retries_oserror_not_injected_crash():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert atomic.retry_io(flaky, retries=3, backoff_seconds=0.0,
                           sleep=lambda _: None) == "ok"
    assert calls["n"] == 3

    def crash():
        raise InjectedCrash("boom")

    with pytest.raises(InjectedCrash):
        atomic.retry_io(crash, retries=5, backoff_seconds=0.0,
                        sleep=lambda _: None)

    def always():
        raise OSError("permanent")

    with pytest.raises(OSError):
        atomic.retry_io(always, retries=2, backoff_seconds=0.0,
                        sleep=lambda _: None)


# --------------------------------------------------------------------- #
# recovery: tag scanning, fallback resolution, GC
# --------------------------------------------------------------------- #
def _fake_tag(root, name, step_ts):
    d = root / name
    d.mkdir()
    (d / "data.bin").write_bytes(b"payload-" + name.encode())
    atomic.write_manifest(str(d))
    os.utime(d, (step_ts, step_ts))
    return d


def test_resolve_intact_tag_fallback_and_tmp_ignored(tmp_path):
    _fake_tag(tmp_path, "global_step1", 1000)
    _fake_tag(tmp_path, "global_step2", 2000)
    bad = _fake_tag(tmp_path, "global_step3", 3000)
    (tmp_path / "global_step9.tmp.dead").mkdir()  # in-flight junk

    assert recovery.list_tags(str(tmp_path)) == [
        "global_step3", "global_step2", "global_step1"]

    # intact request resolves to itself
    tag, problems = recovery.resolve_intact_tag(str(tmp_path), "global_step2")
    assert (tag, problems) == ("global_step2", [])

    # corrupt the newest -> fallback to next-newest intact
    (bad / "data.bin").write_bytes(b"garbage!")
    tag, problems = recovery.resolve_intact_tag(
        str(tmp_path), None, latest_tag="global_step3")
    assert tag == "global_step2"
    assert problems

    # everything corrupt -> loud FileNotFoundError naming the dir
    for name in ("global_step1", "global_step2"):
        (tmp_path / name / "data.bin").write_bytes(b"garbage!")
    with pytest.raises(FileNotFoundError, match="no intact checkpoint"):
        recovery.resolve_intact_tag(str(tmp_path), None,
                                    latest_tag="global_step3")


def test_gc_respects_latest_and_keep_every(tmp_path):
    for i, step in enumerate([10, 20, 30, 40, 50]):
        _fake_tag(tmp_path, f"global_step{step}", 1000 + i)
    # latest deliberately points at an OLD tag
    atomic.write_latest_atomic(str(tmp_path), "global_step10")
    deleted = recovery.gc_checkpoints(
        str(tmp_path), keep_last_n=2, keep_every=30,
        latest_tag="global_step10")
    # newest two (50, 40) kept; 30 kept by keep_every; 10 is latest; 20 goes
    assert deleted == ["global_step20"]
    assert sorted(recovery.list_tags(str(tmp_path))) == [
        "global_step10", "global_step30", "global_step40", "global_step50"]


def test_rescue_interrupted_re_save_of_same_tag(tmp_path):
    """Crash inside commit_tag_dir's re-save window (old dir renamed
    aside, new dir not yet promoted): the intact aside copy is restored
    on the next load instead of being invisible/swept."""
    _fake_tag(tmp_path, "ckpt.old.abc12345", 1000)  # renamed-aside copy
    (tmp_path / "ckpt.tmp.dead").mkdir()            # unpromoted staging
    atomic.write_latest_atomic(str(tmp_path), "ckpt")
    tag, problems = recovery.resolve_intact_tag(str(tmp_path), None,
                                                latest_tag="ckpt")
    assert tag == "ckpt" and problems == []
    assert (tmp_path / "ckpt" / "data.bin").is_file()
    # cleanup never touches .old. copies (only .tmp. staging dirs)
    _fake_tag(tmp_path, "other.old.deadbeef", 2000)
    atomic.cleanup_tmp_dirs(str(tmp_path))
    assert (tmp_path / "other.old.deadbeef").is_dir()
    assert not (tmp_path / "ckpt.tmp.dead").exists()


def test_reserved_tag_markers_rejected(tmp_path):
    e = make_engine()
    run_steps(e, 1)
    for bad in ("model.tmp.v2", "x.old.y"):
        with pytest.raises(ValueError, match="reserved"):
            e.save_checkpoint(str(tmp_path), tag=bad)


def test_finalize_checkpoint_retry_idempotent(tmp_path):
    """A retry wrapper may re-invoke finalize after the commit rename
    succeeded (e.g. a transient `latest`-write error): the second call
    must complete instead of failing on the vanished staging dir."""
    from deepspeed_tpu.runtime.sharded_checkpoint import finalize_checkpoint
    tmp = atomic.tmp_tag_dir(str(tmp_path), "t")
    with open(os.path.join(tmp, "x.bin"), "wb") as f:
        f.write(b"data")
    finalize_checkpoint(str(tmp_path), "t", {"global_steps": 1},
                        tmp_dir=tmp)
    assert not os.path.isdir(tmp)
    finalize_checkpoint(str(tmp_path), "t", {"global_steps": 1},
                        tmp_dir=tmp)  # re-entry after commit
    with open(tmp_path / "latest") as f:
        assert f.read() == "t"
    assert recovery.tag_problems(str(tmp_path), "t") == []


# --------------------------------------------------------------------- #
# crash-mid-save -> resume loads the newest intact tag (acceptance:
# a kill between ANY two file writes leaves the run resumable)
# --------------------------------------------------------------------- #
def _crash_sweep(tmp_path, sharded):
    cfg = {"resilience": dict(RES_ON)}
    if sharded:
        cfg["checkpoint"] = {"sharded": True}
    saver = make_engine(**cfg)
    run_steps(saver, 2)
    saver.save_checkpoint(str(tmp_path), tag="ckpt1")
    snap1 = np_params(saver)
    run_steps(saver, 1)
    snap2 = np_params(saver)

    total = measure_save_bytes(
        lambda: saver.save_checkpoint(str(tmp_path / "probe"), tag="ckpt2"),
        path_prefix=str(tmp_path / "probe"))
    assert total > 0
    loader = make_engine(**cfg)

    budgets = sorted({0, 1, total // 4, total // 2, (3 * total) // 4,
                      total - 1})
    for budget in budgets:
        with crash_after_bytes(budget, path_prefix=str(tmp_path)):
            with pytest.raises(InjectedCrash):
                saver.save_checkpoint(str(tmp_path), tag="ckpt2")
        path, client = loader.load_checkpoint(str(tmp_path), tag=None)
        loaded_tag = os.path.basename(path)
        assert loaded_tag in ("ckpt1", "ckpt2"), path
        want = snap1 if loaded_tag == "ckpt1" else snap2
        assert_params_equal(np_params(loader), want)
        assert client["global_steps"] == (2 if loaded_tag == "ckpt1" else 3)


def test_crash_mid_save_resumes_dense(tmp_path):
    _crash_sweep(tmp_path, sharded=False)


def test_crash_mid_save_resumes_sharded(tmp_path):
    _crash_sweep(tmp_path, sharded=True)


def test_crc_corruption_falls_back_to_previous_tag(tmp_path):
    e = make_engine(resilience=dict(RES_ON))
    run_steps(e, 2)
    e.save_checkpoint(str(tmp_path), tag="ckpt1")
    snap1 = np_params(e)
    run_steps(e, 1)
    e.save_checkpoint(str(tmp_path), tag="ckpt2")

    # flip a byte inside ckpt2's model file: manifest CRC catches it
    model = tmp_path / "ckpt2" / "mp_rank_00_model_states.npz"
    raw = bytearray(model.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    model.write_bytes(bytes(raw))

    loader = make_engine(resilience=dict(RES_ON))
    path, client = loader.load_checkpoint(str(tmp_path), tag=None)
    assert os.path.basename(path) == "ckpt1"
    assert client["global_steps"] == 2
    assert_params_equal(np_params(loader), snap1)


def test_explicit_corrupt_tag_fails_fast_no_substitution(tmp_path):
    """An explicitly requested tag is a contract: verification failure
    must raise (naming the tag and the alternatives), never silently
    load different weights.  Fallback is reserved for tag=None resume."""
    e = make_engine(resilience=dict(RES_ON))
    run_steps(e, 2)
    e.save_checkpoint(str(tmp_path), tag="ckpt1")
    run_steps(e, 1)
    e.save_checkpoint(str(tmp_path), tag="ckpt2")
    model = tmp_path / "ckpt2" / "mp_rank_00_model_states.npz"
    raw = bytearray(model.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    model.write_bytes(bytes(raw))

    loader = make_engine(resilience=dict(RES_ON))
    with pytest.raises(FileNotFoundError) as ei:
        loader.load_checkpoint(str(tmp_path), tag="ckpt2")
    msg = str(ei.value)
    assert "ckpt2" in msg and "ckpt1" in msg and "verification" in msg


def test_engine_gc_keeps_recent_and_latest(tmp_path):
    e = make_engine(resilience={"enabled": True, "keep_last_n": 2})
    run_steps(e, 1)
    for _ in range(4):
        e.save_checkpoint(str(tmp_path))  # default tag global_step1
        run_steps(e, 1)
    tags = recovery.list_tags(str(tmp_path))
    assert len(tags) == 2
    from deepspeed_tpu.runtime.checkpoint import read_latest_tag
    assert read_latest_tag(str(tmp_path)) in tags


# --------------------------------------------------------------------- #
# fail-fast load errors (satellite: name the tag, the dir, the options)
# --------------------------------------------------------------------- #
def test_missing_tag_error_names_tag_dir_and_available(tmp_path):
    e = make_engine()
    run_steps(e, 1)
    e.save_checkpoint(str(tmp_path), tag="have")
    with pytest.raises(FileNotFoundError) as ei:
        e.load_checkpoint(str(tmp_path), tag="nope")
    msg = str(ei.value)
    assert "nope" in msg and str(tmp_path) in msg and "have" in msg


def test_partial_tag_error_mentions_partial(tmp_path):
    e = make_engine()
    run_steps(e, 1)
    e.save_checkpoint(str(tmp_path), tag="t")
    os.remove(tmp_path / "t" / "mp_rank_00_model_states.npz")
    with pytest.raises(FileNotFoundError, match="partial"):
        e.load_checkpoint(str(tmp_path), tag="t")


# --------------------------------------------------------------------- #
# training-health sentinel (bf16: the fp16 overflow skip never fires)
# --------------------------------------------------------------------- #
def sentinel_engine(policy, budget=3, **res_extra):
    return make_engine(
        bf16={"enabled": True},
        resilience={"enabled": True,
                    "sentinel": dict({"enabled": True, "policy": policy,
                                      "anomaly_budget": budget,
                                      "warmup_steps": 50}, **res_extra)})


def test_sentinel_unit_ewma_and_ksigma():
    s = TrainingSentinel(ewma_alpha=0.1, k_sigma=4.0, warmup_steps=5,
                         policy="skip_step", anomaly_budget=3)
    for i in range(20):
        assert not s.observe(i, 1.0 + 0.01 * (i % 3), grad_norm=0.5)
    assert s.observe(20, 100.0, grad_norm=0.5)  # k-sigma spike
    assert s.consecutive_anomalies == 1
    # spike did NOT poison the baseline
    assert s.loss_stat.mean < 2.0
    assert not s.observe(21, 1.0, grad_norm=0.5)
    assert s.consecutive_anomalies == 0
    # NaN flags even during a fresh warmup
    s2 = TrainingSentinel(warmup_steps=100)
    assert s2.observe(0, float("nan"))
    # state round-trips
    sd = s.state_dict()
    s3 = TrainingSentinel()
    s3.load_state_dict(sd)
    assert s3.anomalies_seen == s.anomalies_seen
    assert s3.loss_stat.mean == pytest.approx(s.loss_stat.mean)


def test_sentinel_nan_bf16_skips_then_aborts(tmp_path):
    e = sentinel_engine("skip_step", budget=3)
    it = run_steps(e, 2)
    snap = np_params(e)
    x, y = next(it)
    bad = poison_batch((x, y))

    # two poisoned steps: skipped via the per-leaf select path, weights
    # and optimizer state untouched, counters advance
    for k in range(2):
        e.backward(e.forward(*bad))
        e.step()
        assert e.sentinel.consecutive_anomalies == k + 1
    assert_params_equal(np_params(e), snap)
    assert e.skipped_steps == 2
    assert e.sentinel.counters() == {"anomalies_seen": 2,
                                     "steps_skipped": 2, "rewinds": 0,
                                     "health_events": 0}

    # third consecutive anomaly exhausts the budget -> structured abort
    e.backward(e.forward(*bad))
    with pytest.raises(SentinelAbort) as ei:
        e.step()
    diag = ei.value.diagnostic
    assert diag["consecutive_anomalies"] == 3
    assert diag["anomaly_budget"] == 3
    assert any("non-finite" in r for r in diag["reasons"])
    json.dumps(diag, default=str)  # structured = machine-readable

    # a healthy batch after recovery still trains (engine not wedged)
    e2 = sentinel_engine("skip_step")
    run_steps(e2, 2)
    assert e2.sentinel.anomalies_seen == 0


def test_sentinel_rewind_restores_last_good_checkpoint(tmp_path):
    e = sentinel_engine("rewind", budget=5)
    it = run_steps(e, 2)
    e.save_checkpoint(str(tmp_path), tag="good")
    snap = np_params(e)
    run_steps(e, 1)
    assert e.global_steps == 3

    x, y = next(it)
    e.backward(e.forward(*poison_batch((x, y))))
    e.step()
    assert e.global_steps == 2  # rewound
    assert_params_equal(np_params(e), snap)
    assert e.sentinel.rewinds == 1
    # anomaly bookkeeping survives the rewind (budget still counts down)
    assert e.sentinel.consecutive_anomalies == 1
    run_steps(e, 1)
    assert e.global_steps == 3
    assert e.sentinel.consecutive_anomalies == 0


def test_sentinel_warn_adapts_baseline_on_level_shift():
    """Policy 'warn' trains straight through a spike, so the baseline
    must follow a legitimate permanent level-shift (LR decay, curriculum
    boundary) and finite spikes must never exhaust the abort budget."""
    s = TrainingSentinel(ewma_alpha=0.2, k_sigma=4.0, warmup_steps=5,
                         policy="warn", anomaly_budget=3)
    for i in range(20):
        s.observe(i, 2.0)
    # permanent drop to 1.0: flagged at first, but the baseline adapts
    flagged = sum(bool(s.observe(20 + i, 1.0)) for i in range(30))
    assert flagged >= 1
    assert s.consecutive_anomalies == 0      # finite spikes never abort
    assert not s.over_budget
    assert s.loss_stat.mean == pytest.approx(1.0, abs=0.05)
    # non-finite still counts toward the budget under warn
    for i in range(3):
        s.observe(60 + i, float("nan"))
    assert s.over_budget


def test_sentinel_defers_fp16_scale_warmup_to_scaler():
    """fp16 dynamic loss scaling overflows scaled grads on purpose while
    the scale anneals down — the scaler skips those steps itself, and the
    sentinel must not count them toward the abort budget."""
    e = make_engine(
        fp16={"enabled": True},
        resilience={"enabled": True,
                    "sentinel": {"enabled": True, "policy": "skip_step",
                                 "anomaly_budget": 2}})
    run_steps(e, 6)  # would raise SentinelAbort if warmup overflow counted
    assert e.sentinel.anomalies_seen == 0
    assert e.skipped_steps > 0  # the scaler, not the sentinel, skipped


def test_sentinel_counters_roundtrip_through_checkpoint(tmp_path):
    e = sentinel_engine("skip_step", budget=10)
    it = run_steps(e, 2)
    x, y = next(it)
    e.backward(e.forward(*poison_batch((x, y))))
    e.step()
    assert e.skipped_steps == 1
    e.save_checkpoint(str(tmp_path), tag="c")

    e2 = sentinel_engine("skip_step", budget=10)
    e2.load_checkpoint(str(tmp_path), tag="c")
    assert e2.skipped_steps == 1
    assert e2.sentinel.counters() == {"anomalies_seen": 1,
                                      "steps_skipped": 1, "rewinds": 0,
                                      "health_events": 0}


# --------------------------------------------------------------------- #
# preemption: SIGTERM -> graceful stop + emergency tag -> resume
# --------------------------------------------------------------------- #
def test_sigterm_takes_emergency_checkpoint_and_resumes(tmp_path):
    cfg = {"resilience": {"enabled": True,
                          "preemption": {"enabled": True, "reraise": False,
                                         "save_dir": str(tmp_path)}}}
    e = make_engine(**cfg)
    try:
        it = run_steps(e, 2)
        os.kill(os.getpid(), signal.SIGTERM)  # delivered to our handler
        assert e._preemption.triggered
        x, y = next(it)
        e.backward(e.forward(x, y))
        with pytest.raises(TrainingInterrupted) as ei:
            e.step()  # step 3 applies, then the boundary hook fires
        tag = ei.value.emergency_tag
        assert tag == "emergency_step3"
        assert os.path.isdir(tmp_path / tag)

        e2 = make_engine(**cfg)
        path, client = e2.load_checkpoint(str(tmp_path), tag=None)
        assert os.path.basename(path) == tag
        assert e2.global_steps == 3
        assert_params_equal(np_params(e2), np_params(e))
        run_steps(e2, 1)  # resumes cleanly
        assert e2.global_steps == 4
    finally:
        for eng in (e, locals().get("e2")):
            if eng is not None and eng._preemption is not None:
                eng._preemption.uninstall()


def test_preemption_signals_config_validated():
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
    base = {"train_micro_batch_size_per_gpu": 8}
    ok = DeepSpeedConfig(
        {**base, "resilience": {"preemption": {"signals": "SIGTERM"}}})
    assert ok.resilience_config.preemption.signals == ("SIGTERM",)
    with pytest.raises(DeepSpeedConfigError, match="SIGTREM"):
        DeepSpeedConfig(
            {**base, "resilience": {"preemption": {"signals": ["SIGTREM"]}}})


def test_preemption_handler_restores_prior_handlers():
    from deepspeed_tpu.runtime.resilience.preemption import PreemptionHandler
    prior = signal.getsignal(signal.SIGTERM)
    h = PreemptionHandler(signals=("SIGTERM",), reraise=False).install()
    assert signal.getsignal(signal.SIGTERM) == h._on_signal
    h.request_stop(signal.SIGTERM)
    with pytest.raises(TrainingInterrupted):
        h.finalize()
    assert signal.getsignal(signal.SIGTERM) == prior


# --------------------------------------------------------------------- #
# disabled-path regression: resilience off == pre-resilience behavior
# (except the atomic `latest` rename bugfix)
# --------------------------------------------------------------------- #
def test_disabled_layout_and_outputs_unchanged(tmp_path):
    e = make_engine()  # no resilience block at all
    assert e.sentinel is None and e._preemption is None
    run_steps(e, 2)
    e.save_checkpoint(str(tmp_path), tag="plain")
    # exact legacy file layout: no manifest, no tmp dirs, atomic latest
    assert sorted(os.listdir(tmp_path)) == ["latest", "plain"]
    assert sorted(os.listdir(tmp_path / "plain")) == [
        "ds_meta.json", "mp_rank_00_model_states.npz",
        "zero_pp_rank_0_mp_rank_00_optim_states.npz"]
    with open(tmp_path / "latest") as f:
        assert f.read() == "plain"
    with open(tmp_path / "plain" / "ds_meta.json") as f:
        assert "sentinel" not in json.load(f)["client_state"]

    # step outputs are identical with the block present-but-disabled
    e_dis = make_engine(resilience={"enabled": False})
    run_steps(e_dis, 2)
    assert_params_equal(np_params(e), np_params(e_dis))

    # ...and with atomic commits on, only the layout gains the manifest
    e_at = make_engine(resilience=dict(RES_ON))
    run_steps(e_at, 2)
    assert_params_equal(np_params(e), np_params(e_at))
    e_at.save_checkpoint(str(tmp_path / "at"), tag="plain")
    assert sorted(os.listdir(tmp_path / "at" / "plain")) == [
        "ds_meta.json", "manifest.json", "mp_rank_00_model_states.npz",
        "zero_pp_rank_0_mp_rank_00_optim_states.npz"]
    with np.load(tmp_path / "at" / "plain" / "mp_rank_00_model_states.npz",
                 allow_pickle=False) as a, \
            np.load(tmp_path / "plain" / "mp_rank_00_model_states.npz",
                    allow_pickle=False) as b:
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k])
