"""Fleet observability (monitor/{fleet,health,heartbeat,capture}.py,
docs/telemetry.md "Fleet observability").

Covers the ISSUE-10 acceptance surface with a CPU "fake fleet": the
aggregation/straggler/divergence paths driven by synthetic multi-host
window matrices through an injected gather_fn (no distributed world
needed), the end-to-end chain injected-slow-host -> straggler event with
lane attribution -> sentinel health event -> profiler capture armed and
disarmed after K steps (profiler mocked), heartbeat stale detection and
the --watch table, the boundary-only aggregation guarantee (gather count
== full windows, never on close), the host-sync audit regression
extended to the fleet path, and the schema-v2 satellites (identity
fields, host-gap, trace schema_version, launcher prefixes).
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.config import DeepSpeedConfigError, MonitorConfig
from deepspeed_tpu.monitor import (ATTR_COMPUTE, ATTR_EXPERT_HOTSPOT,
    ATTR_HOST_GAP,
    ATTR_SWAP, EVENT_DEAD_EXPERT, EVENT_DIVERGENCE, EVENT_EP_IMBALANCE,
    EVENT_ROUTER_COLLAPSE,
    EVENT_STRAGGLER, KIND_FLEET, KIND_FLEET_HOST, KIND_HEALTH, KIND_RECONCILE,
    KIND_STEP, SCHEMA_VERSION, STEP_RECORD_FIELDS, FleetAggregator,
    FleetHealth, HeartbeatWriter, ProfileCapture, TrainingMonitor,
    annotate_stale, format_watch_table, read_heartbeats, straggler_verdict,
    summarize_fleet,
    validate_trace_events)
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.monitor.fleet import (VEC_LEN, _encode_host,
                                         decode_window_vector,
                                         encode_window_vector)
from deepspeed_tpu.runtime.resilience.sentinel import TrainingSentinel


# --------------------------------------------------------------------- #
# fake-fleet plumbing
# --------------------------------------------------------------------- #
def _summary(t, loss=2.0, gap=0.0, swap_exp=0.0, step=10, gbps=None,
             **moe):
    """Window summary; the moe_* slots default ABSENT (NaN on the wire)
    exactly like a dense config — pass e.g. moe_local_load=2.0 to rig
    an expert-parallel fleet."""
    d = {"last_step": step, "steps": 5, "step_time_mean_s": t,
         "step_time_max_s": t, "loss_mean": loss,
         "host_gap_mean_s": gap, "swap_read_gbps": gbps,
         "swap_exposed_mean_s": swap_exp}
    d.update(moe)
    return d


def _matrix(rows):
    return np.stack([encode_window_vector(r) for r in rows])


class RiggedGather:
    """Injected gather_fn: serves the one-time hostname exchange, then
    returns the scripted window matrices in order (repeating the last).
    Counts window exchanges — the boundary-only acceptance check."""

    def __init__(self, hosts, matrices):
        self.hosts = hosts
        self.matrices = list(matrices)
        self.window_calls = 0

    def __call__(self, arr):
        arr = np.asarray(arr)
        if arr.dtype == np.uint8:  # hostname side-channel (init-time)
            return np.stack([_encode_host(h) for h in self.hosts])
        self.window_calls += 1
        idx = min(self.window_calls - 1, len(self.matrices) - 1)
        return self.matrices[idx]


class MockProfiler:
    def __init__(self, fail=False):
        self.fail = fail
        self.started = []
        self.stopped = 0
        self.active = False

    def start_trace(self, log_dir):
        if self.fail:
            raise RuntimeError("no profiler on this host")
        assert not self.active, "start_trace while active"
        self.active = True
        self.started.append(log_dir)

    def stop_trace(self):
        assert self.active, "stop_trace while idle"
        self.active = False
        self.stopped += 1


# --------------------------------------------------------------------- #
# window-vector codec + aggregation records
# --------------------------------------------------------------------- #
def test_window_vector_roundtrip():
    s = _summary(0.01, loss=2.5, gap=0.001, swap_exp=0.002, step=7,
                 gbps=12.5)
    vec = encode_window_vector(s)
    assert vec.shape == (VEC_LEN,) and vec.dtype == np.float64
    d = decode_window_vector(vec)
    assert d["step_time_mean_s"] == pytest.approx(0.01)
    assert d["loss_mean"] == pytest.approx(2.5)
    assert d["swap_read_gbps"] == pytest.approx(12.5)
    # absent fields ride as NaN and decode back to None
    d2 = decode_window_vector(encode_window_vector({"last_step": 3}))
    assert d2["last_step"] == 3.0
    assert d2["loss_mean"] is None and d2["swap_read_gbps"] is None


def test_fake_fleet_aggregate_records():
    hosts = ["h0", "h1", "h2", "h3"]
    rows = [_summary(0.010), _summary(0.012), _summary(0.020, gbps=4.0),
            _summary(0.011)]
    rig = RiggedGather(hosts, [_matrix(rows)])
    agg = FleetAggregator(process_index=0, process_count=4, host="h0",
                          gather_fn=rig)
    mat = agg.exchange(_summary(0.010))
    assert rig.window_calls == 1
    per_host = agg.per_host_records(mat)
    assert [r[R.F_HOST] for r in per_host] == hosts
    assert all(r[R.F_KIND] == KIND_FLEET_HOST for r in per_host)
    assert per_host[2][R.FL_SWAP_READ_GBPS] == pytest.approx(4.0)
    fleet = agg.fleet_record(mat)
    assert fleet[R.F_KIND] == KIND_FLEET
    assert fleet[R.FL_HOSTS] == 4
    assert fleet[R.FL_STEP_TIME_MIN_S] == pytest.approx(0.010)
    assert fleet[R.FL_STEP_TIME_MAX_S] == pytest.approx(0.020)
    assert fleet[R.FL_STEP_TIME_MEDIAN_S] == pytest.approx(0.0115)
    assert fleet[R.FL_STEP_TIME_P99_S] <= fleet[R.FL_STEP_TIME_MAX_S] + 1e-9
    assert fleet[R.FL_PER_HOST]["host"] == hosts
    assert fleet[R.FL_PER_HOST]["step_time_s"][2] == pytest.approx(0.020)


def test_single_host_degenerate_summary():
    agg = FleetAggregator(process_index=0, process_count=1, host="solo")
    mat = agg.exchange(_summary(0.01, step=5))
    assert mat.shape == (1, VEC_LEN)
    fleet = agg.fleet_record(mat)
    assert fleet[R.FL_HOSTS] == 1
    assert fleet[R.FL_STEP_TIME_MEDIAN_S] == pytest.approx(0.01)
    v = straggler_verdict(mat, agg.host_names())
    assert v["straggler"] is False and v["ratio"] == pytest.approx(1.0)


def test_fleet_gather_shape_mismatch_is_loud():
    rig = RiggedGather(["a", "b"], [np.zeros((3, VEC_LEN + 1))])
    agg = FleetAggregator(0, 2, host="a", gather_fn=rig)
    with pytest.raises(ValueError, match="mixed monitor schema"):
        agg.exchange(_summary(0.01))


# --------------------------------------------------------------------- #
# straggler / divergence detection
# --------------------------------------------------------------------- #
def _warm(health, hosts, windows=3, t=0.010):
    for w in range(windows):
        assert health.observe(_matrix([_summary(t, step=10 * (w + 1))
                                       for _ in hosts]), hosts) == []


def test_straggler_lane_attribution_swap_and_hostgap():
    hosts = ["h0", "h1", "h2", "h3"]
    health = FleetHealth(warmup_windows=2)
    _warm(health, hosts)
    # host 2 slow, the excess dominated by exposed swap reads
    rows = [_summary(0.010, step=40), _summary(0.010, step=40),
            _summary(0.030, gap=0.001, swap_exp=0.018, step=40),
            _summary(0.010, step=40)]
    evs = health.observe(_matrix(rows), hosts)
    assert len(evs) == 1
    ev = evs[0]
    assert ev[R.F_KIND] == KIND_HEALTH
    assert ev[R.H_EVENT] == EVENT_STRAGGLER
    assert ev[R.F_HOST] == "h2" and ev[R.F_PROCESS_INDEX] == 2
    assert ev[R.H_LANE] == ATTR_SWAP
    assert ev[R.H_RATIO] == pytest.approx(3.0)
    assert ev[R.H_STEP] == 40
    # host-gap dominated excess names the host-gap lane
    health2 = FleetHealth(warmup_windows=2)
    _warm(health2, hosts)
    rows = [_summary(0.010, step=40), _summary(0.010, step=40),
            _summary(0.010, step=40),
            _summary(0.025, gap=0.014, step=40)]
    evs = health2.observe(_matrix(rows), hosts)
    assert len(evs) == 1 and evs[0][R.H_LANE] == ATTR_HOST_GAP
    assert evs[0][R.F_HOST] == "h3"


def test_straggler_needs_warmup_and_ratio():
    hosts = ["h0", "h1"]
    health = FleetHealth(warmup_windows=3, straggler_min_ratio=1.5)
    # a slow host inside the warmup window is NOT flagged
    rows = [_summary(0.010), _summary(0.030)]
    assert health.observe(_matrix(rows), hosts) == []
    _warm(health, hosts, windows=3)
    # past warmup but under the ratio gate: still quiet
    rows = [_summary(0.010, step=40), _summary(0.0125, step=40)]
    assert [e for e in health.observe(_matrix(rows), hosts)
            if e[R.H_EVENT] == EVENT_STRAGGLER] == []


def test_straggler_does_not_drag_baseline():
    """Flagged hosts' samples must not update the EWMA — a persistent
    straggler keeps being flagged instead of becoming the new normal."""
    hosts = ["h0", "h1", "h2", "h3"]
    health = FleetHealth(warmup_windows=1)
    _warm(health, hosts, windows=2)
    for w in range(5):
        rows = [_summary(0.010, step=30 + w)] * 3 + \
            [_summary(0.030, step=30 + w)]
        evs = [e for e in health.observe(_matrix(rows), hosts)
               if e[R.H_EVENT] == EVENT_STRAGGLER]
        assert len(evs) == 1, f"window {w}: straggler went quiet"
        assert evs[0][R.F_HOST] == "h3"


def test_straggler_slow_from_first_window_is_flagged():
    """Review regression: a host that is slow from the job's FIRST
    window (cold NVMe, sick host from boot) must still be flagged —
    its warmup samples must not pollute the EWMA baseline into masking
    it (the ratio gate, which needs no history, keeps it out of the
    baseline)."""
    hosts = ["h0", "h1", "h2", "h3"]
    health = FleetHealth(warmup_windows=2)
    flagged_windows = 0
    for w in range(10):
        rows = [_summary(0.010, step=10 * (w + 1))] * 3 + \
            [_summary(0.020, step=10 * (w + 1))]   # 2x slow from w=0
        evs = [e for e in health.observe(_matrix(rows), hosts)
               if e[R.H_EVENT] == EVENT_STRAGGLER]
        if w >= health.warmup_windows:
            assert len(evs) == 1 and evs[0][R.F_HOST] == "h3", \
                f"window {w}: boot-time straggler masked"
            flagged_windows += 1
    assert flagged_windows == 8


def test_grad_norm_divergence_detected():
    """ISSUE-10 tentpole: divergence watches loss AND grad-norm spread
    — corrupt optimizer state moves the norm windows before the loss."""
    hosts = ["h0", "h1", "h2"]
    health = FleetHealth(warmup_windows=0, divergence_rel_spread=1e-3)
    rows = [dict(_summary(0.01, loss=2.0), grad_norm_mean=1.0),
            dict(_summary(0.01, loss=2.0), grad_norm_mean=1.0),
            dict(_summary(0.01, loss=2.0), grad_norm_mean=5.0)]
    evs = [e for e in health.observe(_matrix(rows), hosts)
           if e[R.H_EVENT] == EVENT_DIVERGENCE]
    assert len(evs) == 1
    assert evs[0][R.H_METRIC] == "grad_norm"
    assert evs[0][R.F_HOST] == "h2"
    # the spread rides the metric-neutral key; a grad-norm magnitude
    # never lands under the loss-labeled field
    assert evs[0][R.H_SPREAD] == pytest.approx(4.0)
    assert R.FL_LOSS_SPREAD not in evs[0]
    # identical norms (and losses): quiet
    rows = [dict(_summary(0.01, loss=2.0), grad_norm_mean=1.0)] * 3
    assert [e for e in health.observe(_matrix(rows), hosts)
            if e[R.H_EVENT] == EVENT_DIVERGENCE] == []


def test_divergence_detection_flags_outlier_replica():
    hosts = ["h0", "h1", "h2"]
    health = FleetHealth(warmup_windows=0, divergence_rel_spread=1e-3)
    rows = [_summary(0.01, loss=2.0), _summary(0.01, loss=2.0),
            _summary(0.01, loss=2.4)]
    evs = [e for e in health.observe(_matrix(rows), hosts)
           if e[R.H_EVENT] == EVENT_DIVERGENCE]
    assert len(evs) == 1
    assert evs[0][R.F_HOST] == "h2"
    assert evs[0][R.FL_LOSS_SPREAD] == pytest.approx(0.4)
    # identical (globally-reduced) losses: quiet
    rows = [_summary(0.01, loss=2.0)] * 3
    assert [e for e in health.observe(_matrix(rows), hosts)
            if e[R.H_EVENT] == EVENT_DIVERGENCE] == []


def test_two_host_straggler_not_masked_by_midpoint_median():
    """Review regression: the ratio gate divides by the PEER median
    (leave-one-out).  An all-host median on P=2 is the midpoint of the
    pair, so a 30% straggler read as only ~1.13x 'the fleet' and
    slipped a 1.15 gate — while its samples kept feeding the EWMA
    baseline."""
    hosts = ["h0", "h1"]
    health = FleetHealth(warmup_windows=1, straggler_min_ratio=1.15)
    _warm(health, hosts, windows=2, t=0.100)
    rows = [_summary(0.100, step=30), _summary(0.130, step=30)]
    evs = [e for e in health.observe(_matrix(rows), hosts)
           if e[R.H_EVENT] == EVENT_STRAGGLER]
    assert len(evs) == 1 and evs[0][R.F_HOST] == "h1"
    assert evs[0][R.H_RATIO] == pytest.approx(1.3)
    # one-shot verdict (one window, no history) uses the same peer median
    v = straggler_verdict(_matrix(rows), hosts, min_ratio=1.15)
    assert v["straggler"] is True and v["host"] == "h1"
    assert v["ratio"] == pytest.approx(1.3)


def test_two_host_divergence_is_ambiguous_not_blamed_on_p0():
    """Review regression: with P=2 both hosts are equidistant from the
    midpoint median — argmax's tie-break blamed the HEALTHY process 0
    (which then armed ITS capture).  The event must mark the
    attribution ambiguous and carry no process_index."""
    hosts = ["h0", "h1"]
    health = FleetHealth(warmup_windows=0, divergence_rel_spread=1e-3)
    rows = [_summary(0.01, loss=1.0), _summary(0.01, loss=2.0)]
    evs = [e for e in health.observe(_matrix(rows), hosts)
           if e[R.H_EVENT] == EVENT_DIVERGENCE]
    assert len(evs) == 1
    ev = evs[0]
    assert ev[R.F_PROCESS_INDEX] is None
    assert ev[R.F_HOST].startswith("ambiguous:")
    assert "h0" in ev[R.F_HOST] and "h1" in ev[R.F_HOST]
    assert ev[R.F_WORLD_SIZE] == 2


def test_straggler_verdict_one_shot():
    hosts = ["h0", "h1", "h2"]
    mat = _matrix([_summary(0.010), _summary(0.010),
                   _summary(0.030, swap_exp=0.015)])
    v = straggler_verdict(mat, hosts)
    assert v["straggler"] is True and v["host"] == "h2"
    assert v["ratio"] == pytest.approx(3.0)
    assert v["lane"] == ATTR_SWAP
    mat = _matrix([_summary(0.010)] * 3)
    assert straggler_verdict(mat, hosts)["straggler"] is False


# --------------------------------------------------------------------- #
# capture: rate limit, K-step disarm, failure path (profiler mocked)
# --------------------------------------------------------------------- #
def test_capture_arm_disarm_and_rate_limit(tmp_path):
    prof = MockProfiler()
    cap = ProfileCapture(str(tmp_path), steps=3, max_captures=2,
                         cooldown_steps=10, profiler=prof)
    assert cap.arm("step_time_above_band", step=5) is True
    assert prof.active and cap.armed
    assert cap.arm("again", step=5) is False      # already armed
    for s in (6, 7):
        cap.observe_step_end(s)
        assert cap.armed
    cap.observe_step_end(8)                        # K-th step: disarm
    assert not cap.armed and prof.stopped == 1
    assert cap.captures[0]["steps"] == 3
    assert os.path.isdir(cap.captures[0]["dir"])
    assert cap.arm("too-soon", step=12) is False   # inside cooldown
    assert cap.arm("ok", step=18) is True          # past cooldown
    cap.observe_step_end(19)
    cap.close(20)                                  # close stops an armed one
    assert prof.stopped == 2 and not prof.active
    assert cap.arm("third", step=100) is False     # max_captures reached
    assert cap.counters() == {"captures": 2, "capture_armed": 0}


def test_capture_trigger_flags_and_failure(tmp_path):
    prof = MockProfiler()
    cap = ProfileCapture(str(tmp_path), steps=1, profiler=prof)
    assert cap.maybe_arm_for_flags(["model_violation"], 1) is False
    assert cap.maybe_arm_for_flags(["swap_below_ceiling_band"], 1) is True
    cap.observe_step_end(2)
    assert prof.stopped == 1
    # a dead profiler disables capture for the run, loudly not fatally
    bad = ProfileCapture(str(tmp_path / "bad"), profiler=MockProfiler(
        fail=True))
    assert bad.arm("x", 1) is False
    assert bad.exhausted
    assert bad.arm("y", 500) is False


# --------------------------------------------------------------------- #
# heartbeat protocol: stale detection + --watch table
# --------------------------------------------------------------------- #
def test_heartbeat_roundtrip_and_stale(tmp_path):
    d = str(tmp_path / "hb")
    for p in range(3):
        HeartbeatWriter(d, process_index=p, world_size=3,
                        host=f"host{p}").beat(step=40 + p)
    beats = read_heartbeats(d)
    assert [b["process_index"] for b in beats] == [0, 1, 2]
    assert [b["step"] for b in beats] == [40, 41, 42]
    assert all(b["age_s"] < 30 for b in beats)
    # age one host artificially: stale only past the threshold
    beats = read_heartbeats(d, now=time.time() + 120)
    annotate_stale(beats, stale_after_s=60)
    assert all(b["stale"] for b in beats)
    table = format_watch_table(read_heartbeats(d), stale_after_s=1e9)
    assert "host0" in table and "running" in table and "STALE" not in table
    table = format_watch_table(read_heartbeats(d, now=time.time() + 120),
                               stale_after_s=60)
    assert "STALE" in table
    # a stopped host is not stale no matter how old its file is
    HeartbeatWriter(d, process_index=1, world_size=3,
                    host="host1").close(step=43)
    beats = annotate_stale(read_heartbeats(d, now=time.time() + 120), 60)
    assert beats[1]["status"] == "stopped" and not beats[1]["stale"]


def test_heartbeat_adaptive_staleness_long_windows(tmp_path):
    """Review regression: a long-step job beats once per ~100 s; the
    staleness threshold must scale to 3x the host's OWN reported beat
    interval instead of crying STALE against a 60 s wall constant."""
    now = time.time()
    beats = [{"host": "big", "process_index": 0, "status": "running",
              "step": 40, "time": now - 150, "age_s": 150.0,
              "interval_s": 100.0}]
    annotate_stale(beats, stale_after_s=60)
    assert beats[0]["stale"] is False          # 150 < 3*100
    beats[0]["age_s"] = 350.0
    annotate_stale(beats, stale_after_s=60)
    assert beats[0]["stale"] is True           # 350 > 3*100
    # a fast-beating host keeps the wall-clock floor
    quick = [{"host": "q", "process_index": 1, "status": "running",
              "age_s": 70.0, "interval_s": 2.0}]
    annotate_stale(quick, stale_after_s=60)
    assert quick[0]["stale"] is True
    # the FIRST beat already reports an interval (monitor build ->
    # first flush, seeded at construction) so a long first window
    # cannot render a transient false STALE before the second beat
    w = HeartbeatWriter(str(tmp_path / "hb1"), 0, 1, host="h")
    w._t_last -= 100.0                 # pretend construction was 100s ago
    w.beat(step=1)
    first = read_heartbeats(str(tmp_path / "hb1"))[0]
    assert first["interval_s"] == pytest.approx(100.0, abs=1.0)
    first["age_s"] = 150.0             # < 3x first interval
    annotate_stale([first], stale_after_s=60)
    assert first["stale"] is False


def test_watch_table_renders_missing_workers(tmp_path):
    """Review regression: a worker that died before its FIRST beat must
    show as MISSING, not be silently absent from the table."""
    d = str(tmp_path / "hb")
    HeartbeatWriter(d, 0, 3, host="alive0").beat(step=5)
    HeartbeatWriter(d, 2, 3, host="alive2").beat(step=5)
    table = format_watch_table(read_heartbeats(d), expected_procs=3)
    assert "alive0" in table and "alive2" in table
    assert "MISSING" in table
    lines = [ln for ln in table.splitlines() if "MISSING" in ln]
    assert len(lines) == 1 and lines[0].lstrip().startswith("1")


def test_heartbeat_corrupt_file_surfaces(tmp_path):
    d = str(tmp_path / "hb")
    HeartbeatWriter(d, 0, 1, host="ok").beat(step=1)
    with open(os.path.join(d, "hb_9.json"), "w") as f:
        f.write("{torn")
    beats = read_heartbeats(d)
    corrupt = [b for b in beats if b["status"] == "corrupt"]
    # the process index is recovered from the filename, so the watch
    # table shows ONE corrupt row — never an extra MISSING row too
    assert len(corrupt) == 1 and corrupt[0]["process_index"] == 9
    table = format_watch_table(beats, expected_procs=10)
    assert "corrupt" in table
    rows_for_9 = [ln for ln in table.splitlines()
                  if ln.lstrip().startswith("9")]
    assert len(rows_for_9) == 1 and "MISSING" not in rows_for_9[0]


def test_resolve_heartbeat_dir_handles_job_name(tmp_path):
    """--watch is pointed at monitor.output_path; the beats live under
    output_path/<job_name>/heartbeat when job_name is set."""
    from deepspeed_tpu.monitor.heartbeat import resolve_heartbeat_dir
    root = str(tmp_path)
    # nothing yet: default guess (may appear later)
    assert resolve_heartbeat_dir(root) == os.path.join(root, "heartbeat")
    # job_name layout
    HeartbeatWriter(os.path.join(root, "run1", "heartbeat"),
                    0, 2, host="w0").beat(step=3)
    assert resolve_heartbeat_dir(root) == os.path.join(
        root, "run1", "heartbeat")
    # empty-job_name layout wins once present
    HeartbeatWriter(os.path.join(root, "heartbeat"),
                    0, 2, host="w0").beat(step=3)
    assert resolve_heartbeat_dir(root) == os.path.join(root, "heartbeat")
    # pointing directly AT the heartbeat dir also works
    assert resolve_heartbeat_dir(
        os.path.join(root, "heartbeat")) == os.path.join(root, "heartbeat")


# --------------------------------------------------------------------- #
# the acceptance chain: slow host -> straggler event -> sentinel ->
# capture armed on the flagged host and disarmed after K steps
# --------------------------------------------------------------------- #
def _fleet_cfg(tmp_path, **kw):
    d = {"enabled": True, "output_path": str(tmp_path),
         "writers": ["jsonl"], "write_interval": 2, "fleet": True,
         "health_warmup_windows": 1, "heartbeat": True}
    d.update(kw)
    return MonitorConfig.from_dict(d)


def _rigged_windows(slow_from=2, windows=6, slow_idx=2):
    """Scripted fleet windows: healthy, then host `slow_idx` 3x slow
    with swap-exposed excess."""
    hosts = [f"host{i}" for i in range(4)]
    mats = []
    for w in range(windows):
        rows = []
        for p in range(4):
            if w >= slow_from and p == slow_idx:
                rows.append(_summary(0.030, gap=0.001, swap_exp=0.018,
                                     step=2 * (w + 1)))
            else:
                rows.append(_summary(0.010, step=2 * (w + 1)))
        mats.append(_matrix(rows))
    return hosts, mats


def test_e2e_slow_host_event_sentinel_capture(tmp_path):
    """ISSUE-10 acceptance: injected slow host -> straggler event with
    correct lane attribution -> sentinel health event recorded ->
    capture armed on the flagged host and disarmed after K steps."""
    hosts, mats = _rigged_windows()
    rig = RiggedGather(hosts, mats)
    prof = MockProfiler()
    sentinel = TrainingSentinel()
    mon = TrainingMonitor(
        _fleet_cfg(tmp_path, capture={"enabled": True, "steps": 2,
                                      "max_captures": 1}),
        process_index=2, world_size=4, host="host2",
        gather_fn=rig, profiler=prof,
        health_sink=sentinel.record_health_event)
    assert not mon.is_emitter  # non-zero rank: no file writers
    assert mon.jsonl_path is None
    step = 0
    for _ in range(2):  # two healthy windows (warmup=1 + baseline)
        for _ in range(2):
            step += 1
            mon.mark_step_start()
            mon.end_step(step, loss=2.0)
    assert rig.window_calls == 2 and not prof.active
    # window 3: the rigged matrix turns host2 (me) into the straggler
    for _ in range(2):
        step += 1
        mon.mark_step_start()
        mon.end_step(step, loss=2.0)
    assert rig.window_calls == 3
    evs = mon.last_health_events
    assert [e[R.H_EVENT] for e in evs] == [EVENT_STRAGGLER]
    assert evs[0][R.F_HOST] == "host2" and evs[0][R.H_LANE] == ATTR_SWAP
    # schema-v2 identity triple rides health events too
    assert evs[0][R.F_WORLD_SIZE] == 4
    # sentinel got the structured event
    assert sentinel.health_events_seen == 1
    assert sentinel.counters()["health_events"] == 1
    assert sentinel.health_events[0][R.H_EVENT] == EVENT_STRAGGLER
    diag = sentinel.diagnostic(step)
    assert diag["recent_health_events"][0][R.F_HOST] == "host2"
    # capture armed on the FLAGGED host (us), and disarms after K=2.
    # A sentinel-rewound step (discard_step) still ran a full
    # forward/backward on device under the live profiler, so it counts
    # toward the K-step bound — a rewind streak must not let the
    # capture outlive its window
    assert prof.active and mon.capture.armed
    mon.mark_step_start()
    mon.discard_step()
    assert mon.capture.armed          # 1 of 2 captured steps (rewound)
    mon.mark_step_start()
    mon.end_step(step + 1, loss=2.0)
    assert not mon.capture.armed      # K-step disarm
    assert prof.stopped == 1
    assert "straggler" in prof.started[0]
    mon.close()
    # heartbeat was written by the non-emitter rank too
    beats = read_heartbeats(os.path.join(mon.out_dir, "heartbeat"))
    assert [b["process_index"] for b in beats] == [2]
    assert beats[0]["status"] == "stopped"


def test_e2e_rank0_emits_fleet_and_health_records(tmp_path):
    """Rank 0 of the same fake fleet: per-host + fleet-aggregate +
    health records ride the JSONL stream; capture is NOT armed (the
    straggler is host2, not us)."""
    hosts, mats = _rigged_windows()
    rig = RiggedGather(hosts, mats)
    prof = MockProfiler()
    mon = TrainingMonitor(
        _fleet_cfg(tmp_path, capture={"enabled": True}),
        process_index=0, world_size=4, host="host0",
        gather_fn=rig, profiler=prof)
    assert mon.is_emitter
    for step in range(1, 7):
        mon.mark_step_start()
        mon.end_step(step, loss=2.0)
    mon.close()
    assert not prof.started  # the anomaly is on host2, not on rank 0
    recs = [json.loads(line) for line in open(mon.jsonl_path)]
    kinds = [r.get(R.F_KIND) for r in recs]
    assert kinds.count(KIND_FLEET) == 3      # one per FULL window
    assert kinds.count(KIND_FLEET_HOST) == 12
    health = [r for r in recs if r.get(R.F_KIND) == KIND_HEALTH]
    assert len(health) == 1 and health[0][R.F_HOST] == "host2"
    fleet = [r for r in recs if r.get(R.F_KIND) == KIND_FLEET][-1]
    assert fleet[R.FL_HOSTS] == 4
    assert fleet[R.FL_STEP_TIME_MAX_S] == pytest.approx(0.030)
    assert fleet[R.FL_STEP_TIME_MEDIAN_S] == pytest.approx(0.010)
    assert fleet[R.FL_PER_HOST]["host"] == hosts
    # every step/reconcile record carries the v2 identity triple
    for r in recs:
        if r.get(R.F_KIND) in (KIND_STEP, KIND_RECONCILE):
            assert r[R.F_HOST] == "host0"
            assert r[R.F_PROCESS_INDEX] == 0
            assert r[R.F_WORLD_SIZE] == 4


def test_aggregation_traffic_boundary_only(tmp_path):
    """Acceptance: cross-host traffic at FULL flush-window boundaries
    only — N steps at window W = N//W exchanges, and close() (a partial
    window may remain, hosts may exit at different times) never adds
    one."""
    hosts = [f"host{i}" for i in range(2)]
    rig = RiggedGather(hosts, [_matrix([_summary(0.01)] * 2)])
    mon = TrainingMonitor(_fleet_cfg(tmp_path, write_interval=3),
                          process_index=0, world_size=2, host="host0",
                          gather_fn=rig)
    for step in range(1, 8):  # 7 steps, window 3 -> 2 full windows
        mon.mark_step_start()
        mon.end_step(step, loss=1.0)
    assert rig.window_calls == 2
    # explicit mid-run flush() with fleet live: no collective AND the
    # partial window stays buffered — flushing it on one host would
    # shift that host's future boundaries off its peers' (window
    # cadence is collective state); close()'s final flush still lands
    # the buffered steps on disk below
    mon.flush()
    assert rig.window_calls == 2
    assert len(mon.stream._pending) == 1
    mon.close()               # final flush: no collective
    assert rig.window_calls == 2
    recs = [json.loads(line) for line in open(mon.jsonl_path)]
    # the partial window's STEP records still made it to disk
    steps = [r[R.F_STEP] for r in recs if r.get(R.F_KIND) == KIND_STEP]
    assert steps == [1, 2, 3, 4, 5, 6, 7]


def test_post_exchange_local_failure_keeps_exchange_alive(tmp_path):
    """Review regression: only a failed EXCHANGE disables the hook.  A
    local bug in record/health processing on one host must not stop
    that host from joining future allgathers — the other hosts would
    block forever on the missing participant."""
    hosts = ["h0", "h1"]
    rig = RiggedGather(hosts, [_matrix([_summary(0.01)] * 2)])
    mon = TrainingMonitor(_fleet_cfg(tmp_path), process_index=0,
                          world_size=2, host="h0", gather_fn=rig)

    def boom(matrix):
        raise RuntimeError("local record bug")

    mon.fleet.per_host_records = boom
    for step in range(1, 7):  # 3 full windows
        mon.mark_step_start()
        mon.end_step(step, loss=1.0)
    mon.close()
    # the collective kept running despite the per-window local failure
    assert rig.window_calls == 3


def test_non_emitter_skips_record_assembly(tmp_path):
    """Review regression: fleet non-emitter ranks have no writers — the
    flush must not pay the records-only boundary reads (lr/loss-scale)
    or assemble step records nobody consumes."""
    hosts = ["h0", "h1"]
    rig = RiggedGather(hosts, [_matrix([_summary(0.01)] * 2)])
    reads = {"n": 0}

    def boundary():
        reads["n"] += 1
        return {"lr": 1e-3}

    mon = TrainingMonitor(_fleet_cfg(tmp_path), process_index=1,
                          world_size=2, host="h1", gather_fn=rig,
                          boundary_fn=boundary)
    for step in range(1, 5):
        mon.mark_step_start()
        mon.end_step(step, loss=1.0)
    mon.close()
    assert reads["n"] == 0
    assert mon.stream.records_emitted == 0
    assert rig.window_calls == 2  # the fleet path still ran


def test_fleet_exchange_failure_degrades_loudly(tmp_path, caplog):
    calls = {"n": 0}

    def broken(arr):
        arr = np.asarray(arr)
        if arr.dtype == np.uint8:
            return np.stack([_encode_host("h0"), _encode_host("h1")])
        calls["n"] += 1
        raise RuntimeError("collective timeout")

    mon = TrainingMonitor(_fleet_cfg(tmp_path), process_index=0,
                          world_size=2, host="h0", gather_fn=broken)
    for step in range(1, 7):
        mon.mark_step_start()
        mon.end_step(step, loss=1.0)
    mon.close()
    assert calls["n"] == 1  # hook disabled after the first failure
    recs = [json.loads(line) for line in open(mon.jsonl_path)]
    # step records keep flowing; no fleet records after the failure
    assert [r[R.F_STEP] for r in recs
            if r.get(R.F_KIND) == KIND_STEP] == [1, 2, 3, 4, 5, 6]
    assert [r for r in recs if r.get(R.F_KIND) == KIND_FLEET] == []
    # the degradation is marked IN the stream, not just this host's log
    degraded = [r for r in recs if r.get("fleet_disabled")]
    assert len(degraded) == 1
    assert "collective timeout" in degraded[0]["fleet_disabled"]


# --------------------------------------------------------------------- #
# host-sync audit regression extended to the fleet path (acceptance)
# --------------------------------------------------------------------- #
def _engine(tmp_path, monitor=None):
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    ds.reset_mesh_context()
    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                     num_layers=2, num_heads=4, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
    }
    if monitor is not None:
        monitor = dict(monitor)
        monitor.setdefault("enabled", True)
        monitor.setdefault("output_path", str(tmp_path))
        config["monitor"] = monitor
    engine, _, _, _ = ds.initialize(
        model=model, config=config,
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    return engine


def test_fleet_monitor_on_adds_zero_host_sync_findings(tmp_path):
    """Acceptance: host-sync audit stays clean with FLEET monitoring
    enabled — zero new auditor findings, unchanged lockstep signature
    and wire bytes vs monitor-off (the fleet exchange is host-side at
    flush boundaries; the traced step programs are identical)."""
    from deepspeed_tpu.analysis import RULE_HOST_SYNC, audit_engine
    plain = _engine(tmp_path)
    plain_report = audit_engine(plain, multihost=False)
    fleet = _engine(tmp_path, monitor={"writers": ["jsonl"],
                                       "write_interval": 2,
                                       "fleet": True, "heartbeat": True})
    assert fleet.monitor is not None and fleet.monitor.fleet is not None
    ids = np.random.RandomState(0).randint(
        0, 64, size=(2, 16)).astype(np.int32)
    for _ in range(4):
        loss = fleet.forward(ids)
        fleet.backward(loss)
        fleet.step()
    report = audit_engine(fleet, multihost=False)
    assert fleet.monitor.fleet.exchanges == 2  # the fleet path RAN
    fleet.monitor.close()
    host_sync = [f for f in report.findings if f.rule == RULE_HOST_SYNC]
    assert host_sync == [], [f.format() for f in host_sync]
    assert report.signature == plain_report.signature
    assert report.wire_bytes_per_step == plain_report.wire_bytes_per_step
    # degenerate single-host fleet records landed
    recs = [json.loads(line) for line in open(fleet.monitor.jsonl_path)]
    fleet_recs = [r for r in recs if r.get(R.F_KIND) == KIND_FLEET]
    assert fleet_recs and fleet_recs[0][R.FL_HOSTS] == 1
    steps = [r for r in recs if r.get(R.F_KIND) == KIND_STEP]
    # schema v2: identity populated on a single-host run too
    assert all(r[R.F_WORLD_SIZE] == 1 and r[R.F_PROCESS_INDEX] == 0
               and r[R.F_HOST] for r in steps)
    # host-gap measured from step 2 on (needs a previous end_step)
    assert all(r[R.F_HOST_GAP_S] is not None for r in steps[1:])


# --------------------------------------------------------------------- #
# schema v2 satellites
# --------------------------------------------------------------------- #
def test_step_record_fields_carry_identity_and_gap():
    for f in (R.F_HOST, R.F_PROCESS_INDEX, R.F_WORLD_SIZE, R.F_HOST_GAP_S):
        assert f in STEP_RECORD_FIELDS
    ident = R.identity()
    assert ident[R.F_PROCESS_INDEX] == 0 and ident[R.F_WORLD_SIZE] >= 1
    assert ident[R.F_HOST]


def test_trace_schema_version_validated():
    from deepspeed_tpu.monitor import TraceEventBuffer
    buf = TraceEventBuffer()
    buf.add_span("x", 1.0, 2.0)
    payload = buf.to_json()
    assert payload["otherData"]["schema_version"] == SCHEMA_VERSION
    assert validate_trace_events(payload) == []
    payload["otherData"]["schema_version"] = SCHEMA_VERSION + 1
    assert any("newer than this validator" in p
               for p in validate_trace_events(payload))
    payload["otherData"]["schema_version"] = "two"
    assert any("not an int" in p for p in validate_trace_events(payload))
    # v1-era traces (no version key) still validate
    del payload["otherData"]["schema_version"]
    assert validate_trace_events(payload) == []


def test_monitor_fleet_config_validation():
    with pytest.raises(DeepSpeedConfigError, match="straggler_min_ratio"):
        MonitorConfig.from_dict({"straggler_min_ratio": 0.9})
    with pytest.raises(DeepSpeedConfigError, match="straggler_zscore"):
        MonitorConfig.from_dict({"straggler_zscore": 0})
    with pytest.raises(DeepSpeedConfigError, match="divergence_rel_spread"):
        MonitorConfig.from_dict({"divergence_rel_spread": -1})
    with pytest.raises(DeepSpeedConfigError, match="capture.steps"):
        MonitorConfig.from_dict({"capture": {"steps": 0}})
    with pytest.raises(DeepSpeedConfigError, match="max_captures"):
        MonitorConfig.from_dict({"capture": {"max_captures": 0}})
    cfg = MonitorConfig.from_dict({"fleet": True, "heartbeat": True,
                                   "capture": {"enabled": True,
                                               "steps": 4}})
    assert cfg.fleet and cfg.heartbeat
    assert cfg.capture.enabled and cfg.capture.steps == 4
    assert MonitorConfig.from_dict(None).fleet is False
    assert MonitorConfig.from_dict(None).capture.enabled is False
    # "capture": true is the turn-it-on shorthand; a non-object value
    # that is not a bool is a config error, not an AttributeError
    assert MonitorConfig.from_dict({"capture": True}).capture.enabled
    assert not MonitorConfig.from_dict({"capture": False}).capture.enabled
    with pytest.raises(DeepSpeedConfigError, match="monitor.capture"):
        MonitorConfig.from_dict({"capture": "yes"})


def test_sentinel_health_event_state_roundtrip():
    s = TrainingSentinel()
    s.record_health_event({R.H_EVENT: EVENT_DIVERGENCE, R.F_HOST: "h1",
                           R.H_STEP: 9})
    sd = s.state_dict()
    s2 = TrainingSentinel()
    s2.load_state_dict(sd)
    assert s2.health_events_seen == 1
    # the bounded ring never grows past its cap
    for i in range(100):
        s.record_health_event({R.H_EVENT: EVENT_STRAGGLER, R.H_STEP: i})
    assert len(s.health_events) == s._HEALTH_EVENTS_KEPT
    assert s.health_events_seen == 101


# --------------------------------------------------------------------- #
# launcher satellites: [host:rank] prefixes + failure naming + --watch
# --------------------------------------------------------------------- #
def test_launcher_prefixes_and_names_failing_host(capsys, caplog):
    from deepspeed_tpu.launcher.runner import launch_and_wait
    from deepspeed_tpu.utils.logging import logger as ds_logger
    ds_logger.addHandler(caplog.handler)  # the DS logger is non-propagating
    try:
        rc = launch_and_wait(
            [[sys.executable, "-c",
              "print('alpha line'); import sys; "
              "print('alpha err', file=sys.stderr)"],
             [sys.executable, "-c", "print('beta line'); import sys; "
              "sys.exit(7)"]],
            ["nodeA", "nodeB"])
    finally:
        ds_logger.removeHandler(caplog.handler)
    assert rc == 7
    out = capsys.readouterr()
    assert "[nodeA:0] alpha line" in out.out
    assert "[nodeB:1] beta line" in out.out
    assert "[nodeA:0] alpha err" in out.err
    messages = " ".join(r.getMessage() for r in caplog.records)
    assert "'nodeB'" in messages and "rc=7" in messages
    assert "nodeA" in messages  # the clean host is named too


def test_launcher_watch_renders_heartbeat_table(tmp_path, capsys):
    from deepspeed_tpu.launcher.runner import launch_and_wait
    from deepspeed_tpu.monitor.heartbeat import HEARTBEAT_DIR
    hb_dir = os.path.join(str(tmp_path), HEARTBEAT_DIR)
    HeartbeatWriter(hb_dir, 0, 2, host="podhost0").beat(step=12)
    HeartbeatWriter(hb_dir, 1, 2, host="podhost1").beat(step=12)
    rc = launch_and_wait(
        [[sys.executable, "-c", "import time; time.sleep(1.2)"],
         [sys.executable, "-c", "import time; time.sleep(1.2)"]],
        ["h0", "h1"], watch_dir=str(tmp_path), watch_interval=0.5)
    assert rc == 0
    out = capsys.readouterr().out
    assert "dslaunch --watch" in out
    assert "podhost0" in out and "podhost1" in out


def test_tpu_pod_labels():
    from deepspeed_tpu.launcher.tpu_discovery import PodInfo
    pod = PodInfo(workers=["10.0.0.5", "10.0.0.6"], my_index=0)
    assert pod.labels() == {"10.0.0.5": "w0", "10.0.0.6": "w1"}


# --------------------------------------------------------------------- #
# one measured window, no history: summary + one-shot verdict
# --------------------------------------------------------------------- #
def test_bench_fleet_summary_degenerate_single_host():
    """One process, one window: the exchange is the identity, the
    summary is that host's numbers and the one-shot verdict names no
    straggler."""
    agg = FleetAggregator(process_index=0, process_count=1)
    matrix = agg.exchange({"last_step": 0, "steps": 1,
                           "step_time_mean_s": 0.012,
                           "step_time_max_s": 0.012, "loss_mean": 3.3})
    hosts = agg.host_names()
    fl = summarize_fleet(matrix)
    assert fl[R.FL_HOSTS] == 1
    assert fl[R.FL_STEP_TIME_MEDIAN_S] == pytest.approx(0.012)
    assert straggler_verdict(matrix, hosts)["straggler"] is False
    assert len(hosts) == 1


# --------------------------------------------------------------------- #
# MoE health rules (ISSUE 15): dead expert, router collapse, EP load
# imbalance — rigged fleet matrices through the full sentinel ->
# capture-arming path
# --------------------------------------------------------------------- #
def _moe_summary(t=0.010, step=10, load=1.0, min_frac=0.9, entropy=0.8,
                 drop=0.01, imb=1.1, cold=2):
    return _summary(t, step=step, moe_drop_frac=drop, moe_entropy=entropy,
                    moe_imbalance=imb, moe_min_count_frac=min_frac,
                    moe_coldest_expert=cold, moe_local_load=load)


def test_dead_expert_rule_needs_consecutive_windows():
    health = FleetHealth(dead_expert_threshold=0.02,
                         dead_expert_windows=3)
    hosts = ["a", "b"]
    sick = _matrix([_moe_summary(min_frac=0.001)] * 2)
    healthy = _matrix([_moe_summary(min_frac=0.5)] * 2)
    assert health.observe(sick, hosts) == []
    assert health.observe(sick, hosts) == []
    # a healthy window resets the streak
    assert health.observe(healthy, hosts) == []
    assert health.observe(sick, hosts) == []
    assert health.observe(sick, hosts) == []
    evs = health.observe(sick, hosts)
    assert [e[R.H_EVENT] for e in evs] == [EVENT_DEAD_EXPERT]
    ev = evs[0]
    # model-level pathology: no process identity, nobody self-arms
    assert ev[R.F_PROCESS_INDEX] is None and ev[R.F_HOST] == "fleet"
    assert ev["expert"] == 2                 # the rigged coldest expert
    assert "dead expert" in ev[R.H_DETAIL] or "fair token share" in \
        ev[R.H_DETAIL]
    assert health.counters()["moe_events_flagged"] == 1


def test_router_collapse_rule_fires_at_entropy_floor():
    health = FleetHealth(entropy_floor=0.05, collapse_windows=2)
    hosts = ["a", "b"]
    collapsed = _matrix([_moe_summary(entropy=0.01)] * 2)
    assert health.observe(collapsed, hosts) == []
    evs = health.observe(collapsed, hosts)
    assert [e[R.H_EVENT] for e in evs] == [EVENT_ROUTER_COLLAPSE]
    assert "entropy" in evs[0][R.H_DETAIL]
    assert evs[0][R.F_PROCESS_INDEX] is None
    # dense fleets (NaN slots) never trip any moe rule
    dense = FleetHealth(entropy_floor=0.5, collapse_windows=1)
    for _ in range(3):
        assert dense.observe(_matrix([_summary(0.01)] * 2),
                             hosts) == []


def test_ep_imbalance_rule_leave_one_out_and_lane():
    health = FleetHealth(ep_imbalance_ratio=1.5, ep_imbalance_windows=2)
    hosts = [f"w{i}" for i in range(4)]
    rows = [_moe_summary(load=2.4 if p == 2 else 0.8)
            for p in range(4)]
    mat = _matrix(rows)
    assert health.observe(mat, hosts) == []  # window 1 of 2
    evs = health.observe(mat, hosts)
    assert [e[R.H_EVENT] for e in evs] == [EVENT_EP_IMBALANCE]
    ev = evs[0]
    assert ev[R.F_HOST] == "w2" and ev[R.F_PROCESS_INDEX] == 2
    assert ev[R.H_LANE] == ATTR_EXPERT_HOTSPOT
    assert ev[R.H_RATIO] == pytest.approx(3.0)  # 2.4 / peer-median 0.8
    assert "expert hot-spot on host w2" in ev[R.H_DETAIL]
    # balanced window resets the streak
    balanced = _matrix([_moe_summary(load=1.0)] * 4)
    assert health.observe(balanced, hosts) == []
    assert health.observe(mat, hosts) == []


def test_straggler_lane_names_expert_hotspot():
    """A straggler whose excess is explained by neither host-gap nor
    swap, but whose local experts carry past the EP gate, attributes as
    expert-hotspot instead of generic compute — the ISSUE 15 verdict
    upgrade."""
    health = FleetHealth(straggler_zscore=2.0, straggler_min_ratio=1.3,
                         warmup_windows=1, ep_imbalance_ratio=1.5)
    hosts = [f"w{i}" for i in range(4)]
    for _ in range(3):
        health.observe(_matrix([_moe_summary(0.010)] * 4), hosts)
    rows = [_moe_summary(0.010, load=0.8) for _ in range(4)]
    rows[2] = _moe_summary(0.030, load=2.4)   # slow AND expert-hot
    evs = health.observe(_matrix(rows), hosts)
    stragglers = [e for e in evs if e[R.H_EVENT] == EVENT_STRAGGLER]
    assert len(stragglers) == 1
    assert stragglers[0][R.H_LANE] == ATTR_EXPERT_HOTSPOT
    # straggler_verdict (the bench-row form) agrees
    verdict = straggler_verdict(_matrix(rows), hosts, min_ratio=1.3)
    assert verdict["straggler"] and verdict["host"] == "w2"
    assert verdict["lane"] == ATTR_EXPERT_HOTSPOT
    # and it honors a CONFIGURED ep gate exactly like the live
    # detector: a stricter ratio demotes the same matrix to compute
    strict = straggler_verdict(_matrix(rows), hosts, min_ratio=1.3,
                               ep_imbalance_ratio=4.0)
    assert strict["lane"] == ATTR_COMPUTE


def test_e2e_ep_imbalance_sentinel_and_capture(tmp_path):
    """ISSUE-15 acceptance: rigged EP-imbalance fleet matrix -> health
    event on the hot host -> sentinel health ring fed (abort budget
    untouched) -> capture armed on the flagged host, K-step disarm."""
    hosts = [f"w{i}" for i in range(4)]
    mats = []
    for w in range(4):
        rows = [_moe_summary(step=2 * (w + 1),
                             load=(2.4 if p == 2 and w >= 1 else 0.8))
                for p in range(4)]
        mats.append(_matrix(rows))
    rig = RiggedGather(hosts, mats)
    prof = MockProfiler()
    sentinel = TrainingSentinel()
    mon = TrainingMonitor(
        _fleet_cfg(tmp_path, capture={"enabled": True, "steps": 2,
                                      "max_captures": 1},
                   moe={"enabled": True, "ep_imbalance_ratio": 1.5,
                        "ep_imbalance_windows": 2}),
        process_index=2, world_size=4, host="w2",
        gather_fn=rig, profiler=prof,
        health_sink=sentinel.record_health_event)
    step = 0
    for _ in range(2):                       # windows 1-2: arming run-up
        for _ in range(2):
            step += 1
            mon.mark_step_start()
            mon.end_step(step, loss=2.0)
    assert not prof.active                   # streak 1 of 2: no event
    for _ in range(2):                       # window 3: streak reaches 2
        step += 1
        mon.mark_step_start()
        mon.end_step(step, loss=2.0)
    evs = mon.last_health_events
    assert [e[R.H_EVENT] for e in evs] == [EVENT_EP_IMBALANCE]
    assert evs[0][R.F_HOST] == "w2" and evs[0][R.F_PROCESS_INDEX] == 2
    # sentinel ring got the structured event; the ABORT budget did not
    assert sentinel.health_events_seen == 1
    assert sentinel.health_events[0][R.H_EVENT] == EVENT_EP_IMBALANCE
    assert sentinel.consecutive_anomalies == 0
    assert not sentinel.over_budget
    # flagged host (us) armed its own capture; K=2 steps then disarm
    assert prof.active and mon.capture.armed
    mon.mark_step_start()
    mon.end_step(step + 1, loss=2.0)
    mon.mark_step_start()
    mon.end_step(step + 2, loss=2.0)
    assert not mon.capture.armed
    assert prof.stopped == 1
    assert "ep_imbalance" in prof.started[0]
    mon.close()


def test_e2e_dead_expert_rank0_record_no_capture(tmp_path):
    """Dead-expert events carry no process identity: rank 0 writes the
    record + feeds its sentinel, and NO host self-arms a capture."""
    hosts = ["w0", "w1"]
    mats = [_matrix([_moe_summary(step=2 * (w + 1),
                                  min_frac=0.001)] * 2)
            for w in range(4)]
    rig = RiggedGather(hosts, mats)
    prof = MockProfiler()
    sentinel = TrainingSentinel()
    mon = TrainingMonitor(
        _fleet_cfg(tmp_path, capture={"enabled": True},
                   moe={"enabled": True, "dead_expert_windows": 2,
                        "dead_expert_threshold": 0.02}),
        process_index=0, world_size=2, host="w0",
        gather_fn=rig, profiler=prof,
        health_sink=sentinel.record_health_event)
    for step in range(1, 9):
        mon.mark_step_start()
        mon.end_step(step, loss=2.0)
    mon.close()
    assert not prof.started                  # nobody self-armed
    recs = [json.loads(line) for line in open(mon.jsonl_path)]
    dead = [r for r in recs if r.get(R.F_KIND) == KIND_HEALTH
            and r.get(R.H_EVENT) == EVENT_DEAD_EXPERT]
    assert len(dead) >= 1
    assert dead[0][R.F_HOST] == "fleet"
    assert sentinel.health_events_seen == len(dead)
    # the rigged fleet records also carry the per-host moe load column
    fleet = [r for r in recs if r.get(R.F_KIND) == KIND_FLEET]
    assert fleet and fleet[0][R.FL_PER_HOST]["moe_local_load"] == [
        1.0, 1.0]


def test_e2e_router_collapse_sentinel_ring_budget_untouched(tmp_path):
    """Router-collapse through the full path: rigged entropy floor ->
    health event -> sentinel ring fed, abort budget untouched, no
    capture (fleet-global event carries no process identity)."""
    hosts = ["w0", "w1"]
    mats = [_matrix([_moe_summary(step=2 * (w + 1),
                                  entropy=0.01)] * 2)
            for w in range(3)]
    rig = RiggedGather(hosts, mats)
    prof = MockProfiler()
    sentinel = TrainingSentinel(anomaly_budget=1)
    mon = TrainingMonitor(
        _fleet_cfg(tmp_path, capture={"enabled": True},
                   moe={"enabled": True, "entropy_floor": 0.05,
                        "collapse_windows": 2}),
        process_index=0, world_size=2, host="w0",
        gather_fn=rig, profiler=prof,
        health_sink=sentinel.record_health_event)
    for step in range(1, 7):
        mon.mark_step_start()
        mon.end_step(step, loss=2.0)
    mon.close()
    assert not prof.started
    recs = [json.loads(line) for line in open(mon.jsonl_path)]
    collapse = [r for r in recs if r.get(R.F_KIND) == KIND_HEALTH
                and r.get(R.H_EVENT) == EVENT_ROUTER_COLLAPSE]
    assert len(collapse) >= 1 and "entropy" in collapse[0][R.H_DETAIL]
    assert sentinel.health_events_seen == len(collapse)
    # a tight abort budget survives: health events never count toward it
    assert sentinel.consecutive_anomalies == 0
    assert not sentinel.over_budget
