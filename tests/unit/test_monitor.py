"""Runtime telemetry subsystem (deepspeed_tpu/monitor/, docs/telemetry.md).

Covers the ISSUE-9 acceptance surface: writer backends round-trip
(JSONL/CSV; trace-event JSON validates against the Chrome schema),
reconciliation math on rigged predicted/measured pairs, the host-sync
audit regression (monitor-on adds zero hot-loop host callbacks and does
not change the program shape), a telemetry-overhead bound, the swap-tier
integration (ZeRO-Infinity records + swap-I/O trace spans), and the
satellite fixes (tensorboard fallback chain, timer exception narrowing,
the wall_clock_breakdown key's one warning).
"""

import csv
import json
import os
import sys
import time

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.config import DeepSpeedConfigError, MonitorConfig
from deepspeed_tpu.monitor import (
    Bands, FLAG_HBM_ABOVE_BAND, FLAG_HBM_BELOW_BAND, FLAG_MODEL_VIOLATION,
    FLAG_STEP_TIME_ABOVE_BAND, FLAG_SWAP_BELOW_CEILING, KIND_RECONCILE,
    KIND_STEP, MetricsStream, STEP_RECORD_FIELDS, ScalarJsonlWriter,
    TraceEventBuffer, attribute_gap, reconcile_window,
    validate_trace_events)
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.monitor.reconcile import (ATTR_COMM_EXPOSED,
                                             ATTR_COMPUTE, ATTR_IO,
                                             ATTR_SWAP)


# --------------------------------------------------------------------- #
# engine fixture (CPU gpt2 — the acceptance config)
# --------------------------------------------------------------------- #
def _engine(tmp_path, monitor=None, num_layers=2, gas=1, extra=None):
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    ds.reset_mesh_context()
    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                     num_layers=num_layers, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     hidden_dropout=0.0)
    model = GPT2Model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
    }
    if monitor is not None:
        monitor = dict(monitor)
        monitor.setdefault("enabled", True)
        monitor.setdefault("output_path", str(tmp_path))
        config["monitor"] = monitor
    config.update(extra or {})
    engine, _, _, _ = ds.initialize(
        model=model, config=config,
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    return engine


def _run_steps(engine, n, seq=16, batch=2, gas=1):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 64, size=(batch, seq)).astype(np.int32)
    for _ in range(n):
        for _ in range(gas):
            loss = engine.forward(ids)
            engine.backward(loss)
            engine.step()
    return loss


# --------------------------------------------------------------------- #
# writer backends round-trip
# --------------------------------------------------------------------- #
def test_jsonl_records_roundtrip(tmp_path):
    """Acceptance: per-step JSONL records carry measured wall time,
    memory high-water, and counters on the CPU gpt2 config."""
    engine = _engine(tmp_path, monitor={"writers": ["jsonl"],
                                        "write_interval": 2})
    _run_steps(engine, 5)
    engine.monitor.close()
    path = engine.monitor.jsonl_path
    recs = [json.loads(line) for line in open(path)]
    steps = [r for r in recs if r.get(R.F_KIND) == KIND_STEP]
    assert [r[R.F_STEP] for r in steps] == [1, 2, 3, 4, 5]
    for rec in steps:
        assert rec[R.F_LOSS] is not None and np.isfinite(rec[R.F_LOSS])
        assert rec[R.F_MEM_PEAK_BYTES] and rec[R.F_MEM_PEAK_BYTES] > 0
        assert rec[R.F_MEM_SOURCE] in ("device", "host_rss")
        assert rec[R.F_SKIPPED_STEPS] == 0
        assert rec[R.F_DISPATCHES_PER_STEP] == 2
        assert rec[R.F_LR] == pytest.approx(1e-3)
    # wall time exists from step 2 on (step 1's clock armed at forward)
    assert all(r[R.F_WALL_TIME_S] is not None and r[R.F_WALL_TIME_S] > 0
               for r in steps)
    assert all(r[R.F_TOKENS_PER_SEC] > 0 for r in steps)
    # reconciliation records ride the same stream, one per flush window
    recons = [r for r in recs if r.get(R.F_KIND) == KIND_RECONCILE]
    assert len(recons) == 3  # windows [1-2], [3-4], [5]
    assert recons[0][R.R_WINDOW_START] == 1
    assert recons[-1][R.R_WINDOW_END] == 5


def test_csv_roundtrip_matches_schema(tmp_path):
    engine = _engine(tmp_path, monitor={"writers": ["jsonl", "csv"],
                                        "write_interval": 3})
    _run_steps(engine, 4)
    engine.monitor.close()
    with open(engine.monitor.csv_path, newline="") as f:
        rows = list(csv.reader(f))
    assert tuple(rows[0]) == STEP_RECORD_FIELDS
    body = rows[1:]
    assert len(body) == 4  # step records only; reconcile stays in JSONL
    step_col = STEP_RECORD_FIELDS.index(R.F_STEP)
    assert [int(r[step_col]) for r in body] == [1, 2, 3, 4]
    loss_col = STEP_RECORD_FIELDS.index(R.F_LOSS)
    assert all(np.isfinite(float(r[loss_col])) for r in body)


def test_monitor_unknown_writer_rejected():
    with pytest.raises(DeepSpeedConfigError, match="unknown backend"):
        MonitorConfig.from_dict({"enabled": True, "writers": ["sqlite"]})
    with pytest.raises(DeepSpeedConfigError, match="list of backend"):
        MonitorConfig.from_dict({"enabled": True, "writers": None})


def test_monitor_band_validation():
    with pytest.raises(DeepSpeedConfigError, match="step_time_ratio_max"):
        MonitorConfig.from_dict({"step_time_ratio_max": 0.5})
    with pytest.raises(DeepSpeedConfigError, match="write_interval"):
        MonitorConfig.from_dict({"write_interval": 0})


# --------------------------------------------------------------------- #
# trace export: Chrome/Perfetto trace-event schema
# --------------------------------------------------------------------- #
def test_trace_export_validates_and_has_step_phases(tmp_path):
    engine = _engine(tmp_path, monitor={"writers": ["jsonl"],
                                        "trace": True})
    _run_steps(engine, 3)
    engine.monitor.close()
    payload = json.load(open(engine.monitor.trace_path))
    assert validate_trace_events(payload) == []
    events = payload["traceEvents"]
    names = {e["name"] for e in events}
    # modular path: the engine's own spans (monitor/trace.py span), the
    # grad and apply dispatch windows among them
    assert "ds.forward.dispatch" in names
    assert "ds.step.dispatch" in names
    x_events = [e for e in events if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in x_events)
    steps = {e.get("args", {}).get("step") for e in x_events}
    assert {1, 2, 3} <= steps
    # flush boundaries appear as instants on the monitor lane
    assert any(e["ph"] == "i" and e["name"] == "flush" for e in events)


def test_trace_step_bound_saturates(tmp_path):
    engine = _engine(tmp_path, monitor={"writers": ["jsonl"],
                                        "trace": True, "trace_steps": 2})
    _run_steps(engine, 4)
    engine.monitor.close()
    payload = json.load(open(engine.monitor.trace_path))
    assert payload["otherData"]["steps_traced"] == 2
    assert payload["otherData"]["truncated_at_max_steps"] is True


def test_trace_buffer_schema_validator_catches_garbage():
    assert validate_trace_events({"traceEvents": "nope"})
    assert validate_trace_events(
        {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                          "ts": 0.0}]})  # X without dur
    buf = TraceEventBuffer()
    buf.add_span("ok", 1.0, 2.0)
    assert validate_trace_events(buf.to_json()) == []


# --------------------------------------------------------------------- #
# reconciliation math on rigged predicted/measured pairs
# --------------------------------------------------------------------- #
def _pred(lb=0.010, compute=0.010, memory=0.002, hidden=0.001,
          exposed=0.0, hbm=None):
    return {"predicted_step_time_lb_s": lb,
            "lanes": {"compute": compute, "memory": memory,
                      "hidden_comm": hidden, "exposed_comm": exposed},
            "peak_hbm_bytes": hbm}


def test_reconcile_within_band_no_flags():
    rec = reconcile_window({"step_time_s": 0.03}, _pred(), Bands())
    assert rec[R.R_STEP_RATIO] == pytest.approx(3.0)
    assert rec[R.R_ATTRIBUTION] == ATTR_COMPUTE
    assert rec[R.R_FLAGS] == []


def test_reconcile_step_time_above_band_flags_with_attribution():
    rec = reconcile_window(
        {"step_time_s": 0.5},
        _pred(lb=0.01, compute=0.002, memory=0.010, hidden=0.0),
        Bands(step_time_ratio_max=10.0))
    assert FLAG_STEP_TIME_ABOVE_BAND in rec[R.R_FLAGS]
    assert rec[R.R_ATTRIBUTION] == ATTR_IO  # memory lane binds


def test_reconcile_measured_below_lower_bound_is_model_violation():
    rec = reconcile_window({"step_time_s": 0.005}, _pred(lb=0.010),
                           Bands())
    assert rec[R.R_FLAGS] == [FLAG_MODEL_VIOLATION]


def test_reconcile_exposed_comm_attribution():
    lanes = {"compute": 0.002, "memory": 0.001, "hidden_comm": 0.0,
             "exposed_comm": 0.008}
    assert attribute_gap(lanes) == ATTR_COMM_EXPOSED


def test_reconcile_swap_exposure_wins_attribution():
    lanes = {"compute": 0.010, "memory": 0.001, "hidden_comm": 0.0,
             "exposed_comm": 0.0}
    swap = {"read_exposed_s": 0.08, "write_exposed_s": 0.0}
    assert attribute_gap(lanes, swap, measured_step_s=0.1) == ATTR_SWAP
    # below the 25% share the roofline lane keeps the attribution
    swap = {"read_exposed_s": 0.01}
    assert attribute_gap(lanes, swap, measured_step_s=0.1) == ATTR_COMPUTE


def test_reconcile_hbm_bands_device_only():
    bands = Bands(hbm_ratio_max=2.0)
    over = reconcile_window(
        {"step_time_s": None, "hbm_peak_bytes": 300, "mem_source":
         "device"}, _pred(hbm=100), bands)
    assert FLAG_HBM_ABOVE_BAND in over[R.R_FLAGS]
    assert over[R.R_HBM_RATIO] == pytest.approx(3.0)
    under = reconcile_window(
        {"step_time_s": None, "hbm_peak_bytes": 40, "mem_source":
         "device"}, _pred(hbm=100), bands)
    assert FLAG_HBM_BELOW_BAND in under[R.R_FLAGS]
    # host-RSS readings are NOT comparable to the HBM estimate: no
    # ratio, no flag (a CPU run must not cry HBM regression)
    rss = reconcile_window(
        {"step_time_s": None, "hbm_peak_bytes": 300, "mem_source":
         "host_rss"}, _pred(hbm=100), bands)
    assert rss[R.R_HBM_RATIO] is None
    assert rss[R.R_FLAGS] == []


def test_reconcile_swap_ceiling_band():
    swap = {"read_gbps": 1.0, "sweep_read_gbps": 20.0,
            "read_vs_ceiling": 0.05, "overlap_fraction": 0.8}
    rec = reconcile_window({"step_time_s": None, "swap": swap}, None,
                           Bands(swap_min_vs_ceiling=0.25))
    assert rec[R.R_FLAGS] == [FLAG_SWAP_BELOW_CEILING]
    assert rec[R.R_SWAP_VS_CEILING] == pytest.approx(0.05)
    assert rec[R.R_OVERLAP_FRACTION] == pytest.approx(0.8)
    ok = dict(swap, read_vs_ceiling=0.6)
    rec = reconcile_window({"step_time_s": None, "swap": ok}, None,
                           Bands(swap_min_vs_ceiling=0.25))
    assert rec[R.R_FLAGS] == []


def test_reconcile_no_predictions_still_self_describing():
    rec = reconcile_window({"step_time_s": 0.1}, None, Bands())
    assert rec[R.R_MEASURED_STEP_S] == pytest.approx(0.1)
    assert rec[R.R_STEP_RATIO] is None
    assert rec[R.R_FLAGS] == []


# --------------------------------------------------------------------- #
# host-sync audit regression: monitor-on adds ZERO hot-loop callbacks
# --------------------------------------------------------------------- #
def test_monitor_on_adds_zero_host_sync_findings(tmp_path):
    """Acceptance: the host_sync audit of the monitored program reports
    zero new hot-loop findings — the monitor lives entirely on the host
    side of the dispatch boundary, so the traced step programs are
    IDENTICAL with it on (same lockstep signature, no callbacks)."""
    from deepspeed_tpu.analysis import RULE_HOST_SYNC, audit_engine
    plain = _engine(tmp_path, monitor=None)
    plain_report = audit_engine(plain, multihost=False)
    monitored = _engine(tmp_path, monitor={"writers": ["jsonl"],
                                           "trace": True})
    _run_steps(monitored, 2)
    report = audit_engine(monitored, multihost=False)
    monitored.monitor.close()
    host_sync = [f for f in report.findings if f.rule == RULE_HOST_SYNC]
    assert host_sync == [], [f.format() for f in host_sync]
    assert report.signature == plain_report.signature
    assert report.wire_bytes_per_step == plain_report.wire_bytes_per_step


def test_monitor_on_train_batch_audit_clean(tmp_path):
    from deepspeed_tpu.analysis import RULE_HOST_SYNC, audit_engine
    engine = _engine(tmp_path, gas=2,
                     monitor={"writers": ["jsonl"], "trace": True},
                     extra={"bf16": {"enabled": True}})
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 64, size=(2, 16)).astype(np.int32)

    def it():
        while True:
            yield (ids,)

    for _ in range(3):
        engine.train_batch(it())
    report = audit_engine(engine, multihost=False)
    engine.monitor.close()
    assert [f for f in report.findings
            if f.rule == RULE_HOST_SYNC] == []
    recs = [json.loads(line) for line in open(engine.monitor.jsonl_path)]
    steps = [r for r in recs if r.get(R.F_KIND) == KIND_STEP]
    assert len(steps) == 3
    assert all(r[R.F_DISPATCHES_PER_STEP] == 4 for r in steps)


def test_dispatches_per_step_counts_the_launches_of_step_programs(tmp_path):
    """The counter is counted where the engine launches a step program,
    not computed from the configuration: gas grad programs, gas - 1
    accumulates and one apply; the eager programs of the rng split are
    not in it."""
    engine = _engine(tmp_path, gas=3, monitor={"writers": ["jsonl"]})
    _run_steps(engine, 2, gas=3)
    engine.monitor.close()
    recs = [json.loads(line) for line in open(engine.monitor.jsonl_path)]
    steps = [r for r in recs if r.get(R.F_KIND) == KIND_STEP]
    assert [r[R.F_DISPATCHES_PER_STEP] for r in steps] == [6, 6]
    names = sorted(name for name, _ in engine.step_programs())
    assert names == ["jit_accumulate", "jit_apply_step",
                     "jit_loss_and_grads"]


# --------------------------------------------------------------------- #
# telemetry overhead bound
# --------------------------------------------------------------------- #
def test_discard_step_resets_arrival_clock():
    """A step that produced no record (sentinel rewind path) must not
    fold its wall time into the next record."""
    sunk = []
    stream = MetricsStream(window=10 ** 9, sink=sunk.extend)
    stream.mark_step_start()
    time.sleep(0.06)                      # the rewound step's wall time
    stream.discard_step()
    stream.end_step(1, loss=1.0)
    stream.flush()
    assert sunk[0][R.F_WALL_TIME_S] < 0.05, sunk[0][R.F_WALL_TIME_S]


def test_per_step_monitor_path_is_cheap():
    """The hot-path call (end_step) is O(1) host work — 1000 calls in
    well under a second even on a loaded CI machine (0.02 s alone; the
    best of three, since one stall of the host under six test workers
    is not the stream's doing)."""
    times = []
    for _ in range(3):
        sunk = []
        stream = MetricsStream(window=10 ** 9, sink=sunk.extend)
        stream.mark_step_start()
        t0 = time.perf_counter()
        for i in range(1000):
            stream.end_step(i, loss=1.0, tokens=1024,
                            counters={R.F_SKIPPED_STEPS: 0})
        times.append(time.perf_counter() - t0)
        stream.flush()
        assert len(sunk) == 1000
    assert min(times) < 0.5, f"1000 end_step calls took {min(times):.3f}s"


def test_monitor_overhead_within_tolerance(tmp_path):
    """Monitor-on vs monitor-off step loop on CPU: the monitored loop
    must stay within a generous constant factor (the budget absorbs CI
    noise; a per-step device sync regression would blow it by far
    more)."""
    steps = 30

    def timed(monitor):
        engine = _engine(tmp_path, monitor=monitor)
        loss = _run_steps(engine, 3)          # warmup + compile
        float(np.asarray(loss))
        t0 = time.perf_counter()
        loss = _run_steps(engine, steps)
        float(np.asarray(loss))
        dt = time.perf_counter() - t0
        if engine.monitor is not None:
            engine.monitor.close()
        return dt

    t_off = timed(None)
    t_on = timed({"writers": ["jsonl", "csv"], "write_interval": 10})
    assert t_on < t_off * 2.0 + 0.75, (
        f"monitored loop {t_on:.3f}s vs bare {t_off:.3f}s — telemetry "
        "is not boundary-only anymore?")


# --------------------------------------------------------------------- #
# ZeRO-Infinity: swap stats flow into records + swap-I/O trace spans
# --------------------------------------------------------------------- #
def test_infinity_monitor_records_and_swap_trace(tmp_path):
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    ds.reset_mesh_context()
    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                     num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     hidden_dropout=0.0)
    model = GPT2Model(cfg)
    nvme = tmp_path / "nvme"
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "nvme", "nvme_path": str(nvme),
                              "buffer_count": 2, "prefetch_depth": 2},
            "offload_optimizer": {"device": "cpu"}},
        "monitor": {"enabled": True, "output_path": str(tmp_path),
                    "writers": ["jsonl"], "write_interval": 2,
                    "trace": True},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=config,
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 64, size=(2, 16)).astype(np.int32)
    for _ in range(2):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
    engine.monitor.close()
    recs = [json.loads(line) for line in open(engine.monitor.jsonl_path)]
    steps = [r for r in recs if r.get(R.F_KIND) == KIND_STEP]
    assert len(steps) == 2
    # acceptance: swap-tier achieved GB/s + overlap flow into records
    assert all(r[R.F_SWAP_READ_GBPS] is not None and
               r[R.F_SWAP_READ_GBPS] > 0 for r in steps)
    assert all(r[R.F_SWAP_OVERLAP_FRACTION] is not None for r in steps)
    payload = json.load(open(engine.monitor.trace_path))
    assert validate_trace_events(payload) == []
    cats = {e.get("cat") for e in payload["traceEvents"]}
    assert "swap_in" in cats, sorted(cats)
    assert "swap_out" in cats, sorted(cats)
    recons = [r for r in recs if r.get(R.F_KIND) == KIND_RECONCILE]
    assert recons and recons[-1][R.R_SWAP_GBPS] is not None


# --------------------------------------------------------------------- #
# satellites
# --------------------------------------------------------------------- #
def test_tensorboard_fallback_chain_without_torch(tmp_path, monkeypatch):
    """engine._configure_tensorboard: torch -> tensorboardX -> JSONL
    scalar fallback.  With both blocked, a torch-free host still gets a
    working add_scalar sink (one loud warning, not a silent None)."""
    engine = _engine(tmp_path)
    monkeypatch.setitem(sys.modules, "torch", None)
    monkeypatch.setitem(sys.modules, "torch.utils", None)
    monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
    monkeypatch.setitem(sys.modules, "tensorboardX", None)
    engine.config.tensorboard_config.enabled = True
    engine.config.tensorboard_config.output_path = str(tmp_path / "tb")
    # a null job_name (present-but-null config key) must degrade, not
    # TypeError out of engine init
    engine.config.tensorboard_config.job_name = None
    writer = engine._configure_tensorboard()
    assert isinstance(writer, ScalarJsonlWriter)
    writer.add_scalar("Train/loss", 1.25, 7)
    writer.close()
    lines = [json.loads(line) for line in open(writer.path)]
    assert lines == [{"tag": "Train/loss", "value": 1.25, "step": 7}]


def test_device_sync_narrowed_exceptions(monkeypatch):
    """_device_sync swallows only ImportError/RuntimeError (logged at
    debug, once); anything else propagates — a real sync failure can no
    longer be silently timed as ~0."""
    from deepspeed_tpu.utils import timer as timer_mod
    timer_mod._device_sync()  # healthy path

    class _Boom:
        def __call__(self, *a, **k):
            raise ValueError("not a sync failure")

    import jax.numpy as jnp
    monkeypatch.setattr(jnp, "zeros", _Boom())
    with pytest.raises(ValueError):
        timer_mod._device_sync()

    def _runtime_err(*a, **k):
        raise RuntimeError("backend torn down")

    monkeypatch.setattr(jnp, "zeros", _runtime_err)
    timer_mod._device_sync()  # swallowed (logged once at debug)


@pytest.mark.parametrize("asked", [True, False])
def test_wall_clock_breakdown_warns_once_and_the_spans_remain(
        tmp_path, caplog, asked):
    """The key stays accepted (upstream configurations carry it): `true`
    logs ONE warning that names the ds.* spans, which are the breakdown
    and are emitted whatever the key says."""
    from deepspeed_tpu.utils.logging import logger as ds_logger
    ds_logger.addHandler(caplog.handler)  # the DS logger is non-propagating
    try:
        engine = _engine(tmp_path, extra={"wall_clock_breakdown": asked},
                         monitor={"writers": ["jsonl"], "trace": True})
        _run_steps(engine, 2)
    finally:
        ds_logger.removeHandler(caplog.handler)
    warned = [r.getMessage() for r in caplog.records
              if r.getMessage().startswith("wall_clock_breakdown:")]
    assert len(warned) == int(asked), warned
    assert all(name in line for line in warned
               for name in ("ds.forward", "ds.backward", "ds.step"))
    assert not hasattr(engine, "timers")
    engine.monitor.close()
    names = {e["name"] for e in
             json.load(open(engine.monitor.trace_path))["traceEvents"]}
    assert {"ds.forward", "ds.backward", "ds.step"} <= names


def test_inflight_tensor_write_timestamps_feed_trace(tmp_path):
    """InflightTensorWrite carries the same issue/wait timestamp split
    as InflightGroupRead, and AsyncTensorSwapper's drained events become
    valid swap_out trace spans — the write-side handle contract for any
    tier built on the async swapper (the streaming engine's production
    write-back spans come from the param swapper's write→flush
    windows)."""
    from deepspeed_tpu.runtime.swap_tensor import AsyncTensorSwapper
    from deepspeed_tpu.runtime.swap_tensor.aio_handle import AsyncIOHandle
    h = AsyncIOHandle(block_size=4096, queue_depth=4, thread_count=1,
                      backend="batched")
    sw = AsyncTensorSwapper(h, buffer_bytes=64 * 1024, buffer_count=2)
    arr = np.arange(1000, dtype=np.float32)
    op = sw.swap_out(arr, str(tmp_path / "w.bin"))
    assert op.t_issue > 0 and op.nbytes == arr.nbytes
    op.wait()
    assert op.hidden_s is not None and op.exposed_s is not None
    events = sw.drain_write_events()
    assert len(events) == 1
    ev = events[0]
    assert ev["bytes"] == arr.nbytes
    assert ev["t_done"] >= ev["t_issue"]
    assert sw.drain_write_events() == []  # return-and-reset
    buf = TraceEventBuffer()
    buf.add_swap_write_events(events, step=1)
    payload = buf.to_json()
    assert validate_trace_events(payload) == []
    assert any(e.get("cat") == "swap_out" for e in payload["traceEvents"])


def test_writer_thread_close_drains(tmp_path):
    from deepspeed_tpu.monitor import JsonlWriter, WriterThread
    path = str(tmp_path / "wt.jsonl")
    wt = WriterThread([JsonlWriter(path)])
    for i in range(50):
        wt.submit([{R.F_KIND: KIND_STEP, R.F_STEP: i}])
    wt.close()
    assert len(open(path).read().splitlines()) == 50
    wt.close()  # idempotent
