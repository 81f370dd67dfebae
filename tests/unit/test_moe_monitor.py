"""MoE routing observability (ISSUE 15, docs/telemetry.md).

Covers the acceptance surface: the device-resident RoutingStats
accumulator reaches the JSONL stream as ``moe`` records with the
ExpertPopularitySnapshot embedded (round-trip pinned on a rigged skewed
router — the consumable contract ROADMAP item 6's NVMe expert streamer
keys on), the host-sync audit regression (monitor.moe adds ZERO
findings and leaves the lockstep signature + wire bytes bit-identical),
the accumulation over a step's micro-batches on the device, the
boundary-only fetch cadence, the monitor-on-vs-off wall tolerance on
the MoE row, and the config/schema validation satellites.
"""

import json
import time

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.config import DeepSpeedConfigError, MonitorConfig
from deepspeed_tpu.monitor import (
    KIND_MOE, KIND_STEP, MetricsStream, MoeRoutingAggregator,
    SNAPSHOT_SCHEMA, TrainingMonitor, snapshot_from_record,
    summarize_window, validate_snapshot, validate_trace_events)
from deepspeed_tpu.monitor import record as R

V, S, H = 128, 16, 32


# --------------------------------------------------------------------- #
# engine fixtures (tiny GPT-MoE on an expert=4 mesh)
# --------------------------------------------------------------------- #
def _moe_engine(tmp_path, monitor_moe=True, gas=1,
                num_layers=2, monitor=True):
    from deepspeed_tpu.models import GPTMoEConfig, GPTMoEModel
    ds.reset_mesh_context()
    ds.initialize_mesh(expert=4, data=-1)
    cfg = GPTMoEConfig(vocab_size=V, n_positions=S, hidden_size=H,
                       num_layers=num_layers, num_heads=4, num_experts=4,
                       top_k=2, bf16=False, embd_dropout=0.0,
                       attn_dropout=0.0, hidden_dropout=0.0,
                       capacity_factor=1.0, min_capacity=2)
    model = GPTMoEModel(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
    }
    if monitor:
        config["monitor"] = {
            "enabled": True, "output_path": str(tmp_path),
            "writers": ["jsonl"], "write_interval": 2,
            "moe": {"enabled": monitor_moe}}
    engine, _, _, _ = ds.initialize(
        model=model, config=config,
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    return engine, cfg


def _run(engine, n, batch=8):
    ids = np.random.RandomState(0).randint(
        0, V, size=(batch, S)).astype(np.int32)
    for _ in range(n):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
    return loss


# --------------------------------------------------------------------- #
# moe records + the popularity snapshot contract
# --------------------------------------------------------------------- #
def test_moe_records_reach_jsonl_with_snapshot(tmp_path):
    engine, cfg = _moe_engine(tmp_path)
    _run(engine, 5)
    engine.monitor.close()
    recs = [json.loads(line) for line in open(engine.monitor.jsonl_path)]
    moe = [r for r in recs if r.get(R.F_KIND) == KIND_MOE]
    # windows [1-2], [3-4], [5] — one moe record each
    assert len(moe) == 3
    assert [m[R.M_WINDOW_END] for m in moe] == [2, 4, 5]
    n_moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
    for m in moe:
        assert m[R.M_EXPERTS] == 4
        assert m[R.M_LAYERS_PER_STEP] == n_moe_layers
        # token-slot accounting: layers x tokens x k per optimizer step
        assert m[R.M_TOKENS_PER_STEP] == n_moe_layers * 8 * S * 2
        assert 0.0 <= m[R.M_DROP_FRAC] <= 1.0
        assert m[R.M_IMBALANCE] >= 1.0
        assert 0.0 < m[R.M_ENTROPY] <= 1.0
        assert len(m[R.M_COUNTS]) == 4 and len(m[R.M_OVERFLOW]) == 4
        # routed + overflowed slots == wanted slots (drop accounting)
        total = sum(m[R.M_COUNTS]) + sum(m[R.M_OVERFLOW])
        assert total == pytest.approx(
            m[R.M_TOKENS_PER_STEP] * m[R.M_STEPS], rel=1e-6)
        # identity triple rides moe records too (schema v2)
        assert m[R.F_PROCESS_INDEX] == 0 and R.F_HOST in m
        snap = snapshot_from_record(m)
        assert validate_snapshot(snap) == [], snap
    # step records are untouched alongside
    assert [r[R.F_STEP] for r in recs
            if r.get(R.F_KIND) == KIND_STEP] == [1, 2, 3, 4, 5]


def test_snapshot_roundtrip_pins_skewed_router():
    """Acceptance: a rigged skewed router produces ranked hot/cold
    lists and a hit-rate-under-K curve that survive a JSONL round-trip
    — the exact artifact ROADMAP item 6's streamer will key on."""
    agg = MoeRoutingAggregator(ewma_alpha=1.0, hot_k=2)
    # 8 experts, popularity heavily skewed: 3 hot, 5 cold
    counts = np.array([400., 10., 300., 5., 20., 200., 50., 15.])
    raw = {"expert_counts": counts,
           "overflow_counts": np.zeros(8),
           "tokens": counts.sum(), "dropped": 0.0,
           "entropy": 1000.0 * np.log(8) * 0.5, "confidence": 700.0,
           "gate_tokens": 1000.0, "l_aux": 1.1, "layers": 1.0,
           "steps": 2}
    rec = agg.observe_window(raw, 1, 2)
    assert rec[R.F_KIND] == KIND_MOE
    line = json.dumps(rec)                 # JSONL round-trip
    back = json.loads(line)
    snap = snapshot_from_record(back)
    assert snap == rec[R.M_POPULARITY]
    assert validate_snapshot(snap) == []
    assert snap["schema"] == SNAPSHOT_SCHEMA
    # ranked hot list (hot_k=2): experts 0 then 2; cold ranked from the
    # least popular up: 3, 1, 15-count 7, 4, 6 (the complement)
    assert snap["hot"] == [0, 2]
    assert snap["cold"] == [3, 1, 7, 4, 6, 5]
    share = counts / counts.sum()
    # hit-rate-under-K: pinning the top-K experts in HBM catches this
    # fraction of routed tokens (cumulative sorted share)
    expected = np.cumsum(np.sort(share)[::-1])
    np.testing.assert_allclose(snap["hit_rate_under_k"], expected,
                               atol=1e-5)
    assert snap["hit_rate_under_k"][-1] == pytest.approx(1.0)
    # EWMA with alpha=1 equals the window share
    np.testing.assert_allclose(snap["ewma_share"], share, atol=1e-5)


def test_popularity_ewma_smooths_windows():
    agg = MoeRoutingAggregator(ewma_alpha=0.5, hot_k=1)

    def raw(counts):
        counts = np.asarray(counts, np.float64)
        return {"expert_counts": counts, "overflow_counts": np.zeros(4),
                "tokens": counts.sum(), "dropped": 0.0, "entropy": 1.0,
                "confidence": 1.0, "gate_tokens": 4.0, "l_aux": 1.0,
                "layers": 1.0, "steps": 1}
    agg.observe_window(raw([100, 0, 0, 0]), 1, 2)
    rec = agg.observe_window(raw([0, 100, 0, 0]), 3, 4)
    snap = rec[R.M_POPULARITY]
    # one window at alpha=.5 cannot dethrone the incumbent: 0.5 vs 0.5
    # share — hot stays stable (argsort is stable, expert 0 first)
    assert snap["ewma_share"][0] == pytest.approx(0.5)
    assert snap["ewma_share"][1] == pytest.approx(0.5)
    assert snap["windows_seen"] == 2


def test_summarize_window_dense_is_none():
    assert summarize_window({"layers": 0.0}) is None


def test_validate_snapshot_catches_garbage():
    assert validate_snapshot({"schema": "wrong"})
    good = {"schema": SNAPSHOT_SCHEMA, R.M_EXPERTS: 2,
            "ewma_share": [0.5, 0.5], "hit_rate_under_k": [0.5, 1.0],
            "hot": [0], "cold": [1], "hot_k": 1}
    assert validate_snapshot(good) == []
    bad = dict(good, hit_rate_under_k=[1.0, 0.5])
    assert any("non-decreasing" in p for p in validate_snapshot(bad))
    bad = dict(good, ewma_share=[0.9, 0.9])
    assert any("sums" in p for p in validate_snapshot(bad))
    bad = dict(good, cold=[0])
    assert any("overlap" in p for p in validate_snapshot(bad))


# --------------------------------------------------------------------- #
# host-sync audit regression (acceptance: ZERO new findings, unchanged
# lockstep signature + wire bytes with monitor.moe on)
# --------------------------------------------------------------------- #
def test_moe_monitor_on_adds_zero_host_sync_findings(tmp_path):
    from deepspeed_tpu.analysis import RULE_HOST_SYNC, audit_engine
    plain, _ = _moe_engine(tmp_path, monitor=False)
    plain_report = audit_engine(plain, multihost=False)
    monitored, _ = _moe_engine(tmp_path, monitor_moe=True)
    _run(monitored, 2)
    report = audit_engine(monitored, multihost=False)
    monitored.monitor.close()
    host_sync = [f for f in report.findings if f.rule == RULE_HOST_SYNC]
    assert host_sync == [], [f.format() for f in host_sync]
    # routing stats ride as pure device math: the collective story is
    # bit-identical — signature AND traced wire unchanged
    assert report.signature == plain_report.signature
    assert report.wire_bytes_per_step == plain_report.wire_bytes_per_step


def test_moe_monitor_train_batch_audit_clean_and_gas_accumulates(tmp_path):
    from deepspeed_tpu.analysis import RULE_HOST_SYNC, audit_engine
    engine, cfg = _moe_engine(tmp_path, gas=2)
    ids = np.random.RandomState(0).randint(0, V, (8, S)).astype(np.int32)

    def it():
        while True:
            yield (ids,)

    for _ in range(4):
        engine.train_batch(it())
    report = audit_engine(engine, multihost=False)
    assert [f for f in report.findings
            if f.rule == RULE_HOST_SYNC] == []
    engine.monitor.close()
    recs = [json.loads(line) for line in open(engine.monitor.jsonl_path)]
    moe = [r for r in recs if r.get(R.F_KIND) == KIND_MOE]
    assert len(moe) == 2
    n_moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
    for m in moe:
        # summed on the device over the step: both microbatches' slots
        # land in one per-step total (layers x tokens x k x gas)
        assert m[R.M_TOKENS_PER_STEP] == n_moe_layers * 8 * S * 2 * 2
        assert m[R.M_STEPS] == 2


def test_dense_model_under_monitor_moe_is_inert(tmp_path):
    """monitor.moe on a dense model: no moe records, NaN-absent fleet
    slots, nothing crashes — the accumulator simply never fills."""
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    ds.reset_mesh_context()
    cfg = GPT2Config(vocab_size=V, n_positions=S, hidden_size=H,
                     num_layers=2, num_heads=4, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    engine, _, _, _ = ds.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "monitor": {"enabled": True, "output_path": str(tmp_path),
                            "writers": ["jsonl"], "write_interval": 2,
                            "moe": {"enabled": True}},
                "steps_per_print": 10 ** 9})
    ids = np.random.RandomState(0).randint(0, V, (2, S)).astype(np.int32)
    for _ in range(3):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
    engine.monitor.close()
    recs = [json.loads(line) for line in open(engine.monitor.jsonl_path)]
    assert [r for r in recs if r.get(R.F_KIND) == KIND_MOE] == []
    assert len([r for r in recs if r.get(R.F_KIND) == KIND_STEP]) == 3


# --------------------------------------------------------------------- #
# boundary-only cadence + overhead tolerance (acceptance)
# --------------------------------------------------------------------- #
def test_moe_fetch_is_flush_boundary_only():
    """The accumulator fetch runs once per FLUSH, never per step — the
    same cadence as the loss/memory reads (host-sync contract)."""
    calls = []

    def fake_fetch():
        calls.append(1)
        return {"expert_counts": np.array([5., 5.]),
                "overflow_counts": np.zeros(2), "tokens": 10.0,
                "dropped": 0.0, "entropy": 1.0, "confidence": 1.0,
                "gate_tokens": 10.0, "l_aux": 1.0, "layers": 1.0,
                "steps": 1}

    agg = MoeRoutingAggregator()

    def hook(raw, start, end):
        rec = agg.observe_window(raw, start, end)
        return rec, agg.fleet_fields()

    sunk = []
    stream = MetricsStream(window=4, sink=sunk.extend,
                           moe_stats_fn=fake_fetch, moe_hook=hook)
    for step in range(1, 13):
        stream.mark_step_start()
        stream.end_step(step, loss=1.0)
    assert len(calls) == 3                  # 12 steps / window 4
    stream.flush()                          # nothing pending: no fetch
    assert len(calls) == 3
    moe = [r for r in sunk if r.get(R.F_KIND) == KIND_MOE]
    assert len(moe) == 3
    assert [m[R.M_WINDOW_START] for m in moe] == [1, 5, 9]


def test_moe_monitor_overhead_within_tolerance(tmp_path):
    """Monitor-on (moe included) vs monitor-off on the MoE row: same
    generous band as the dense row — a per-step device sync regression
    in the stats accumulator would blow it by far more."""
    steps = 20

    def timed(monitor):
        engine, _ = _moe_engine(tmp_path, monitor=monitor)
        loss = _run(engine, 3)              # warmup + compile
        float(np.asarray(loss))
        t0 = time.perf_counter()
        loss = _run(engine, steps)
        float(np.asarray(loss))
        dt = time.perf_counter() - t0
        if engine.monitor is not None:
            engine.monitor.close()
        return dt

    t_off = timed(False)
    t_on = timed(True)
    assert t_on < t_off * 2.0 + 0.75, (
        f"moe-monitored loop {t_on:.3f}s vs bare {t_off:.3f}s — routing "
        "telemetry is not boundary-only anymore?")


# --------------------------------------------------------------------- #
# trace counter lanes + config validation satellites
# --------------------------------------------------------------------- #
def test_trace_moe_counter_lanes(tmp_path):
    rawgen = iter(range(100))

    def fake_fetch():
        next(rawgen)
        return {"expert_counts": np.array([9., 1.]),
                "overflow_counts": np.array([3., 0.]), "tokens": 13.0,
                "dropped": 3.0, "entropy": 2.0, "confidence": 8.0,
                "gate_tokens": 13.0, "l_aux": 1.0, "layers": 1.0,
                "steps": 1}

    cfg = MonitorConfig.from_dict({
        "enabled": True, "output_path": str(tmp_path),
        "writers": ["jsonl"], "write_interval": 2, "trace": True,
        "reconcile": False, "moe": {"enabled": True}})
    mon = TrainingMonitor(cfg, moe_stats_fn=fake_fetch)
    for step in range(1, 5):
        mon.mark_step_start()
        mon.end_step(step, loss=1.0)
    mon.close()
    payload = json.load(open(mon.trace_path))
    assert validate_trace_events(payload) == []
    counters = [e for e in payload["traceEvents"] if e.get("ph") == "C"]
    assert len(counters) == 2               # one per full window
    assert counters[0]["name"] == "moe routing"
    args = counters[0]["args"]
    assert args["drop_fraction"] == pytest.approx(3.0 / 13.0, rel=1e-4)
    assert args["imbalance"] == pytest.approx(9.0 / 5.0, rel=1e-4)
    # the moe record rode the JSONL stream alongside
    recs = [json.loads(line) for line in open(mon.jsonl_path)]
    assert [r for r in recs if r.get(R.F_KIND) == KIND_MOE]


def test_monitor_moe_config_validation():
    ok = MonitorConfig.from_dict({"enabled": True,
                                  "moe": {"enabled": True, "hot_k": 2}})
    assert ok.moe.enabled and ok.moe.hot_k == 2
    # `true` shorthand like monitor.capture
    assert MonitorConfig.from_dict({"moe": True}).moe.enabled
    assert not MonitorConfig.from_dict({}).moe.enabled
    with pytest.raises(DeepSpeedConfigError, match="ewma_alpha"):
        MonitorConfig.from_dict(
            {"moe": {"popularity_ewma_alpha": 0.0}})
    with pytest.raises(DeepSpeedConfigError, match="hot_k"):
        MonitorConfig.from_dict({"moe": {"hot_k": 0}})
    with pytest.raises(DeepSpeedConfigError, match="dead_expert"):
        MonitorConfig.from_dict({"moe": {"dead_expert_threshold": 1.5}})
    with pytest.raises(DeepSpeedConfigError, match="entropy_floor"):
        MonitorConfig.from_dict({"moe": {"entropy_floor": 1.0}})
    with pytest.raises(DeepSpeedConfigError, match="ep_imbalance_ratio"):
        MonitorConfig.from_dict({"moe": {"ep_imbalance_ratio": 1.0}})
    with pytest.raises(DeepSpeedConfigError, match="windows"):
        MonitorConfig.from_dict({"moe": {"collapse_windows": 0}})
    with pytest.raises(DeepSpeedConfigError, match="config object"):
        MonitorConfig.from_dict({"moe": "yes"})


def test_local_expert_slice_is_union_of_local_devices(tmp_path,
                                                      monkeypatch):
    """Review regression: a host whose local devices span SEVERAL
    expert-axis coordinates owns the union of their shards — resolving
    only local_devices()[0] would report shard 0's load on every host
    and blind the EP-imbalance rule."""
    engine, _ = _moe_engine(tmp_path, monitor=False)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    # on the 8-device sim this process's devices cover ALL 4 expert
    # coordinates: the slice is the whole axis (exactly-fair load),
    # never shard 0's (0, 2) range
    assert engine._moe_local_expert_slice(8) == (0, 8)
    # indivisible expert counts and ep=1 meshes degrade to exactly-fair
    assert engine._moe_local_expert_slice(6) == (0, 6)


# --------------------------------------------------------------------- #
# dispatch_chunks: passes over a dropless layer's row buffers
# --------------------------------------------------------------------- #
def _chunk_raw(chunks, layers, steps):
    counts = np.array([40., 10., 30., 20.])
    return {"expert_counts": counts, "overflow_counts": np.zeros(4),
            "tokens": counts.sum(), "dropped": 0.0, "entropy": 1.0,
            "confidence": 1.0, "gate_tokens": 4.0, "l_aux": 0.0,
            "layers": layers, "steps": steps, "held_rows_max": 40.0,
            "dispatch_chunks": chunks, "local_expert_slice": (0, 2)}


@pytest.mark.parametrize("chunks, layers, steps, mean", [
    (8.0, 8.0, 2, 1.0),      # one trip a layer and step
    (11.0, 8.0, 2, 1.375),   # three layer-steps took a second trip
    (6.0, 8.0, 2, 0.75),     # two saw no pick on a held expert
])
def test_summary_reports_chunks_a_layer_and_step(chunks, layers, steps,
                                                 mean):
    summary = summarize_window(_chunk_raw(chunks, layers, steps))
    assert summary[R.M_DISPATCH_CHUNKS] == mean
    assert summary[R.M_HELD_ROWS_MAX] == 40.0 / layers
    # an accumulator from before the field (an older checkpointed
    # monitor state) summarises as it did
    older = _chunk_raw(chunks, layers, steps)
    del older["dispatch_chunks"]
    assert R.M_DISPATCH_CHUNKS not in summarize_window(older)


def test_dispatch_chunks_sum_like_held_rows_max():
    """The field rides the RoutingStats pytree: a tap's entries sum with
    the same ``jax.tree.map(jnp.add)`` as every other field, and the
    capacity layer's gates each count one pass."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe.dropless import DroplessMoE
    from deepspeed_tpu.moe.sharded_moe import (
        RoutingStats, sum_routing_stats, top1gating)
    # the last two every gate fills; after them what a model that emits
    # one entry for all its gates adds, None (no leaf) elsewhere
    assert RoutingStats._fields[-3:] == (
        "held_rows_max", "dispatch_chunks", "layer_counts")
    assert RoutingStats._field_defaults == {"layer_counts": None}
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    capped = top1gating(logits, capacity_factor=1.0, min_capacity=2)[-1]
    assert float(capped.dispatch_chunks) == 1.0
    layer = DroplessMoE(8, 4, 2, 8, experts_held=(0, 1))
    x = jax.random.normal(jax.random.PRNGKey(1), (1024, 8))
    params = layer.init_params(jax.random.PRNGKey(2))
    # 2,048 picks, a held share of 512: every token's first pick here is
    # 1,024 rows, two chunks; none here, none
    here = layer.stats(layer.route(params, x, jnp.tile(
        jnp.asarray([[0, 3]], jnp.int32), (1024, 1))))
    away = layer.stats(layer.route(params, x, jnp.tile(
        jnp.asarray([[2, 3]], jnp.int32), (1024, 1))))
    assert layer.capacity(1024) == 512
    assert (float(here.dispatch_chunks), float(away.dispatch_chunks)) == (
        2.0, 0.0)
    total = sum_routing_stats([capped, here, away])
    assert float(total.dispatch_chunks) == 3.0
    assert float(total.layers) == 3.0
    assert float(total.held_rows_max) == float(
        capped.held_rows_max + here.held_rows_max + away.held_rows_max)
