"""perf/wait_trace.py and the eight readers built on it (PR 56): the
set-up metrics on compile records written by hand, the three step
metrics on spans written by hand and on one recorded set (tests/perf/
data/toy.wait-spans.json), and all eight through the harness's entry on
the CPU in a throw-away cell with telemetry on."""

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

from perf import program_trace as pt
from perf import run
from perf import trace_reduce as tr
from perf import wait_trace as wt

REPO = Path(__file__).resolve().parents[2]
PERF = REPO / "perf"
DATA = Path(__file__).resolve().parent / "data"
SETUP = ("setup_compile_s", "setup_cache_fetch_s", "setup_trace_lower_s",
         "setup_cache_misses", "setup_initialize_s")
STEP = ("engine_wait_ms", "engine_python_ms", "monitor_host_ms")


def _reader(name):
    return run.load_module(str(REPO), "layer_metrics", name)


def request(program, when_ns, outcome, trace_s=0.0, lower_s=0.0,
            backend_s=0.0, fetch_s=0.0):
    return {"seq": when_ns, "program": program, "when_ns": when_ns,
            "outcome": outcome, "trace_s": trace_s, "lower_s": lower_s,
            "backend_s": backend_s, "fetch_s": fetch_s,
            "saved_s": 0.0, "during": None, "step": None}


# a cold parity, a warm reference, an engine whose grad program missed and
# whose apply program was fetched, and a lowering after the window
RECORDS = [
    request("jit_init_params", 10, "compiled", 0.5, 0.25, 4.0),
    request("jit_loss_and_grads", 20, "compiled", 6.0, 10.0, 200.0),
    request("jit_reference", 30, "fetched", 1.0, 2.0, 3.5, fetch_s=3.0),
    request("jit_convert_element_type", 40, "uncached", 0.0, 0.125, 0.5),
    request("jit_apply_step", 50, "fetched", 0.5, 1.0, 1.5, fetch_s=1.25),
    request("jit_loss_and_grads", 90, "fetched", 0.0, 8.0, 2.0, fetch_s=1.0),
]
MARKS = {"initialize_ns": (35, 35 + 2_400_000_000), "steady_since_ns": 60}


def test_set_up_is_what_began_before_the_newest_engine_was_steady():
    assert [r["program"] for r in wt.setup_requests(RECORDS, MARKS)] == [
        "jit_init_params", "jit_loss_and_grads", "jit_reference",
        "jit_convert_element_type", "jit_apply_step"]
    assert wt.setup_sum(RECORDS, MARKS, wt.compile_s) == 4.0 + 200.0 + 0.5
    assert wt.setup_sum(RECORDS, MARKS, wt.fetch_s) == 3.0 + 1.25
    assert wt.setup_sum(RECORDS, MARKS, wt.trace_lower_s) == (
        0.75 + 16.0 + 3.0 + 0.125 + 1.5)
    assert wt.setup_sum(RECORDS, MARKS, wt.missed) == 2.0
    assert wt.initialize_s(MARKS) == 2.4
    # a warm run: nothing compiled, nothing missed, and those are 0.0
    warm = [dict(r, outcome="fetched", fetch_s=0.25) for r in RECORDS]
    assert wt.setup_sum(warm, MARKS, wt.compile_s) == 0.0
    assert wt.setup_sum(warm, MARKS, wt.missed) == 0.0
    assert wt.setup_sum(warm, MARKS, wt.fetch_s) == 1.25


@pytest.mark.parametrize("records, marks", [
    (None, None),                                   # no such module
    (RECORDS, None),                                # no engine yet
    (RECORDS, {"initialize_ns": None, "steady_since_ns": None}),
])
def test_a_program_without_the_record_or_a_steady_engine_gives_none(
        monkeypatch, records, marks):
    assert wt.setup_requests(records, marks) is None
    assert wt.setup_sum(records, marks, wt.compile_s) is None
    assert wt.initialize_s(marks) is None
    monkeypatch.setattr(wt, "program_record", lambda: (records, marks))
    for name in SETUP:
        assert _reader(name).reduce({}, {}) is None, name


@pytest.mark.parametrize("name, want", [
    ("setup_compile_s", 204.5), ("setup_cache_fetch_s", 4.25),
    ("setup_trace_lower_s", 21.375), ("setup_cache_misses", 2.0),
    ("setup_initialize_s", 2.4)])
def test_each_set_up_reader_on_the_hand_written_record(monkeypatch, name,
                                                       want):
    monkeypatch.setattr(wt, "program_record", lambda: (RECORDS, MARKS))
    assert _reader(name).reduce({}, {}) == want


def test_the_readers_read_the_program_s_own_record():
    """Through ``deepspeed_tpu.monitor.trace``, as program_trace.py
    reads ``scope_map.live()``: the record of this very process."""
    from deepspeed_tpu.monitor import trace
    records, marks = wt.program_record()
    assert records == trace.compiles(0)
    assert marks is trace.newest_engine()
    assert all(r["outcome"] != "imported" for r in records)


# --------------------------------------------------------------------- #
# the three step metrics
# --------------------------------------------------------------------- #
def span(name, start, end, **stats):
    return [name, start, end, stats]


# one optimizer step of the modular loop by hand: the engine waits 40 for
# the loss, the monitor's record takes 20 of which its flush 15, and the
# apply program's first launch lies inside its dispatch
SPANS = sorted([
    span("ds.forward", 0, 100, step=1, micro=0),
    span("ds.forward.prepare", 2, 10),
    span("ds.forward.await_loss", 15, 55, step=1, micro=0),
    span("ds.forward.dispatch", 60, 95, program="jit_loss_and_grads"),
    span("ds.backward", 100, 130, step=1, micro=0),
    span("ds.step", 140, 200, step=1),
    span("ds.step.dispatch", 145, 165, program="jit_apply_step"),
    span("ds.launch.first", 146, 164, program="jit_apply_step"),
    span("ds.step.bookkeeping", 165, 195),
    span("ds.monitor.record", 170, 190, step=1),
    span("ds.monitor.flush", 172, 187, window=10),
], key=lambda s: (s[1], -s[2]))


def test_host_parts_by_hand():
    parts = wt.host_parts(SPANS)
    # ds.forward 100 - 35 dispatch, ds.backward 30, ds.step 60 - 20
    assert parts["host"] == 65 + 30 + 40 == pt.engine_times(SPANS)[0]
    assert parts["wait"] == 40
    assert parts["monitor"] == 20          # the flush inside it once
    assert parts["python"] == 135 - 40 - 20
    assert parts["wait"] + parts["monitor"] + parts["python"] == \
        parts["host"]
    # a first launch OUTSIDE a dispatch span is no Python of the engine
    outside = sorted(SPANS + [span("ds.launch.first", 131, 139,
                                   program="jit_x"),
                              span("ds.backward", 130.5, 139.5)],
                     key=lambda s: (s[1], -s[2]))
    assert wt.host_parts(outside)["python"] == 75 + 1
    # an engine that never waited, no monitor on: 0, not nothing
    plain = [s for s in SPANS if "await" not in s[0]
             and "monitor" not in s[0]]
    assert wt.host_parts(plain) == {"host": 135, "wait": 0, "monitor": 0,
                                    "python": 135}


@pytest.fixture
def recorded(monkeypatch):
    body = json.loads((DATA / "toy.wait-spans.json").read_text())
    monkeypatch.setattr(pt, "read", lambda: {"spans": body["spans"],
                                             "maps": {}})
    return body


def test_the_three_parts_sum_to_engine_host_ms_on_recorded_spans(recorded):
    info = {"steps_traced": recorded["steps"]}
    host = _reader("engine_host_ms").reduce({}, info)
    wait, python, monitor = (_reader(n).reduce({}, info) for n in STEP)
    assert wait > 0 and python > 0 and monitor > 0
    assert wait + python + monitor == pytest.approx(host, rel=1e-9)
    names = {s[0] for s in recorded["spans"]}
    assert {"ds.forward.await_loss", "ds.monitor.record",
            "ds.monitor.flush"} <= names
    # the file's six micro-batches each waited once, its three steps
    # each wrote a record, and every other step flushed
    count = [s[0] for s in recorded["spans"]].count
    assert (count("ds.forward.await_loss"), count("ds.monitor.record")) \
        == (6, 3)
    assert 1 <= count("ds.monitor.flush") <= 2
    # the monitor's part is its records' time, flushes inside them
    records = [pt.interval(s) for s in recorded["spans"]
               if s[0] == "ds.monitor.record"]
    assert monitor == pytest.approx(
        tr.per_step(tr.measure(records), recorded["steps"]))


def test_no_spans_at_all_give_none(monkeypatch):
    monkeypatch.setattr(pt, "read", lambda: {"spans": [], "maps": {}})
    for name in STEP:
        assert _reader(name).reduce({}, {"steps_traced": 5}) is None


# --------------------------------------------------------------------- #
# the eight through the harness, telemetry on
# --------------------------------------------------------------------- #
@pytest.fixture
def restore_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


MONITOR = {"enabled": True, "output_path": ".perf_trace/monitor",
           "write_interval": 10, "reconcile": False}


def toy_checkout(tmp_path, **monitor):
    """A copy of perf/ with ``gpt2-large.s1024.monitor``'s job block on a
    toy configuration, as the cell ``toy.monitor``; ``monitor`` overrides
    keys of the cell's monitor block."""
    root = tmp_path / "checkout"
    shutil.copytree(PERF, root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.loads(
        (PERF / "workloads/gpt2-large.s1024.monitor.json").read_text())
    assert cell["job"]["ds_config"]["monitor"] == MONITOR
    monitor = dict(MONITOR, output_path=str(root / ".perf_trace/monitor"),
                   **monitor)
    (root / "perf/configs/toy.json").write_text(json.dumps({
        "family": "gpt2", "source": "a test", "reduced": [],
        "activation_function": "gelu_new", "attn_pdrop": 0.1,
        "embd_pdrop": 0.1, "resid_pdrop": 0.1, "initializer_range": 0.02,
        "layer_norm_epsilon": 1e-5, "n_embd": 64, "n_head": 2, "n_layer": 2,
        "n_positions": 64, "vocab_size": 250,
        "assumed": {"vocab_rows_padded": 256}}))
    (root / "perf/traffic/toy.b2.s64.json").write_text(json.dumps({
        "generator": "zipf_tokens", "batch_per_chip": 2, "seq": 64,
        "exponent": 1.0, "pool_steps": 512}))
    (root / "perf/workloads/toy.monitor.json").write_text(json.dumps({
        "config": "toy", "traffic": "toy.b2.s64", "chips": 1, "why": "test",
        "job": {"gradient_accumulation_steps": 1,
                "activation_checkpointing": True,
                "ds_config": {
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 2},
                    "monitor": monitor},
                "parity": {"layers": 2, "rows_per_chip": 2}},
        "loss_check": {"steps": [3, 7], "rise": 1.5},
        "per_layer": ["engine_host_ms"] + list(SETUP + STEP)}))
    return root


def test_a_cell_with_telemetry_on_reports_the_eight(
        tmp_path, monkeypatch, restore_compile_cache):
    """``gpt2-large.s1024.monitor``'s job block on a toy configuration,
    run traced from a copy of perf/ on the CPU: every one of the eight
    has a value, the stream holds step records, flushes inside the
    window and the compile records of set-up."""
    root = toy_checkout(tmp_path)
    monkeypatch.setattr(pt, "ROOT", str(root))
    pt._read.cache_clear()
    traced = run.run_cell("toy.monitor", seed=2 ** 31 + 56, seconds=1.0,
                          trace=True, root=str(root), platform="cpu")
    pt._read.cache_clear()
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(got) == {"engine_host_ms"} | set(SETUP + STEP)
    assert traced["correct"] and traced["failed"] == 0
    units = {k: v["unit"] for k, v in traced["metrics"].items()}
    assert {units[n] for n in STEP} == {"ms"}
    assert units["setup_cache_misses"] == "count"
    # this process compiled or fetched everything set-up needed
    assert got["setup_compile_s"] + got["setup_cache_fetch_s"] > 0
    assert got["setup_trace_lower_s"] > 0
    assert 0 < got["setup_initialize_s"] < 60
    assert got["monitor_host_ms"] > 0 and got["engine_python_ms"] > 0
    assert got["engine_wait_ms"] >= 0
    assert (got["engine_wait_ms"] + got["engine_python_ms"]
            + got["monitor_host_ms"]) == pytest.approx(
        got["engine_host_ms"], rel=0.01)
    recs = [json.loads(line) for line in
            (root / ".perf_trace/monitor/metrics.jsonl").read_text()
            .splitlines()]
    kinds = types.SimpleNamespace(**{k: [r for r in recs
                                         if r["kind"] == k]
                                     for k in ("step", "compile")})
    assert len(kinds.step) >= 10 and len(kinds.step) % 10 == 0
    assert kinds.compile[0]["outcome"] == "imported"
    assert {"jit_loss_and_grads", "jit_apply_step"} <= {
        r["program"] for r in kinds.compile}
    assert all(r["compiles"] == 0 for r in kinds.step[2:])


@pytest.mark.parametrize("reconcile", [False, True])
def test_the_result_is_the_last_line_a_run_with_telemetry_on_prints(
        tmp_path, reconcile):
    """The harness's contract is the LAST line of standard output, and
    the engine's monitor flushes its last window from a hook at
    interpreter exit, after perf/run.py has printed the result: with the
    cell's block as it is, and with ``reconcile`` on (whose window line
    the hook used to print last: the driver's first check of PR 56)."""
    root = toy_checkout(tmp_path, reconcile=reconcile)
    ran = subprocess.run(
        [sys.executable, "-c",
         "import json, sys\n"
         "from perf import program_trace, run\n"
         "program_trace.ROOT = sys.argv[1]\n"
         "print(json.dumps(run.run_cell('toy.monitor', seed=2 ** 31 + 56, "
         "seconds=1.0, trace=False, root=sys.argv[1], platform='cpu')), "
         "flush=True)", str(root)],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert ran.returncode == 0, ran.stderr[-2000:]
    result = json.loads(ran.stdout.splitlines()[-1])
    assert result["correct"] and set(result["metrics"]) == {
        "tokens_per_s", "step_ms_p50", "setup_s"}
    kinds = [json.loads(line)["kind"] for line in
             (root / ".perf_trace/monitor/metrics.jsonl").read_text()
             .splitlines()]
    # the hook wrote the last, partial window too
    assert kinds.count("step") >= result["attempted"] >= 10
    assert ("reconcile" in kinds) == reconcile
    assert ("[monitor-reconcile]" in ran.stdout) == reconcile
