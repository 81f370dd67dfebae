"""The engine's own spans (deepspeed_tpu/monitor/trace.py ``span``) as a
``jax.profiler`` trace of a tiny engine on the CPU shows them: names,
ids, nesting, counts per optimizer step, and the same spans on the step
lane of the monitor's Chrome export (docs/telemetry.md)."""

import collections
import glob
import json

import numpy as np
import pytest

import jax

from deepspeed_tpu.monitor import validate_trace_events
from deepspeed_tpu.monitor.trace import SPAN_PREFIX, span

from .test_monitor import _engine as _monitored_engine

STEPS = 3
FORWARD_CHILDREN = ("ds.forward.prepare", "ds.forward.shard_batch",
                    "ds.forward.rng", "ds.forward.dispatch")


def _engine(tmp_path, gas):
    """test_monitor.py's two-layer GPT-2 engine, Chrome export on."""
    return _monitored_engine(tmp_path, gas=gas, monitor={
        "writers": ["jsonl"], "trace": True})


def _profiled(tmp_path, work):
    """The ``ds.*`` events [name, start, end, stats] the profiler saw on
    the thread that ran ``work``."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    lines = [[[e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats)] for e in line.events
              if e.name.startswith(SPAN_PREFIX)]
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines]
    return sorted(max(lines, key=len), key=lambda s: (s[1], -s[2]))


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.mark.parametrize("gas", [1, 2])
def test_modular_loop_spans(tmp_path, gas):
    engine = _engine(tmp_path, gas)
    # a loaded test machine can stall two launches in a row, and the
    # engine would then wait for the loss (ds.forward.await_loss:
    # tests/unit/test_host_trace.py); not here
    engine.LAUNCH_STALL_S = float("inf")
    ids = np.random.RandomState(0).randint(0, 64, (2, 16)).astype(np.int32)

    def loop(steps):
        for _ in range(steps * gas):
            engine.backward(engine.forward(ids))
            engine.step()
        jax.block_until_ready(engine.params)

    loop(1)  # compile outside the trace
    spans = _profiled(tmp_path, lambda: loop(STEPS))
    by_name = collections.defaultdict(list)
    for s in spans:
        by_name[s[0]].append(s)
    calls = STEPS * gas
    # every span of the table, once per call of what it covers
    want = {name: calls for name in ("ds.forward", "ds.backward")
            + FORWARD_CHILDREN}
    want.update({"ds.step": STEPS, "ds.step.dispatch": STEPS,
                 "ds.step.bookkeeping": STEPS, "ds.monitor.record": STEPS})
    if gas > 1:  # the first micro-batch adopts the gradient buffer
        want["ds.backward.dispatch"] = STEPS * (gas - 1)
    assert {n: len(v) for n, v in by_name.items()} == want
    # ids: the optimizer step about to complete (the first ran untraced)
    # and the micro-batch within it
    for name in ("ds.forward", "ds.backward"):
        assert [(s[3]["step"], s[3]["micro"]) for s in by_name[name]] == [
            (step, micro) for step in range(2, 2 + STEPS)
            for micro in range(gas)]
    assert [s[3]["step"] for s in by_name["ds.step"]] == list(
        range(2, 2 + STEPS))
    programs = {name: {s[3]["program"] for s in by_name[name]}
                for name in by_name if name.endswith(".dispatch")}
    assert programs["ds.forward.dispatch"] == {"jit_loss_and_grads"}
    assert programs["ds.step.dispatch"] == {"jit_apply_step"}
    if gas > 1:
        assert programs["ds.backward.dispatch"] == {"jit_accumulate"}
    # children lie inside their parents, in the table's order
    for parent in by_name["ds.forward"]:
        kids = [s for s in spans if s is not parent and _inside(s, parent)]
        assert tuple(s[0] for s in kids) == FORWARD_CHILDREN
    for parent in by_name["ds.step"]:
        kids = [s[0] for s in spans if s is not parent
                and _inside(s, parent)]
        assert kids == ["ds.step.dispatch", "ds.step.bookkeeping",
                        "ds.monitor.record"]
    for child in by_name.get("ds.backward.dispatch", []):
        assert any(_inside(child, p) for p in by_name["ds.backward"])
    # one thread, one call after another: the outer spans do not overlap
    outer = [s for s in spans if s[0] in ("ds.forward", "ds.backward",
                                          "ds.step")]
    assert all(a[2] <= b[1] for a, b in zip(outer, outer[1:]))

    # the Chrome export's step lane holds the same spans
    engine.monitor.close()
    payload = json.load(open(engine.monitor.trace_path))
    assert validate_trace_events(payload) == []
    chrome = collections.Counter(
        e["name"] for e in payload["traceEvents"] if e["ph"] == "X")
    for name in ("ds.forward.dispatch", "ds.step.dispatch"):
        assert chrome[name] == want[name] + (gas if "forward" in name
                                             else 1)
    if gas > 1:
        assert chrome["ds.backward.dispatch"] == (STEPS + 1) * (gas - 1)
    dispatched = [e for e in payload["traceEvents"]
                  if e["name"] == "ds.step.dispatch"]
    assert [e["args"] for e in dispatched] == [
        {"program": "jit_apply_step"}] * (STEPS + 1)
    stepped = [e["args"]["step"] for e in payload["traceEvents"]
               if e["name"] == "ds.step"]
    assert stepped == list(range(1, 2 + STEPS))


def test_a_span_without_profiler_or_monitor_is_inert():
    with span("nothing", step=1) as s:
        assert s is not None
    with pytest.raises(KeyError):
        with span("raises"):
            raise KeyError("passes through")
