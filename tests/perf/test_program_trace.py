"""perf/program_trace.py: the program's spans and scope maps as the
per-layer readers use them, on lists written by hand and on one
optimizer step of ``gpt2-large.gas4`` recorded on the v5e (tests/perf/
data/gpt2-large.gas4.one-step.json.gz: the reduced trace, the ``ds.*``
spans and the scope maps of PR 24's chip run, cut as the file's
``about`` says)."""

import gzip
import json
import types
from pathlib import Path

import pytest

from perf import program_trace as pt
from perf import run
from perf import trace_reduce as tr

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
NEW_READERS = ("engine_host_ms", "engine_dispatch_ms", "gap_explained_pct",
               "attn_ms", "mlp_ms", "head_ms", "recompute_ms",
               "scope_unattributed_pct")


def span(name, start, end, **stats):
    return [name, start, end, stats]


def device(ops, modules):
    return {"ops": sorted(([n, "", s, e] for n, s, e in ops),
                          key=lambda o: (o[2], -o[3])),
            "modules": sorted(([n, s, e] for n, s, e in modules),
                              key=lambda m: m[1])}


# one micro-step and an optimizer step of the modular loop, by hand
SPANS = sorted([
    span("ds.forward", 0, 100, step=1, micro=0),
    span("ds.forward.prepare", 2, 10),
    span("ds.forward.shard_batch", 10, 30),
    span("ds.forward.rng", 30, 50),
    span("ds.forward.dispatch", 60, 95, program="jit_loss_and_grads"),
    span("ds.backward", 100, 130, step=1, micro=0),
    span("ds.backward.dispatch", 105, 125, program="jit_accumulate"),
    span("ds.step", 140, 200, step=1),
    span("ds.step.dispatch", 145, 165, program="jit_apply_step"),
    span("ds.step.bookkeeping", 165, 195),
], key=lambda s: (s[1], -s[2]))


def test_children_and_leaves():
    names = [s[0] for s in SPANS]
    inside = pt.children(SPANS)
    assert [names[k] for k in inside[names.index("ds.forward")]] == [
        "ds.forward.prepare", "ds.forward.shard_batch", "ds.forward.rng",
        "ds.forward.dispatch"]
    assert [names[k] for k in inside[names.index("ds.step")]] == [
        "ds.step.dispatch", "ds.step.bookkeeping"]
    assert inside[names.index("ds.forward.rng")] == []
    assert sorted(s[0] for s in pt.leaf_spans(SPANS)) == sorted(
        n for n in names if n.count(".") == 2)
    # a grandchild is its parent's child only
    deep = [span("a", 0, 10), span("b", 1, 9), span("c", 2, 3)]
    assert pt.children(deep) == [[1], [2], []]
    assert pt.self_time(deep) == {"a": 2, "b": 7, "c": 1}


def test_self_time_is_duration_minus_what_children_cover():
    own = pt.self_time(SPANS)
    assert own["ds.forward"] == 100 - (8 + 20 + 20 + 35)
    assert own["ds.backward"] == 30 - 20
    assert own["ds.step"] == 60 - 50
    assert own["ds.forward.rng"] == 20
    # two spans of one name add up
    twice = SPANS + [span("ds.forward", 300, 310, step=2, micro=0)]
    assert pt.self_time(sorted(twice, key=lambda s: (s[1], -s[2])))[
        "ds.forward"] == own["ds.forward"] + 10


def test_engine_host_and_dispatch_sum_to_the_outer_spans():
    host, dispatch = pt.engine_times(SPANS)
    assert dispatch == 35 + 20 + 20
    assert host == (100 - 35) + (30 - 20) + (60 - 20)
    assert host + dispatch == 100 + 30 + 60
    assert pt.engine_times([]) == (0, 0)


def test_idle_time_between_programs_goes_to_the_leaf_span_over_it():
    dev = device(ops=[], modules=[
        ("jit_loss_and_grads(1)", 0, 40), ("jit__unstack(2)", 44, 45),
        ("jit_loss_and_grads(1)", 62, 150), ("jit_apply_step(3)", 170, 180)])
    # gaps: 40..44, 45..62, 150..170
    idle, under, outside = pt.gap_attribution(dev, SPANS)
    assert idle == 4 + 17 + 20
    assert under == {"ds.forward.rng": 4 + 5,
                     "ds.forward.dispatch": 2,
                     "ds.step.dispatch": 15, "ds.step.bookkeeping": 5}
    assert outside == 10  # 50..60 of ds.forward itself: no leaf over it
    assert idle == sum(under.values()) + outside
    assert pt.covered([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert pt.gap_attribution(device([], []), SPANS) == (0, {}, 0)


MAPS = {"jit_loss_and_grads": {
            "fusion.1": ("attn", "forward"), "flash_fwd.2": ("attn", "recompute"),
            "fusion.3": ("mlp", "recompute"), "fusion.4": ("mlp", "backward"),
            "fusion.5": ("head", "forward"), "while.9": ("layer", "forward")},
        "jit_apply_step": {"fusion.1": ("other", "forward")}}


def by_hand_trace():
    return {"host": [], "devices": {"0": device(
        ops=[("fusion.1", 0, 10), ("while.9", 10, 60),
             ("flash_fwd.2", 12, 30), ("fusion.3", 30, 45),
             ("fusion.4", 45, 58), ("fusion.5", 60, 70), ("copy.7", 70, 74),
             ("fusion.1", 100, 130), ("fusion.1", 200, 201)],
        modules=[("jit_loss_and_grads(77)", 0, 80),
                 ("jit_apply_step(78)", 100, 130)])}}


def test_operations_go_to_their_program_then_to_scope_and_pass():
    times = pt.by_scope(by_hand_trace(), MAPS)
    assert times == {
        "jit_loss_and_grads": {
            ("attn", "forward"): 10, ("attn", "recompute"): 18,
            ("mlp", "recompute"): 15, ("mlp", "backward"): 13,
            ("head", "forward"): 10, ("other", "forward"): 4},
        # the same instruction name in another program is another thing;
        # the operation after the last program belongs to none
        "jit_apply_step": {("other", "forward"): 30}}
    # the loop itself is a container, not a leaf: its tag counts nothing
    assert pt.scope_time(times, scope="layer") == 0
    assert pt.scope_time(times, scope="attn") == 28
    assert pt.scope_time(times, phase="recompute") == 33
    assert pt.scope_time(times, scope="other",
                         program="loss_and_grads") == 4
    assert pt.scope_time(times, program="loss_and_grads") == 70
    assert pt.scope_time(times) == 100
    # a program without a map: all of it is unattributed
    assert pt.by_scope(by_hand_trace(), {})["jit_loss_and_grads"] == {
        ("other", "forward"): 70}
    assert pt.by_scope({"host": [], "devices": {}}, MAPS) == {}


def test_program_of_a_module_name():
    assert pt.program_of("jit_loss_and_grads(14105827211927823)") == (
        "jit_loss_and_grads")
    assert pt.program_of("jit__unstack") == "jit__unstack"


def _reader(name):
    return run.load_module(str(REPO), "layer_metrics", name)


def _run_info(steps=1):
    family = types.SimpleNamespace(
        GRAD_PROGRAM="loss_and_grads", APPLY_PROGRAM="apply_step",
        FLASH_KERNELS=("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"))
    return {"steps_traced": steps, "family": family}


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_give_nothing_for_a_program_without_spans_or_maps(
        name, monkeypatch):
    """The parent commit, and the toy cell of test_manifest.py: no
    ``ds.*`` span in the trace and no scope map to be had."""
    monkeypatch.setattr(pt, "read", lambda: {"spans": [], "maps": {}})
    assert _reader(name).reduce(by_hand_trace(), _run_info()) is None
    assert _reader(name).reduce({"host": [], "devices": {}},
                                _run_info()) is None


def test_no_trace_directory_reads_as_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(pt, "ROOT", str(tmp_path))
    assert pt.newest_xplane() is None
    assert pt.read() == {"spans": [], "maps": {}}


def test_readers_on_the_trace_written_by_hand(monkeypatch, capsys):
    monkeypatch.setattr(pt, "read", lambda: {"spans": SPANS, "maps": MAPS})
    trace, info = by_hand_trace(), _run_info()
    value = {name: _reader(name).reduce(trace, info)
             for name in NEW_READERS}
    assert value["engine_host_ms"] == pytest.approx(115e-6)
    assert value["engine_dispatch_ms"] == pytest.approx(75e-6)
    assert value["attn_ms"] == pytest.approx(28e-6)
    assert value["mlp_ms"] == pytest.approx(28e-6)
    assert value["head_ms"] == pytest.approx(10e-6)
    assert value["recompute_ms"] == pytest.approx(33e-6)
    assert value["scope_unattributed_pct"] == pytest.approx(100 * 4 / 70)
    # one gap, 80..100, all of it under ds.forward.dispatch (60..95) or
    # nothing
    assert value["gap_explained_pct"] == pytest.approx(100 * 15 / 20)
    printed = capsys.readouterr().out
    assert "idle between programs by leaf span" in printed
    assert "ds.forward.dispatch" in printed
    assert "grad program by scope and pass" in printed


# ---------------------------------------------------------------------- #
# one optimizer step recorded on the v5e
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "gpt2-large.gas4.one-step.json.gz", "rt") as f:
        data = json.load(f)
    data["maps"] = {program: {name: tuple(tag) for name, tag in m.items()}
                    for program, m in data["maps"].items()}
    return data


def test_the_recorded_file_is_no_larger_than_the_older_one():
    assert (DATA / "gpt2-large.gas4.one-step.json.gz").stat().st_size <= (
        DATA / "gpt2-large.s1024.one-step.json.gz").stat().st_size


def test_recorded_step_has_the_loop_the_cell_exists_for(recorded):
    dev = recorded["reduced"]["devices"]["0"]
    programs = [pt.program_of(m[0]) for m in dev["modules"]]
    # 4 grad programs, 3 accumulates, 1 apply, 4 x 5 of the eager split
    assert len(programs) == 28
    assert programs.count("jit_loss_and_grads") == 4
    assert programs.count("jit_accumulate") == 3
    assert programs[-1] == "jit_apply_step"
    names = {s[0] for s in recorded["spans"]}
    assert {"ds.forward", "ds.forward.prepare", "ds.forward.shard_batch",
            "ds.forward.rng", "ds.forward.dispatch", "ds.backward",
            "ds.backward.dispatch", "ds.step", "ds.step.dispatch",
            "ds.step.bookkeeping"} == names
    stats = {s[0]: s[3] for s in recorded["spans"]}
    assert set(stats["ds.forward"]) == {"step", "micro"}
    assert stats["ds.forward.dispatch"] == {"program": "jit_loss_and_grads"}
    assert stats["ds.backward.dispatch"] == {"program": "jit_accumulate"}
    assert stats["ds.step.dispatch"] == {"program": "jit_apply_step"}


def test_recorded_scopes_sum_to_the_grad_programs_busy_time(recorded):
    reduced, maps = recorded["reduced"], recorded["maps"]
    dev = reduced["devices"]["0"]
    times = pt.by_scope(reduced, maps)
    # the file keeps the operations of the first grad execution only
    first = next((m[1], m[2]) for m in dev["modules"]
                 if pt.program_of(m[0]) == "jit_loss_and_grads")
    busy = pt.covered([first], tr.busy_intervals(dev))
    grad = pt.scope_time(times, program="loss_and_grads")
    assert grad == busy
    assert 0.99 < busy / (first[1] - first[0]) <= 1.0
    by = {scope: pt.scope_time(times, scope=scope, program="loss_and_grads")
          for scope in ("embed", "attn", "mlp", "head", "layer", "other")}
    assert sum(by.values()) == grad
    flash, calls = tr.kernel_time(dev["ops"],
                                  "flash_fwd|flash_bwd_dkdv|flash_bwd_dq")
    assert calls == 4 * 36  # forward, recomputed, and two backward kernels
    assert by["attn"] >= flash > 0.5 * by["attn"]
    assert by["attn"] > by["mlp"] > by["head"] > by["embed"] > 0
    assert by["other"] / grad < 0.10
    # every pass is there; the head and the embedding are not recomputed
    phases = {scope: {p for (s, p), ns in times["jit_loss_and_grads"].items()
                      if s == scope and ns} for scope in by}
    assert phases["attn"] == phases["mlp"] == {"forward", "recompute",
                                               "backward"}
    assert phases["head"] == phases["embed"] == {"forward", "backward"}
    recompute = pt.scope_time(times, phase="recompute")
    forward = pt.scope_time(times, phase="forward", program="loss_and_grads")
    assert 0.5 * forward < recompute < forward
    # the accumulate and apply programs carry no scope of the model
    for program in ("jit_accumulate", "jit_apply_step"):
        assert set(times[program]) == {("other", "forward")}


def test_recorded_spans_say_where_the_host_sits_and_the_device_waits(
        recorded):
    spans = recorded["spans"]
    dev = recorded["reduced"]["devices"]["0"]
    host, dispatch = pt.engine_times(spans)
    assert host + dispatch == sum(
        s[2] - s[1] for s in spans if s[0] in pt.OUTER)
    # the host sits inside the calls of the step programs
    assert dispatch > 20 * host
    idle, under, outside = pt.gap_attribution(dev, spans)
    assert idle == tr.measure(tr.module_gaps(dev)) > 0
    assert idle == pytest.approx(sum(under.values()) + outside)
    # the device waits, most of all, while the host is inside the call
    # of the grad program
    assert max(under, key=under.get) == "ds.forward.dispatch"
    assert under["ds.forward.dispatch"] > 0.5 * idle


def test_readers_on_the_recorded_step(recorded, monkeypatch, capsys):
    monkeypatch.setattr(pt, "read", lambda: recorded)
    info = _run_info()
    value = {name: _reader(name).reduce(recorded["reduced"], info)
             for name in NEW_READERS}
    assert all(v is not None and v > 0 for v in value.values()), value
    flash = _reader("flash_ms").reduce(recorded["reduced"], info)
    assert value["attn_ms"] >= flash
    assert value["scope_unattributed_pct"] < 10
    assert value["gap_explained_pct"] > 50
    printed = capsys.readouterr().out
    assert "'ds.forward.dispatch'" in printed and "attn.recompute" in printed
