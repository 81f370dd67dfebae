"""perf/scope_parts.py and the six readers built on it: the device time
by (scope, part, pass) on lists written by hand, whose rows sum to
``program_trace.by_scope``'s, and nothing for a program that names no
part."""

import types
from pathlib import Path

import pytest

from perf import program_trace as pt
from perf import run
from perf import scope_parts as sp
from perf import trace_reduce as tr

REPO = Path(__file__).resolve().parents[2]
PART_READERS = ("attn_proj_ms", "attn_layout_ms", "attn_core_ms",
                "attn_unsplit_pct", "cast_ms", "stack_ms")

# one micro-batch of a grad program and an apply program, by hand:
# instruction -> (scope, phase) and -> part
MAPS = {
    "jit_loss_and_grads": {
        "convert.1": ("other", "forward"), "slice.2": ("other", "forward"),
        "while.3": ("other", "forward"), "fusion.4": ("attn", "forward"),
        "copy.5": ("attn", "forward"), "flash_fwd.6": ("attn", "forward"),
        "copy.7": ("layer", "forward"), "fusion.8": ("attn", "forward"),
        "fusion.9": ("mlp", "forward"), "fusion.10": ("attn", "recompute"),
        "flash_bwd.11": ("attn", "backward"), "copy.12": ("attn", "backward"),
        "fusion.13": ("attn", "backward"), "dus.14": ("other", "backward"),
        "convert.15": ("other", "backward"), "mul.16": ("attn", "backward"),
        "add.17": ("layer", "backward")},
    "jit_apply_step": {"slice.2": ("other", "forward")}}
PARTS = {
    "jit_loss_and_grads": {
        "convert.1": "cast", "slice.2": "stack", "while.3": None,
        "fusion.4": "qkv", "copy.5": "layout", "flash_fwd.6": "core",
        "copy.7": "layout", "fusion.8": "out", "fusion.9": None,
        "fusion.10": "qkv", "flash_bwd.11": "core", "copy.12": "layout",
        "fusion.13": "out", "dus.14": "stack", "convert.15": "cast",
        "mul.16": None, "add.17": None},
    "jit_apply_step": {"slice.2": None}}


US = 1000  # the lists below are in microseconds, a trace's in ns


def device(ops, modules):
    return {"ops": sorted(([n, "", s * US, e * US] for n, s, e in ops),
                          key=lambda o: (o[2], -o[3])),
            "modules": sorted(([n, s * US, e * US] for n, s, e in modules),
                              key=lambda m: m[1])}


def by_hand_trace():
    return {"host": [], "devices": {"0": device(
        ops=[("convert.1", 0, 7), ("while.3", 7, 90), ("slice.2", 7, 10),
             ("fusion.4", 10, 22), ("copy.5", 22, 24),
             ("flash_fwd.6", 24, 34), ("copy.7", 34, 35),
             ("fusion.8", 35, 41), ("fusion.9", 41, 50),
             ("fusion.10", 50, 61), ("flash_bwd.11", 61, 76),
             ("copy.12", 76, 78), ("fusion.13", 78, 84),
             ("mul.16", 84, 85), ("add.17", 85, 87), ("dus.14", 87, 90),
             ("convert.15", 90, 94), ("unknown.18", 94, 96),
             ("slice.2", 100, 120)],
        modules=[("jit_loss_and_grads(77)", 0, 98),
                 ("jit_apply_step(78)", 100, 120)])}}


def test_operations_go_to_scope_part_and_pass():
    times = sp.by_part(by_hand_trace(), MAPS, PARTS)
    want = {
        "jit_loss_and_grads": {
            ("other", "cast", "forward"): 7, ("other", "stack", "forward"): 3,
            ("attn", "qkv", "forward"): 12, ("attn", "layout", "forward"): 2,
            ("attn", "core", "forward"): 10,
            ("layer", "layout", "forward"): 1,
            ("attn", "out", "forward"): 6, ("mlp", None, "forward"): 9,
            ("attn", "qkv", "recompute"): 11,
            ("attn", "core", "backward"): 15,
            ("attn", "layout", "backward"): 2,
            ("attn", "out", "backward"): 6, ("attn", None, "backward"): 1,
            ("layer", None, "backward"): 2,
            ("other", "stack", "backward"): 3,
            ("other", "cast", "backward"): 4,
            # an operation the maps do not name: in no scope, in no part
            ("other", None, "forward"): 2},
        # the same instruction name in another program is another thing
        "jit_apply_step": {("other", None, "forward"): 20}}
    assert times == {program: {tag: us * US for tag, us in tags.items()}
                     for program, tags in want.items()}


def test_the_parts_of_a_scope_sum_to_by_scopes_number():
    trace = by_hand_trace()
    parts, scopes = sp.by_part(trace, MAPS, PARTS), pt.by_scope(trace, MAPS)
    assert set(parts) == set(scopes)
    for program, tags in scopes.items():
        for (scope, phase), ns in tags.items():
            assert sp.part_time(parts, (scope,), phase=phase,
                                program=program) == ns
    assert sp.part_time(parts, ("attn",)) == pt.scope_time(
        scopes, scope="attn") == 65 * US
    assert sp.part_time(parts, ("attn",), ("qkv", "out")) == 35 * US
    assert sp.part_time(parts, ("attn", "layer"), ("layout",)) == 5 * US
    assert sp.part_time(parts, ("attn",), (None,)) == 1 * US
    assert sp.part_time(parts, ("other",),
                        program="loss_and_grads") == 19 * US
    assert sp.part_time(parts, ("other",)) == 39 * US
    assert sp.part_time(parts, ("other",), ("stack",), "backward") == 3 * US


def test_the_tables_hold_every_cell_that_is_not_empty():
    parts = sp.by_part(by_hand_trace(), MAPS, PARTS)
    attn = sp.table(parts, ("attn",), sp.ATTN_PARTS, steps=1)
    assert list(attn) == [
        "qkv.forward", "qkv.recompute", "layout.forward", "layout.backward",
        "core.forward", "core.backward", "out.forward", "out.backward",
        "none.backward"]
    assert sum(attn.values()) == pytest.approx(0.065)
    other = sp.table(parts, ("other",), sp.OTHER_PARTS, 1, "loss_and_grads")
    assert other == {"cast.forward": 0.007, "cast.backward": 0.004,
                     "stack.forward": 0.003, "stack.backward": 0.003,
                     "none.forward": 0.002}


def test_a_program_in_one_map_only_or_in_none_counts_nothing():
    trace = by_hand_trace()
    only_grad = {"jit_loss_and_grads": PARTS["jit_loss_and_grads"]}
    assert set(sp.by_part(trace, MAPS, only_grad)) == {"jit_loss_and_grads"}
    assert sp.by_part(trace, MAPS, {}) is None
    assert sp.by_part(trace, {}, PARTS) is None
    assert sp.by_part({"host": [], "devices": {}}, MAPS, PARTS) is None


def _reader(name):
    return run.load_module(str(REPO), "layer_metrics", name)


def _run_info(steps=1):
    return {"steps_traced": steps,
            "family": types.SimpleNamespace(GRAD_PROGRAM="loss_and_grads")}


@pytest.mark.parametrize("name", PART_READERS)
def test_readers_give_nothing_for_a_program_that_names_no_part(
        name, monkeypatch):
    """The parent commit: scope maps and no part map; and a program with
    neither."""
    monkeypatch.setattr(sp, "_live_parts", lambda path: {})
    monkeypatch.setattr(pt, "read", lambda: {"spans": [], "maps": MAPS})
    assert _reader(name).reduce(by_hand_trace(), _run_info()) is None
    monkeypatch.setattr(pt, "read", lambda: {"spans": [], "maps": {}})
    assert _reader(name).reduce(by_hand_trace(), _run_info()) is None


def test_a_scope_map_without_live_parts_reads_as_no_part_map(monkeypatch):
    from deepspeed_tpu.profiling import scope_map
    monkeypatch.delattr(scope_map, "live_parts")
    sp._live_parts.cache_clear()
    assert sp._live_parts("a path") == {}
    sp._live_parts.cache_clear()


@pytest.mark.parametrize("name, want", [
    ("attn_proj_ms", 0.035), ("attn_layout_ms", 0.005),
    ("attn_core_ms", 0.025), ("attn_unsplit_pct", 100 * 1 / 65),
    ("cast_ms", 0.011), ("stack_ms", 0.006)])
def test_readers_on_the_trace_written_by_hand(name, want, monkeypatch,
                                              capsys):
    monkeypatch.setattr(sp, "_live_parts", lambda path: PARTS)
    monkeypatch.setattr(pt, "read", lambda: {"spans": [], "maps": MAPS})
    assert _reader(name).reduce(by_hand_trace(), _run_info()) == (
        pytest.approx(want))
    # over two traced steps, half of it a step; a share stays
    half = _reader(name).reduce(by_hand_trace(), _run_info(steps=2))
    assert half == pytest.approx(want if name.endswith("_pct")
                                 else want / 2)
    printed = capsys.readouterr().out
    if name == "attn_unsplit_pct":
        assert "attn by part and pass" in printed
        assert "'none.backward': 0.001}; sum 0.065; in" in printed
        assert "in scope layer: {'layout.forward'" in printed
    if name == "stack_ms":
        assert "other by part and pass" in printed
        assert "'cast.forward'" in printed


def test_the_parts_metrics_add_up_to_attn_ms(monkeypatch):
    """proj + layout + core + the unsplit share, less the layout that
    lies in scope layer, is ``attn_ms`` (no rotary, gate or diff in this
    trace)."""
    monkeypatch.setattr(sp, "_live_parts", lambda path: PARTS)
    monkeypatch.setattr(pt, "read", lambda: {"spans": [], "maps": MAPS})
    trace, info = by_hand_trace(), _run_info()
    value = {name: _reader(name).reduce(trace, info)
             for name in PART_READERS + ("attn_ms",)}
    in_layer = tr.per_step(sp.part_time(
        sp.by_part(trace), ("layer",), ("layout",)), 1)
    unsplit = value["attn_unsplit_pct"] / 100 * value["attn_ms"]
    assert (value["attn_proj_ms"] + value["attn_layout_ms"] - in_layer
            + value["attn_core_ms"] + unsplit) == pytest.approx(
                value["attn_ms"])
