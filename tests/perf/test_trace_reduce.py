"""The reduction from trace to numbers, on interval lists written by hand
and on a trace recorded on the v5e (tests/perf/data, cut from PR 22's
first chip trace by ``trace_reduce.trim``)."""

import gzip
import json
from pathlib import Path

import pytest

from perf import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def op(name, start, end, scope=""):
    return [name, scope, start, end]


def device(ops, modules=()):
    return {"ops": sorted(ops, key=lambda o: (o[2], -o[3])),
            "modules": sorted(modules, key=lambda m: m[1])}


def test_union_measure_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]
    assert tr.measure([(0, 10), (2, 3), (8, 12)]) == 12
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 15)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert tr.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []


def test_only_operations_that_contain_no_other_count_as_running():
    # a while spanning 0..100 with two body operations and a hole between
    dev = device([op("while.1", 0, 100), op("fusion.1", 10, 30),
                  op("fusion.2", 50, 90), op("copy.3", 100, 120)])
    assert [o[0] for o in tr.leaves(dev["ops"])] == [
        "fusion.1", "fusion.2", "copy.3"]
    assert tr.busy_intervals(dev) == [(10, 30), (50, 90), (100, 120)]
    # nested twice, and a child that starts with its parent
    deep = device([op("while.1", 0, 100), op("while.2", 0, 60),
                   op("dot.1", 0, 20), op("dot.2", 70, 80)])
    assert [o[0] for o in tr.leaves(deep["ops"])] == ["dot.1", "dot.2"]


def test_idle_share_and_busy_seconds_over_the_chips():
    trace = {"devices": {
        "0": device([op("a", 0, 40), op("b", 60, 100)]),
        "1": device([op("a", 0, 20), op("b", 80, 100)])}, "host": []}
    assert tr.window_of(trace) == (0, 100)
    assert tr.busiest(trace) == "0"
    busy_s, window_s = tr.device_busy(trace)
    assert (busy_s, window_s) == (60e-9, 100e-9)
    empty = {"devices": {}, "host": []}
    assert tr.window_of(empty) is None and tr.device_busy(empty) == (0, 0)


def test_kernel_time_by_name():
    dev = device([
        op("flash_fwd.7", 0, 10, "(bf16[4,20,1024,64], f32[4,20,1024,8])"),
        op("fusion.3", 10, 14), op("fusion.4", 14, 20),
        op("flash_bwd_dq.9", 20, 50, "bf16[4,20,1024,64]"),
        op("while.1", 60, 100), op("flash_fwd.2", 70, 75)])
    assert tr.kernel_time(dev["ops"], "flash_fwd") == (15, 2)
    assert tr.kernel_time(dev["ops"], "flash_fwd|flash_bwd_dq") == (45, 3)
    assert tr.kernel_time(dev["ops"], "flash_bwd_dkdv") == (0, 0)


def test_instruction_name_and_shape_from_the_event_text():
    assert tr.instruction(
        "%fusion.648 = bf16[32,128,1280]{2,1,0:T(8,128)(2,1)S(1)} fusion("
        "bf16[32,128,1280]{2,1,0:T(8,128)(2,1)S(1)} %get-tuple-element.2079"
        "), kind=kOutput, calls=%fused_computation.431") == (
        "fusion.648", "bf16[32,128,1280]")
    assert tr.instruction(
        "%flash_fwd.19 = (bf16[4,20,1024,64]{3,2,1,0:T(8,128)(2,1)}, "
        "f32[4,20,1024,8]{3,2,1,0:T(8,128)}) custom-call(s32[1]{0:T(128)} "
        "%bitcast.266), custom_call_target=\"tpu_custom_call\"") == (
        "flash_fwd.19", "(bf16[4,20,1024,64], f32[4,20,1024,8])")
    assert tr.instruction("%all-gather-start.3 = (bf16[8]{0}, bf16[32]{0}) "
                          "all-gather-start(bf16[8]{0} %p)")[0] == \
        "all-gather-start.3"
    assert tr.instruction("something else") == ("something else", "")


def test_collectives_hidden_and_exposed():
    dev = device([
        op("all-gather-start.1", 0, 2), op("fusion.1", 2, 30),
        op("all-gather-done.1", 30, 40),          # waits 10: exposed
        op("all-gather-start.2", 40, 41), op("all-gather-start.3", 41, 42),
        op("fusion.2", 42, 60),
        op("all-gather-done.2", 60, 61), op("all-gather-done.3", 61, 62),
        op("reduce-scatter.5", 70, 90),           # synchronous: all exposed
        op("fusion.3", 90, 100)])
    transfers, holds = tr.collectives(dev["ops"])
    # first in, first out within a kind
    assert transfers == [(0, 40), (40, 61), (41, 62), (70, 90)]
    assert tr.measure(holds) == 2 + 10 + 1 + 1 + 1 + 1 + 20
    assert tr.measure(transfers) == 62 + 20
    others = tr.compute_intervals(dev["ops"])
    assert others == [(2, 30), (42, 60), (90, 100)]
    assert tr.exposed(transfers, others) == (2 + 10) + (2 + 2) + 20
    assert tr.collective_kind("all-reduce-start.12") == ("all-reduce",
                                                         "start")
    assert tr.collective_kind("collective-permute.1") == (
        "collective-permute", "sync")
    assert tr.collective_kind("reduce_scatter.83") == ("reduce-scatter",
                                                       "sync")
    assert tr.collective_kind("async-collective-done.4") == (
        "async-collective", "done")
    assert tr.collective_kind("fusion.1") is None
    assert tr.collective_kind("convert_reduce_fusion.2") is None


def test_per_step_grouping_by_program_executions():
    modules = [["jit_loss_and_grads(1)", 0, 80], ["jit_apply_step(2)", 85, 95],
               ["jit_loss_and_grads(1)", 100, 180],
               ["jit_apply_step(2)", 190, 200]]
    dev = device([op("fusion.1", 0, 80)], modules)
    assert tr.module_time(dev, "apply_step") == (20, 2)
    assert tr.module_time(dev, "loss_and_grads") == (160, 2)
    assert tr.module_gaps(dev) == [(80, 85), (95, 100), (180, 190)]
    assert tr.per_step(tr.measure(tr.module_gaps(dev)), 2) == 10 / 1e6
    assert tr.module_gaps(device([])) == []


def test_breakdown_names_operations_and_what_the_host_did_in_each_gap():
    trace = {"devices": {"0": device([
        op("fusion.1", 0, 50), op("fusion.2", 50, 60), op("fusion.1", 100, 150),
        op("copy.1", 170, 175)])},
        "host": [["perf.forward", 55, 98], ["perf.step", 98, 101],
                 ["perf.make_batch", 150, 152]]}
    got = tr.breakdown(trace, top=2, gaps=2)
    assert got["device_ops"] == [["fusion.1", 100e-9], ["fusion.2", 10e-9]]
    shaped = {"devices": {"0": device([op("dot.1", 0, 5, "bf16[8,8]")])},
              "host": []}
    assert tr.breakdown(shaped)["device_ops"] == [["dot.1 bf16[8,8]", 5e-9]]
    assert got["idle_gaps"] == [["forward", 40e-9], ["make_batch", 20e-9]]
    assert tr.breakdown({"devices": {}, "host": []}) == {
        "device_ops": [], "idle_gaps": []}


def test_trim_keeps_what_lies_inside_the_window():
    trace = {"devices": {"0": device([op("a", 0, 10), op("b", 20, 30)],
                                     [["m", 0, 10], ["m", 20, 30]]),
                         "1": device([op("a", 0, 10)])},
             "host": [["perf.step", 1, 2], ["perf.step", 25, 40]]}
    cut = tr.trim(trace, (15, 35), chips=("0",))
    assert cut == {"devices": {"0": {"ops": [op("b", 20, 30)],
                                     "modules": [["m", 20, 30]]}},
                   "host": []}


@pytest.fixture(scope="module")
def recorded():
    """One optimizer step of gpt2-large.s1024 on one v5e chip (PR 22's
    first chip trace), times in ns from the cut's start."""
    with gzip.open(DATA / "gpt2-large.s1024.one-step.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_step_programs_and_gaps(recorded):
    dev = recorded["devices"]["0"]
    assert [m[0].split("(")[0] for m in dev["modules"]] == [
        "jit_reshape", "jit__threefry_split_foldlike", "jit_transpose",
        "jit_reshape", "jit__unstack", "jit_loss_and_grads",
        "jit_apply_step"]
    assert tr.module_time(dev, "loss_and_grads") == (247927272, 1)
    assert tr.module_time(dev, "apply_step") == (33068953, 1)
    assert tr.measure(tr.module_gaps(dev)) == 18356
    assert tr.busiest(recorded) == "0"


def test_recorded_step_operations_and_kernels(recorded):
    ops = recorded["devices"]["0"]["ops"]
    assert len(ops) == 12840
    containers = {tuple(o) for o in ops if o[3] > o[2]} - {
        tuple(o) for o in tr.leaves(ops)}
    # the forward and the backward layer scans, and nothing else
    assert sorted(c[0] for c in containers) == ["while.13", "while.14"]
    # 36 layers: the forward kernel runs twice (recomputation), each
    # backward kernel once
    assert tr.kernel_time(ops, "flash_fwd") == (32604515, 72)
    assert tr.kernel_time(ops, "flash_bwd_dkdv") == (22378797, 36)
    assert tr.kernel_time(ops, "flash_bwd_dq") == (14787388, 36)
    assert tr.collectives(ops) == ([], [])
    busy = tr.measure(tr.busy_intervals(recorded["devices"]["0"]))
    window = tr.window_of(recorded)
    assert busy == 280898682 and window == (1003, 281016999)
    # inside one step the chip is never left waiting for long
    assert 1 - busy / (window[1] - window[0]) < 0.001


def test_recorded_step_through_the_cell_readers(recorded):
    """The readers of perf/layer_metrics on the recorded step, with the
    cell's own shapes."""
    from perf import run
    from perf.families import gpt2
    from perf.peaks import peaks
    root = str(Path(__file__).resolve().parents[2])
    info = {"steps_traced": 1, "chips": 1, "family": gpt2,
            "peak": peaks("TPU v5 lite"),
            "config": run.read_json(root, "configs", "gpt2-large"),
            "job": {"batch_per_chip": 4, "seq": 1024}}

    def read(name):
        return run.load_module(root, "layer_metrics", name).reduce(
            recorded, info)

    assert read("dispatches_per_step") == 7
    assert read("apply_ms") == pytest.approx(33.068953)
    assert read("host_gap_ms") == pytest.approx(0.018356)
    assert read("flash_ms") == pytest.approx(69.7707)
    # 72 x 2 + 36 x 4 + 36 x 3 products of 4 x 20 x 1024^2 x 64 FLOPs
    # (causal: half of 2 x that) at 197 TFLOP/s are 10.8 ms of the 69.8
    assert read("flash_roofline_pct") == pytest.approx(15.468, abs=0.001)
    assert read("device_idle_pct") == pytest.approx(0.0417, abs=1e-3)
    assert read("collective_ms") == 0 and read("collective_exposed_ms") == 0
