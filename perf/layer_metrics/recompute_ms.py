"""Per optimizer step, device time of the leaf operations of the busiest
chip that run the forward pass again inside the backward pass
(``rematted_computation`` on the instruction's ``op_name``), whatever
their scope: what activation checkpointing costs on the device.
Nothing where the program gives no scope map."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = pt.scoped(trace)
    if times is None:
        return None
    return tr.per_step(pt.scope_time(times, phase="recompute"),
                       run["steps_traced"])
