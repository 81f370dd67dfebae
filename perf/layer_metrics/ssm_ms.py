"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is ``ssm`` (a Mamba mixer: its
projections, the conv, the selective scan, the gate), in every pass; it
contains ``sscan_ms``.  Nothing where the program gives no scope map or
names no such scope (a program from before the scope existed)."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = pt.scoped(trace)
    if times is None:
        return None
    total = pt.scope_time(times, scope="ssm")
    return tr.per_step(total, run["steps_traced"]) if total else None
