"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is ``ssm`` in a program whose mixers
are Mamba-2 (its projections, the conv, the chunked scan, the gated
norm), in every pass; it contains ``ssd_ms`` and ``mamba2_around_ms``.
The reading ``ssm_ms`` takes in the Mamba-1 cell, under a name of this
cell's own until a benchmark PR appends the cell to that metric's list.
Nothing where the program gives no scope map or names no such scope."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = pt.scoped(trace)
    if times is None:
        return None
    total = pt.scope_time(times, scope="ssm")
    return tr.per_step(total, run["steps_traced"]) if total else None
