"""Per optimizer step, device time of the projections of attention: the
operations of scope ``attn`` in part ``qkv`` (the input projection or
projections, their bias, the split into q, k and v) or ``out`` (the
output projection, its bias, dropout and residual), every pass, busiest
chip (perf/scope_parts.py ``by_part``).  Nothing where the program names
no part."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = sp.by_part(trace)
    if times is None:
        return None
    return tr.per_step(sp.part_time(times, ("attn",), ("qkv", "out")),
                       run["steps_traced"])
