"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is ``mlp``, in every pass (forward,
recomputed forward, backward): each operation is given to the program
execution that holds it, then to the scope the compiled program's text
names for its instruction (perf/program_trace.py ``by_scope``).
Nothing where the program gives no scope map."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = pt.scoped(trace)
    if times is None:
        return None
    return tr.per_step(pt.scope_time(times, scope="mlp"),
                       run["steps_traced"])
