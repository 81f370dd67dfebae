"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is the family's ``ROUTER_SCOPE``
(``router``) in a program whose router is an MLP that carries a state
from layer to layer (models/zaya.py): the float32 down-projection, the
carried state's scale and sum, the norm, the three products with their
exact gelus, the softmax, the biased choice and the counts, every pass.
Nothing where the family names no such scope, the program gives no scope
map or the map names none."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    scope = getattr(run["family"], "ROUTER_SCOPE", None)
    times = pt.scoped(trace) if scope else None
    if times is None:
        return None
    total = pt.scope_time(times, scope=scope)
    return tr.per_step(total, run["steps_traced"]) if total else None
