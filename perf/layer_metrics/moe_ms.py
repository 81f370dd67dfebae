"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is one of a sparse FFN's four (the
family's ``MOE_SCOPES``: ``router``, ``dispatch``, ``experts``,
``shared``), in every pass; it contains ``gmm_ms``.  Nothing where the
family names no such scopes, the program gives no scope map, or the map
names none of them (a program from before the scopes existed)."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    scopes = getattr(run["family"], "MOE_SCOPES", None)
    times = pt.scoped(trace) if scopes else None
    if times is None:
        return None
    total = sum(pt.scope_time(times, scope=scope) for scope in scopes)
    return tr.per_step(total, run["steps_traced"]) if total else None
