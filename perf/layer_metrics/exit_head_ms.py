"""Per optimizer step, device time of the exit work of a stack run
several times on the same weights, on the busiest chip: the leaf
operations whose ``op_name`` path lies under the family's
``EXIT_REGION`` (scope ``exit``: the final norm after every pass, the
exit gate, the passes over the head, the exit distribution and its KL
term), every pass of differentiation
(``deepspeed_tpu/profiling/scope_map.py`` ``live_regions``, joined to
the trace as ``perf/program_trace.py`` ``by_scope`` joins the scopes).
Nothing where the family names no region or the program has no such
map (``mtp_ms.py``'s ``regions``, the reader this one is modelled
on)."""

from perf import program_trace as pt
from perf import trace_reduce as tr
from perf.layer_metrics.mtp_ms import regions

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    region = getattr(run["family"], "EXIT_REGION", None)
    maps = regions() if region else {}
    times = pt.by_scope(trace, maps) if maps else {}
    if not any(program in maps for program in times):
        return None
    return tr.per_step(sum(tags.get((region,), 0)
                           for tags in times.values()),
                       run["steps_traced"])
