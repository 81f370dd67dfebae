"""Per optimizer step, device time of the multi-token-prediction module
on the busiest chip: the leaf operations whose ``op_name`` path lies
under the family's ``MTP_REGION`` (scope ``mtp``: the module's norms and
projection, its block with the block's own ``attn`` / ``router`` /
``experts``, its pass over the shared head), every pass
(``deepspeed_tpu/profiling/scope_map.py`` ``live_regions``, joined to
the trace as ``perf/program_trace.py`` ``by_scope`` joins the scopes).
Nothing where the family names no region or the program has no such
map."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def regions():
    """{program: {instruction: (region,)}} of this process's engines, or
    {} where the program has no such door."""
    try:
        from deepspeed_tpu.profiling import scope_map
    except ImportError:
        return {}
    maps = getattr(scope_map, "live_regions", dict)()
    return {program: {name: (region,) for name, region in tags.items()}
            for program, tags in maps.items()}


def reduce(trace, run):
    region = getattr(run["family"], "MTP_REGION", None)
    maps = regions() if region else {}
    times = pt.by_scope(trace, maps) if maps else {}
    if not any(program in maps for program in times):
        return None
    return tr.per_step(sum(tags.get((region,), 0)
                           for tags in times.values()),
                       run["steps_traced"])
