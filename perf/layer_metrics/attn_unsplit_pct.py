"""Share of scope ``attn``'s device time in NO part: how much of the
split of attention is missing, as ``scope_unattributed_pct`` says it one
level up.  Prints, on an earlier line, ``attn`` by part and pass in ms
per optimizer step, whose sum is ``attn_ms``, and apart from it the
``layout`` work that lies in scope ``layer``.  Nothing where the program
names no part or the trace shows no attention."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = sp.by_part(trace)
    total = times and sp.part_time(times, ("attn",))
    if not total:
        return None
    steps = run["steps_traced"]
    print(f"attn by part and pass, ms a step: "
          f"{sp.table(times, ('attn',), sp.ATTN_PARTS, steps)}; sum "
          f"{tr.per_step(total, steps):.3f}; in scope layer: "
          f"{sp.table(times, ('layer',), ('layout',), steps)}", flush=True)
    return 100.0 * sp.part_time(times, ("attn",), (None,)) / total
