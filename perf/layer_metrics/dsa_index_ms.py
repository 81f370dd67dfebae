"""Per optimizer step, device time of the learned indexer and its
selection: the operations of scope ``attn`` in the parts the family
lists (``DSA_INDEX_PARTS``: ``index``, the indexer's three projections,
its LayerNorm, its rotation and the index-score product; ``select``,
each query's k-th largest score and the packed keep-set), every pass,
busiest chip (perf/scope_parts.py ``by_part``): what choosing the keys
costs before the restricted attention runs.  Nothing where the family
lists no such parts or the program names none."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    parts = getattr(run["family"], "DSA_INDEX_PARTS", None)
    times = sp.by_part(trace) if parts else None
    if times is None:
        return None
    return tr.per_step(sp.part_time(times, ("attn",), parts),
                       run["steps_traced"])
