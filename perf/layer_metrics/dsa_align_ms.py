"""Per optimizer step, device time of the indexer's alignment term: the
operations of scope ``attn`` in the parts the family lists
(``DSA_ALIGN_PARTS``: ``align``, the KL of the main attention's mean
probabilities on the kept keys against the indexer's softmax over them,
and the indexer's gradients of it), every pass, busiest chip
(perf/scope_parts.py ``by_part``): what training the indexer costs
beside the attention it serves.  Nothing where the family lists no such
parts or the program names none."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    parts = getattr(run["family"], "DSA_ALIGN_PARTS", None)
    times = sp.by_part(trace) if parts else None
    if times is None:
        return None
    return tr.per_step(sp.part_time(times, ("attn",), parts),
                       run["steps_traced"])
