"""Per optimizer step, device time of compressed convolutional
attention's mixing on the busiest chip: the operations of scope ``attn``
in the parts the family lists (``CCA_PARTS``: ``mix``, the q-k mean, the
two causal convs over the sequence and the value shift; ``qk_norm``, the
unit norm of every head and the keys' learned temperature), every pass
(perf/scope_parts.py ``by_part``): what lies between the latent
projections and the rotation.  A fusion takes the part of its root, so
the split between the two parts is XLA's; their sum is not.  Nothing
where the family lists no such parts or the program names none."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    parts = getattr(run["family"], "CCA_PARTS", None)
    times = sp.by_part(trace) if parts else None
    if times is None:
        return None
    total = sp.part_time(times, ("attn",), parts)
    return tr.per_step(total, run["steps_traced"]) if total else None
