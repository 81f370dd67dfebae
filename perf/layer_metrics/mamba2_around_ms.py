"""Per optimizer step, device time of a Mamba-2 mixer's work on the
vector unit around its scan: the operations of scope ``ssm`` in the
parts the family lists (``SSM_AROUND_PARTS``: ``conv``, the depthwise
causal conv over x, B and C with its bias and silu; ``gate``, the gated
RMSNorm over all the channels), every pass, busiest chip
(perf/scope_parts.py ``by_part``).  A fusion takes the part of its root,
so the split between these parts and their neighbours is XLA's.  Nothing
where the family lists no such parts or the program names none (a
program from before the parts of ``ssm`` existed)."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    parts = getattr(run["family"], "SSM_AROUND_PARTS", None)
    times = sp.by_part(trace) if parts else None
    if times is None:
        return None
    total = sp.part_time(times, ("ssm",), parts)
    return tr.per_step(total, run["steps_traced"]) if total else None
