"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is one of the family's
``TIED_TABLE_SCOPES`` (``embed`` and ``head``) in a program whose head is
the embedding's own table: the gather of the rows and its transpose (the
embedding's gradient), the final norm, the chunked projection onto the
table's rows with the fused cross-entropy and both of its gradients,
every pass; the two gradients of the ONE table land on one leaf.
Nothing where the family names no such scopes or the program gives no
scope map."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    scopes = getattr(run["family"], "TIED_TABLE_SCOPES", None)
    times = pt.scoped(trace) if scopes else None
    if times is None:
        return None
    total = sum(pt.scope_time(times, scope=scope) for scope in scopes)
    return tr.per_step(total, run["steps_traced"]) if total else None
