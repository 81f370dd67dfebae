"""Per optimizer step, device time of part ``stack`` in the grad
program: what a layer scan does to its own stacked operands, outside
every scope: the slices of stacked weights and saved carries, the
updates of kept residuals and gradient stacks (instructions whose
``op_name`` ends directly in a ``while`` body with one of the four
primitives a scan writes there: ``scope_map.STACK_OPS``).  Prints, on an earlier line, scope ``other``
of the grad program by part and pass in ms per optimizer step, whose sum
is the numerator of ``scope_unattributed_pct``.  Busiest chip
(perf/scope_parts.py ``by_part``).  Nothing where the program names no
part."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = sp.by_part(trace)
    if times is None:
        return None
    grad, steps = run["family"].GRAD_PROGRAM, run["steps_traced"]
    other = sp.part_time(times, ("other",), program=grad)
    print(f"other by part and pass, ms a step: "
          f"{sp.table(times, ('other',), sp.OTHER_PARTS, steps, grad)}; "
          f"sum {tr.per_step(other, steps):.3f}", flush=True)
    return tr.per_step(
        sp.part_time(times, ("other",), ("stack",), program=grad), steps)
