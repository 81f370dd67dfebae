"""Per optimizer step, device time of part ``cast`` in the grad program:
the engine's casts of the fp32 weights to the compute dtype, once a
micro-batch, and their transposes (runtime/engine.py ``_cast_weights``),
outside every scope of the model.  Busiest chip (perf/scope_parts.py
``by_part``).  Nothing where the program names no part."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "ms", "step_ms_p50",
                              "device_trace")


def reduce(trace, run):
    times = sp.by_part(trace)
    if times is None:
        return None
    return tr.per_step(
        sp.part_time(times, ("other",), ("cast",),
                     program=run["family"].GRAD_PROGRAM),
        run["steps_traced"])
