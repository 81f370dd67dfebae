"""Per optimizer step, the host time inside the engine's ``*.dispatch``
spans (``ds.forward.dispatch``, ``ds.backward.dispatch``, ``ds.step.
dispatch``): how long the host sat inside the calls of the step
programs.  Near a whole step while a call blocks until the device has
caught up; near nothing once the host runs ahead.  With
``engine_host_ms`` it sums to the three outer spans' durations."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "ms", "step_ms_p50",
                              "program_span")


def reduce(trace, run):
    spans = pt.read()["spans"]
    if not spans:
        return None
    return tr.per_step(pt.engine_times(spans)[1], run["steps_traced"])
