"""Per optimizer step, the engine's own host time: the durations of its
``ds.forward``, ``ds.backward`` and ``ds.step`` spans minus their
``*.dispatch`` children.  Python and eager work of the step loop
(``ds.forward.prepare``, ``.shard_batch``, ``.rng``, ``ds.step.
bookkeeping`` and what lies between them), on the profiler's clock.
Nothing where the program writes no such span."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "ms", "step_ms_p50",
                              "program_span")


def reduce(trace, run):
    spans = pt.read()["spans"]
    if not spans:
        return None
    return tr.per_step(pt.engine_times(spans)[0], run["steps_traced"])
