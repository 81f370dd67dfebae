"""Per optimizer step over the traced steps, the engine's own Python
and eager work: what ``engine_host_ms`` reads, less the time under
``ds.forward.await_loss``, ``ds.monitor.*`` and ``ds.launch.first``
(perf/wait_trace.py ``host_parts``).  Nothing where the program writes
no span."""

from perf import wait_trace as wt

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "ms", "step_ms_p50",
                              "program_span")


def reduce(trace, run):
    return wt.step_part("python", run)
