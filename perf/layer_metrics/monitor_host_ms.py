"""Per optimizer step over the traced steps, the host time under
``ds.monitor.record`` and ``ds.monitor.flush`` (a flush lies inside the
record that filled the window): what telemetry costs the step loop.
0.0 with no monitor on; nothing where the program writes no span."""

from perf import wait_trace as wt

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "ms", "step_ms_p50",
                              "program_span")


def reduce(trace, run):
    return wt.step_part("monitor", run)
