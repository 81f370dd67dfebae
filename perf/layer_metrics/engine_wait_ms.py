"""Per optimizer step over the traced steps, the host time under
``ds.forward.await_loss``: the engine blocked on the last loss before a
launch (a busy device: the host has nothing else to do).  0.0 where the
engine never waited; nothing where the program writes no span."""

from perf import wait_trace as wt

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "ms", "step_ms_p50",
                              "program_span")


def reduce(trace, run):
    return wt.step_part("wait", run)
