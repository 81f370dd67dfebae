"""Seconds inside the newest engine's ``ds.initialize`` span: the
config, the mesh, the placement of the parameters, the optimizer's
state, the recomputation plan, the step programs (docs/telemetry.md
names its leaves).  Nothing where the program writes no such span."""

from perf import wait_trace as wt

LAYER, UNIT, MOVES, SOURCE = ("entry", "s", "setup_s",
                              "program_span")


def reduce(trace, run):
    return wt.initialize_s(wt.program_record()[1])
