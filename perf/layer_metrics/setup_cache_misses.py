"""Programs compiled and written to the persistent cache during
set-up: the compile requests with outcome ``compiled`` (a miss of a
cache that is on) that began before the newest engine was steady
(perf/wait_trace.py).  0 on a warm run; nothing where the program keeps
no compile record."""

from perf import wait_trace as wt

LAYER, UNIT, MOVES, SOURCE = ("entry", "count", "setup_s",
                              "program_counter")


def reduce(trace, run):
    return wt.setup_sum(*wt.program_record(), wt.missed)
