"""Seconds of tracing and lowering during set-up, which no cache
saves: ``trace_s + lower_s`` of every compile request that began before
the newest engine was steady (perf/wait_trace.py).  Nothing where the
program keeps no compile record."""

from perf import wait_trace as wt

LAYER, UNIT, MOVES, SOURCE = ("entry", "s", "setup_s",
                              "program_counter")


def reduce(trace, run):
    return wt.setup_sum(*wt.program_record(), wt.trace_lower_s)
