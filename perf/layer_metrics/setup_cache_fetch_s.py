"""Seconds spent reading compiled programs out of the persistent
cache during set-up: ``fetch_s`` of the compile requests with outcome
``fetched`` that began before the newest engine was steady
(perf/wait_trace.py).  Nothing where the program keeps no compile
record."""

from perf import wait_trace as wt

LAYER, UNIT, MOVES, SOURCE = ("entry", "s", "setup_s",
                              "program_counter")


def reduce(trace, run):
    return wt.setup_sum(*wt.program_record(), wt.fetch_s)
