"""Seconds the backend compiled during set-up: ``backend_s`` of the
compile requests with outcome ``compiled`` or ``uncached`` that began
before the newest engine was steady (``deepspeed_tpu.monitor.trace``'s
compile record; perf/wait_trace.py).  0 on a warm run; nothing where the
program keeps no such record."""

from perf import wait_trace as wt

LAYER, UNIT, MOVES, SOURCE = ("entry", "s", "setup_s",
                              "program_counter")


def reduce(trace, run):
    return wt.setup_sum(*wt.program_record(), wt.compile_s)
