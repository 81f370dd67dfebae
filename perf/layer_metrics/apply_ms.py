"""Per step, device time of the engine's optimizer-apply program on the
busiest chip."""

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = ("optimizer apply", "ms", "step_ms_p50",
                              "device_trace")


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    if dev is None:
        return None
    total, count = tr.module_time(dev,
                                  run["family"].APPLY_PROGRAM)
    return tr.per_step(total, run["steps_traced"]) if count else None
