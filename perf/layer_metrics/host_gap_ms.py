"""Per step, the time the busiest chip spent between the end of one
program and the start of the next with nothing to run: the device
waiting for the host's next dispatch."""

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "ms", "step_ms_p50",
                              "device_trace")


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    if dev is None or not dev["modules"]:
        return None
    return tr.per_step(tr.measure(tr.module_gaps(dev)),
                       run["steps_traced"])
