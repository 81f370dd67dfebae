"""Executions of a jitted program per optimizer step, on the chip that
has most of them (one-device programs run on the first chip only): the
grad program, the apply program, and whatever else the engine's step
loop launches around them."""

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "count", "step_ms_p50",
                              "device_trace")


def reduce(trace, run):
    most = max((len(d["modules"]) for d in trace["devices"].values()),
               default=0)
    return most / run["steps_traced"] if most else None
