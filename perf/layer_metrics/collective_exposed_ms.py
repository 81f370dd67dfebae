"""Per step, the part of ``collective_ms`` during which no other
operation ran on that chip: communication the schedule did not hide."""

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = ("ZeRO placement and streamed ZeRO-3", "ms",
                              "step_ms_p50", "device_trace")


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    if dev is None:
        return None
    ops = dev["ops"]
    transfers, _ = tr.collectives(ops)
    return tr.per_step(tr.exposed(transfers, tr.compute_intervals(ops)),
                       run["steps_traced"])
