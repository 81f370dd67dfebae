"""Per step, the time collectives (all-gather, reduce-scatter,
all-reduce, collective-permute, all-to-all) were under way on the
busiest chip, an asynchronous one from its start to its done, hidden
behind compute or not."""

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = ("ZeRO placement and streamed ZeRO-3", "ms",
                              "step_ms_p50", "device_trace")


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    if dev is None:
        return None
    transfers, _ = tr.collectives(dev["ops"])
    return tr.per_step(tr.measure(transfers), run["steps_traced"])
