"""Share of the traced window in which no operation ran on the busiest
chip: gaps between programs and stalls inside them."""

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "device", "%", "tokens_per_s", "device_trace"


def reduce(trace, run):
    dev, window = tr.busiest_chip(trace), tr.window_of(trace)
    if dev is None or window is None:
        return None
    busy = tr.measure(tr.busy_intervals(dev))
    return 100.0 * (1.0 - busy / (window[1] - window[0]))
