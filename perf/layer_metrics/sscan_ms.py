"""Per step, device time of the selective scan's kernels (forward, and
the backward pass's rebuild-and-walk-back) on the busiest chip.  Nothing
where the family names no such kernels or the trace shows none."""

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    kernels = getattr(run["family"], "SSCAN_KERNELS", None)
    if dev is None or not dev["ops"] or not kernels:
        return None
    total, calls = tr.kernel_time(dev["ops"], "|".join(kernels))
    return tr.per_step(total, run["steps_traced"]) if calls else None
