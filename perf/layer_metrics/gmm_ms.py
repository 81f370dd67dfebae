"""Per step, device time of the grouped product's kernels (rows times an
expert's weights, the same on the transposed weights, the per-expert
x^T dy) on the busiest chip.  Nothing where the family names no such
kernels or the trace shows none."""

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    kernels = getattr(run["family"], "GMM_KERNELS", None)
    if dev is None or not dev["ops"] or not kernels:
        return None
    total, calls = tr.kernel_time(
        dev["ops"], "^(" + "|".join(kernels) + r")(\.\d+)?$")
    return tr.per_step(total, run["steps_traced"]) if calls else None
