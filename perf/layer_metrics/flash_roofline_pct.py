"""The flash kernels' share of their roofline on the busiest chip: the
least time the chip could take for the calls the trace shows (FLOPs and
bytes from the cell's shapes by perf/flops.py, causal at half, over the
published peaks, whichever bound is the larger for each kernel) over the
time the calls took.  Nothing where the kernels did not run."""

from perf import flops
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    if dev is None:
        return None
    shape = run["family"].flash_operand(run["config"], run["job"])
    least = taken = 0.0
    for kernel in run["family"].FLASH_KERNELS:
        ns, calls = tr.kernel_time(dev["ops"], kernel)
        seconds, _ = flops.roofline_seconds(
            flops.flash_call_flops(kernel, *shape),
            flops.flash_call_bytes(kernel, *shape), run["peak"])
        least += calls * seconds
        taken += ns / 1e9
    return 100.0 * least / taken if taken else None
