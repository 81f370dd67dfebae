"""The selective scan kernels' share of their roofline on the busiest
chip: the least time the chip could take for the calls the trace shows
(operations and bytes from the family's ``sscan_call_cost``: the larger
of the HBM bound and the compute bound at the published peaks of
perf/peaks.py, for each kernel) over the time the calls took.  The
recurrence runs on the vector unit, which has no published peak; its
operations are set against the MXU's bf16 peak, so at these shapes the
HBM bound is the larger and the figure is a floor on what is left to
win: a kernel at 100% could not be faster, one at 10% is not thereby
ten times too slow.  Nothing where the kernels did not run."""

from perf import flops
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    family = run["family"]
    if dev is None or not hasattr(family, "sscan_call_cost"):
        return None
    least = taken = 0.0
    for kernel in family.SSCAN_KERNELS:
        ns, calls = tr.kernel_time(dev["ops"], kernel)
        seconds, _ = flops.roofline_seconds(
            *family.sscan_call_cost(kernel, run["config"], run["job"]),
            run["peak"])
        least += calls * seconds
        taken += ns / 1e9
    return 100.0 * least / taken if taken else None
