"""The flash kernels' share of their roofline on the busiest chip in a
cell whose calls are not all alike: the least time the chip could take
for the calls the trace shows, each kernel name counted by its own mask
(a ``*_band`` call over the band of the window, a plain causal one over
half the square; grouped key/value heads at their own size; the family's
``flash_call_cost``, the larger of the compute and the HBM bound at the
published peaks), over the time the calls took.  Nothing where the
family has no such count or the kernels did not run."""

import re

from perf import flops
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    family = run["family"]
    if dev is None or not hasattr(family, "flash_call_cost"):
        return None
    least = taken = 0.0
    for base in family.FLASH_KERNELS:
        for kernel in (base, base + family.BAND):
            # the name, then the trace's own suffix (".3") or nothing
            ns, calls = tr.kernel_time(
                dev["ops"], "^" + re.escape(kernel) + r"(\.\d+)?$")
            seconds, _ = flops.roofline_seconds(
                *family.flash_call_cost(kernel, run["config"], run["job"]),
                run["peak"])
            least += calls * seconds
            taken += ns / 1e9
    return 100.0 * least / taken if taken else None
