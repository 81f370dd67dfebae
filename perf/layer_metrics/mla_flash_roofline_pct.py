"""Latent attention's kernel calls' share of their roofline on the
busiest chip: the least time the chip could take for the attention
kernel calls the trace shows (the family's ``flash_call_cost``: the
mathematics of the call, 20 heads with scores and values at 256, causal
at half the square, whatever kernel implements it; the larger of the
compute and the HBM bound at the published peaks of perf/peaks.py) over
the time the calls took.  A later kernel that keeps the rotated key
unbroadcast or fuses the rotation is read against the same work.
Nothing where the family has no such count or the kernels did not run."""

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
    for kernel in family.FLASH_KERNELS:
        # the name, then the trace's own suffix (".3") or nothing
        ns, calls = tr.kernel_time(
            dev["ops"], "^" + re.escape(kernel) + r"(\.\d+)?$")
        seconds, _ = flops.roofline_seconds(
            *family.flash_call_cost(kernel, run["config"], run["job"]),
            run["peak"])
        least += calls * seconds
        taken += ns / 1e9
    return 100.0 * least / taken if taken else None
