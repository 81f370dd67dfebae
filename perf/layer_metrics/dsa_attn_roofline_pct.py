"""The restricted attention's kernel calls' share of their roofline on
the busiest chip: the least time the chip could take for the calls the
trace shows (the family's ``dsa_attn_call_cost``: the MATHEMATICS of a
call, the pairs each query keeps, ``sum_t min(t + 1, topk)`` a head,
with scores and values at the head's width, whatever the kernel
computes; the larger of the compute and the HBM bound at the published
peaks of perf/peaks.py) over the time the calls took.  A kernel that
computes every tile up to the diagonal reads the share of the pairs
that are kept times its own efficiency; one that skips empty tiles or
gathers the kept keys is read against the same work.  Nothing where the
family has no such count or the kernels did not run."""

import re

from perf import flops
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"
KERNELS, COST = "DSA_ATTN_KERNELS", "dsa_attn_call_cost"


def share(trace, run, kernels, cost):
    """100 x least / taken over the family's ``kernels`` by its
    ``cost``; None where either is missing or no call ran."""
    dev = tr.busiest_chip(trace)
    family = run["family"]
    if dev is None or not hasattr(family, cost):
        return None
    least = taken = 0.0
    for kernel in getattr(family, kernels):
        # the name, then the trace's own suffix (".3") or nothing
        ns, calls = tr.kernel_time(
            dev["ops"], "^" + re.escape(kernel) + r"(\.\d+)?$")
        seconds, _ = flops.roofline_seconds(
            *getattr(family, cost)(kernel, run["config"], run["job"]),
            run["peak"])
        least += calls * seconds
        taken += ns / 1e9
    return 100.0 * least / taken if taken else None


def reduce(trace, run):
    return share(trace, run, KERNELS, COST)
