"""The grouped product's share of its roofline on the busiest chip: the
least time the chip could take for the calls the trace shows (operations
and bytes from the family's ``gmm_call_cost`` on the rows the routing
really sent, by the program's counter; the larger of the compute and the
HBM bound at the published peaks of perf/peaks.py, for each kernel) over
the time the calls took.  The rows a call multiplies and throws away (a
tile that two experts share, the worst-case rows past the routed ones)
are in the time and not in the count.  Nothing where the kernels did not
run, and nothing where no counter says how many rows were routed."""

import re

from perf import flops
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"


def routed_rows(run):
    """Rows one call multiplies: tokens x picks x the share the counter
    says landed on the held experts; None without the counter."""
    config, job = run["config"], run["job"]
    share = (run["family"].routing_counters() or {}).get("held_pick_share")
    if not share:
        return None
    return (job["batch_per_chip"] * job["seq"]
            * config["num_experts_per_tok"] * share)


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    family = run["family"]
    if dev is None or not hasattr(family, "gmm_call_cost"):
        return None
    rows = routed_rows(run)
    if rows is None:
        return None
    least = taken = 0.0
    for kernel in family.GMM_KERNELS:
        ns, calls = tr.kernel_time(
            dev["ops"], "^" + re.escape(kernel) + r"(\.\d+)?$")
        seconds, _ = flops.roofline_seconds(
            *family.gmm_call_cost(kernel, run["config"], run["job"], rows),
            run["peak"])
        least += calls * seconds
        taken += ns / 1e9
    return 100.0 * least / taken if taken else None
