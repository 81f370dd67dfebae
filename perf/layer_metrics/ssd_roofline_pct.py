"""The chunked scan's kernels' share of their roofline on the busiest
chip: the least time the chip could take for the calls the trace shows
(the family's ``ssd_call_cost``: the MATHEMATICS of a call at the
configuration's own chunk, whatever implements it; the larger of the
compute bound at the MXU's bf16 peak and the HBM bound at the published
peaks of perf/peaks.py, for each kernel by its own name) over the time
the calls took.  A name the family does not cost is credited nothing and
its time still counts.  The kernels' masks, exponentials and running
sums run on the vector unit, which has no published peak, so the figure
is a floor on what is left to win, as for ``sscan_roofline_pct``.
Nothing where the family has no such count or the kernels did not run."""

from perf import flops
from perf.layer_metrics.ssd_ms import calls

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    family = run["family"]
    found = calls(trace, run) if hasattr(family, "ssd_call_cost") else None
    if found is None:
        return None
    least = taken = 0.0
    for kernel, (ns, events) in found.items():
        seconds, _ = flops.roofline_seconds(
            *family.ssd_call_cost(kernel, run["config"], run["job"]),
            run["peak"])
        least += events * seconds
        taken += ns / 1e9
    return 100.0 * least / taken if taken else None
