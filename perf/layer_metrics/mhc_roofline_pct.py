"""The hyper-connections' share of their roofline on the busiest chip:
the least time the chip could take for the applications the trace shows
(the family's ``mhc_call_cost``: the MATHEMATICS of one sublayer's
hyper-connection in a pass, (3 n + 2) widths of bf16 a token forward and
recomputed, (6 n + 3) backward, and the projection's FLOPs; the larger
of the HBM and the compute bound at the published peaks of
perf/peaks.py; ``mhc_calls_per_step`` sublayers a step in every pass
that shows any time) over the time scope ``hc`` took (``mhc_ms``'s).
Counted by scope, so an XLA fusion and a kernel are read against the
same work.  The Sinkhorn rounds run on the vector unit, which has no
published peak, so the figure is a floor on what is left to win.
Nothing where the family has no such count or the scope took no time."""

from perf import flops
from perf import program_trace as pt
from perf.layer_metrics.mhc_ms import SCOPE

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    family = run["family"]
    times = pt.scoped(trace) if hasattr(family, "mhc_call_cost") else None
    if times is None:
        return None
    calls = run["steps_traced"] * family.mhc_calls_per_step(run["config"])
    least = taken = 0.0
    for phase in ("forward", "recompute", "backward"):
        ns = pt.scope_time(times, scope=SCOPE, phase=phase)
        if ns:
            seconds, _ = flops.roofline_seconds(
                *family.mhc_call_cost(phase, run["config"], run["job"]),
                run["peak"])
            least += calls * seconds
            taken += ns / 1e9
    return 100.0 * least / taken if taken else None
