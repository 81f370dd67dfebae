"""The mixing's share of its roofline on the busiest chip: the least
time the chip could take for the applications the trace shows (the
family's ``cca_mix_cost``: the MATHEMATICS of one layer's mixing and
unit norm in a pass, 1,536 bf16 channels a token read and written
forward and recomputed, three such arrays read and one and a half
written backward, and the products of the conv within a head; the larger
of the HBM and the compute bound at the published peaks of
perf/peaks.py; ``cca_calls_per_step`` layers a step in every pass that
shows any time) over the time the parts ``mix`` and ``qk_norm`` of scope
``attn`` took (``cca_mix_ms``'s).  Counted by part, so an XLA fusion and
a kernel are read against the same work.  The means, the depthwise conv
and the norms run on the vector unit, which has no published peak, so
the figure is a floor on what is left to win.  Nothing where the family
has no such count or the parts took no time."""

from perf import flops
from perf import scope_parts as sp

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    family = run["family"]
    times = sp.by_part(trace) if hasattr(family, "cca_mix_cost") else None
    if times is None:
        return None
    calls = run["steps_traced"] * family.cca_calls_per_step(run["config"])
    least = taken = 0.0
    for phase in sp.PHASES:
        ns = sp.part_time(times, ("attn",), family.CCA_PARTS, phase)
        if ns:
            seconds, _ = flops.roofline_seconds(
                *family.cca_mix_cost(phase, run["config"], run["job"]),
                run["peak"])
            least += calls * seconds
            taken += ns / 1e9
    return 100.0 * least / taken if taken else None
