"""Per optimizer step, device time of latent attention around its
kernels: the operations of scope ``attn`` in the parts the family lists
(``LATENT_PARTS``: ``latent``, the two down-projections and the latents'
norms; ``qkv``, the two up-projections; ``rotary``; ``layout``, the head
transposes, the broadcast of the one rotated key and the joins; ``out``),
every pass and the prediction module's block included, busiest chip
(perf/scope_parts.py ``by_part``): what the latent path costs around the
attention call.  A fusion takes the part of its root, so the split
between these parts is XLA's; their sum is not.  Nothing where the
family lists no such parts or the program names none."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    parts = getattr(run["family"], "LATENT_PARTS", None)
    times = sp.by_part(trace) if parts else None
    if times is None:
        return None
    return tr.per_step(sp.part_time(times, ("attn",), parts),
                       run["steps_traced"])
