"""Per optimizer step, device time of part ``layout``: the reshapes and
transposes between ``[B, S, H * D]`` and the kernels' ``[B, H, S, D]``,
any repeat of grouped key/value heads, and the context's way back, which
ops/transformer.py writes between two ``attn`` blocks, in scope ``layer``
(counted here, and in ``layer`` for every other reader).  Every pass,
busiest chip (perf/scope_parts.py ``by_part``).  A lower bound: a
transpose that XLA fuses into a projection or into a kernel's operand
copy takes that fusion's part.  Nothing where the program names no
part."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = sp.by_part(trace)
    if times is None:
        return None
    return tr.per_step(sp.part_time(times, ("attn", "layer"), ("layout",)),
                       run["steps_traced"])
