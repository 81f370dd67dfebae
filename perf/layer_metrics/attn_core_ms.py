"""Per optimizer step, device time of part ``core`` of scope ``attn``:
the call of the attention itself, which is the Pallas kernels and what
the wrapper puts around them (padding, log-sum-exp, dropout seeds), so
``attn_core_ms - flash_ms`` is the wrapper's; below ``AUTO_MIN_SEQ`` it
is the whole XLA path (scores, mask, softmax, dropout, values).  Every
pass, busiest chip (perf/scope_parts.py ``by_part``).  Nothing where the
program names no part."""

from perf import scope_parts as sp
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = sp.by_part(trace)
    if times is None:
        return None
    return tr.per_step(sp.part_time(times, ("attn",), ("core",)),
                       run["steps_traced"])
