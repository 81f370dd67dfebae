"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is ``hc`` (a hyper-connection,
``deepspeed_tpu/ops/hyper_connection.py``: the norm of the streams, the
projection to the mixes, the Sinkhorn rounds, the sublayer's input read
from the streams and its output written back, the sum of the streams
after the last layer), in every pass: what the residual path of several
streams costs around its sublayers.  Nothing where the program gives no
scope map or names no such scope."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"
SCOPE = "hc"


def reduce(trace, run):
    times = pt.scoped(trace)
    if times is None:
        return None
    total = pt.scope_time(times, scope=SCOPE)
    return tr.per_step(total, run["steps_traced"]) if total else None
