"""Share of the grad program's busy time on the busiest chip whose
operations carry none of the model's named scopes (``other``): how much
of the scope map is missing.  Prints, on an earlier line, the grad
program's time per optimizer step by scope and pass, whose sum is that
program's busy time.  Nothing where the program gives no scope map."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "model", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    times = pt.scoped(trace)
    if times is None:
        return None
    grad, steps = run["family"].GRAD_PROGRAM, run["steps_traced"]
    dev = tr.busiest_chip(trace)
    executions = [(m[1], m[2]) for m in dev["modules"]
                  if grad in pt.program_of(m[0])]
    busy = pt.covered(executions, tr.busy_intervals(dev))
    total = pt.scope_time(times, program=grad)
    if not total:
        return None
    table = {f"{scope}.{phase}": round(tr.per_step(ns, steps), 3)
             for name, tags in times.items() if grad in name
             for (scope, phase), ns in sorted(tags.items())}
    print(f"grad program by scope and pass, ms a step: {table}; sum "
          f"{tr.per_step(total, steps):.3f}, busy "
          f"{tr.per_step(busy, steps):.3f}", flush=True)
    return 100.0 * pt.scope_time(times, scope="other", program=grad) / total
