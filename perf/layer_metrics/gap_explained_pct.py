"""Of the idle time between programs on the busiest chip (the intervals
``host_gap_ms`` sums), the share lying under a leaf ``ds.*`` span of the
program: how much of the device's waiting the program's own spans
account for.  Prints, on an earlier line, the idle time per optimizer
step under each leaf span: which statement the device waits for.
Nothing where the program writes no such span."""

from perf import program_trace as pt
from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = ("engine step loop", "%", "step_ms_p50",
                              "program_span")


def reduce(trace, run):
    spans, dev = pt.read()["spans"], tr.busiest_chip(trace)
    if not spans or dev is None or not dev["modules"]:
        return None
    idle, under, outside = pt.gap_attribution(dev, spans)
    if not idle:
        return None
    steps = run["steps_traced"]
    table = {name: round(tr.per_step(ns, steps), 4) for name, ns in
             sorted(under.items(), key=lambda kv: -kv[1])}
    print(f"idle between programs by leaf span, ms a step: {table}; under "
          f"no ds.* span {tr.per_step(outside, steps):.4f} of "
          f"{tr.per_step(idle, steps):.4f}", flush=True)
    return 100.0 * (idle - outside) / idle
