"""Per step, device time of the chunked state-space scan's kernels on
the busiest chip: every leaf operation whose name starts with the
family's ``SSD_KERNEL_PREFIX`` (``ssd_``), forward, recomputed and
backward.  Nothing where the family names no such prefix or the trace
shows no such kernel."""

import re

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "ms", "step_ms_p50", "device_trace"


def calls(trace, run):
    """{kernel name: (ns, events)} of the scan's kernels on the busiest
    chip, a name without the trace's own suffix (``.3``); None where
    there is nothing to read."""
    dev = tr.busiest_chip(trace)
    prefix = getattr(run["family"], "SSD_KERNEL_PREFIX", None)
    if dev is None or not dev["ops"] or not prefix:
        return None
    found = {}
    for op in tr.matching(dev["ops"], "^" + re.escape(prefix)):
        name = re.sub(r"\.\d+$", "", op[0])
        ns, events = found.get(name, (0, 0))
        found[name] = (ns + op[3] - op[2], events + 1)
    return found or None


def reduce(trace, run):
    found = calls(trace, run)
    if found is None:
        return None
    return tr.per_step(sum(ns for ns, _ in found.values()),
                       run["steps_traced"])
