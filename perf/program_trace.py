"""What the program itself wrote into the profiler's trace, for the
per-layer readers: its spans on the host plane and, through the compiled
programs' text, the named scope and the pass of every device operation.

``perf/trace_reduce.py`` ``load`` keeps the benchmark's own ``perf.*``
host spans and no event's stats, and ``perf/run.py`` hands a reader
``(reduced, run)`` with neither the trace's path nor the engine.  So this
module finds the run's ``.xplane.pb`` itself: the newest under
``<root>/.perf_trace/`` (``run.py`` clears and rewrites the cell's
directory on every traced run), read once per process.

    read() -> {"spans": [[name, start_ns, end_ns, stats], ...],
               "maps":  {program: {instruction: (scope, phase)}}}

``spans`` are the events named ``ds.*`` (``deepspeed_tpu/monitor/
trace.py`` ``span``) of the host thread that has most of them, sorted by
(start, -end); a span's parent is the span that contains it.  ``maps``
come from ``deepspeed_tpu.profiling.scope_map.live()``, which lowers the
engine's step programs again (the compile cache serves them): after the
window, part of no metric.  A program without such spans or without that
module gives empty ones, and every reader built on them returns None.

The rest is arithmetic on lists of intervals, checked on lists written
by hand and on a recorded trace (tests/perf/test_program_trace.py).  The
interval functions are ``trace_reduce``'s; none is copied.
"""

import bisect
import functools
import glob
import os
import re
from collections import defaultdict

from perf import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_PREFIX = "ds."
DISPATCH = ".dispatch"
# the engine's calls of the modular loop; their ``*.dispatch`` children
# are the calls of the step programs
OUTER = ("ds.forward", "ds.backward", "ds.step")
NO_TAG = ("other", "forward")


# ---------------------------------------------------------------------- #
# the trace file
# ---------------------------------------------------------------------- #
def newest_xplane(root=None):
    """Path of the newest ``.xplane.pb`` under ``<root>/.perf_trace``,
    or None."""
    found = glob.glob(os.path.join(root or ROOT, ".perf_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load_spans(path):
    """The ``ds.*`` events of the host thread that has most of them."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            lines.append([
                [e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)]
                for e in line.events if e.name.startswith(SPAN_PREFIX)])
    spans = max(lines, key=len, default=[])
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def live_maps():
    """{program: {instruction: (scope, phase)}} of the engines alive in
    this process; empty where the program has no scope map."""
    try:
        from deepspeed_tpu.profiling import scope_map
    except ImportError:
        return {}
    return scope_map.live()


@functools.lru_cache(maxsize=2)
def _read(path):
    return {"spans": load_spans(path), "maps": live_maps()}


def read():
    """Spans and maps of this run's trace (see the module's text)."""
    path = newest_xplane()
    return _read(path) if path else {"spans": [], "maps": {}}


# ---------------------------------------------------------------------- #
# spans
# ---------------------------------------------------------------------- #
def interval(span):
    return (span[1], span[2])


def children(spans):
    """For each span of ``spans`` (sorted by (start, -end)), the spans
    directly inside it, as lists of indices."""
    inside, stack = [[] for _ in spans], []
    for i, span in enumerate(spans):
        while stack and spans[stack[-1]][2] <= span[1]:
            stack.pop()
        if stack and span[2] <= spans[stack[-1]][2]:
            inside[stack[-1]].append(i)
        stack.append(i)
    return inside


def self_time(spans):
    """{name: ns}: each span's duration minus what its child spans
    cover, summed by name."""
    out = defaultdict(int)
    for span, kids in zip(spans, children(spans)):
        out[span[0]] += tr.measure(tr.subtract(
            [interval(span)], [interval(spans[k]) for k in kids]))
    return dict(out)


def leaf_spans(spans):
    """The spans that contain no other."""
    return [s for s, kids in zip(spans, children(spans)) if not kids]


def engine_times(spans):
    """(host ns, dispatch ns) of the engine's calls: the time inside the
    ``*.dispatch`` spans, and the rest of ``ds.forward`` + ``ds.backward``
    + ``ds.step``.  The two sum to the three outer spans' durations."""
    calls = [s for s in spans if s[0] in OUTER or s[0].endswith(DISPATCH)]
    own = self_time(calls)
    host = sum(own.get(name, 0) for name in OUTER)
    dispatch = sum(ns for name, ns in own.items() if name.endswith(DISPATCH))
    return host, dispatch


def covered(intervals, by):
    """Length of ``intervals`` that lies under ``by``."""
    return tr.measure(intervals) - tr.measure(tr.subtract(intervals, by))


def gap_attribution(dev, spans):
    """(idle ns between programs, {leaf span name: idle ns under it},
    idle ns under no leaf span) for one chip's {"ops", "modules"}: which
    statement of the program the device waits for."""
    gaps = tr.module_gaps(dev)
    by_name = defaultdict(list)
    for span in leaf_spans(spans):
        by_name[span[0]].append(interval(span))
    under = {name: covered(gaps, ivs) for name, ivs in by_name.items()}
    everything = [iv for ivs in by_name.values() for iv in ivs]
    return (tr.measure(gaps), {n: v for n, v in under.items() if v},
            tr.measure(tr.subtract(gaps, everything)))


# ---------------------------------------------------------------------- #
# device operations by program, scope and pass
# ---------------------------------------------------------------------- #
def program_of(module_name):
    """``jit_loss_and_grads(1234)`` -> ``jit_loss_and_grads``."""
    return module_name.split("(", 1)[0]


def by_scope(reduced, maps):
    """{program: {(scope, phase): ns}} over the leaf operations of the
    busiest chip.  An operation belongs to the program execution whose
    interval holds its start (instruction names repeat between
    programs, so the program comes first), then to what that program's
    map says of its instruction; ``("other", "forward")`` where the map
    or the program is missing."""
    dev = tr.busiest_chip(reduced)
    out = defaultdict(lambda: defaultdict(int))
    if dev is None or not dev["modules"]:
        return {}
    modules = sorted(dev["modules"], key=lambda m: m[1])
    starts = [m[1] for m in modules]
    for name, _, start, end in tr.leaves(dev["ops"]):
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= modules[at][2]:
            continue  # outside every program: not the step's work
        program = program_of(modules[at][0])
        out[program][maps.get(program, {}).get(name, NO_TAG)] += end - start
    return {p: dict(tags) for p, tags in out.items()}


def scope_time(times, scope=None, phase=None, program=None):
    """ns of ``by_scope``'s result in ``scope`` and ``phase`` (None: any)
    over the programs whose name matches ``program`` (None: all)."""
    rx = re.compile(program) if program else None
    return sum(ns for name, tags in times.items()
               if rx is None or rx.search(name)
               for (s, p), ns in tags.items()
               if scope in (None, s) and phase in (None, p))


def scoped(reduced):
    """``by_scope`` of this run, or None where the program gave no map
    of a program the trace shows."""
    maps = read()["maps"]
    times = by_scope(reduced, maps) if maps else {}
    return times if any(p in maps for p in times) else None
