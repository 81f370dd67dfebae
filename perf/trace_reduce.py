"""From the profiler's trace to numbers: the one reduction every PR uses.

``load`` turns an ``.xplane.pb`` (read with ``jax.profiler.ProfileData``,
nothing but JAX) into a plain dictionary,

    {"devices": {"0": {"ops":     [[name, shape, start_ns, end_ns], ...],
                       "modules": [[name, start_ns, end_ns], ...]}, ...},
     "host": [[name, start_ns, end_ns], ...]}

and everything else here is arithmetic on lists of intervals, so it can
be checked on lists written by hand (tests/perf/test_trace_reduce.py) and
on a recorded trace kept in that form.

What the v5e's trace looks like (read by hand, PR 22): one plane
``/device:TPU:<n>`` per chip.  Its line ``XLA Modules`` has one event per
execution of a jitted program, named ``jit_<function>(<fingerprint>)``.
Its line ``XLA Ops`` has one event per HLO operation, named by the whole
text of the instruction (``%fusion.648 = bf16[32,128,1280]{...} fusion(
...``), of which ``load`` keeps the instruction's name and the shape it
produces; a Mosaic kernel's instruction is named after the kernel
(``flash_fwd.19``).  The events carry no ``op_name``, so the program's
named scopes (``attn``, ``mlp``) do NOT reach the trace.  The events are
NESTED: a ``while`` (every ``lax.scan``) spans the operations of its
body.  Only operations that contain no other count as running; a
container's own time is the loop's bookkeeping between them.  An
asynchronous operation is a ``-start`` and a ``-done`` instruction with
compute between them (the line ``Async XLA Ops`` shows the whole span);
the transfer lasts from the one's start to the other's end, and only the
two instructions themselves hold the core.  A synchronous collective
(``reduce_scatter.83``, ``all-gather.724``) holds the core for as long as
it lasts: all of it is exposed.  Host spans written with
``jax.profiler.TraceAnnotation`` land on the ``/host:CPU`` plane on the
same clock, to within a fraction of a millisecond.
"""

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
# Prefix of the benchmark's own host spans (perf/run.py writes them).
SPAN_PREFIX = "perf."
# ``%name = shape opcode(operands)``: the name, and the shape with its
# layouts (``{2,1,0:T(8,128)(2,1)S(1)}``) taken out.
INSTRUCTION = re.compile(r"^%?(\S+) = (.*?) [a-z][\w\-]*\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
# Instruction names of collectives, ``_`` read as ``-``: XLA names some
# after the JAX primitive (``reduce_scatter.83``), and one it made
# asynchronous itself ``async-collective-start`` / ``-done`` whatever its
# kind.
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
               "collective-permute", "all-to-all", "async-collective")


# ---------------------------------------------------------------------- #
# the trace as plain data
# ---------------------------------------------------------------------- #
def load(path):
    """The plain dictionary of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        chip = DEVICE_PLANE.match(plane.name)
        if chip:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        name, shape = instruction(e.name)
                        dev["ops"].append([name, shape, e.start_ns,
                                           e.start_ns + e.duration_ns])
                elif line.name == MODULES_LINE:
                    dev["modules"] = [
                        [e.name, e.start_ns, e.start_ns + e.duration_ns]
                        for e in line.events]
            out["devices"][chip.group(1)] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [
                    [e.name, e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
    for dev in out["devices"].values():
        dev["ops"].sort(key=lambda o: (o[2], -o[3]))
        dev["modules"].sort(key=lambda m: m[1])
    out["host"].sort(key=lambda s: s[1])
    return out


def instruction(text):
    """(name, shape produced) of an ``XLA Ops`` event's text."""
    m = INSTRUCTION.match(text)
    if m is None:
        return text.lstrip("%"), ""
    return m.group(1), LAYOUT.sub("", m.group(2))


def describe(path, events=4):
    """Planes, lines and the first events of a trace, with their stats:
    what to read by hand before trusting ``load``."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        lines.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:events]:
                lines.append(f"    {e.name!r} start={e.start_ns} "
                             f"dur={e.duration_ns} {dict(e.stats)}")
    return "\n".join(lines)


# ---------------------------------------------------------------------- #
# interval arithmetic; an interval is (start, end), half open
# ---------------------------------------------------------------------- #
def union(intervals):
    """Disjoint, sorted intervals covering the same points."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [tuple(m) for m in merged]


def measure(intervals):
    """Total length of the union."""
    return sum(end - start for start, end in union(intervals))


def subtract(intervals, covered):
    """The parts of ``intervals`` no interval of ``covered`` covers."""
    out, covered = [], union(covered)
    for start, end in union(intervals):
        at = start
        for c0, c1 in covered:
            if c1 <= at:
                continue
            if c0 >= end:
                break
            if c0 > at:
                out.append((at, c0))
            at = max(at, c1)
        if at < end:
            out.append((at, end))
    return out


# ---------------------------------------------------------------------- #
# device operations
# ---------------------------------------------------------------------- #
def leaves(ops):
    """The operations that contain no other: what actually ran.  ``ops``
    is sorted by (start, -end), as ``load`` leaves it.  Events of no
    length (markers the runtime writes at an operation's start) neither
    run nor make the operation around them a container."""
    ops = [op for op in ops if op[3] > op[2]]
    out = []
    for op, nxt in zip(ops, ops[1:] + [None]):
        # a child starts inside its parent and ends no later
        if nxt is None or not (nxt[2] < op[3] and nxt[3] <= op[3]
                               and (nxt[2], nxt[3]) != (op[2], op[3])):
            out.append(op)
    return out


def spans(ops):
    return [(op[2], op[3]) for op in ops]


def busy_intervals(dev):
    return union(spans(leaves(dev["ops"])))


def window_of(trace):
    """[first operation's start, last operation's end) over all chips."""
    ops = [op for dev in trace["devices"].values() for op in dev["ops"]]
    if not ops:
        return None
    return (min(op[2] for op in ops), max(op[3] for op in ops))


def busiest(trace):
    """Key of the chip that ran operations for longest."""
    return max(trace["devices"], key=lambda k: measure(
        busy_intervals(trace["devices"][k])), default=None)


def busiest_chip(trace):
    """That chip's {"ops", "modules"}, or None for a trace without one."""
    key = busiest(trace)
    return None if key is None else trace["devices"][key]


def matching(ops, pattern):
    """Leaf operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [op for op in leaves(ops) if rx.search(op[0])]


def kernel_time(ops, pattern):
    """(summed duration in ns, number of events) of the matching leaves."""
    hit = matching(ops, pattern)
    return sum(op[3] - op[2] for op in hit), len(hit)


def collective_kind(name):
    """('all-gather', 'start' | 'done' | 'sync') or None."""
    name = name.replace("_", "-")
    for kind in COLLECTIVES:
        if name.startswith(kind):
            rest = name[len(kind):]
            if rest.startswith("-start"):
                return kind, "start"
            if rest.startswith("-done"):
                return kind, "done"
            return kind, "sync"
    return None


def collectives(ops):
    """(transfers, holds) of a chip's operations.  ``transfers``: the
    interval each collective lasts, an asynchronous one from its
    ``-start`` to the end of the ``-done`` that follows it (first in,
    first out within a kind).  ``holds``: the intervals in which a
    collective operation itself occupies the core."""
    transfers, holds, open_starts = [], [], defaultdict(list)
    for op in leaves(ops):
        kind = collective_kind(op[0])
        if kind is None:
            continue
        holds.append((op[2], op[3]))
        if kind[1] == "start":
            open_starts[kind[0]].append(op[2])
        elif kind[1] == "done" and open_starts[kind[0]]:
            transfers.append((open_starts[kind[0]].pop(0), op[3]))
        else:
            transfers.append((op[2], op[3]))
    return transfers, holds


def exposed(transfers, others):
    """Length of ``transfers`` during which nothing of ``others`` runs."""
    return measure(subtract(transfers, others))


def compute_intervals(ops):
    """Leaf operations that are not collectives."""
    return spans([op for op in leaves(ops)
                  if collective_kind(op[0]) is None])


def module_time(dev, pattern):
    """(summed ns, executions) of the modules whose name matches."""
    rx = re.compile(pattern)
    hit = [m for m in dev["modules"] if rx.search(m[0])]
    return sum(m[2] - m[1] for m in hit), len(hit)


def module_gaps(dev):
    """Idle intervals between one program's end and the next one's
    start: the device waiting for the host to hand it work."""
    return subtract([(dev["modules"][0][1], dev["modules"][-1][2])],
                    [(m[1], m[2]) for m in dev["modules"]]
                    ) if dev["modules"] else []


def per_step(total_ns, steps):
    """Nanoseconds over a window into milliseconds a step."""
    return total_ns / steps / 1e6


# ---------------------------------------------------------------------- #
# what a traced run reports beside its metrics
# ---------------------------------------------------------------------- #
def device_busy(trace):
    """(busy seconds averaged over the chips, window seconds)."""
    window = window_of(trace)
    if window is None:
        return 0.0, 0.0
    busy = [measure(busy_intervals(d)) for d in trace["devices"].values()]
    return sum(busy) / len(busy) / 1e9, (window[1] - window[0]) / 1e9


def breakdown(trace, top=10, gaps=5):
    """The operations of the busiest chip that took most time, by name,
    and its longest idle gaps, each under the benchmark's host span that
    covers most of it (``none`` where no span does).  Seconds."""
    dev = busiest_chip(trace)
    if dev is None:
        return {"device_ops": [], "idle_gaps": []}
    by_name = defaultdict(float)
    for op in leaves(dev["ops"]):
        by_name[f"{op[0]} {op[1]}"[:96].strip()] += (op[3] - op[2]) / 1e9
    idle = subtract([window_of(trace)], busy_intervals(dev))
    named = []
    for start, end in sorted(idle, key=lambda g: g[0] - g[1])[:gaps]:
        cover = defaultdict(float)
        for name, s0, s1 in trace["host"]:
            cover[name] += max(0, min(end, s1) - max(start, s0))
        best = max(cover, key=cover.get, default=None)
        named.append([best[len(SPAN_PREFIX):] if best and cover[best] > 0
                      else "none", (end - start) / 1e9])
    return {"device_ops": [[n, s] for n, s in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": named}


def trim(trace, window, chips=None):
    """The events of ``trace`` that lie inside ``window``: how the
    recorded trace under tests/perf/data was cut."""
    lo, hi = window
    out = {"devices": {}, "host": [s for s in trace["host"]
                                   if s[1] >= lo and s[2] <= hi]}
    for key, dev in trace["devices"].items():
        if chips is not None and key not in chips:
            continue
        out["devices"][key] = {
            "ops": [o for o in dev["ops"] if o[2] >= lo and o[3] <= hi],
            "modules": [m for m in dev["modules"]
                        if m[1] >= lo and m[2] <= hi]}
    return out


def main(argv):
    """``describe <xplane.pb>`` prints what a trace holds;
    ``dump <xplane.pb> <out.json.gz>`` writes the plain dictionary."""
    import gzip
    import json
    if len(argv) == 2 and argv[0] == "describe":
        print(describe(argv[1]))
    elif len(argv) == 3 and argv[0] == "dump":
        with gzip.open(argv[2], "wt") as f:
            json.dump(load(argv[1]), f)
    else:
        raise SystemExit(main.__doc__)


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
