"""One level under the scope map: the device time of a traced run by
(scope, part, pass), for the readers of the parts of ``attn`` and of
scope ``other``.

A part is a second name the program writes inside one of its scopes
(``deepspeed_tpu/profiling/scope_map.py`` ``PARTS``: ``qkv``, ``rotary``,
``layout``, ``core``, ``gate``, ``diff``, ``out`` inside ``attn``,
``layout`` in ``layer`` too; ``cast`` and ``stack`` outside every
scope).  ``scope_map.live_parts()`` gives {program: {instruction: part
or None}} from the same compiled texts as the scope maps.

    by_part(reduced) -> {program: {(scope, part, phase): ns}}

on the busiest chip, an operation given to its program execution and
then to its instruction's tags by ``program_trace.by_scope`` itself: the
maps handed to it carry (scope, part, phase) where its own carry (scope,
phase), so summing a scope's parts gives ``by_scope``'s number for the
scope.  A fusion takes the tags of its root: a transpose that XLA fuses
into a projection or into a kernel's operand copy is counted there, so
part ``layout`` is a lower bound.  A program without ``live_parts`` (one
from before the parts were named) gives None, and so does every reader
built on this.
"""

import functools
import re

from perf import program_trace as pt
from perf import trace_reduce as tr

ATTN_PARTS = ("qkv", "rotary", "layout", "core", "gate", "diff", "out", None)
OTHER_PARTS = ("cast", "stack", None)
PHASES = ("forward", "recompute", "backward")


@functools.lru_cache(maxsize=2)
def _live_parts(path):
    try:
        from deepspeed_tpu.profiling import scope_map
    except ImportError:
        return {}
    return getattr(scope_map, "live_parts", dict)()


def joined(maps, parts):
    """{program: {instruction: (scope, part, phase)}} of the programs
    both maps know."""
    return {program: {name: (scope, parts[program].get(name), phase)
                      for name, (scope, phase) in tags.items()}
            for program, tags in maps.items() if program in parts}


def by_part(reduced, maps=None, parts=None):
    """See the module's text.  ``maps`` and ``parts`` default to this
    run's (the program's scope and part maps, lowered once a process);
    None where the program names no part."""
    if maps is None:
        maps = pt.read()["maps"]
        parts = _live_parts(pt.newest_xplane())
    both = joined(maps, parts or {})
    times = pt.by_scope(reduced, both) if both else {}
    if not any(program in both for program in times):
        return None
    # by_scope's tag for an operation no map names has no part
    return {program: {(t[0], t[1] if len(t) == 3 else None, t[-1]): ns
                      for t, ns in tags.items()}
            for program, tags in times.items() if program in both}


def part_time(times, scopes, parts=None, phase=None, program=None):
    """ns of ``by_part``'s result in one of ``scopes``, one of ``parts``
    (None: any; a None among them: in no part) and ``phase`` (None:
    any), over the programs whose name matches ``program`` (None:
    all)."""
    rx = re.compile(program) if program else None
    return sum(ns for name, tags in times.items()
               if rx is None or rx.search(name)
               for (s, part, p), ns in tags.items()
               if s in scopes and (parts is None or part in parts)
               and phase in (None, p))


def table(times, scopes, parts, steps, program=None):
    """{"part.pass": ms a step} of the cells that are not empty, the
    time in no part written ``none``."""
    cells = {f"{part or 'none'}.{phase}": part_time(
        times, scopes, (part,), phase, program)
        for part in parts for phase in PHASES}
    return {key: round(tr.per_step(ns, steps), 3)
            for key, ns in cells.items() if ns}
