"""The program's spans, the record of its compiles, and the Chrome/
Perfetto trace-event exporter.

``span`` is the one way the program marks where its host time goes.  A
span is three things at once: a ``jax.profiler.TraceAnnotation`` named
``ds.<name>``, so it lies on the profiler's clock beside the device's
operations whenever a profiler session is open (and is inert when none
is); one entry ``(name, start_ns, end_ns, ids)`` of the process-wide
bounded collector (``spans()``), on ``time.perf_counter_ns``, whoever
listens; and, through that collector, one event of the monitor's Chrome
export when that is on (``TraceEventBuffer.collect``).

The compile record (``compiles()``) holds one entry per program JAX was
asked to compile from the import of ``deepspeed_tpu`` on: what was
traced, lowered and compiled or fetched from the persistent cache, how
long each took and under which span (``install`` registers the
``jax.monitoring`` listeners; ``explain_compiles`` adds the cache key
and the hashes of its components).

The exporter turns the monitor's host-side timeline — those spans (the
engine's forward / backward / step calls and, inside them, the dispatch
windows of the compiled programs), swap-tier I/O (``InflightGroupRead``/``InflightTensorWrite``
issue→done windows with their exposed-wait tails), and flush boundaries
— into trace-event JSON that chrome://tracing and https://ui.perfetto.dev
open directly.

Semantics caveat, stated once and embedded in the trace metadata: spans
are measured on the HOST with ``time.perf_counter``.  For compiled-step
phases that is the *dispatch* window (XLA executes asynchronously
behind it), which is exactly the timeline that matters for the async
host loop: a phase span that balloons means the host blocked — the
hot-loop-sync failure mode the Program Auditor lints statically.  Swap
I/O spans are real wall windows (issue→completion of the disk read).

Format: the JSON-object form ``{"traceEvents": [...]}`` of the Trace
Event Format; complete events (``ph: "X"``) with microsecond ``ts``/
``dur``, one named tid per lane, thread-name metadata events.
"""

import contextlib
import gc
import json
import logging
import os
import re
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax

from ..utils.logging import log_dist
from .record import SCHEMA_VERSION

# Prefix of every span the program writes into the profiler's trace.
SPAN_PREFIX = "ds."

# lane -> tid (thread_name metadata emitted on first use)
TID_STEP = 1
TID_SWAP_IN = 2
TID_SWAP_OUT = 3
TID_MARKS = 4
TID_MOE = 5

_LANE_NAMES = {TID_STEP: "step phases", TID_SWAP_IN: "swap in (NVMe read)",
               TID_SWAP_OUT: "swap out (NVMe write)", TID_MARKS: "monitor",
               TID_MOE: "moe routing"}

# ---------------------------------------------------------------------- #
# the collector
# ---------------------------------------------------------------------- #
# Closed spans, oldest first, in the order they closed: (name, start_ns,
# end_ns, ids) on time.perf_counter_ns.  About 20 spans a micro-batch, so
# the bound holds some thousands of steps; the oldest fall out.
COLLECTOR_SPANS = 65536
_closed: deque = deque(maxlen=COLLECTOR_SPANS)
# the spans open now, outermost first (the thread that runs the step loop
# opens them; list.append and list.pop need no lock)
_open: list = []
# time.time_ns() - time.perf_counter_ns(), noted once: what lays JAX's own
# time spans and a profiler's events (both on the epoch) on the collector
CLOCK_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


class span:
    """``with span("forward.dispatch", step=3, program="jit_f"):``

    Opens ``jax.profiler.TraceAnnotation("ds." + name, **ids)``: on the
    host plane of a profiler trace the event carries ``ids`` as its
    stats, and spans nest by time on the thread that opened them.  On
    exit the span is appended to the collector.  Always on the code
    path; there is no switch."""

    __slots__ = ("name", "ids", "_annotation", "started_ns", "_leaf")

    def __init__(self, name: str, **ids):
        self.name = SPAN_PREFIX + name
        self.ids = ids
        self._leaf = None

    def __enter__(self, _now=time.perf_counter_ns, _push=_open.append):
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.ids)
        self._annotation.__enter__()
        _push(self)
        self.started_ns = _now()
        return self

    def __exit__(self, *exc, _now=time.perf_counter_ns,
                 _keep=_closed.append):
        if self._leaf is not None:
            self.phase(None)
        end = _now()
        if _open and _open[-1] is self:
            _open.pop()
        elif self in _open:
            # a child left open by an exception goes with its parent
            del _open[_open.index(self):]
        _keep((self.name, self.started_ns, end, self.ids))
        return self._annotation.__exit__(*exc)

    def note(self, **ids):
        """Ids known only once the span is under way (``stalled=1``):
        on the collector's entry, not on the profiler's event, whose
        stats were written when it opened."""
        self.ids = {**self.ids, **ids}

    def phase(self, name: Optional[str], **ids):
        """Closes the leaf the last call opened and, unless ``name`` is
        None, opens ``<this span>.<name>``: consecutive leaves of a long
        function without a ``with`` block each."""
        if self._leaf is not None:
            self._leaf.__exit__(None, None, None)
            self._leaf = None
        if name is not None:
            self._leaf = span(f"{self.name[len(SPAN_PREFIX):]}.{name}",
                              **ids).__enter__()


def phase(of: str, name: Optional[str], **ids) -> None:
    """``span.phase(name)`` on the innermost open span called
    ``ds.<of>``; nothing where none is open (an engine built without
    ``deepspeed_tpu.initialize``)."""
    for s in reversed(_open):
        if s.name == SPAN_PREFIX + of:
            s.phase(name, **ids)
            return


def mark(name: str, **ids) -> None:
    """A point of the collector, ``(ds.<name>, now, now, ids)``: what
    the program counted while it was traced (the streamed stack's
    ``zero3.grad_wire``), under the span open at that moment.  No
    profiler event: there is no stretch of time to show."""
    now = time.perf_counter_ns()
    _closed.append((SPAN_PREFIX + name, now, now, ids))


def last_span() -> tuple:
    """The span that closed last."""
    return _closed[-1]


def spans(since_ns: int = 0) -> List[tuple]:
    """The collector's spans that closed after ``since_ns``, oldest
    first."""
    out = []
    for s in reversed(_closed):
        if s[2] <= since_ns:
            break
        out.append(s)
    out.reverse()
    return out


def open_span():
    """(name of the innermost open span or None, the ``step`` id of the
    innermost open span that has one or None)."""
    if not _open:
        return None, None
    step = next((s.ids["step"] for s in reversed(_open)
                 if "step" in s.ids), None)
    return _open[-1].name, step


def leaf_times(closed: List[tuple], start_ns: int, end_ns: int
               ) -> Dict[str, int]:
    """{name: ns} under the spans of ``closed`` that hold no other,
    clipped to [start_ns, end_ns]."""
    order = sorted(closed, key=lambda s: (s[1], -s[2]))
    out: Dict[str, int] = {}
    for i, (name, a, b, _) in enumerate(order):
        if i + 1 < len(order) and order[i + 1][1] < b:
            continue  # the next span starts inside this one
        ns = min(b, end_ns) - max(a, start_ns)
        if ns > 0:
            out[name] = out.get(name, 0) + ns
    return out


# ---------------------------------------------------------------------- #
# engines: where set-up ends
# ---------------------------------------------------------------------- #
_engines: deque = deque(maxlen=64)


def new_engine() -> Dict[str, Any]:
    """The marks of an engine under construction, kept here so that a
    reader finds the newest engine's: ``initialize_ns`` (start, end) of
    its ``ds.initialize``, ``steady_since_ns`` the end of its first
    optimizer step that launched no program for the first time.  Set-up,
    seen from inside, is everything before the newest engine's mark."""
    marks = {"initialize_ns": None, "steady_since_ns": None}
    _engines.append(marks)
    return marks


def newest_engine() -> Optional[Dict[str, Any]]:
    return _engines[-1] if _engines else None


# ---------------------------------------------------------------------- #
# the compile record
# ---------------------------------------------------------------------- #
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
# a request slower than this gets a log line
COMPILE_LOG_S = 1.0
COMPILE_RECORDS = 4096
# the eight parts of a persistent-cache key, as jax/_src/cache_key.py
# names them in its DEBUG lines
KEY_COMPONENTS = ("computation", "jax_lib version", "backend version",
                  "XLA flags", "compile_options", "accelerator_config",
                  "compression", "custom_hook")
_KEY_LINE = re.compile(r"get_cache_key hash (of serialized|after serializing) "
                       r"(.+): ([0-9a-f]+)$")

_compiles: deque = deque(maxlen=COMPILE_RECORDS)
# what has arrived of the request under way: JAX reports a request's
# tracing, its lowering, the cache's hit or miss and last the backend's
# time, which names the program
_pending: Dict[str, Any] = {}
_gc = {"ns": 0, "started": 0}


def _fresh_pending() -> None:
    _pending.clear()
    _pending.update(traced={}, lowered={}, hit=False, miss=False,
                    fetch_s=0.0, saved_s=0.0, key=None, components={})


def _on_time_span(event, start, end, fun_name=None, **_):
    if event == TRACE_EVENT:
        # the function's own name; an inner jit's tracing arrives first
        # and lies inside the outer's
        _pending["traced"][re.sub(r"\W", "_", str(fun_name))] = (start, end)
    elif event == LOWER_EVENT:
        _pending["lowered"][str(fun_name)] = (start, end)
    elif event == BACKEND_EVENT:
        _close_request(str(fun_name), start, end)


def _on_event(event, **_):
    if event == CACHE_HIT_EVENT:
        _pending["hit"] = True
    elif event == CACHE_MISS_EVENT:
        _pending["miss"] = True


def _on_duration(event, seconds, **_):
    if event == CACHE_FETCH_EVENT:
        _pending["fetch_s"] = float(seconds)
    elif event == CACHE_SAVED_EVENT:
        _pending["saved_s"] = float(seconds)


def _close_request(program: str, start: float, end: float) -> None:
    """The backend's time span closes a request: one record of it."""
    # "jit(apply_step)" here, "jit_apply_step" on the device's trace
    lowered = _pending["lowered"].get(program)
    inner = program.partition("(")[2]
    program = re.sub(r"\W", "_", program).rstrip("_")
    traced = _pending["traced"].get(
        re.sub(r"\W", "_", inner[:-1]) if inner else
        program.split("_", 1)[-1])
    first = min(t[0] for t in (traced, lowered, (start, end)) if t)
    during, step = open_span()
    outcome = ("fetched" if _pending["hit"] else
               "compiled" if _pending["miss"] else "uncached")
    rec = {
        "seq": _compiles[-1]["seq"] + 1,
        "program": program,
        "when_ns": int(first * 1e9) - CLOCK_OFFSET_NS,
        "end_ns": int(end * 1e9) - CLOCK_OFFSET_NS,
        "trace_s": (traced[1] - traced[0]) if traced else 0.0,
        "lower_s": (lowered[1] - lowered[0]) if lowered else 0.0,
        "backend_s": end - start,
        "outcome": outcome,
        "fetch_s": _pending["fetch_s"] if outcome == "fetched" else 0.0,
        "saved_s": _pending["saved_s"] if outcome == "fetched" else 0.0,
        "during": during, "step": step,
    }
    if _pending["key"] is not None:
        rec["key"] = f"{program}-{_pending['key']}"
        rec["key_components"] = dict(_pending["components"])
    _fresh_pending()
    _compiles.append(rec)
    _closed.append((SPAN_PREFIX + "compile", rec["when_ns"], rec["end_ns"],
                    {"program": program, "outcome": outcome}))
    total = rec["trace_s"] + rec["lower_s"] + rec["backend_s"]
    if total > COMPILE_LOG_S:
        log_dist(format_compile_line(rec), ranks=[0])


def format_compile_line(rec: Dict[str, Any]) -> str:
    """``compiled jit_loss_and_grads in 212.4 s (persistent cache MISS;
    traced 6.1 s, lowered 9.8 s) during ds.forward.dispatch, step 1``"""
    verb, cache = {
        "compiled": ("compiled", "persistent cache MISS"),
        "fetched": ("fetched", f"persistent cache HIT, read in "
                               f"{rec['fetch_s']:.1f} s, "
                               f"{rec['saved_s']:.1f} s saved"),
        "uncached": ("compiled", "no persistent cache entry"),
    }[rec["outcome"]]
    where = f" during {rec['during']}" if rec["during"] else ""
    step = f", step {rec['step']}" if rec["step"] is not None else ""
    return (f"{verb} {rec['program']} in {rec['backend_s']:.1f} s ({cache}; "
            f"traced {rec['trace_s']:.1f} s, lowered {rec['lower_s']:.1f} s)"
            f"{where}{step}")


def _on_gc(phase_, info):
    if phase_ == "start":
        _gc["started"] = time.perf_counter_ns()
    elif _gc["started"]:
        _gc["ns"] += time.perf_counter_ns() - _gc["started"]
        _gc["started"] = 0


def gc_ns() -> int:
    """Nanoseconds the collector of garbage has run since ``install``."""
    return _gc["ns"]


def install() -> None:
    """Registers the ``jax.monitoring`` listeners and the ``gc``
    callback, once a process (``import deepspeed_tpu`` calls it), and
    writes the record's first line: how long after the process started
    the import ended."""
    if _pending:
        return
    _fresh_pending()
    import jax.monitoring as monitoring
    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    gc.callbacks.append(_on_gc)
    _compiles.append({"seq": 0, "program": None, "outcome": "imported",
                      "when_ns": time.perf_counter_ns(),
                      "since_process_start_s": _since_process_start()})


def _since_process_start() -> Optional[float]:
    """Seconds since the kernel started this process, by field 22 of
    ``/proc/self/stat``; None where there is no such file."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def compiles(since_seq: int = -1) -> List[Dict[str, Any]]:
    """The compile record past ``since_seq``, oldest first; the first
    line of all (``seq`` 0, outcome ``imported``) is the import's."""
    return [r for r in _compiles if r["seq"] > since_seq]


def compile_count() -> int:
    """Requests recorded so far (the import's line not counted)."""
    return _compiles[-1]["seq"] if _compiles else 0


class _KeyLines(logging.Handler):
    """Takes the cache key's hashes out of cache_key.py's DEBUG lines."""

    def emit(self, record):
        found = _KEY_LINE.search(record.getMessage())
        if not found:
            return
        which, name, digest = found.groups()
        if which == "of serialized":
            _pending["components"][name] = digest
        elif name == KEY_COMPONENTS[-1]:
            _pending["key"] = digest  # the hash after the last part


@contextlib.contextmanager
def explain_compiles():
    """Inside it, every compile record also carries the persistent
    cache's ``key`` and ``key_components``, the hash of each of its
    eight parts: on a miss, the part that differs from the run that
    wrote the entry says why.  Takes them from the DEBUG lines of
    ``jax._src.cache_key``, whose logger is set to DEBUG, cut off from
    its parents (nothing is printed that was not before) and put back
    as it was on the way out.  An operator's call; no key turns it on."""
    log = logging.getLogger("jax._src.cache_key")
    was = (log.level, log.propagate, list(log.handlers))
    handler = _KeyLines(level=logging.DEBUG)
    log.handlers = [handler]
    log.propagate = False
    log.setLevel(logging.DEBUG)
    try:
        yield
    finally:
        log.setLevel(was[0])
        log.propagate = was[1]
        log.handlers = was[2]


# ---------------------------------------------------------------------- #
# the Chrome / Perfetto exporter
# ---------------------------------------------------------------------- #
class TraceEventBuffer:
    """The Chrome export: a writer over the collector (``collect``) plus
    what is no ``ds.*`` span (swap I/O windows, flush marks, counter
    samples); write() emits the JSON file.

    ``max_steps`` bounds the number of optimizer steps traced (a
    long run would otherwise grow the trace without limit); once
    saturated, add calls become no-ops and the truncation is recorded
    in the trace metadata.  ``origin`` (``time.perf_counter`` seconds)
    is the trace's zero: the monitor gives its own construction, and
    spans that opened before it stay out; without one the first event
    added is the origin."""

    def __init__(self, max_steps: int = 128,
                 origin: Optional[float] = None):
        self.max_steps = int(max_steps)
        self.events: List[Dict[str, Any]] = []
        self._t0: Optional[float] = origin
        self._pid = os.getpid()
        self._steps_seen: set = set()
        self._lanes_named: set = set()
        self.truncated = False
        # the collector's spans that closed up to here are in the trace
        self._collected_ns = int((origin or 0.0) * 1e9)
        # whether the last span with a ``step`` id was within the bound:
        # its children, which carry none, go the same way
        self._step_kept = True

    # ------------------------------------------------------------------ #
    @property
    def saturated(self) -> bool:
        return len(self._steps_seen) >= self.max_steps

    def note_untraced_step(self, step: int) -> None:
        """Record that a step happened past the bound (callers stop
        adding spans once saturated, so the buffer learns about
        truncation from this)."""
        if self.saturated and step not in self._steps_seen:
            self.truncated = True

    def note_step(self, step: int) -> bool:
        """Register an optimizer step; False once the bound is hit."""
        if step in self._steps_seen:
            return True
        if self.saturated:
            self.truncated = True
            return False
        self._steps_seen.add(step)
        return True

    def collect(self) -> None:
        """The ``ds.*`` spans that closed since the last call, from the
        collector onto the step lane: the same names and windows a
        profiler session shows.  The monitor calls it at every flush
        boundary and at close, well inside the collector's bound."""
        if self.truncated:
            return
        fresh = spans(self._collected_ns)
        if not fresh:
            return
        self._collected_ns = fresh[-1][2]
        floor = int((self._t0 or 0.0) * 1e9)
        for name, start, end, ids in sorted(fresh,
                                            key=lambda s: (s[1], -s[2])):
            if start < floor:
                continue
            ids = dict(ids)
            step = ids.pop("step", None)
            if step is not None:
                self._step_kept = self.note_step(step)
            if self._step_kept:
                self.add_span(name, start / 1e9, end / 1e9, tid=TID_STEP,
                              step=step, args=ids)

    def _ts(self, t: float) -> float:
        if self._t0 is None:
            self._t0 = t
        return (t - self._t0) * 1e6  # seconds -> microseconds

    def _name_lane(self, tid: int) -> None:
        if tid not in self._lanes_named:
            self._lanes_named.add(tid)
            self.events.append({
                "name": "thread_name", "ph": "M", "pid": self._pid,
                "tid": tid, "args": {"name": _LANE_NAMES.get(tid,
                                                             f"lane{tid}")}})

    # ------------------------------------------------------------------ #
    def add_span(self, name: str, t_start: float, t_end: float,
                 tid: int = TID_STEP, cat: str = "phase",
                 step: Optional[int] = None,
                 args: Optional[Dict[str, Any]] = None) -> None:
        """One complete event from perf_counter timestamps (seconds)."""
        if step is not None and not self.note_step(step):
            return
        self._name_lane(tid)
        ev: Dict[str, Any] = {
            "name": name, "cat": cat, "ph": "X",
            "ts": round(self._ts(t_start), 3),
            "dur": round(max(t_end - t_start, 0.0) * 1e6, 3),
            "pid": self._pid, "tid": tid,
        }
        a = dict(args or {})
        if step is not None:
            a["step"] = step
        if a:
            ev["args"] = a
        self.events.append(ev)

    def add_counter(self, name: str, t: float,
                    values: Dict[str, float],
                    tid: int = TID_MOE) -> None:
        """One counter sample (``ph: "C"`` — Perfetto renders these as
        stacked value tracks).  Used for the per-window MoE routing
        lanes: drop rate and expert-load imbalance sampled at every
        flush boundary.  Absent (None) values are SKIPPED, not zeroed
        — a window that routed nothing must read as a gap in the
        counter track, never as a confident 0.0."""
        args = {k: round(float(v), 6)
                for k, v in values.items() if v is not None}
        if not args:
            return
        self._name_lane(tid)
        self.events.append({
            "name": name, "cat": "counter", "ph": "C",
            "ts": round(self._ts(t), 3), "pid": self._pid, "tid": tid,
            "args": args})

    def add_instant(self, name: str, t: float, tid: int = TID_MARKS,
                    args: Optional[Dict[str, Any]] = None) -> None:
        self._name_lane(tid)
        ev: Dict[str, Any] = {"name": name, "cat": "mark", "ph": "i",
                              "ts": round(self._ts(t), 3), "s": "t",
                              "pid": self._pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def add_swap_read_events(self, events: List[Dict[str, Any]],
                             step: Optional[int] = None) -> None:
        """Spans from the streaming engine's swap-in window accounting
        (zero/infinity.py _swap_events): the issue→done window per group,
        plus an explicit `wait` sub-span for the exposed (caller-blocked)
        tail — serialized swap-ins are visible at a glance."""
        if step is not None and not self.note_step(step):
            return
        for e in events:
            t_issue = e.get("t_issue")
            t_done = e.get("t_done")
            if t_issue is None or t_done is None:
                continue
            self.add_span(
                f"swap_in:{e.get('name', '?')}", t_issue, t_done,
                tid=TID_SWAP_IN, cat="swap_in",
                args={"bytes": e.get("bytes"),
                      "hidden_s": round(e.get("hidden_s") or 0.0, 6),
                      "exposed_s": round(e.get("exposed_s") or 0.0, 6),
                      **({"step": step} if step is not None else {})})
            exposed = e.get("exposed_s") or 0.0
            if exposed > 1e-5:
                self.add_span(
                    f"wait:{e.get('name', '?')}", t_done - exposed, t_done,
                    tid=TID_SWAP_IN, cat="swap_wait",
                    args={"exposed_s": round(exposed, 6)})

    def add_swap_write_events(self, events: List[Dict[str, Any]],
                              step: Optional[int] = None) -> None:
        """Spans from write-side handles (InflightTensorWrite /
        PartitionedParamSwapper write→flush windows)."""
        if step is not None and not self.note_step(step):
            return
        for e in events:
            t_issue = e.get("t_issue")
            t_done = e.get("t_done")
            if t_issue is None or t_done is None:
                continue
            self.add_span(
                f"swap_out:{e.get('name', '?')}", t_issue, t_done,
                tid=TID_SWAP_OUT, cat="swap_out",
                args={"bytes": e.get("bytes"),
                      "wait_s": round(e.get("wait_s") or 0.0, 6)})

    # ------------------------------------------------------------------ #
    def to_json(self) -> Dict[str, Any]:
        return {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
            "otherData": {
                "source": "deepspeed_tpu.monitor",
                "schema_version": SCHEMA_VERSION,
                "clock": "host perf_counter (dispatch windows for "
                         "compiled phases; wall windows for swap I/O)",
                "steps_traced": len(self._steps_seen),
                "truncated_at_max_steps": self.truncated,
                "exported_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime()),
            },
        }

    def write(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
        return path


def validate_trace_events(payload: Dict[str, Any]) -> List[str]:
    """Schema check for the Trace Event Format subset this module emits
    (used by tests and available to consumers): returns a list of
    problems, empty when the payload is loadable by chrome://tracing/
    Perfetto."""
    problems = []
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents is not a list"]
    # schema-version check (v2+): absent = a v1-era trace, accepted; a
    # version from the FUTURE means this validator predates the writer
    other = payload.get("otherData")
    if isinstance(other, dict) and "schema_version" in other:
        ver = other["schema_version"]
        if not isinstance(ver, int):
            problems.append(f"otherData.schema_version is not an int "
                            f"({ver!r})")
        elif ver > SCHEMA_VERSION:
            problems.append(
                f"trace schema_version {ver} is newer than this "
                f"validator ({SCHEMA_VERSION}) — upgrade the reader")
    for i, ev in enumerate(events):
        for key in ("name", "ph", "pid", "tid"):
            if key not in ev:
                problems.append(f"event {i} missing {key!r}")
        ph = ev.get("ph")
        if ph not in ("X", "B", "E", "i", "I", "M", "C"):
            problems.append(f"event {i} has unknown ph {ph!r}")
        if ph == "X":
            if not isinstance(ev.get("ts"), (int, float)):
                problems.append(f"event {i} (X) non-numeric ts")
            elif ev["ts"] < 0:
                # an event recorded from before the trace origin (e.g.
                # pre-step I/O leaking into a step span set)
                problems.append(f"event {i} (X) negative ts")
            if not isinstance(ev.get("dur"), (int, float)):
                problems.append(f"event {i} (X) missing numeric dur")
            elif ev["dur"] < 0:
                problems.append(f"event {i} (X) negative dur")
        elif ph in ("i", "I") and not isinstance(ev.get("ts"),
                                                 (int, float)):
            problems.append(f"event {i} (instant) non-numeric ts")
    return problems
