"""What the program's host thread waited for, for the per-layer readers
that move ``setup_s`` and the three that split ``engine_host_ms``.

Two sources, both the program's own:

* ``deepspeed_tpu.monitor.trace``: the compile record (one entry per
  program JAX was asked to compile since the package was imported:
  ``trace_s``, ``lower_s``, ``backend_s``, ``outcome`` ``compiled`` |
  ``fetched`` | ``uncached``, ``fetch_s``) and the newest engine's marks
  (``initialize_ns``, ``steady_since_ns``: the end of its first optimizer
  step that launched no program for the first time).  Set-up, seen from
  inside, is every request that began before that mark.  A program
  without the module, without the record or without a steady engine
  gives None, and so does every reader built on it.
* the traced steps' ``ds.*`` spans, which ``perf/program_trace.py``
  ``read()`` hands over by name: ``ds.forward.await_loss`` (the host
  blocked on the device before a launch), ``ds.monitor.record`` and
  ``ds.monitor.flush`` (telemetry), ``ds.launch.first`` (a program's
  first call).  Where the engine never waited or no monitor is on these
  read 0.0; where the program writes no span at all, None.

The rest is arithmetic on lists, checked on lists written by hand
(tests/perf/test_wait_metrics.py).
"""

from perf import program_trace as pt
from perf import trace_reduce as tr

AWAIT = "ds.forward.await_loss"
MONITOR = "ds.monitor."
FIRST_LAUNCH = "ds.launch.first"


# ---------------------------------------------------------------------- #
# set-up: the compile record before the newest engine was steady
# ---------------------------------------------------------------------- #
def program_record():
    """(compile records, the newest engine's marks) of this process, or
    (None, None) where the program keeps no such record."""
    try:
        from deepspeed_tpu.monitor import trace
        return trace.compiles(0), trace.newest_engine()
    except (ImportError, AttributeError):
        return None, None


def setup_requests(records, marks):
    """The compile requests that began before the newest engine's
    ``steady_since_ns``; None without a record or a steady engine."""
    steady = (marks or {}).get("steady_since_ns")
    if records is None or steady is None:
        return None
    return [r for r in records if r["when_ns"] < steady]


def setup_sum(records, marks, seconds):
    """Sum of ``seconds(request)`` over the set-up requests, or None."""
    requests = setup_requests(records, marks)
    return None if requests is None else float(sum(map(seconds, requests)))


def compile_s(r):
    return r["backend_s"] if r["outcome"] in ("compiled", "uncached") else 0.0


def fetch_s(r):
    return r["fetch_s"] if r["outcome"] == "fetched" else 0.0


def trace_lower_s(r):
    return r["trace_s"] + r["lower_s"]


def missed(r):
    return 1.0 if r["outcome"] == "compiled" else 0.0


def initialize_s(marks):
    """Seconds of the newest engine's ``ds.initialize``, or None."""
    window = (marks or {}).get("initialize_ns")
    return None if not window else (window[1] - window[0]) / 1e9


# ---------------------------------------------------------------------- #
# the traced steps: the engine's host time by what it waited for
# ---------------------------------------------------------------------- #
def host_intervals(spans):
    """What ``engine_host_ms`` measures, as intervals: the engine's
    ``ds.forward`` / ``ds.backward`` / ``ds.step`` spans less their
    ``*.dispatch`` children."""
    outer = tr.union([pt.interval(s) for s in spans if s[0] in pt.OUTER])
    dispatch = [pt.interval(s) for s in spans if s[0].endswith(pt.DISPATCH)]
    return tr.subtract(outer, tr.union(dispatch))


def named(spans, *prefixes):
    return tr.union([pt.interval(s) for s in spans
                     if s[0].startswith(prefixes)])


def host_parts(spans):
    """{"host", "wait", "monitor", "python"} in ns: the engine's host
    time and its three parts.  ``wait`` lies under
    ``ds.forward.await_loss``, ``monitor`` under ``ds.monitor.*``,
    ``python`` under neither and under no ``ds.launch.first``; with no
    first launch among the traced steps the three sum to ``host``."""
    host = host_intervals(spans)
    wait = pt.covered(host, named(spans, AWAIT))
    monitor = pt.covered(host, named(spans, MONITOR))
    rest = tr.subtract(host, named(spans, AWAIT, MONITOR, FIRST_LAUNCH))
    return {"host": tr.measure(host), "wait": wait, "monitor": monitor,
            "python": tr.measure(rest)}


def step_part(part, run):
    """ms a traced step of ``host_parts``'s ``part``; None where the
    program wrote no span."""
    spans = pt.read()["spans"]
    if not spans:
        return None
    return tr.per_step(host_parts(spans)[part], run["steps_traced"])
