"""Chaos plane: seeded, deterministic, config-driven fault injection.

One mechanism for every failure surface the stack owns.  Subsystems
register *named injection points* at their real failure sites (the AIO
pread/pwrite, the checkpoint stage/commit/manifest steps, the fleet
exchange, the heartbeat write, the input batch, the step boundary) and
call :func:`maybe_fire` there; a :class:`ChaosPlane` — built from the
``resilience.chaos`` config block, off by default — decides from its
schedule whether a fault fires at that call.

Determinism is the contract: triggers are call counts, step numbers and
byte offsets (never wall clock), randomized parameters draw from a
``random.Random(seed)`` private to the plane, and the fired-fault log
carries no timestamps — so the same seed and schedule produce a
bitwise-identical fired log across two runs (pinned by test).  Every
fired fault also emits a structured ``chaos`` monitor record, so a
post-mortem can separate injected faults from organic ones.

The pre-existing single-purpose injectors (``crash_after_bytes``,
``poison_batch``, ``InjectedCrash``) live here now.
"""

import builtins
import io
import os
import random
import signal
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ...utils.logging import logger

# --------------------------------------------------------------------- #
# fault kinds
# --------------------------------------------------------------------- #
KIND_EIO = "eio"                    # OSError(EIO) raised at the surface
KIND_ENOSPC = "enospc"              # OSError(ENOSPC) raised at the surface
KIND_SHORT_READ = "short_read"      # read returns fewer bytes than asked
KIND_LATENCY = "latency"            # sleep, then proceed (perf spike)
KIND_CRASH = "crash"                # InjectedCrash (simulated kill -9)
KIND_TORN_MANIFEST = "torn_manifest"  # manifest truncated mid-write
KIND_HANG = "hang"                  # long sleep (watchdog fodder)
KIND_EXCEPTION = "exception"        # InjectedFault raised at the surface
KIND_DELAY = "delay"                # bounded sleep (delayed host)
KIND_STALE = "stale"                # heartbeat write skipped
KIND_CORRUPT = "corrupt"            # heartbeat file torn/garbage
KIND_POISON = "poison"              # batch floats -> NaN (or args value)
KIND_SIGTERM = "sigterm"            # SIGTERM to self at a step boundary

#: kinds the plane applies itself inside fire() (raise / sleep / signal).
#: every other kind is *cooperative*: fire() returns the fault and the
#: registering subsystem applies the effect at its surface (truncate the
#: manifest, skip the beat, poison the batch, ...).
_RAISING_KINDS = (KIND_EIO, KIND_ENOSPC, KIND_CRASH, KIND_EXCEPTION)
_SLEEPING_KINDS = (KIND_LATENCY, KIND_DELAY, KIND_HANG)

# --------------------------------------------------------------------- #
# injection-point catalog
# --------------------------------------------------------------------- #
POINT_AIO_PREAD = "aio.pread"
POINT_AIO_PWRITE = "aio.pwrite"
POINT_CKPT_STAGE = "checkpoint.stage"
POINT_CKPT_COMMIT = "checkpoint.commit"
POINT_CKPT_MANIFEST = "checkpoint.manifest"
POINT_FLEET_EXCHANGE = "fleet.exchange"
POINT_HEARTBEAT = "heartbeat.beat"
POINT_BATCH = "batch.next"
POINT_STEP = "step.boundary"

#: point -> fault kinds that make sense there.  Config validation
#: rejects (point, kind) pairs outside this table so a typo'd schedule
#: fails at parse time, not silently never-fires.  Subsystems may extend
#: it via register_point().
INJECTION_POINTS: Dict[str, Tuple[str, ...]] = {
    POINT_AIO_PREAD: (KIND_EIO, KIND_SHORT_READ, KIND_LATENCY),
    POINT_AIO_PWRITE: (KIND_EIO, KIND_ENOSPC, KIND_LATENCY),
    POINT_CKPT_STAGE: (KIND_EIO, KIND_ENOSPC, KIND_CRASH),
    POINT_CKPT_COMMIT: (KIND_CRASH, KIND_ENOSPC),
    POINT_CKPT_MANIFEST: (KIND_TORN_MANIFEST, KIND_ENOSPC),
    POINT_FLEET_EXCHANGE: (KIND_HANG, KIND_EXCEPTION, KIND_DELAY),
    POINT_HEARTBEAT: (KIND_STALE, KIND_CORRUPT),
    POINT_BATCH: (KIND_POISON,),
    POINT_STEP: (KIND_SIGTERM, KIND_CRASH),
}


def register_point(point: str, kinds: Iterable[str],
                   replace: bool = False) -> None:
    """Extension API: a subsystem adding a new failure surface registers
    its point name + legal kinds so config validation knows about it."""
    kinds = tuple(kinds)
    if not replace and point in INJECTION_POINTS:
        raise ValueError(f"chaos injection point {point!r} already "
                         "registered (pass replace=True to override)")
    INJECTION_POINTS[point] = kinds


class InjectedFault(RuntimeError):
    """A chaos-injected generic exception (the fleet-exchange
    ``exception`` kind and friends) — grep-able, never organic."""


class InjectedCrash(RuntimeError):
    """Simulated mid-save process death (deliberately NOT an OSError so
    the resilience retry wrapper does not absorb it)."""


# --------------------------------------------------------------------- #
# schedule
# --------------------------------------------------------------------- #
@dataclass
class ChaosFault:
    """One scheduled fault: kind x point x trigger x repeat budget.

    Exactly one trigger must be set: ``at_call`` (1-based call count of
    the point), ``at_step`` (engine global step), or ``after_bytes``
    (byte offset into a write scope; only meaningful for crash kinds on
    write surfaces).  ``repeat`` widens the trigger to that many
    consecutive calls/steps — e.g. ``at_call=3, repeat=2`` fires on
    calls 3 and 4."""

    point: str
    kind: str
    at_call: Optional[int] = None
    at_step: Optional[int] = None
    after_bytes: Optional[int] = None
    repeat: int = 1
    args: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        validate_fault(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ChaosFault":
        known = {"point", "kind", "at_call", "at_step", "after_bytes",
                 "repeat", "args"}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"chaos fault spec has unknown keys {sorted(unknown)} "
                f"(known: {sorted(known)})")
        return cls(point=d.get("point", ""), kind=d.get("kind", ""),
                   at_call=d.get("at_call"), at_step=d.get("at_step"),
                   after_bytes=d.get("after_bytes"),
                   repeat=int(d.get("repeat", 1)),
                   args=dict(d.get("args") or {}))


def validate_fault(f: ChaosFault) -> None:
    if f.point not in INJECTION_POINTS:
        raise ValueError(
            f"chaos fault targets unknown injection point {f.point!r}; "
            f"registered points: {sorted(INJECTION_POINTS)}")
    if f.kind not in INJECTION_POINTS[f.point]:
        raise ValueError(
            f"chaos fault kind {f.kind!r} is not valid at point "
            f"{f.point!r} (valid: {list(INJECTION_POINTS[f.point])})")
    triggers = [t for t in (f.at_call, f.at_step, f.after_bytes)
                if t is not None]
    if len(triggers) != 1:
        raise ValueError(
            f"chaos fault at {f.point!r} must set exactly one trigger "
            "of at_call / at_step / after_bytes "
            f"(got {len(triggers)})")
    if f.repeat < 1:
        raise ValueError("chaos fault repeat must be >= 1")
    for t in triggers:
        if int(t) < 0:
            raise ValueError("chaos fault trigger must be >= 0")


# --------------------------------------------------------------------- #
# the plane
# --------------------------------------------------------------------- #
class ChaosPlane:
    """Holds the schedule, the per-point call counters, and the fired
    log.  ``fire(point, step)`` is the single entry every surface calls;
    it matches the schedule, logs deterministically, applies raising /
    sleeping kinds itself, and returns the fault (or None) so
    cooperative kinds can be applied by the caller."""

    def __init__(self, faults: Iterable[ChaosFault], seed: int = 0):
        self.faults: List[ChaosFault] = list(faults)
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        self._lock = threading.RLock()
        self._calls: Dict[str, int] = {}
        # remaining repeat budget per schedule slot
        self._budget: List[int] = [f.repeat for f in self.faults]
        #: deterministic fired log: dicts with seq/point/kind/call/step/
        #: detail — deliberately NO timestamps (same seed+schedule =>
        #: identical log across runs, pinned by test)
        self.fired: List[Dict[str, Any]] = []
        self._records: List[Dict[str, Any]] = []

    @classmethod
    def from_config(cls, chaos_config) -> "ChaosPlane":
        faults = [f if isinstance(f, ChaosFault) else
                  ChaosFault.from_dict(dict(f))
                  for f in chaos_config.faults]
        return cls(faults, seed=chaos_config.seed)

    # ---- matching ----------------------------------------------------- #
    def _match(self, point: str, call: int,
               step: Optional[int]) -> Optional[int]:
        for i, f in enumerate(self.faults):
            if f.point != point or self._budget[i] <= 0:
                continue
            if f.at_call is not None:
                if f.at_call <= call < f.at_call + f.repeat:
                    return i
            elif f.at_step is not None and step is not None:
                if f.at_step <= step < f.at_step + f.repeat:
                    return i
            # after_bytes faults are consumed via crash_scope(), not
            # per-call matching
        return None

    def _log_fire(self, fault: ChaosFault, call: int,
                  step: Optional[int], detail: str) -> Dict[str, Any]:
        entry = {
            "seq": len(self.fired) + 1,
            "point": fault.point,
            "kind": fault.kind,
            "call": call,
            "step": step,
            "detail": detail,
        }
        self.fired.append(entry)
        self._records.append(dict(entry))
        logger.warning(f"chaos: firing {fault.kind} at {fault.point} "
                       f"(call {call}, step {step}) — {detail}")
        return entry

    # ---- the single entry every surface calls -------------------------- #
    def fire(self, point: str, step: Optional[int] = None
             ) -> Optional[ChaosFault]:
        with self._lock:
            call = self._calls.get(point, 0) + 1
            self._calls[point] = call
            idx = self._match(point, call, step)
            if idx is None:
                return None
            fault = self.faults[idx]
            self._budget[idx] -= 1
            detail = self._describe(fault)
            self._log_fire(fault, call, step, detail)
        # effects run OUTSIDE the lock: hang/latency must not hold it,
        # raised faults must not poison the plane state
        self._apply(fault, detail)
        return fault

    def _describe(self, fault: ChaosFault) -> str:
        if fault.kind in _SLEEPING_KINDS:
            return f"sleep {self._sleep_s(fault)}s"
        return f"chaos-injected {fault.kind} at {fault.point}"

    def _sleep_s(self, fault: ChaosFault) -> float:
        default = 3600.0 if fault.kind == KIND_HANG else 0.05
        return float(fault.args.get("seconds", default))

    def _apply(self, fault: ChaosFault, detail: str) -> None:
        k = fault.kind
        if k == KIND_EIO or k == KIND_SHORT_READ:
            # the python AIO fallback reports a real short read as
            # OSError(EIO) too — same observable, chaos-named message
            raise OSError(5, detail)
        if k == KIND_ENOSPC:
            raise OSError(28, detail)
        if k == KIND_CRASH:
            raise InjectedCrash(detail)
        if k == KIND_EXCEPTION:
            raise InjectedFault(detail)
        if k in _SLEEPING_KINDS:
            time.sleep(self._sleep_s(fault))
            return
        if k == KIND_SIGTERM:
            os.kill(os.getpid(), signal.SIGTERM)
            return
        # cooperative kinds (torn_manifest, stale, corrupt, poison):
        # the caller applies the effect at its surface
        return

    # ---- byte-offset crashes (write scopes) ---------------------------- #
    @contextmanager
    def crash_scope(self, point: str, path_prefix: Optional[str] = None):
        """Wrap a write phase so a pending ``after_bytes`` fault at
        `point` crashes it at the scheduled byte offset (the folded
        crash_after_bytes surface).  Yields the byte counter (or None
        when no such fault is pending)."""
        with self._lock:
            idx = next((i for i, f in enumerate(self.faults)
                        if f.point == point and self._budget[i] > 0
                        and f.after_bytes is not None), None)
            if idx is not None:
                self._budget[idx] -= 1
                fault = self.faults[idx]
        if idx is None:
            yield None
            return
        with crash_after_bytes(fault.after_bytes, path_prefix) as counter:
            try:
                yield counter
            finally:
                if counter.crashed:
                    with self._lock:
                        self._log_fire(
                            fault, self._calls.get(point, 0), None,
                            f"chaos-injected crash after "
                            f"{counter.bytes_written} bytes "
                            f"(budget {fault.after_bytes})")
                else:
                    # the write phase finished under budget: refund so
                    # a later, larger scope can still hit it
                    with self._lock:
                        self._budget[idx] += 1

    # ---- monitor integration ------------------------------------------- #
    def drain_records(self) -> List[Dict[str, Any]]:
        """Fired-fault records since the last drain, monitor-ready."""
        from ...monitor import record as R
        with self._lock:
            out, self._records = self._records, []
        # the fired entry's own "kind" (the fault kind) moves to
        # fault_kind so the record kind column stays the stream schema
        return [{**{k: v for k, v in e.items() if k != "kind"},
                 "fault_kind": e["kind"], R.F_KIND: R.KIND_CHAOS}
                for e in out]


# --------------------------------------------------------------------- #
# process-global install (the subsystems have no engine handle)
# --------------------------------------------------------------------- #
_ACTIVE: Optional[ChaosPlane] = None


def install(plane: Optional[ChaosPlane]) -> None:
    global _ACTIVE
    if plane is not None and _ACTIVE is not None and _ACTIVE is not plane:
        logger.warning("chaos: replacing an already-installed plane")
    _ACTIVE = plane
    if plane is not None:
        logger.warning(
            f"chaos: fault-injection plane ACTIVE (seed {plane.seed}, "
            f"{len(plane.faults)} scheduled faults) — this process is a "
            "chaos run")


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> Optional[ChaosPlane]:
    return _ACTIVE


def maybe_fire(point: str, step: Optional[int] = None
               ) -> Optional[ChaosFault]:
    """The call every injection surface makes; near-free when no plane
    is installed."""
    plane = _ACTIVE
    if plane is None:
        return None
    return plane.fire(point, step)


@contextmanager
def installed(plane: ChaosPlane):
    """Test helper: install `plane` for the body, always uninstall."""
    install(plane)
    try:
        yield plane
    finally:
        uninstall()


# --------------------------------------------------------------------- #
# the single-purpose injectors
# --------------------------------------------------------------------- #
class _CountingFile:
    def __init__(self, f, injector):
        self._f = f
        self._injector = injector

    def write(self, data):
        if self._injector.crashed:
            # the simulated process is dead: later writes (e.g. zipfile
            # finalizers unwinding) go nowhere instead of re-raising
            return len(data)
        self._injector.charge(len(data))
        return self._f.write(data)

    def writelines(self, lines):
        for line in lines:
            self.write(line)

    def __getattr__(self, name):
        return getattr(self._f, name)

    def __enter__(self):
        self._f.__enter__()
        return self

    def __exit__(self, *exc):
        return self._f.__exit__(*exc)

    def __iter__(self):
        return iter(self._f)


class crash_after_bytes:
    """Context manager: writes under `path_prefix` crash once `nbytes`
    have been written.  `bytes_written` after a clean exit reports the
    total write volume — sweep budgets in [0, total) to cover every
    inter-write crash point."""

    def __init__(self, nbytes: float, path_prefix: Optional[str] = None):
        self.budget = nbytes
        self.prefix = (os.path.abspath(path_prefix)
                       if path_prefix is not None else None)
        self.bytes_written = 0
        self.crashed = False
        self._real_open = None

    def charge(self, n: int) -> None:
        if self.bytes_written + n > self.budget:
            self.crashed = True
            raise InjectedCrash(
                f"injected crash after {self.bytes_written} bytes "
                f"(budget {self.budget}, next write {n})")
        self.bytes_written += n

    def _in_scope(self, file, mode: str) -> bool:
        if not any(m in mode for m in ("w", "a", "x", "+")):
            return False
        if not isinstance(file, (str, bytes, os.PathLike)):
            return False
        path = os.path.abspath(os.fsdecode(file))
        return self.prefix is None or path.startswith(self.prefix)

    def __enter__(self) -> "crash_after_bytes":
        self._real_open = builtins.open

        def opener(file, mode="r", *args, **kwargs):
            f = self._real_open(file, mode, *args, **kwargs)
            if self._in_scope(file, mode):
                return _CountingFile(f, self)
            return f

        builtins.open = opener
        io.open = opener  # np.savez/zipfile resolve io.open at call time
        return self

    def __exit__(self, *exc):
        builtins.open = self._real_open
        io.open = self._real_open
        return False


def measure_save_bytes(save_fn, path_prefix: Optional[str] = None) -> int:
    """Run `save_fn()` under an unlimited counter; returns total bytes
    written — the sweep range for crash_after_bytes."""
    with crash_after_bytes(float("inf"), path_prefix) as counter:
        save_fn()
    return counter.bytes_written


def poison_batch(batch, value: float = float("nan")):
    """Return `batch` with every float array replaced by `value` — the
    deterministic forced-NaN (or Inf/spike) loss hook."""

    def poison(x):
        arr = np.asarray(x)
        if np.issubdtype(arr.dtype, np.floating):
            return np.full_like(arr, value)
        return x

    import jax
    return jax.tree.map(poison, batch)
