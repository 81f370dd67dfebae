"""Collective-lockstep signature: the ordered collective sequence of a
program, hashed into a per-config fingerprint.

Why: on a multihost pod every process must issue the SAME collectives in
the SAME order or the pod deadlocks — and the failure mode is a hang, not
a stack trace (PR 2's multihost resilience work had to hand-audit exactly
this).  The sequence of collective equations is a static property of the
traced program, so config drift (one host with qwZ on, another off; a
skinny-leaf gate flipping a gather dense on one host) is catchable BEFORE
dispatch by comparing signatures instead of burning a pod to find out.

Scope note: this sees EXPLICIT collectives (shard_map regions, the ZeRO-3
streamed gathers, qwZ/qgZ) — the same surface `collective_wire_bytes`
accounts.  GSPMD-inserted collectives (jit + shardings) are compiled per
identical HLO on every host and cannot drift independently of the traced
program, so hashing the traced sequence is the right invariant.
"""

import hashlib
from typing import List, Optional, Tuple

from .jaxpr_walk import iter_eqns

# collective primitives, by wire direction (superset of
# low_bandwidth.collective_wire_bytes's families: psum2 is what a psum
# inside shard_map traces to on jax 0.4.x, and ppermute/pmax/pmin matter
# for lockstep even though the wire accounting ignores them)
GATHER_PRIMS = ("all_gather",)
REDUCE_PRIMS = ("psum_scatter", "reduce_scatter", "all_to_all", "psum",
                "psum2", "ppermute", "pmax", "pmin")
COLLECTIVE_PRIMS = GATHER_PRIMS + REDUCE_PRIMS


def _axes_of(eqn) -> str:
    axes = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if isinstance(axes, (tuple, list)):
        return ",".join(str(a) for a in axes)
    return str(axes)


def collective_sequence(jaxpr) -> List[str]:
    """Ordered, canonical description of every collective equation —
    primitive, mesh axes, operand shape/dtype, and the static trip
    multiplier (a collective inside the gas=4 scan runs 4x and must stay
    in lockstep on every iteration)."""
    seq = []
    for ctx in iter_eqns(jaxpr):
        name = ctx.eqn.primitive.name
        if name not in COLLECTIVE_PRIMS:
            continue
        operand = next((v for v in ctx.eqn.invars
                        if hasattr(v, "aval")), None)
        aval = getattr(operand, "aval", None)
        shape = tuple(getattr(aval, "shape", ()))
        dtype = str(getattr(aval, "dtype", "?"))
        seq.append(f"{name}[{_axes_of(ctx.eqn)}]"
                   f"{list(shape)}:{dtype}x{ctx.mult}")
    return seq


def lockstep_signature(jaxpr) -> Tuple[str, List[str]]:
    """(hex digest, sequence) for a traced program."""
    seq = collective_sequence(jaxpr)
    return signature_of_sequence(seq), seq


def signature_of_sequence(seq: List[str]) -> str:
    h = hashlib.sha256()
    for item in seq:
        h.update(item.encode())
        h.update(b"\n")
    return h.hexdigest()


def combine_signatures(sigs: List[str]) -> str:
    """Per-engine signature over several traced programs (grad + apply):
    order-sensitive, like the dispatch order."""
    h = hashlib.sha256()
    for s in sigs:
        h.update(s.encode())
        h.update(b"\n")
    return h.hexdigest()


def first_divergence(a: List[str], b: List[str]) -> Optional[str]:
    """Human-readable description of where two collective sequences
    diverge (None when identical) — the message a hung pod never gives."""
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return f"position {i}: {x!r} vs {y!r}"
    if len(a) != len(b):
        longer, n = (a, len(b)) if len(a) > len(b) else (b, len(a))
        return (f"length {len(a)} vs {len(b)} — first extra collective: "
                f"{longer[n]!r}")
    return None
