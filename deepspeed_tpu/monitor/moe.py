"""MoE routing observability — the host half (docs/telemetry.md).

The gate already computes everything an operator (or an NVMe expert
streamer) needs — per-expert routed counts, capacity drops, router
entropy — but until ISSUE 15 none of it left the traced program.  The
in-program half (``moe/sharded_moe.py RoutingStats``) accumulates those
scalars device-side across layers, microbatches, and optimizer steps;
the engine hands this module ONE fetched accumulator per flush window
(boundary-only host read, the same contract as every other monitor
read).  This module turns it into:

  * a ``moe`` record per window (record.py ``KIND_MOE``): drop
    fraction, per-expert counts/overflow, normalized router entropy,
    top-k confidence, mean l_aux, load imbalance;
  * the **ExpertPopularitySnapshot** — an EWMA expert-popularity
    ranking with hot/cold lists and a hit-rate-under-K curve.  This is
    the *prefetch oracle* ROADMAP item 6's NVMe expert streaming keys
    its swap-in schedule on: ``hit_rate_under_k[K-1]`` estimates the
    fraction of routed tokens that hit one of the top-K experts, i.e.
    the HBM hit rate of pinning K experts resident and streaming the
    rest (arXiv:2104.07857's 10-100x-beyond-HBM endgame applied to
    experts).  The snapshot is plain JSON and round-trips through the
    JSONL record stream — the consumable contract is pinned by
    tests/unit/test_moe_monitor.py;
  * scalar slots for the fleet window vector (fleet.py ``moe_*``
    fields) so expert-parallel pods see per-host load skew, and the
    three MoE health rules (health.py: dead expert, router collapse,
    EP load imbalance) have deterministic inputs.

Everything here is pure host math over already-fetched numpy values —
nothing touches a device.
"""

import math
from typing import Any, Dict, List, Optional

import numpy as np

from . import record as R

# schema tag of the exported popularity snapshot (the streamer-facing
# contract — version it like the autotuner's results schema)
SNAPSHOT_SCHEMA = "ds_expert_popularity_v1"


def _f(v) -> float:
    return float(np.asarray(v))


def summarize_window(raw: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """One window's fetched RoutingStats accumulator -> summary scalars.

    ``raw`` carries the RoutingStats field names as numpy values plus
    ``steps`` (optimizer steps accumulated) and optionally
    ``local_expert_slice`` ((lo, hi) — the experts THIS host's shard of
    the expert mesh axis owns, for the per-host load-skew slot).
    Returns None when the accumulator saw no gate invocations (a dense
    model under ``monitor.moe`` — the fleet slots then stay NaN)."""
    layers = _f(raw.get("layers", 0.0))
    if layers <= 0.0:
        return None
    counts = np.asarray(raw["expert_counts"], dtype=np.float64)
    overflow = np.asarray(raw["overflow_counts"], dtype=np.float64)
    tokens = _f(raw["tokens"])
    dropped = _f(raw["dropped"])
    gate_tokens = _f(raw["gate_tokens"])
    num_experts = int(counts.shape[0])
    steps = max(1, int(raw.get("steps", 1)))

    mean_count = counts.mean() if counts.size else 0.0
    routed = counts.sum()
    summary: Dict[str, Any] = {
        R.M_EXPERTS: num_experts,
        R.M_STEPS: steps,
        R.M_LAYERS_PER_STEP: round(layers / steps, 3),
        R.M_TOKENS_PER_STEP: round(tokens / steps, 1),
        R.M_DROP_FRAC: round(dropped / tokens, 6) if tokens > 0 else None,
        R.M_COUNTS: [round(float(c), 1) for c in counts],
        R.M_OVERFLOW: [round(float(c), 1) for c in overflow],
        R.M_IMBALANCE: (round(float(counts.max() / mean_count), 4)
                        if mean_count > 0 else None),
        R.M_MIN_COUNT_FRAC: (round(float(counts.min() / mean_count), 6)
                             if mean_count > 0 else None),
        # normalized entropy: mean per-token router entropy / ln(E);
        # 1.0 = perfectly uniform router, -> 0 = collapsed
        R.M_ENTROPY: (round(_f(raw["entropy"])
                            / (gate_tokens * math.log(num_experts)), 6)
                      if gate_tokens > 0 and num_experts > 1 else None),
        R.M_CONFIDENCE: (round(_f(raw["confidence"]) / gate_tokens, 6)
                         if gate_tokens > 0 else None),
        R.M_LAUX: round(_f(raw["l_aux"]) / layers, 6),
        "hottest_expert": int(counts.argmax()) if routed > 0 else None,
        "coldest_expert": int(counts.argmin()) if routed > 0 else None,
    }
    sl = raw.get("local_expert_slice")
    if sl is not None and routed > 0:
        lo, hi = int(sl[0]), int(sl[1])
        share = counts[lo:hi].sum() / routed
        fair = (hi - lo) / num_experts
        # normalized: 1.0 = this host's experts carry exactly their
        # fair share of routed tokens; 2.0 = twice it (a hot-spot)
        summary[R.M_LOCAL_LOAD] = (round(float(share / fair), 4)
                                   if fair > 0 else None)
    else:
        summary[R.M_LOCAL_LOAD] = None
    held_max = raw.get("held_rows_max")
    if held_max is not None and sl is not None and routed > 0:
        lo, hi = int(sl[0]), int(sl[1])
        held_rows = counts[lo:hi].sum()
        summary[R.M_HELD_RANGE] = [lo, hi]
        summary[R.M_HELD_ROWS_MAX] = round(_f(held_max) / layers, 2)
        summary[R.M_HELD_ROWS_MEAN] = round(
            float(held_rows / (hi - lo) / layers), 2)
        summary[R.M_HELD_PICK_SHARE] = round(float(held_rows / tokens), 6)
    chunks = raw.get("dispatch_chunks")
    if chunks is not None:
        summary[R.M_DISPATCH_CHUNKS] = round(_f(chunks) / layers, 4)
    # what the model counts of its own (engine.model_counters(): the
    # names are the model's, averaged over the window's micro-batches)
    for name, value in (raw.get("model_counters") or {}).items():
        summary[name] = round(value, 6)
    return summary


class ExpertPopularityTracker:
    """Per-window EWMA of the expert-popularity distribution.

    Each window contributes its routed-count SHARE vector (sums to 1);
    the EWMA smooths window-to-window routing noise so the streamer's
    pin/evict decisions don't thrash on one bursty batch."""

    def __init__(self, alpha: float = 0.2):
        self.alpha = float(alpha)
        self.ewma_share: Optional[np.ndarray] = None
        self.windows_seen = 0

    def update(self, counts: np.ndarray) -> Optional[np.ndarray]:
        counts = np.asarray(counts, dtype=np.float64)
        total = counts.sum()
        if total <= 0:
            return self.ewma_share
        share = counts / total
        if (self.ewma_share is None
                or self.ewma_share.shape != share.shape):
            self.ewma_share = share
        else:
            self.ewma_share = (self.ewma_share
                               + self.alpha * (share - self.ewma_share))
        self.windows_seen += 1
        return self.ewma_share

    def snapshot(self, window_end_step: Optional[int],
                 hot_k: int = 4) -> Optional[Dict[str, Any]]:
        """Export the streamer-facing ExpertPopularitySnapshot."""
        if self.ewma_share is None:
            return None
        share = self.ewma_share
        order = list(np.argsort(-share, kind="stable"))
        cumulative = np.cumsum(share[order])
        return {
            "schema": SNAPSHOT_SCHEMA,
            R.M_WINDOW_END: (int(window_end_step)
                             if window_end_step is not None else None),
            R.M_EXPERTS: int(share.shape[0]),
            "windows_seen": int(self.windows_seen),
            "ewma_share": [round(float(s), 6) for s in share],
            # ranked expert ids: hot = most popular first (the pin
            # set), cold = least popular first (the stream-from-NVMe
            # set); hot is truncated to hot_k, cold to the complement
            "hot": [int(e) for e in order[:hot_k]],
            "cold": [int(e) for e in order[::-1][:max(
                0, share.shape[0] - hot_k)]],
            "hot_k": int(hot_k),
            # hit_rate_under_k[K-1]: estimated fraction of routed
            # tokens hitting one of the top-K experts — the HBM hit
            # rate of pinning K experts resident
            "hit_rate_under_k": [round(float(c), 6) for c in cumulative],
        }


def validate_snapshot(d: Dict[str, Any]) -> List[str]:
    """Schema check for a round-tripped ExpertPopularitySnapshot —
    the contract ROADMAP item 6's streamer consumes."""
    problems = []
    if not isinstance(d, dict):
        return ["snapshot is not an object"]
    if d.get("schema") != SNAPSHOT_SCHEMA:
        problems.append(f"schema is {d.get('schema')!r}, expected "
                        f"{SNAPSHOT_SCHEMA!r}")
    n = d.get(R.M_EXPERTS)
    if not isinstance(n, int) or n < 1:
        problems.append(f"{R.M_EXPERTS} missing/invalid: {n!r}")
        return problems
    share = d.get("ewma_share")
    if not isinstance(share, list) or len(share) != n:
        problems.append(f"ewma_share is not a length-{n} list")
    elif abs(sum(share) - 1.0) > 1e-3:
        problems.append(f"ewma_share sums to {sum(share)}, expected 1")
    hit = d.get("hit_rate_under_k")
    if not isinstance(hit, list) or len(hit) != n:
        problems.append(f"hit_rate_under_k is not a length-{n} list")
    elif any(b < a - 1e-9 for a, b in zip(hit, hit[1:])):
        problems.append("hit_rate_under_k is not non-decreasing")
    hot, cold = d.get("hot"), d.get("cold")
    if not isinstance(hot, list) or not all(
            isinstance(e, int) and 0 <= e < n for e in hot):
        problems.append(f"hot is not a list of expert ids: {hot!r}")
    if not isinstance(cold, list) or not all(
            isinstance(e, int) and 0 <= e < n for e in cold):
        problems.append(f"cold is not a list of expert ids: {cold!r}")
    if isinstance(hot, list) and isinstance(cold, list) and set(
            hot) & set(cold):
        problems.append("hot and cold lists overlap")
    return problems


class MoeRoutingAggregator:
    """Window-boundary consumer of the fetched RoutingStats accumulator:
    builds the ``moe`` record (with the popularity snapshot embedded),
    updates the EWMA popularity, and exposes the scalar slots the fleet
    window vector and health rules key on."""

    def __init__(self, ewma_alpha: float = 0.2, hot_k: int = 4,
                 identity: Optional[Dict[str, Any]] = None):
        self.tracker = ExpertPopularityTracker(ewma_alpha)
        self.hot_k = int(hot_k)
        self.identity = dict(identity or {})
        self.last_summary: Optional[Dict[str, Any]] = None
        self.last_snapshot: Optional[Dict[str, Any]] = None
        self.windows_observed = 0

    def observe_window(self, raw: Dict[str, Any],
                       window_start: Optional[int],
                       window_end: Optional[int]
                       ) -> Optional[Dict[str, Any]]:
        """One fetched accumulator -> the window's ``moe`` record (None
        when the window routed nothing)."""
        summary = summarize_window(raw)
        if summary is None:
            return None
        self.windows_observed += 1
        self.tracker.update(np.asarray(raw["expert_counts"],
                                       dtype=np.float64))
        snap = self.tracker.snapshot(window_end, hot_k=self.hot_k)
        self.last_summary = summary
        self.last_snapshot = snap
        rec: Dict[str, Any] = {R.F_KIND: R.KIND_MOE,
                               R.M_WINDOW_START: window_start,
                               R.M_WINDOW_END: window_end}
        rec.update(summary)
        rec[R.M_POPULARITY] = snap
        for k, v in self.identity.items():
            rec.setdefault(k, v)
        return rec

    def fleet_fields(self) -> Dict[str, Optional[float]]:
        """The moe_* slots of the fleet window vector (fleet.py
        VEC_FIELDS) for the LAST observed window; all-None (-> NaN on
        the wire) when nothing routed."""
        s = self.last_summary
        if s is None:
            return {}
        return {
            "moe_drop_frac": s.get(R.M_DROP_FRAC),
            "moe_entropy": s.get(R.M_ENTROPY),
            "moe_imbalance": s.get(R.M_IMBALANCE),
            "moe_min_count_frac": s.get(R.M_MIN_COUNT_FRAC),
            "moe_coldest_expert": s.get("coldest_expert"),
            "moe_local_load": s.get(R.M_LOCAL_LOAD),
        }


def snapshot_from_record(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Extract the ExpertPopularitySnapshot from a round-tripped ``moe``
    JSONL record (the consumer-side accessor the streamer will use)."""
    if rec.get(R.F_KIND) != R.KIND_MOE:
        return None
    return rec.get(R.M_POPULARITY)


def format_moe_line(rec: Dict[str, Any]) -> str:
    """One-line log form of a ``moe`` window record."""
    bits = [f"E={rec.get(R.M_EXPERTS)}"]
    drop = rec.get(R.M_DROP_FRAC)
    if drop is not None:
        bits.append(f"drop {drop * 100:.2f}%")
    imb = rec.get(R.M_IMBALANCE)
    if imb is not None:
        bits.append(f"imbalance {imb:.2f}x")
    ent = rec.get(R.M_ENTROPY)
    if ent is not None:
        bits.append(f"entropy {ent:.3f}")
    snap = rec.get(R.M_POPULARITY) or {}
    hot = snap.get("hot")
    if hot:
        bits.append("hot=" + ",".join(str(e) for e in hot))
    return "[monitor-moe] " + " ".join(bits)
