"""GShard-style gated mixture-of-experts with expert parallelism.

Reference: deepspeed/moe/sharded_moe.py — top1gating:99, top2gating:173,
TopKGate:247 (fp32 gate, capacity factor, jitter/RSample noise, l_aux
load-balance loss), MOELayer:312 (einsum dispatch → all-to-all → experts →
all-to-all → einsum combine), _AllToAll:77.

TPU-native design: the reference wraps torch all_to_all_single in an autograd
Function; here dispatch/combine are einsums whose operands carry sharding
constraints — tokens sharded over the data axes, the dispatched [E, C, d]
buffer and stacked expert params sharded over the "expert" mesh axis.  XLA
lowers the resharding between those layouts to the same all-to-all over ICI,
and reverses it in the backward pass automatically.  Gating math stays fp32
exactly like the reference's fp32 gate (sharded_moe.py:247).

Capacity is static (token count is known at trace time), keeping shapes
XLA-friendly; tokens over capacity are dropped by the position mask exactly
like the reference's `locations < capacity` test.
"""

import contextlib
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..parallel.mesh import EXPERT_AXIS

JITTER_EPS = 1e-2

# entropy clip floor: softmax outputs are strictly positive, but fp32
# underflow on very peaked routers would otherwise produce 0 * -inf
_ENTROPY_EPS = 1e-20


class RoutingStats(NamedTuple):
    """Per-gate routing telemetry, a pure pytree of device scalars/[E]
    vectors so it sums across layers (``emit_routing_stats`` inside one
    traced forward), microbatches (the fused gas scan), and optimizer
    steps (the engine's device-resident accumulator) with plain
    ``jax.tree.map(jnp.add)`` — and is host-read ONLY at monitor
    flush-window boundaries (docs/telemetry.md "MoE routing
    observability").  Everything is POST-capacity-mask reality: a token
    the ``locations < capacity`` test dropped never counts as routed.

    This is the in-program half of the expert-popularity prefetch
    oracle ROADMAP item 6's NVMe expert streaming keys on
    (monitor/moe.py turns the accumulated ``expert_counts`` into the
    ``ExpertPopularitySnapshot`` the streamer consumes)."""
    expert_counts: jnp.ndarray    # f32[E] routed token-slots per expert
    overflow_counts: jnp.ndarray  # f32[E] capacity-dropped slots per
    #                               WANTED expert (where demand exceeded
    #                               the slot budget)
    tokens: jnp.ndarray           # f32[] token-slots wanted (k x tokens,
    #                               used_token-masked)
    dropped: jnp.ndarray          # f32[] token-slots dropped (= tokens
    #                               - routed)
    entropy: jnp.ndarray          # f32[] sum over tokens of router
    #                               softmax entropy (nats)
    confidence: jnp.ndarray       # f32[] sum over tokens of raw top-k
    #                               gate probability mass
    gate_tokens: jnp.ndarray      # f32[] tokens contributing entropy/
    #                               confidence
    l_aux: jnp.ndarray            # f32[] summed load-balance loss
    layers: jnp.ndarray           # f32[] gate invocations folded in
    held_rows_max: jnp.ndarray    # f32[] summed over gate invocations:
    #                               rows of the busiest expert among those
    #                               THIS program holds (all of them unless
    #                               the layer was given a held range)
    dispatch_chunks: jnp.ndarray  # f32[] summed over gate invocations:
    #                               passes over the row buffers it took
    #                               to move the routed rows (one, unless
    #                               the layer sizes its buffers under the
    #                               worst case: moe/dropless.py)
    # What a model fills when it emits ONE entry for all its gates
    # (models/glm4_moe_lite.py); None elsewhere, which is no leaf, so
    # every other model's programs are what they were.
    layer_counts: Optional[jnp.ndarray] = None  # f32[L, E] picks an
    #                               expert, a row a gate: what a selection
    #                               bias is moved by after the step


def _routing_stats(gates, wanted_counts, routed_counts, topk_mass,
                   l_aux, used_token=None, held=None,
                   chunks=1) -> RoutingStats:
    """Assemble one gate invocation's RoutingStats.

    ``wanted_counts``/``routed_counts``: [E] pre-/post-capacity-mask
    token-slot counts; ``topk_mass``: [S] raw gate probability mass on
    the selected (pre-capacity) experts; ``used_token``: optional [S]
    validity mask (padding tokens contribute nothing); ``held``:
    optional static (first, count) of the experts this program holds;
    ``chunks``: passes over the row buffers this invocation made."""
    ent = -jnp.sum(gates * jnp.log(jnp.clip(gates, _ENTROPY_EPS, 1.0)),
                   axis=-1)
    if used_token is not None:
        u = used_token.astype(jnp.float32)
        ent = ent * u
        topk_mass = topk_mass * u
        gate_tokens = u.sum()
    else:
        gate_tokens = jnp.float32(gates.shape[0])
    wanted = wanted_counts.astype(jnp.float32)
    routed = routed_counts.astype(jnp.float32)
    first, count = held if held is not None else (0, routed.shape[0])
    return RoutingStats(
        expert_counts=routed,
        overflow_counts=wanted - routed,
        tokens=wanted.sum(),
        dropped=(wanted - routed).sum(),
        entropy=ent.sum().astype(jnp.float32),
        confidence=topk_mass.sum().astype(jnp.float32),
        gate_tokens=gate_tokens,
        l_aux=l_aux.astype(jnp.float32),
        layers=jnp.float32(1.0),
        held_rows_max=routed[first:first + count].max(),
        dispatch_chunks=jnp.asarray(chunks, jnp.float32))


# ---- routing-stats collection tap ------------------------------------ #
# The model's loss function returns a scalar, so routing stats leave the
# traced program through a trace-time side channel: the engine installs
# a tap around the model apply INSIDE its loss_fn (same trace scope),
# MOELayer.apply emits each gate's RoutingStats into it, and the engine
# returns the summed pytree as a grad aux output.  The stack is plain
# trace-time Python state (tracing is single-threaded per process);
# nothing here runs per step at execution time.
_ACTIVE_TAPS: List[list] = []


@contextlib.contextmanager
def collect_routing_stats():
    """Context manager: collect every RoutingStats emitted while tracing
    the enclosed computation.  MUST wrap code in the SAME trace scope as
    the emissions — stats emitted inside an inner lax.scan body cannot
    escape to an outer tap (see sum_routing_stats)."""
    tap: list = []
    _ACTIVE_TAPS.append(tap)
    try:
        yield tap
    finally:
        _ACTIVE_TAPS.pop()


def emit_routing_stats(stats: RoutingStats) -> None:
    """Offer one gate invocation's stats to the innermost active tap
    (no-op when no tap is installed — gating stays side-effect-free
    outside a collecting engine)."""
    if _ACTIVE_TAPS:
        _ACTIVE_TAPS[-1].append(stats)


_SUM_WARNED = set()


def sum_routing_stats(entries: list) -> Optional[RoutingStats]:
    """Sum a tap's collected stats into one RoutingStats (None when
    nothing was emitted — a dense model under a collecting engine).

    Two degradations, both loud-once instead of crashing the trace:
    mixed expert counts across layers cannot share one [E] accumulator
    (entries whose num_experts differs from the first gate's are dropped
    entirely — the simplest honest contract); and stats emitted inside
    an INNER scan body (e.g. a
    hypothetical MoE layer under the ZeRO-3 streamed layer scan) are
    body-local tracers that cannot escape to this scope — they surface
    as escaped-tracer errors here and are dropped with a warning naming
    the fix (thread the layer out of the streamed scan or disable
    monitor.moe)."""
    if not entries:
        return None
    from ..utils.logging import logger
    e0 = entries[0].expert_counts.shape[0]
    keep, skipped = [], 0
    for s in entries:
        if s.expert_counts.shape[0] != e0:
            skipped += 1
            continue
        keep.append(s)
    if skipped and "mixed_E" not in _SUM_WARNED:
        _SUM_WARNED.add("mixed_E")
        logger.warning(
            f"routing stats: {skipped} gate(s) with num_experts != {e0} "
            "dropped from the accumulator — per-layer expert counts must "
            "match to share one [E] histogram (first layer wins)")
    total = keep[0]
    try:
        for s in keep[1:]:
            total = jax.tree.map(jnp.add, total, s)
        # touch the result so an escaped tracer surfaces HERE (a single
        # leaked entry raises on first use, which may be the return)
        total = jax.tree.map(lambda x: x + 0.0, total)
    except Exception as e:  # noqa: BLE001 — escaped inner-scan tracers
        if "escaped" not in _SUM_WARNED:
            _SUM_WARNED.add("escaped")
            logger.warning(
                "routing stats: emitted stats could not escape their "
                f"trace scope ({type(e).__name__}) — MoE layers inside "
                "an inner scan (e.g. the ZeRO-3 streamed layer scan) "
                "cannot feed the outer accumulator; their stats are "
                "dropped for this program")
        return None
    return total


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    """Static per-expert slot count (reference: sharded_moe.py:90)."""
    cap = int(np.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(idx, num_classes):
    return jax.nn.one_hot(idx.astype(jnp.int32), num_classes,
                          dtype=jnp.float32)


def gumbel_rsample(rng, shape):
    """Gumbel noise for the RSample noisy gate policy
    (reference: sharded_moe.py:57)."""
    return jax.random.gumbel(rng, shape, dtype=jnp.float32)


def top1gating_compact(
        logits: jnp.ndarray, capacity_factor: float = 1.0,
        min_capacity: int = 4, used_token: Optional[jnp.ndarray] = None,
        noisy_gate_policy: Optional[str] = None,
        rng: Optional[jax.Array] = None):
    """Top-1 gating, compact form — the single source of routing truth.

    Returns (l_aux, capacity, experts [S,1], slots [S,1], weights [S,1]
    fp32 with zeros for dropped tokens, exp_counts [E], stats
    RoutingStats).  ``exp_counts`` is POST-capacity-mask: a token the
    ``locations < capacity`` test dropped is not routed anywhere, so it
    must not count (the pre-capacity demand survives in
    ``stats.overflow_counts``).  The [S,E,C] mask form (top1gating)
    expands from this; the scatter dispatcher consumes it directly with
    O(S·d) memory instead of O(S·E·C).
    """
    num_tokens, num_experts = logits.shape
    capacity = _capacity(num_tokens, num_experts, capacity_factor,
                         min_capacity)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    select_logits = logits
    if noisy_gate_policy == "RSample":
        assert rng is not None, "RSample needs an rng"
        select_logits = logits + gumbel_rsample(rng, logits.shape)
    indices1 = jnp.argmax(select_logits, axis=-1)
    mask1 = _one_hot(indices1, num_experts)
    if used_token is not None:  # mask out padding tokens
        mask1 = mask1 * used_token.astype(mask1.dtype)[:, None]

    wanted_counts = mask1.sum(axis=0)  # pre-capacity demand per expert
    topk_mass = (gates * mask1).sum(axis=-1)

    # load-balance loss (reference: sharded_moe.py:133): fraction of router
    # probability × fraction of tokens per expert
    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    l_aux = jnp.sum(me * ce) * num_experts

    # position of each token within its expert's queue; drop over-capacity
    locations1 = jnp.cumsum(mask1, axis=0) - mask1
    mask1 = mask1 * (locations1 < capacity)
    locations1_s = (locations1 * mask1).sum(axis=-1)
    gates1_s = (gates * mask1).sum(axis=-1)  # 0 for dropped tokens

    exp_counts = mask1.sum(axis=0)
    stats = _routing_stats(gates, wanted_counts, exp_counts, topk_mass,
                           l_aux, used_token)
    return (l_aux, capacity, indices1[:, None],
            locations1_s.astype(jnp.int32)[:, None], gates1_s[:, None],
            exp_counts, stats)


def _expand_compact(capacity, num_experts, experts, slots, weights):
    """Compact routing -> legacy (combine [S,E,C], dispatch [S,E,C])."""
    combine = jnp.zeros((experts.shape[0], num_experts, capacity),
                        jnp.float32)
    for i in range(experts.shape[1]):
        combine = combine + (weights[:, i, None, None] *
                             _one_hot(experts[:, i], num_experts)[:, :, None] *
                             _one_hot(slots[:, i], capacity)[:, None, :])
    return combine, combine > 0


def top1gating(logits: jnp.ndarray, capacity_factor: float = 1.0,
               min_capacity: int = 4, used_token: Optional[jnp.ndarray] = None,
               noisy_gate_policy: Optional[str] = None,
               rng: Optional[jax.Array] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-1 gating (reference: sharded_moe.py:99).

    Returns (l_aux, combine_weights [S,E,C], dispatch_mask [S,E,C] bool,
    exp_counts [E] post-capacity, stats RoutingStats).
    """
    (l_aux, capacity, experts, slots, weights, exp_counts,
     stats) = top1gating_compact(
        logits, capacity_factor, min_capacity, used_token,
        noisy_gate_policy, rng)
    combine, dispatch = _expand_compact(capacity, logits.shape[1],
                                        experts, slots, weights)
    return l_aux, combine, dispatch, exp_counts, stats


def top2gating_compact(
        logits: jnp.ndarray, capacity_factor: float = 1.0,
        min_capacity: int = 4, rng: Optional[jax.Array] = None,
        noisy_gate_policy: Optional[str] = None):
    """Top-2 gating, compact form (see top1gating_compact).

    Returns (l_aux, capacity, experts [S,2], slots [S,2], weights [S,2]
    fp32 normalized over the kept choices with zeros for dropped slots,
    exp_counts [E] post-capacity, stats RoutingStats).  Top-2 doubles
    the slot budget (2 * capacity_factor), so stats.overflow_counts
    reflects demand against the DOUBLED capacity.
    """
    num_tokens, num_experts = logits.shape
    capacity = _capacity(num_tokens, num_experts, 2 * capacity_factor,
                         min_capacity)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    indices1 = jnp.argmax(logits, axis=-1)
    mask1 = _one_hot(indices1, num_experts)

    select2 = logits.astype(jnp.float32)
    if noisy_gate_policy == "RSample":
        assert rng is not None, "RSample needs an rng"
    if rng is not None:
        # Reference noises the second choice unconditionally
        # (sharded_moe.py:180 logits_w_noise); here that needs a key.
        select2 = select2 + gumbel_rsample(rng, logits.shape)
    select2 = select2 + mask1 * -1e9  # exclude the first expert
    indices2 = jnp.argmax(select2, axis=-1)
    mask2 = _one_hot(indices2, num_experts)

    wanted_counts = (mask1 + mask2).sum(axis=0)
    topk_mass = (gates * (mask1 + mask2)).sum(axis=-1)

    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    l_aux = jnp.sum(me * ce) * num_experts

    locations1 = jnp.cumsum(mask1, axis=0) - mask1
    # second-choice tokens queue behind all first-choice tokens
    locations2 = (jnp.cumsum(mask2, axis=0) - mask2 +
                  mask1.sum(axis=0, keepdims=True))
    mask1 = mask1 * (locations1 < capacity)
    mask2 = mask2 * (locations2 < capacity)
    locations1_s = (locations1 * mask1).sum(axis=-1)
    locations2_s = (locations2 * mask2).sum(axis=-1)

    gates1_s = (gates * mask1).sum(axis=-1)
    gates2_s = (gates * mask2).sum(axis=-1)
    denom = jnp.clip(gates1_s + gates2_s, 1e-9, None)
    gates1_s = gates1_s / denom
    gates2_s = gates2_s / denom

    experts = jnp.stack([indices1, indices2], axis=1)
    slots = jnp.stack([locations1_s, locations2_s], axis=1).astype(jnp.int32)
    weights = jnp.stack([gates1_s, gates2_s], axis=1)
    exp_counts = (mask1 + mask2).sum(axis=0)
    stats = _routing_stats(gates, wanted_counts, exp_counts, topk_mass,
                           l_aux)
    return l_aux, capacity, experts, slots, weights, exp_counts, stats


def top2gating(logits: jnp.ndarray, capacity_factor: float = 1.0,
               min_capacity: int = 4, rng: Optional[jax.Array] = None,
               noisy_gate_policy: Optional[str] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-2 gating (reference: sharded_moe.py:173).

    Second expert chosen with the top-1 expert masked out; gumbel noise is
    added to the selection when an rng is available (the reference noises
    unconditionally via torch's implicit global RNG; JAX needs an explicit
    key, so pass rng= for reference-parity stochastic second choice).
    Top-2 capacity doubles the slot budget like the reference (2 * S / E).
    Returns (l_aux, combine, dispatch, exp_counts [E] post-capacity,
    stats RoutingStats).
    """
    (l_aux, capacity, experts, slots, weights, exp_counts,
     stats) = top2gating_compact(
        logits, capacity_factor, min_capacity, rng, noisy_gate_policy)
    combine, dispatch = _expand_compact(capacity, logits.shape[1],
                                        experts, slots, weights)
    return l_aux, combine, dispatch, exp_counts, stats


class TopKGate:
    """Router with fp32 gate weights (reference: sharded_moe.py:247)."""

    def __init__(self, model_dim: int, num_experts: int, k: int = 1,
                 capacity_factor: float = 1.0,
                 eval_capacity_factor: float = 1.0, min_capacity: int = 4,
                 noisy_gate_policy: Optional[str] = None):
        assert k in (1, 2), "Only top-1 and top-2 gating are supported"
        self.model_dim = model_dim
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy

    def init_params(self, rng):
        scale = 1.0 / np.sqrt(self.model_dim)
        return {"wg": (jax.random.normal(
            rng, (self.model_dim, self.num_experts), jnp.float32) * scale)}

    def apply(self, params, x, rng=None, train=True):
        """x: [S, d] tokens → (l_aux, combine, dispatch, exp_counts,
        stats) — the legacy [S,E,C] form, expanded from the compact
        routing so the einsum and scatter dispatch paths can never route
        differently."""
        l_aux, capacity, experts, slots, weights, exp_counts, stats = \
            self.apply_compact(params, x, rng=rng, train=train)
        combine, dispatch = _expand_compact(capacity, self.num_experts,
                                            experts, slots, weights)
        return l_aux, combine, dispatch, exp_counts, stats

    def apply_compact(self, params, x, rng=None, train=True):
        """x: [S, d] → (l_aux, capacity, experts [S,k], slots [S,k],
        weights [S,k], exp_counts, stats) — no [S,E,C]
        materialization."""
        x32 = x.astype(jnp.float32)
        if train and self.noisy_gate_policy == "Jitter":
            if rng is None:
                raise ValueError(
                    "noisy_gate_policy='Jitter' needs an rng during training "
                    "— pass rng= to MoE.apply (RSample enforces the same)")
            rng, sub = jax.random.split(rng)
            x32 = x32 * jax.random.uniform(
                sub, x32.shape, jnp.float32, 1.0 - JITTER_EPS, 1.0 + JITTER_EPS)
        logits = x32 @ params["wg"]
        cf = self.capacity_factor if train else self.eval_capacity_factor
        policy = self.noisy_gate_policy if train else None
        rng = rng if train else None
        if self.k == 1:
            return top1gating_compact(logits, cf, self.min_capacity,
                                      noisy_gate_policy=policy, rng=rng)
        return top2gating_compact(logits, cf, self.min_capacity, rng=rng,
                                  noisy_gate_policy=policy)


class MOELayer:
    """GShard MoE layer (reference: sharded_moe.py:312).

    expert: an object with init_params(rng, x) / apply(params, x, rng=None)
    (the PipeLayer protocol) applied per-expert to [C, d] slot buffers.
    """

    def __init__(self, gate: TopKGate, expert, num_local_experts_total: int,
                 dispatch_impl: str = "scatter"):
        if dispatch_impl not in ("scatter", "einsum"):
            raise ValueError(f"dispatch_impl must be 'scatter' or 'einsum', "
                             f"got {dispatch_impl!r}")
        self.gate = gate
        self.expert = expert
        self.num_experts = num_local_experts_total
        self.dispatch_impl = dispatch_impl

    def init_params(self, rng, x):
        gate_rng, exp_rng = jax.random.split(rng)
        token_shape = x.reshape(-1, x.shape[-1])[:1]
        expert_params = []
        for i in range(self.num_experts):
            expert_params.append(self.expert.init_params(
                jax.random.fold_in(exp_rng, i), token_shape))
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                               *expert_params)
        return {"gate": self.gate.init_params(gate_rng), "experts": stacked}

    def param_partition_specs(self, params=None):
        from jax.sharding import PartitionSpec
        if params is None:
            # Zero-arg protocol (engine/pipe discovery): recover the param
            # tree structure abstractly — no arrays are materialized.
            params = jax.eval_shape(
                self.init_params, jax.random.PRNGKey(0),
                jax.ShapeDtypeStruct((1, self.gate.model_dim), jnp.float32))
        return {
            "gate": jax.tree.map(lambda _: None, params["gate"]),
            "experts": jax.tree.map(lambda _: PartitionSpec(EXPERT_AXIS),
                                    params["experts"]),
        }

    def apply(self, params, x, rng=None, train=True, tp_axis=None):
        """x: [..., d] → (y [..., d], l_aux, exp_counts).

        Two dispatch implementations (both lower the token→slot resharding
        to the reference's two all-to-alls, sharded_moe.py:358,366):

        - "scatter" (default): tokens scatter-add into their [E, C, d]
          slots by flat slot id and gather back weighted — O(S·k·d)
          working set, the TPU-idiomatic form at scale;
        - "einsum": the GShard-paper [S, E, C] mask einsums — O(S·E·C)
          memory, kept as the parity reference.

        tp_axis: MANUAL tensor parallelism over the expert FFNs — the
        gate runs replicated (wg replicated → identical logits → every
        model peer routes identically), dispatch/combine stay local, and
        each expert computes with local Megatron shards + explicit psum
        (ExpertMLP.apply_tp).  This is how MoE composes with the gated
        pipeline executor's manual model axis (reference: the expert FFN
        position of sharded_moe.py:312 under Megatron mp).
        """
        if self.dispatch_impl == "scatter":
            return self._apply_scatter(params, x, rng=rng, train=train,
                                       tp_axis=tp_axis)
        return self._apply_einsum(params, x, rng=rng, train=train,
                                  tp_axis=tp_axis)

    def _expert_apply(self, params, dispatched, tp_axis):
        if tp_axis is not None:
            return jax.vmap(
                lambda p, slot: self.expert.apply_tp(p, slot, tp_axis))(
                    params, dispatched)
        return jax.vmap(
            lambda p, slot: self.expert.apply(p, slot, rng=None))(
                params, dispatched)

    def _apply_scatter(self, params, x, rng=None, train=True, tp_axis=None):
        orig_shape = x.shape
        d_model = x.shape[-1]
        tokens = x.reshape(-1, d_model)
        s = tokens.shape[0]

        l_aux, capacity, experts, slots, weights, exp_counts, stats = \
            self.gate.apply_compact(params["gate"], tokens, rng=rng,
                                    train=train)
        emit_routing_stats(stats)
        k = experts.shape[1]
        e_total = self.num_experts
        valid = weights > 0.0
        # flat slot id; dropped tokens land in a dump row that is sliced off
        flat_slot = jnp.where(valid, experts * capacity + slots,
                              e_total * capacity)

        # manual TP: the "f" operator on the EXPERT-dispatch input only
        # (identity fwd / psum bwd) — each peer's expert shard produces a
        # PARTIAL token cotangent that the psum restores to full for the
        # replicated upstream.  The gate above reads the raw tokens: its
        # computation is replicated per peer and its cotangent is already
        # full — routing it through the psum would overcount it by tp.
        tokens_e = tokens
        if tp_axis is not None:
            from ..ops.tp_collectives import tp_fcast
            tokens_e = tp_fcast(tokens, tp_axis)

        # dispatch (all-to-all #1): scatter-add — valid (expert, slot)
        # pairs are unique by construction, so add == set for them
        flat = jnp.zeros((e_total * capacity + 1, d_model), x.dtype)
        contrib = jnp.where(valid[..., None],
                            jnp.broadcast_to(tokens_e[:, None, :],
                                             (s, k, d_model)), 0)
        flat = flat.at[flat_slot.reshape(-1)].add(
            contrib.reshape(-1, d_model).astype(x.dtype))
        dispatched = _constrain_expert(
            flat[:e_total * capacity].reshape(e_total, capacity, d_model))

        expert_out = self._expert_apply(params["experts"], dispatched,
                                        tp_axis)
        expert_out = _constrain_expert(expert_out)

        # combine (all-to-all #2): gather each token's k slot outputs and
        # weight them; the dump row contributes zero weight
        flat_out = jnp.concatenate(
            [expert_out.reshape(e_total * capacity, d_model),
             jnp.zeros((1, d_model), expert_out.dtype)], axis=0)
        gathered = flat_out[flat_slot]                  # [S, k, d]
        out = (weights[..., None].astype(gathered.dtype) * gathered).sum(
            axis=1)
        return out.astype(x.dtype).reshape(orig_shape), l_aux, exp_counts

    def _apply_einsum(self, params, x, rng=None, train=True, tp_axis=None):
        orig_shape = x.shape
        d_model = x.shape[-1]
        tokens = x.reshape(-1, d_model)

        l_aux, combine, dispatch, exp_counts, stats = self.gate.apply(
            params["gate"], tokens, rng=rng, train=train)
        emit_routing_stats(stats)

        tokens_e = tokens
        if tp_axis is not None:  # see _apply_scatter: expert input only
            from ..ops.tp_collectives import tp_fcast
            tokens_e = tp_fcast(tokens, tp_axis)

        # dispatch: [S, E, C] × [S, d] → [E, C, d]   (all-to-all #1)
        dispatched = jnp.einsum("sec,sd->ecd",
                                dispatch.astype(x.dtype), tokens_e)
        dispatched = _constrain_expert(dispatched)

        expert_out = self._expert_apply(params["experts"], dispatched,
                                        tp_axis)
        expert_out = _constrain_expert(expert_out)

        # combine: [S, E, C] × [E, C, d] → [S, d]    (all-to-all #2)
        out = jnp.einsum("sec,ecd->sd", combine.astype(x.dtype), expert_out)
        return out.reshape(orig_shape), l_aux, exp_counts


def _constrain_expert(x):
    """Pin the [E, C, d] buffer's leading dim to the expert axis when a mesh
    is live (no-op otherwise, so gating stays unit-testable without a mesh)."""
    from ..parallel import mesh as mesh_mod
    ctx = mesh_mod.get_mesh_context(required=False)
    if ctx is None or ctx.axis_size(EXPERT_AXIS) == 1:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, PartitionSpec(EXPERT_AXIS)))
