"""A mixture-of-experts layer that drops no token and pads to no
capacity: k of E routing, rows sorted by expert, the experts' products as
one grouped product over ragged counts (ops/grouped_matmul.py), a shared
expert added once.  Which expert the routed and the shared ones are is
the builder's (``expert``: moe/experts.py ``GatedExpertMLP``, silu-gated
with three matrices, unless told ``ReluSquaredExpertMLP``, not gated,
with two); the routing, the dispatch and the walk do not look inside.

The layer is told which experts it HOLDS, a contiguous range of the E
the router scores.  It routes over all E, computes its own experts' part
of the result for the tokens routed to them, and leaves out what the
others would have added: what expert parallelism asks of one rank.  A
pick that lands on an absent expert still takes its part of the
normalisation.  The held range comes from ``experts_held`` (first,
count); with a mesh whose expert axis is larger than one it would be the
rank's share (not written yet: ROADMAP R1), so such a mesh is refused.

The row buffers hold the held experts' even share of the picks
(``dispatch_capacity``), not the ``tokens x k`` rows of the worst case:
the routed rows are walked in chunks of that many, as many as they take
(a trip count made on the device: none where no pick landed here, one
under an even router, ``num_experts / count`` where every pick did), so
that nothing is dropped and no shape is dynamic.  A layer built with
``first_chunk_always`` walks its first chunk whatever the counts and
leaves the chunks beyond it to the trip count: a trip costs what its
buffers cost, not what its rows do, so a rank whose experts get no pick
in one step and a stray one in the next pays by the step otherwise.

The older ``MOELayer`` (top-1 / top-2 with a capacity and drops) is
untouched beside it.
"""

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.grouped_matmul import TILE_ROWS
from ..parallel import mesh as mesh_mod
from .experts import GatedExpertMLP
from .sharded_moe import RoutingStats, _routing_stats

# The picks of a layer, named so that a recomputing checkpoint keeps them
# (checkpointing.ALWAYS_KEPT): a top-k recomputed in other fusions can
# flip a near tie, and the backward pass must differentiate the experts
# the forward pass used.
PICKS_NAME = "routing_picks"


class Routing(NamedTuple):
    picks: jnp.ndarray      # int32 [T, k] experts picked, of all E
    weights: jnp.ndarray    # f32 [T, k] what each pick's output is times
    scores: jnp.ndarray     # f32 [T, E] the router's scores
    counts: jnp.ndarray     # int32 [E] picks an expert
    inputs: Optional[jnp.ndarray] = None   # [T, d] what the router read


def route_topk(logits, k: int, score: str = "sigmoid",
               renormalize: bool = True, scale: float = 1.0,
               picks=None, bias=None) -> Routing:
    """k of E without a capacity.  ``logits`` f32 [T, E]; ``score``:
    "sigmoid" or "softmax" over the E; ``renormalize``: the k picked
    scores are divided by their sum; ``scale`` multiplies the weights.
    ``picks`` (int32 [T, k]) replaces the choice and keeps everything
    else: the weights are the scores at those picks.  ``bias`` (f32 [E])
    is added to the scores for the choice alone: the k largest of
    ``scores + bias`` are picked, ``scores`` and the weights are what
    they are without it, and no gradient reaches it (a choice has
    none)."""
    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"score must be sigmoid or softmax, got {score!r}")
    logits = logits.astype(jnp.float32)
    scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if picks is None:
        chosen_by = scores if bias is None else (
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)))
        _, picks = jax.lax.top_k(chosen_by, k)
    picks = checkpoint_name(picks.astype(jnp.int32), PICKS_NAME)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) if (
        renormalize) else picked
    # a compare and a sum, which the TPU does far faster than a scatter
    counts = jnp.sum(picks[..., None] == jnp.arange(logits.shape[-1]),
                     axis=(0, 1), dtype=jnp.int32)
    return Routing(picks, scale * weights, scores, counts)


def sort_by_expert(picks, first: int, count: int):
    """The picks that landed on the held experts ``[first, first +
    count)``, sorted by expert.  Returns (order, position):

    order     int32 [T k]: ``order[r]`` is the flat pick (token k + j)
              at sorted row r; the picks that landed elsewhere come last
    position  int32 [T k]: the inverse, the row of each flat pick
    """
    local = picks.reshape(-1) - first
    held = jnp.logical_and(local >= 0, local < count)
    order = jnp.argsort(jnp.where(held, local, count),
                        stable=True).astype(jnp.int32)
    # a second sort inverts the permutation in 0.30 ms; a scatter takes
    # 0.67 (my chip run, PR 36)
    return order, jnp.argsort(order).astype(jnp.int32)


def dispatch_capacity(tokens: int, k: int, count: int,
                      num_experts: int, headroom: float = 1.0) -> int:
    """Rows of a chunk of the dispatch: the ``tokens k`` picks' even
    share for ``count`` of ``num_experts`` experts, what a router that
    favours nobody sends here, times ``headroom``, in whole tiles of the
    grouped product; never more than the picks there are (every expert
    held: one chunk of all of them)."""
    picks = tokens * k
    share = -(-picks * count // num_experts)
    if headroom != 1.0:
        share = math.ceil(share * headroom)
    return min(-(-share // TILE_ROWS) * TILE_ROWS, picks)


def dispatch_chunks(counts, capacity: int):
    """Chunks of ``capacity`` rows that hold the ``sum(counts)`` routed
    rows (int32 scalar; 0 where no pick landed here)."""
    return (jnp.sum(counts) + capacity - 1) // capacity


def _chunk_counts(counts, start, capacity: int):
    """Rows of each expert that lie in sorted rows ``[start, start +
    capacity)``."""
    hi = jnp.cumsum(counts)
    lo = hi - counts
    return (jnp.clip(hi, start, start + capacity)
            - jnp.clip(lo, start, start + capacity))


# The two halves of the dispatch, each the other's transpose, both
# gathers: the transpose of a gather by a permutation is the gather by
# its inverse, which XLA cannot know and would scatter-add (1.3 us a row:
# PERF.md section 5, ``embed``).
def _chunk_picks(order, start, capacity: int):
    """The flat picks at sorted rows ``[start, start + capacity)``."""
    return jax.lax.dynamic_slice(order, (start,), (capacity,))


def _rows_from_tokens(x, picks, k: int):
    """x [T, d] -> [C, d]: row r holds the token of flat pick
    ``picks[r]``."""
    return x[picks // k]


def _row_of_pick(position, start, capacity: int):
    """Where each pick's row lies in the chunk at ``start``; ``capacity``
    (the row after the chunk's) where it lies in another."""
    at = position - start
    return jnp.where(jnp.logical_and(at >= 0, at < capacity), at, capacity)


def _tokens_from_rows(rows, position, start, weights, acc):
    """rows [C, d] -> acc [T, d] (float32) plus, for token t, the rows
    of its picks that lie in sorted rows ``[start, start + C)``, each
    times its pick's weight (``weights`` None: times one); a pick whose
    row lies elsewhere reads a zero row.  One gather of T rows a pick:
    no [T k, d] array."""
    at = _row_of_pick(position, start, rows.shape[0])
    rows = jnp.pad(rows, ((0, 1), (0, 0)))
    for j in range(position.shape[1]):
        picked = rows[at[:, j]].astype(jnp.float32)
        if weights is not None:
            picked = picked * weights[:, j, None]
        acc = acc + picked
    return acc


class _Chunks(NamedTuple):
    """What the walk over chunks is told and does not differentiate."""
    expert: GatedExpertMLP
    k: int
    capacity: int
    first_always: bool = False


def _walk(chunks: _Chunks, trips, chunk, init):
    """``chunk(c, carry)`` over the ``trips`` chunks that hold the routed
    rows, from ``init``.  With ``first_always`` chunk 0 runs outside the
    loop, rows or none (its sums start from zeros that XLA folds away),
    and the loop takes the chunks beyond it."""
    if chunks.first_always:
        return jax.lax.fori_loop(1, trips, chunk, chunk(0, init))
    return jax.lax.fori_loop(0, trips, chunk, init)


def _padded_order(order, capacity):
    """``order`` to a whole number of chunks, so that the last chunk's
    slice starts where it says (the rows added lie past every count)."""
    return jnp.pad(order, (0, -order.shape[0] % capacity))


# A loop with a trip count made on the device has no reverse-mode rule,
# so the routed experts are one custom_vjp: the backward rule walks the
# same chunks, runs a chunk's experts again and takes their vjp.  Nothing
# of a chunk's size is kept between the passes.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed_experts(chunks: _Chunks, x, params, weights, order, position,
                    counts):
    """The held experts' part of the layer's sum: x [T, d], ``params``
    the experts' stacked weights, ``weights`` f32 [T, k], ``order`` /
    ``position`` of ``sort_by_expert`` (position as [T, k]), ``counts``
    [G] rows a held expert -> [T, d] in x's dtype, summed in float32."""
    expert, k, capacity = chunks[:3]
    order = _padded_order(order, capacity)

    def chunk(c, acc):
        start = c * capacity
        with jax.named_scope("dispatch"):
            rows = _rows_from_tokens(
                x, _chunk_picks(order, start, capacity), k)
        with jax.named_scope("experts"):
            out = expert.apply_grouped(
                params, rows, _chunk_counts(counts, start, capacity))
        with jax.named_scope("dispatch"):
            return _tokens_from_rows(out, position, start, weights, acc)

    y = _walk(chunks, dispatch_chunks(counts, capacity), chunk,
              jnp.zeros(x.shape, jnp.float32))
    return y.astype(x.dtype)


def _routed_experts_fwd(chunks, x, params, weights, order, position, counts):
    return (_routed_experts(chunks, x, params, weights, order, position,
                            counts),
            (x, params, weights, order, position, counts))


def _routed_experts_bwd(chunks, res, g):
    expert, k, capacity = chunks[:3]
    x, params, weights, order, position, counts = res
    order = _padded_order(order, capacity)

    def chunk(c, carry):
        dx, dparams, dweights = carry
        start = c * capacity
        with jax.named_scope("dispatch"):
            picks = _chunk_picks(order, start, capacity)
            rows = _rows_from_tokens(x, picks, k)
            g_rows = _rows_from_tokens(g, picks, k).astype(jnp.float32)

        def experts_of(params, rows):
            # the scope next under jax.vjp's mark is the one XLA names an
            # operation by: the kernels keep their names (gmm_rows, ...)
            with jax.named_scope("experts"):
                return expert.apply_grouped(
                    params, rows, _chunk_counts(counts, start, capacity))

        out, vjp = jax.vjp(experts_of, params, rows)
        with jax.named_scope("dispatch"):
            # a pick's weight: its row's output against its token's
            # cotangent, read by the pick's position like the row itself
            per_row = jnp.sum(g_rows * out.astype(jnp.float32), axis=-1)
            dweights = dweights + jnp.pad(per_row, (0, 1))[
                _row_of_pick(position, start, capacity)]
            # the transpose of tokens-from-rows: a row's cotangent is its
            # token's times its pick's weight
            dout = g_rows * weights.reshape(-1)[picks][:, None]
        dp, drows = vjp(dout.astype(out.dtype))
        with jax.named_scope("experts"):
            dparams = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), dparams, dp)
        with jax.named_scope("dispatch"):
            dx = _tokens_from_rows(drows, position, start, None, dx)
        return dx, dparams, dweights

    dx, dparams, dweights = _walk(
        chunks, dispatch_chunks(counts, capacity), chunk,
        jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                     (x, params, weights)))
    return (dx.astype(x.dtype),
            jax.tree.map(lambda d, p: d.astype(p.dtype), dparams, params),
            dweights.astype(weights.dtype), None, None, None)


_routed_experts.defvjp(_routed_experts_fwd, _routed_experts_bwd)


class DroplessMoE:
    """Router, held routed experts and a shared expert; the PipeLayer
    protocol (init_params / apply) like ``MoE``."""

    def __init__(self, hidden_size: int, num_experts: int, k: int,
                 expert_ff_size: int, shared_ff_size: Optional[int] = None,
                 score: str = "sigmoid", renormalize: bool = True,
                 scale: float = 1.0,
                 experts_held: Optional[Tuple[int, int]] = None,
                 init_std: float = 0.02, selection_bias: bool = False,
                 first_chunk_always: bool = False,
                 dispatch_headroom: float = 1.0,
                 expert=GatedExpertMLP, own_router: bool = True):
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(
                f"experts_held=({first}, {count}) is no range of the "
                f"{num_experts} experts the router scores")
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        self.k = k
        self.score, self.renormalize, self.scale = score, renormalize, scale
        self.experts_held = (first, count)
        # ``expert``: the class of the routed experts and of the shared one
        self.expert = expert(hidden_size, expert_ff_size, init_std)
        self.shared = (expert(hidden_size, shared_ff_size, init_std)
                       if shared_ff_size else None)
        self.init_std = init_std
        # a float32 leaf [E] beside the router's matrix, added to the
        # scores for the choice alone (route_topk); no gradient moves it:
        # whoever trains the layer moves it from the experts' counts
        self.selection_bias = selection_bias
        # the walk's first chunk runs whatever the counts (``_walk``)
        self.first_chunk_always = first_chunk_always
        # even shares of the picks the row buffers hold.  At 1 a router
        # that favours nobody sits on the edge between one trip and two
        # (the routed rows scatter about the share, and a trip costs its
        # buffers); with room above the share an even router takes one
        # trip every step (models/keye_vl2.py has the chip's numbers)
        if dispatch_headroom < 1.0:
            raise ValueError("dispatch_headroom is at least one even "
                             f"share, got {dispatch_headroom}")
        self.dispatch_headroom = dispatch_headroom
        # False: the layer has no router matrix of its own and is handed
        # the logits of the caller's router module (``apply(logits=)``:
        # models/zaya.py, an MLP with a state carried from layer to layer)
        self.own_router = own_router

    def _check_mesh(self):
        ctx = mesh_mod.get_mesh_context(required=False)
        if ctx is not None and ctx.expert_parallel_world_size > 1:
            raise NotImplementedError(
                "DroplessMoE takes its held range from experts_held; the "
                "mesh's expert axis is "
                f"{ctx.expert_parallel_world_size}: the rank's share and "
                "the exchange of rows between ranks are not written")

    def init_params(self, rng, x=None):
        k_router, k_experts, k_shared = jax.random.split(rng, 3)
        first, count = self.experts_held
        # expert e's weights depend on e alone, not on the range held
        keys = jax.vmap(lambda e: jax.random.fold_in(k_experts, e))(
            first + jnp.arange(count))
        params = {"experts": jax.vmap(self.expert.init_params)(keys)}
        if self.own_router:
            params["router"] = self.init_std * jax.random.normal(
                k_router, (self.hidden_size, self.num_experts), jnp.float32)
        if self.shared is not None:
            params["shared"] = self.shared.init_params(k_shared)
        if self.selection_bias:
            params["bias"] = jnp.zeros((self.num_experts,), jnp.float32)
        return params

    def param_partition_specs(self, params=None):
        self._check_mesh()
        if params is None:
            params = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), params)

    def capacity(self, tokens: int) -> int:
        """Rows of the layer's row buffers on ``tokens`` tokens: the held
        experts' even share of the picks times ``dispatch_headroom``
        (``dispatch_capacity``)."""
        return dispatch_capacity(tokens, self.k, self.experts_held[1],
                                 self.num_experts, self.dispatch_headroom)

    def chunks_walked(self, counts, tokens: int):
        """Trips of the walk over ``counts`` [G] rows a held expert."""
        trips = dispatch_chunks(counts, self.capacity(tokens))
        return jnp.maximum(trips, 1) if self.first_chunk_always else trips

    def working_set_bytes(self, tokens: int, itemsize: int) -> int:
        """Bytes of the rows one layer holds at once, forward or backward:
        a chunk of ``capacity`` rows three times at the model's width
        (the gathered rows, their tokens' cotangents, the experts'
        output) and, at the experts' width, the first product's result
        (twice as wide under a gate, ``first_widths``) and the rows
        between the two products, three times in all for a gated expert
        and twice for one without a gate; and the float32 [tokens, d]
        sum the chunks add into (tests/unit/test_dropless_chunks.py
        reads the same off the backward walk's jaxpr)."""
        return (self.capacity(tokens) * itemsize * (
            3 * self.hidden_size
            + (self.expert.first_widths + 1) * self.expert.d_ff)
            + tokens * self.hidden_size * 4)

    def route(self, params, x, picks=None, logits=None) -> Routing:
        """x [T, d] -> the routing; the product in float32 whatever x is.
        ``logits`` (f32 [T, E]) are a caller's router's and take the
        product's place: a layer built with ``own_router=False`` has no
        matrix and takes nothing else."""
        if (logits is None) == (not self.own_router):
            raise ValueError(
                "DroplessMoE: a layer with its own router takes no logits, "
                "and one built with own_router=False needs its caller's")
        with jax.named_scope("router"):
            if logits is None:
                logits = jnp.dot(x.astype(jnp.float32),
                                 params["router"].astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST)
            return route_topk(logits, self.k, self.score, self.renormalize,
                              self.scale, picks, params.get("bias")
                              )._replace(inputs=x)

    def stats(self, routing: Routing) -> RoutingStats:
        """The layer's RoutingStats: every pick is routed (none dropped);
        entropy of the scores normalised to sum to one."""
        share = routing.scores / jnp.sum(routing.scores, -1, keepdims=True)
        mass = jnp.sum(jnp.take_along_axis(share, routing.picks, -1), -1)
        first, count = self.experts_held
        return _routing_stats(
            share, routing.counts, routing.counts, mass, jnp.float32(0.0),
            held=self.experts_held, chunks=self.chunks_walked(
                routing.counts[first:first + count], share.shape[0]))

    def apply(self, params, x, picks=None, logits=None):
        """x [..., d] -> (y [..., d], Routing).  ``picks`` forces the
        choice (a comparison with a reference on the same picks);
        ``logits`` [..., E] are the caller's router's (``route``)."""
        self._check_mesh()
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        tokens, k = x.shape[0], self.k
        first, count = self.experts_held
        if logits is not None:
            logits = logits.reshape(-1, self.num_experts)
        routing = self.route(params, x, picks, logits)
        with jax.named_scope("dispatch"):
            order, position = sort_by_expert(routing.picks, first, count)
        y = _routed_experts(
            _Chunks(self.expert, k, self.capacity(tokens),
                    self.first_chunk_always), x,
            params["experts"], routing.weights, order,
            position.reshape(tokens, k), routing.counts[first:first + count])
        if self.shared is not None:
            with jax.named_scope("shared"):
                y = y + self.shared.apply(params["shared"], x)
        return y.reshape(shape), routing
