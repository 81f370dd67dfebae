"""A mixture-of-experts layer that drops no token and pads to no
capacity: k of E routing, rows sorted by expert, the experts' products as
one grouped product over ragged counts (ops/grouped_matmul.py), a shared
expert added once.

The layer is told which experts it HOLDS, a contiguous range of the E
the router scores.  It routes over all E, computes its own experts' part
of the result for the tokens routed to them, and leaves out what the
others would have added: what expert parallelism asks of one rank.  A
pick that lands on an absent expert still takes its part of the
normalisation.  The held range comes from ``experts_held`` (first,
count); with a mesh whose expert axis is larger than one it would be the
rank's share (not written yet: ROADMAP R1), so such a mesh is refused.

The older ``MOELayer`` (top-1 / top-2 with a capacity and drops) is
untouched beside it.
"""

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

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
               picks=None) -> Routing:
    """k of E without a capacity.  ``logits`` f32 [T, E]; ``score``:
    "sigmoid" or "softmax" over the E; ``renormalize``: the k picked
    scores are divided by their sum; ``scale`` multiplies the weights.
    ``picks`` (int32 [T, k]) replaces the choice and keeps everything
    else: the weights are the scores at those picks."""
    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"score must be sigmoid or softmax, got {score!r}")
    logits = logits.astype(jnp.float32)
    scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if picks is None:
        _, picks = jax.lax.top_k(scores, k)
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


# The transpose of a gather by a permutation is the gather by its
# inverse, which XLA cannot know and would scatter-add: both directions
# of the dispatch are written as gathers.  At the benchmark cell's shapes
# (131,072 rows of 2,048, bf16; my chip run, PR 36) the transposes written
# here take 5.26 and 4.41 ms a layer where autodiff's of the plain
# ``x[idx]`` take 9.67 and 14.76.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_rows(x, order, position, k):
    """x [T, d] -> [T k, d]: sorted row r holds the token of flat pick
    ``order[r]``."""
    return x[order // k]


def _to_rows_fwd(x, order, position, k):
    return x[order // k], (position,)


def _to_rows_bwd(k, res, g):
    (position,) = res
    g = g[position].reshape(-1, k, g.shape[-1])
    return jnp.sum(g.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _to_picks(rows, order, position):
    """rows [T k, d] -> the same in flat pick order."""
    return rows[position]


def _to_picks_fwd(rows, order, position):
    return rows[position], (order,)


def _to_picks_bwd(res, g):
    (order,) = res
    return g[order], None, None


_to_picks.defvjp(_to_picks_fwd, _to_picks_bwd)


class DroplessMoE:
    """Router, held routed experts and a shared expert; the PipeLayer
    protocol (init_params / apply) like ``MoE``."""

    def __init__(self, hidden_size: int, num_experts: int, k: int,
                 expert_ff_size: int, shared_ff_size: Optional[int] = None,
                 score: str = "sigmoid", renormalize: bool = True,
                 scale: float = 1.0,
                 experts_held: Optional[Tuple[int, int]] = None,
                 init_std: float = 0.02):
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
        self.expert = GatedExpertMLP(hidden_size, expert_ff_size, init_std)
        self.shared = (GatedExpertMLP(hidden_size, shared_ff_size, init_std)
                       if shared_ff_size else None)
        self.init_std = init_std

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
        params = {
            "router": self.init_std * jax.random.normal(
                k_router, (self.hidden_size, self.num_experts), jnp.float32),
            "experts": jax.vmap(self.expert.init_params)(keys)}
        if self.shared is not None:
            params["shared"] = self.shared.init_params(k_shared)
        return params

    def param_partition_specs(self, params=None):
        self._check_mesh()
        if params is None:
            params = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), params)

    def working_set_bytes(self, tokens: int, itemsize: int) -> int:
        """Bytes of the rows one layer holds at once, forward or backward:
        every pick a row (the worst case the buffers are sized for), in
        and out of the experts at the model's width three times over (the
        sorted rows, the experts' output, the same back in pick order)
        and three times the experts' width between the two products."""
        return tokens * self.k * itemsize * 3 * (
            self.hidden_size + self.expert.d_ff)

    def route(self, params, x, picks=None) -> Routing:
        """x [T, d] -> the routing; the product in float32 whatever x is."""
        with jax.named_scope("router"):
            logits = jnp.dot(x.astype(jnp.float32),
                             params["router"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            return route_topk(logits, self.k, self.score, self.renormalize,
                              self.scale, picks)._replace(inputs=x)

    def stats(self, routing: Routing) -> RoutingStats:
        """The layer's RoutingStats: every pick is routed (none dropped);
        entropy of the scores normalised to sum to one."""
        share = routing.scores / jnp.sum(routing.scores, -1, keepdims=True)
        mass = jnp.sum(jnp.take_along_axis(share, routing.picks, -1), -1)
        return _routing_stats(share, routing.counts, routing.counts, mass,
                              jnp.float32(0.0), held=self.experts_held)

    def apply(self, params, x, picks=None):
        """x [..., d] -> (y [..., d], Routing).  ``picks`` forces the
        choice (a comparison with a reference on the same picks)."""
        self._check_mesh()
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        tokens, k = x.shape[0], self.k
        first, count = self.experts_held
        routing = self.route(params, x, picks)
        with jax.named_scope("dispatch"):
            order, position = sort_by_expert(routing.picks, first, count)
            rows = routing.counts[first:first + count]
            sorted_x = _to_rows(x, order, position, k)
        with jax.named_scope("experts"):
            out = self.expert.apply_grouped(params["experts"], sorted_x,
                                            rows)
        with jax.named_scope("dispatch"):
            # back in (token, pick) order; a pick that landed elsewhere
            # reads a row past the held ones: zero
            out = _to_picks(out, order, position).reshape(tokens, k, -1)
            y = jnp.sum(out.astype(jnp.float32)
                        * routing.weights[..., None], axis=1).astype(x.dtype)
        if self.shared is not None:
            with jax.named_scope("shared"):
                y = y + self.shared.apply(params["shared"], x)
        return y.reshape(shape), routing
