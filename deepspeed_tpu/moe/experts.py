"""Expert modules (reference: deepspeed/moe/experts.py:9 — class Experts).

The reference deep-copies the user's expert module `num_local_experts` times
per rank and tags params `allreduce=False, group_name` so the engine reduces
them over the expert-data group only.  Under SPMD the stacked [E, ...] expert
params carry a leading "expert" PartitionSpec instead (each expert-parallel
shard holds E/ep_size experts), and the gradient reduction scope follows from
the sharding — no tags needed.

Three experts live here: ``ExpertMLP`` (GeLU with biases, the capacity
path's default), and the dropless path's two without bias
(moe/dropless.py takes either as ``expert=``): ``GatedExpertMLP``
(``silu(gate) * up``, ``w1 [d, 2 ff]``) and ``ReluSquaredExpertMLP`` (no
gate, ``relu(up)^2``, ``w1 [d, ff]``), both with ``apply`` for one expert
and ``apply_grouped`` for a stack on rows sorted by expert.
"""

import numpy as np

import jax
import jax.numpy as jnp


class ExpertMLP:
    """Default expert: 2-layer GeLU MLP, the standard GShard/transformer
    expert shape (plays the role of the user-supplied expert module in
    reference moe/layer.py:18)."""

    def __init__(self, d_model: int, d_ff: int = None):
        self.d_model = d_model
        self.d_ff = d_ff or 4 * d_model

    def init_params(self, rng, x):
        k1, k2 = jax.random.split(rng)
        s1 = 1.0 / np.sqrt(self.d_model)
        s2 = 1.0 / np.sqrt(self.d_ff)
        return {
            "wi": jax.random.normal(k1, (self.d_model, self.d_ff),
                                    jnp.float32) * s1,
            "bi": jnp.zeros((self.d_ff,), jnp.float32),
            "wo": jax.random.normal(k2, (self.d_ff, self.d_model),
                                    jnp.float32) * s2,
            "bo": jnp.zeros((self.d_model,), jnp.float32),
        }

    def apply(self, params, x, rng=None):
        h = jax.nn.gelu(x @ params["wi"].astype(x.dtype) +
                        params["bi"].astype(x.dtype))
        return h @ params["wo"].astype(x.dtype) + params["bo"].astype(x.dtype)

    def apply_tp(self, params, x, tp_axis: str):
        """Megatron-split expert for MANUAL tensor parallelism: params are
        LOCAL shards (wi/bi column-split, wo row-split on the d_ff dim —
        tp_partition_specs) and the output partials are psum'd explicitly
        (tp_psum is branch-safe inside the gated executor's lax.cond,
        unlike GSPMD-placed collectives).  The replicated output bias is
        added AFTER the psum, once."""
        from ..ops.tp_collectives import tp_psum

        h = jax.nn.gelu(x @ params["wi"].astype(x.dtype) +
                        params["bi"].astype(x.dtype))
        out = tp_psum(h @ params["wo"].astype(x.dtype), tp_axis)
        return out + params["bo"].astype(x.dtype)

    @staticmethod
    def tp_partition_specs(model_axis: str):
        """Per-leaf specs over the model axis for the manual-TP shards
        (leading dims — expert stack — handled by the caller)."""
        from jax.sharding import PartitionSpec as P
        return {"wi": P(None, model_axis), "bi": P(model_axis),
                "wo": P(model_axis, None), "bo": P()}


class GatedExpertMLP:
    """A gated expert, ``(silu(x Wg) * (x Wu)) Wd`` without bias, the
    gate and the up projection in one matrix ``w1 [d, 2 ff]`` (gate
    first).  ``apply`` is one expert on its rows; ``apply_grouped`` is a
    stack of experts ``[G, ...]`` on rows sorted by expert, through the
    grouped product (ops/grouped_matmul.py): no slot buffer, no capacity."""

    # the first product's columns over ``d_ff``: the gate's and the up's
    first_widths = 2

    def __init__(self, d_model: int, d_ff: int, init_std: float = 0.02):
        self.d_model = d_model
        self.d_ff = d_ff
        self.init_std = init_std

    def init_params(self, rng, x=None):
        k1, k2 = jax.random.split(rng)
        return {
            "w1": self.init_std * jax.random.normal(
                k1, (self.d_model, self.first_widths * self.d_ff),
                jnp.float32),
            "w2": self.init_std * jax.random.normal(
                k2, (self.d_ff, self.d_model), jnp.float32),
        }

    @staticmethod
    def _act(h):
        """What the second product reads of the first's result."""
        gate, up = jnp.split(h, 2, axis=-1)
        return up * jax.nn.silu(gate)

    def apply(self, params, x, rng=None):
        h = x @ params["w1"].astype(x.dtype)
        return self._act(h) @ params["w2"].astype(x.dtype)

    def apply_grouped(self, params, rows, counts):
        """rows [R, d] sorted by expert, counts [G] rows an expert; zero
        past their sum."""
        from ..ops.grouped_matmul import gmm
        h = gmm(rows, params["w1"], counts)
        return gmm(self._act(h), params["w2"], counts)


class ReluSquaredExpertMLP(GatedExpertMLP):
    """An expert WITHOUT a gate, ``(relu(x Wu))^2 Wd`` without bias: two
    matrices, ``w1 [d, ff]`` and ``w2 [ff, d]`` (``mlp_hidden_act:
    relu2``, models/nemotron_h.py); the rest is GatedExpertMLP's."""

    first_widths = 1

    @staticmethod
    def _act(h):
        return jnp.square(jax.nn.relu(h))
