"""Keye-VL-2.0's language model (Kwai-Keye Keye-VL-2.0-30B-A3B's
``config.json``, ``model_type: KeyeVL2``; text tokens only): a decoder
whose grouped-query attention runs over the keys a learned indexer picks
for every query, above sparse experts chosen by a softmax router.

Every layer is ``h = x + Attn(RMSNorm(x)); out = h + MoE(RMSNorm(h))``
without bias; ``u`` the normed input:

  attention   ``q = u Wq`` (32 heads of 128), ``k = u Wk``, ``v = u Wv``
              (4 heads); an RMSNorm over the 128 of every head of q and
              of k; rotary, rotate-half over the whole head at
              ``rope_theta`` (on text the three position ids of
              ``mrope_section`` are equal: the plain rotation).
  indexer     on ``stop_gradient(u)``: ``qI = rot(u WqI)`` (16 heads of
              64), ``kI = rot(LayerNorm(u WkI))`` (ONE head), ``w = u
              Ww`` (16 a token); ``I[t, s] = 64^-1/2 16^-1/2 sum_j
              w[t, j] ReLU(qI[t, j] . kI[s])``; ``S_t`` the ``topk`` keys
              ``s <= t`` of largest ``I[t, s]`` (all of them while ``t <
              topk``).  The main attention's softmax runs over ``S_t``
              alone (ops/indexed_attention.py has the four pieces and
              their two forms).
  experts     ``moe.DroplessMoE``: a softmax over all E experts in
              float32, the k largest renormalised, the held experts'
              part of the sum; no shared expert.

The step's loss is ``L_lm + c sum_layers L_I``, ``L_I = mean_t KL(pbar_t
|| softmax_{s in S_t} I[t, s])`` with ``pbar_t`` the heads' mean of the
main attention's probabilities taken as a constant (the DeepSeek-V3.2
report's sparse training stage).  The indexer's three matrices and its
norm see ``L_I`` alone, every other weight ``L_lm`` alone, and the
selection has no gradient: the indexer reads ``stop_gradient(u)`` and
the alignment term reads the main attention's q, k and row statistics as
constants, so differentiation itself keeps the two apart.

TPU-native structure as ``models/laguna.py``: the layers are one stacked
group run by one body; a cut is ``num_hidden_layers``, ``experts_held``
and ``vocab_size``.  The head norm is plain ``rms_norm`` on the QKV
product's heads before ``ops/rotary.py``'s pass (where the shape is that
kernel's), which then writes q, k and v head-major.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe.dropless import DroplessMoE
from ..moe.sharded_moe import emit_routing_stats
from ..monitor import record as R
from ..ops.fused_cross_entropy import fused_linear_cross_entropy
from ..ops.indexed_attention import (
    index_alignment, index_select, indexed_attention, kept_lse, kept_pairs,
    kernels_take, pack_block)
from ..ops.normalize import layer_norm_reference, rms_norm
from ..ops.rotary import lane_tables, rotary_block, rotate_qkv
from .laguna import EMBEDDING_STD, ExpertStack, apply_rotary, rotary_table
from .layer_stack import run_layer_stack

KIND = "indexed_attention"
# Even shares of the picks the experts' row buffers hold
# (``DroplessMoE.dispatch_headroom``).  No shared expert carries the FFN
# here, so the routers do not learn their way off this rank's experts as
# Laguna's and GLM's do, and a layer's routed rows lie anywhere from 0.3
# to 1.8 even shares by the seed's router and the step's tokens (Zipf ids
# repeat, and a repeated id routes alike).  A trip beyond the first costs
# 14.7 ms a layer at 16,384 tokens, so buffers of one share made a step's
# time the seed's; of two, every layer of every run stayed inside them (my
# chip runs, PR 50: 1,112 ms a step against 1,056 at 1.5 shares, which
# one seed in eight overran, and 1,044 at one)
DISPATCH_HEADROOM = 2.0


@dataclass
class KeyeVL2Config:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48          # the first layers; all are alike
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    # (first, count) of the routed experts held here; None is all
    experts_held: Optional[Tuple[int, int]] = None
    # sa_config
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    index_topk: int = 2048
    # the indexer's key norm is a LayerNorm with this epsilon, and the
    # alignment term enters the loss times this (both assumed: the
    # configuration file says why)
    indexer_norm_eps: float = 1e-6
    index_loss_weight: float = 1.0
    initializer_range: float = 0.02
    bf16: bool = True
    activation_checkpointing: bool = False

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of the "
                             "key/value heads")
        if self.head_dim % 2 or self.indexer_head_dim % 2:
            raise ValueError("the rotated dimensions pair up")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(self.experts_held)

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32


class KeyeVL2Model(ExpertStack):
    """The decoder over one stacked group of like layers; trained through
    ``deepspeed_tpu.initialize`` like LagunaModel."""

    # the scalars of ``__call__``'s dict that the engine sums on the
    # device for whoever reads ``engine.model_counters()``
    aux_counters = (R.M_MAIN_LOSS, R.M_INDEX_LOSS, R.M_KEPT_SHARE)
    # engine paths this model has not been run under, each with its
    # reason; the engine raises NotImplementedError with it
    refuses = {
        "zero3_streaming": (
            "the streamed ZeRO-3 layer scan carries the hidden state "
            "alone, and every layer here also hands out its alignment "
            "term and its selection's count"),
        "pipeline": (
            "the pipeline engine's stages return one loss, and the "
            "alignment terms of the layers on the earlier stages would "
            "have to travel with the activations"),
    }

    def __init__(self, config: KeyeVL2Config):
        super().__init__(config, DroplessMoE(
            config.hidden_size, config.num_experts,
            config.num_experts_per_tok, config.moe_intermediate_size,
            None, score="softmax", renormalize=config.norm_topk_prob,
            experts_held=config.experts_held,
            init_std=config.initializer_range,
            # one rank of eight: models/glm4_moe_lite.py has the reason
            first_chunk_always=True,
            dispatch_headroom=DISPATCH_HEADROOM))

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng):
        cfg = self.config
        hid, dim = cfg.hidden_size, cfg.head_dim
        heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        ih, idim = cfg.indexer_num_heads, cfg.indexer_head_dim
        keys = jax.random.split(rng, 6)
        std = cfg.initializer_range

        def normal(key, shape):
            return std * jax.random.normal(key, shape, jnp.float32)

        return {
            "ln1": jnp.ones((hid,), jnp.float32),
            "attn": {
                "qkv_w": normal(keys[0], (hid, (heads + 2 * kv) * dim)),
                "q_norm": jnp.ones((dim,), jnp.float32),
                "k_norm": jnp.ones((dim,), jnp.float32),
                "out_w": normal(keys[1], (heads * dim, hid))},
            "indexer": {
                "q_w": normal(keys[2], (hid, ih * idim)),
                "k_w": normal(keys[3], (hid, idim)),
                "k_norm_w": jnp.ones((idim,), jnp.float32),
                "k_norm_b": jnp.zeros((idim,), jnp.float32),
                "w_w": normal(keys[4], (hid, ih))},
            "ln2": jnp.ones((hid,), jnp.float32),
            "moe": self.moe.init_params(keys[5])}

    def init_params(self, rng):
        cfg = self.config
        k_wte, k_head, k_layers = jax.random.split(rng, 3)
        # a layer's weights depend on its published index alone
        keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
            jnp.arange(cfg.num_hidden_layers))
        return {
            "wte": EMBEDDING_STD * jax.random.normal(
                k_wte, (cfg.vocab_size, cfg.hidden_size), jnp.float32),
            "ln_f": jnp.ones((cfg.hidden_size,), jnp.float32),
            "head": cfg.initializer_range * jax.random.normal(
                k_head, (cfg.hidden_size, cfg.vocab_size), jnp.float32),
            "layers": jax.vmap(self._init_layer)(keys)}

    def param_partition_specs(self):
        """No tensor- or expert-parallel split is written for this family
        yet: every leaf replicated over the model axis."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    # -- the layer ------------------------------------------------------ #
    def rotary_plan(self, seq):
        """(positions, heads) of the rotary kernels' block, or None where
        ``apply_rotary`` runs (ops/rotary.py rotary_block)."""
        cfg = self.config
        return rotary_block(seq, cfg.head_dim, cfg.num_attention_heads,
                            cfg.num_key_value_heads)

    def indexed_plan(self, seq):
        """Whether ops/indexed_attention.py's kernels run on ``seq``
        positions; else its blocked XLA forms."""
        cfg = self.config
        return kernels_take(seq, cfg.head_dim, cfg.indexer_head_dim)

    def _tables(self, seq):
        """The rotary tables of a step, float32, built once outside the
        body: the main heads' (per lane where the kernel reads them) and
        the indexer's."""
        cfg = self.config

        def table(dim):
            i = jnp.arange(dim // 2, dtype=jnp.float32)
            return rotary_table(seq, cfg.rope_theta ** (-2.0 * i / dim))

        main = table(cfg.head_dim)
        if self.rotary_plan(seq) is not None:
            main = (*lane_tables(*main, cfg.head_dim), main[0].shape[-1])
        return main, table(cfg.indexer_head_dim)

    def _head_norm(self, p, qkv):
        """RMSNorm over every head of q and of k, on the flat product."""
        cfg = self.config
        dim = cfg.head_dim
        heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        x = qkv.reshape(*qkv.shape[:-1], heads + 2 * kv, dim)
        gamma = jnp.concatenate([
            jnp.broadcast_to(p["q_norm"], (heads, dim)),
            jnp.broadcast_to(p["k_norm"], (kv, dim))])
        normed = rms_norm(x[..., :heads + kv, :], gamma, cfg.rms_norm_eps)
        return jnp.concatenate([normed, x[..., heads + kv:, :]],
                               axis=-2).reshape(qkv.shape)

    def _indexer(self, p, u, table):
        """(qI [B, Hi, S, Di], kI [B, S, Di], w float32 [B, Hi, S]) of
        ``u``, which the caller has cut from the graph."""
        cfg = self.config
        batch, seq, _ = u.shape
        ih, idim = cfg.indexer_num_heads, cfg.indexer_head_dim
        q_idx = (u @ p["q_w"]).reshape(batch, seq, ih, idim).transpose(
            0, 2, 1, 3)
        k_idx = layer_norm_reference(u @ p["k_w"], p["k_norm_w"],
                                     p["k_norm_b"], cfg.indexer_norm_eps)
        q_idx = apply_rotary(q_idx, table)
        k_idx = apply_rotary(k_idx[:, None], table)[:, 0]
        w = (u @ p["w_w"]).astype(jnp.float32) * (
            (idim * ih) ** -0.5)
        return q_idx, k_idx, w.transpose(0, 2, 1)

    def _attention(self, p, p_idx, u, tables, forced_keep=None):
        """(Attn(u), the layer's alignment term, the pairs it kept, the
        packed keep-set)."""
        cfg = self.config
        batch, seq, _ = u.shape
        dim = cfg.head_dim
        heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        table, idx_table = tables
        kernels = self.indexed_plan(seq)
        # inside "attn" the work is named once more, by part
        # (profiling/scope_map.py PARTS); names only
        with jax.named_scope("attn"):
            with jax.named_scope("attn_qkv"):
                qkv = u @ p["qkv_w"]
            with jax.named_scope("attn_qk_norm"):
                qkv = self._head_norm(p, qkv)
            if self.rotary_plan(seq) is not None:
                with jax.named_scope("attn_rotary"):
                    q, k, v = rotate_qkv(qkv, *table, heads, kv)
            else:
                def by_head(t, n):
                    with jax.named_scope("attn_layout"):
                        return t.reshape(batch, seq, n, dim).transpose(
                            0, 2, 1, 3)

                def rotary(t):
                    with jax.named_scope("attn_rotary"):
                        return apply_rotary(t, table)

                q, k, v = jnp.split(
                    qkv, [heads * dim, (heads + kv) * dim], axis=-1)
                q = rotary(by_head(q, heads))
                k = rotary(by_head(k, kv))
                v = by_head(v, kv)
            with jax.named_scope("attn_index"):
                q_idx, k_idx, w = self._indexer(
                    p_idx, jax.lax.stop_gradient(u), idx_table)
            with jax.named_scope("attn_select"):
                if forced_keep is None:
                    keep, lse_idx = index_select(q_idx, k_idx, w,
                                                 cfg.index_topk, kernels)
                else:
                    keep = forced_keep
                    lse_idx = kept_lse(q_idx, k_idx, w, keep,
                                       pack_block(seq))
                kept = kept_pairs(keep)
            with jax.named_scope("attn_core"):
                a, lse = indexed_attention(q, k, v, keep, kernels=kernels)
            with jax.named_scope("attn_align"):
                align = index_alignment(q_idx, k_idx, w, q, k, lse, keep,
                                        lse_idx, kernels=kernels)
            with jax.named_scope("attn_layout"):
                a = a.transpose(0, 2, 1, 3)
            with jax.named_scope("attn_out"):
                out = a.reshape(batch, seq, heads * dim) @ p["out_w"]
        return out, align, kept, keep

    def _layer(self, p, x, tables, picks=None, forced_keep=None):
        """(layer output, the experts' Routing, the alignment term, the
        pairs kept, the packed keep-set)."""
        eps = self.config.rms_norm_eps
        with jax.named_scope("layer"):
            a, align, kept, keep = self._attention(
                p["attn"], p["indexer"], rms_norm(x, p["ln1"], eps), tables,
                forced_keep)
            h = x + a
            y, routing = self.moe.apply(p["moe"], rms_norm(h, p["ln2"], eps),
                                        picks=picks)
            return h + y, routing, align, kept, keep

    # -- the stack ------------------------------------------------------ #
    def stack_plan(self, seq):
        """The M_STACK_* fields of this stack on ``seq`` positions."""
        cfg = self.config
        rotary = self.rotary_plan(seq)
        return {
            R.M_STACK_LAYERS: tuple(
                (i, KIND + "+experts", 0)
                for i in range(cfg.num_hidden_layers)),
            R.M_STACK_EXPERTS_HELD: (*cfg.experts_held, cfg.num_experts),
            R.M_STACK_INDEXER: (
                cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.index_topk,
                "kernels" if self.indexed_plan(seq) else "xla"),
            R.M_STACK_ROTARY: (
                (KIND, "kernel", *rotary) if rotary else (KIND, "xla"),)}

    def _run(self, params, input_ids, picks, keep_of, forced_keep=None,
             with_keep=False):
        """(hidden states before the final norm, per layer stacked:
        ``keep_of(routing)``, the alignment term, the pairs kept, and
        with ``with_keep`` the packed keep-set)."""
        cfg = self.config
        with jax.named_scope("embed"):
            h = params["wte"].astype(cfg.dtype)[input_ids]
        seq = input_ids.shape[1]
        tables = self._tables(seq)

        def body(carry, xs):
            p, forced_picks, forced = xs
            out, routing, align, kept, keep = self._layer(
                p, carry, tables, forced_picks, forced)
            return out, (keep_of(routing), align, kept,
                         keep if with_keep else None)

        xs = (params["layers"], picks, forced_keep)
        count = cfg.num_hidden_layers
        wrap = self._layer_wrapper([(body, xs, True, count)], h,
                                   self.stack_plan(seq))
        return run_layer_stack(wrap(body), h, xs, count > 1, with_ys=True)

    def routing(self, params, input_ids, with_inputs=False):
        """(scores f32 [L, T, E], picks int32 [L, T, k], the packed
        keep-sets int32 [L, B, S / 32, S]) of the L layers on
        ``input_ids``, from the same forward pass as the loss; with
        ``with_inputs`` also what each router read, [L, T, hidden],
        before the keep-sets."""
        _, (kept, _, _, keep) = self._run(
            params, input_ids, None,
            lambda r: (r.scores, r.picks) + ((r.inputs,) * with_inputs),
            with_keep=True)
        return (*kept, keep)

    def _objective(self, params, input_ids, labels=None, picks=None,
                   keep=None):
        """(L, the counters of ``aux_counters``).  Every layer's
        RoutingStats go to the collecting tap, if the engine installed
        one (moe/sharded_moe.py)."""
        cfg = self.config
        h, (stats, align, kept, _) = self._run(
            params, input_ids, picks, self.moe.stats, keep)
        for i in range(stats.layers.shape[0]):
            emit_routing_stats(jax.tree.map(lambda a: a[i], stats))
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"], cfg.rms_norm_eps)
            if labels is None:
                h, labels = h[:, :-1], input_ids[:, 1:]
            main = fused_linear_cross_entropy(
                h.reshape(-1, cfg.hidden_size),
                params["head"].astype(h.dtype),
                labels.reshape(-1).astype(jnp.int32))
        batch, seq = input_ids.shape
        index = jnp.sum(align)
        counters = {
            R.M_MAIN_LOSS: main, R.M_INDEX_LOSS: index,
            R.M_KEPT_SHARE: jnp.sum(kept) / (
                cfg.num_hidden_layers * batch * seq * (seq + 1) / 2)}
        return main + cfg.index_loss_weight * index, counters

    def loss_terms(self, params, input_ids, labels=None, picks=None,
                   keep=None):
        """(L, L_lm, sum of the layers' L_I)."""
        objective, counters = self._objective(params, input_ids, labels,
                                              picks, keep)
        return objective, counters[R.M_MAIN_LOSS], counters[R.M_INDEX_LOSS]

    def loss(self, params, rng, input_ids, labels=None, picks=None,
             keep=None):
        """The objective ``L_lm + c sum L_I``.  `rng` is unused (no
        dropout).  ``picks`` int32 [L, T, k] forces every router's
        choice and ``keep`` (packed, int32 [L, B, S / 32, S]) every
        layer's selection."""
        return self._objective(params, input_ids, labels, picks, keep)[0]

    def __call__(self, params, rng, input_ids, labels=None, picks=None,
                 keep=None):
        """(L, {"main_loss", "index_loss", "kept_share"}): the engine
        differentiates and reports the first and sums the scalars of the
        second (``aux_counters``)."""
        return self._objective(params, input_ids, labels, picks, keep)
