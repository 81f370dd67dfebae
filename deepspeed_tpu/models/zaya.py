"""ZAYA (``model_type: zaya``, Zyphra ZAYA1-8B's ``config.json``; the
ZAYA1 technical report, arXiv:2511.17127, and "Compressed Convolutional
Attention", arXiv:2510.04476): a decoder of alike layers, each an
attention sublayer in a compressed latent and a top-1 expert sublayer
whose router is an MLP with a state carried from layer to layer, every
residual sum with learned scales and biases, a tied table.

With ``h = RMSNorm(x)`` (its own gain a sublayer), ``t`` a position:

  merge      every sublayer ``f``: ``x <- a * (x + c) + g * (f(h) + d)``,
             ``a, c, g, d`` of the model's width, ``a = g = 1`` and ``c =
             d = 0`` at the start, float32 arithmetic.  The first
             sublayer of the model (layer 0's attention) has no ``a, c``
             (the released code scales the stream at the NEXT sublayer's
             entry): ``params["entry"]`` holds the ``a, c`` of the
             attention sublayers of layers 1 on.
  attention  ops/cca.py: ``q~ = h W_q`` (8 heads of 128), ``k~ = h W_k``
             (2), ``v = h [W_v1 | W_v2]``; the q-k mean, the two causal
             convs over the sequence (depthwise, then dense within a head;
             ``cca_time0`` and ``cca_time1`` taps), the value shift (part
             ``mix``); every head to unit norm times ``sqrt(128)``, a key
             head times its learned temperature ``tau`` besides (part
             ``qk_norm``); rotate-half over the first ``partial_rotary_
             factor`` of a head at ``rope_theta`` (ops/rotary.py where the
             shape is its kernels', ``apply_rotary`` elsewhere); causal
             softmax at ``1 / sqrt(128)`` through the flash kernels, query
             head i on key/value head ``i // 4``; ``y = o W_o``.  No bias
             on the four projections.
  router     float32 throughout, under scope ``router``: ``r_l = h W_d +
             b_d`` (``router_hidden_size`` wide); ``r_l <- r_l + gamma_l *
             r_{l-1}`` for l > 0 (``params["entry"]["gamma"]``; layer 0
             reads zeros and has none); what layer l + 1 reads is ``r_l``
             as it now stands: the SECOND CARRY of the stack, kept a layer
             like the stream and charged to the byte budget
             (checkpointing.checkpoint_layers).  ``logits = gelu(gelu(
             N(r_l) W_1 + b_1) W_2 + b_2) W_3``, exact gelu; ``p =
             softmax(logits)``; the pick is ``argmax(p + beta)``, ``beta``
             a float32 leaf no gradient reaches and the optimizer does not
             own (models/glm4_moe_lite.py ``SelectionBiasUpdate`` moves it
             after each step); the pick's weight is ``p[pick]`` AS IT IS:
             renormalised, a top-1 weight is 1 and the router is cut off
             from the loss, so ``ZayaConfig`` refuses that.
  experts    ``moe.DroplessMoE`` handed those logits (``own_router=
             False``): ``p[pick] (silu(h W_g) * (h W_u)) W_dn`` of the
             picked expert if it is held here, nothing otherwise (``d``
             still enters the merge); no shared expert.
  head       the final norm, logits on the embedding's own rows, the mean
             cross-entropy (ops/fused_cross_entropy.py; ``head_chunk``
             picks equal parts, so no column is padding).

NOT built: the skip "expert" of the sibling configurations
(``zaya_use_mod``: mixture of depths).  ZAYA1-8B's ``config.json`` has
no key for it, its router's last matrix is ``num_experts`` wide, and
neither paper gives the skip path's equation.

Keys of the released file that are read: ``hidden_size``,
``num_hidden_layers``, ``layer_types`` (its first ``num_hidden_layers``
entries, all ``"hybrid"``), ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``cca_time0``, ``cca_time1``,
``partial_rotary_factor``, ``rope_parameters.hybrid.rope_theta``,
``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``router_hidden_size``, ``rms_norm_eps``, ``vocab_size``,
``tie_word_embeddings`` (true), ``attention_bias`` / ``lm_head_bias``
(false), ``hidden_act`` (silu).  Ignored: ``sliding_window`` (null) and
``rope_parameters.hybrid_sliding`` (no layer of this row is of that
type: the 74B sibling's every fourth layer is), ``max_position_
embeddings`` (rotary tables are made for the positions of the batch).

A cut of the model is ``num_hidden_layers`` (the first layers),
``experts_held`` (first, count) and ``vocab_size``.  The alike layers
are ONE stacked group (``params["layers"]``, a leading axis of layers)
run by one body, scanned or unrolled (``scan_layers``).
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
from ..ops import cca
from ..ops.flash_attention import flash_attention
from ..ops.fused_cross_entropy import (_CE_CHUNK_ELEM_BUDGET, even_chunk,
                                       fused_linear_cross_entropy)
from ..ops.normalize import rms_norm
from ..ops.rotary import lane_tables, rotary_block, rotate_qkv
from .glm4_moe_lite import SelectionBiasUpdate
from .laguna import ExpertStack, apply_rotary, rotary_table
from .layer_stack import run_layer_stack

HYBRID = "hybrid"
_HIGHEST = jax.lax.Precision.HIGHEST


def head_chunk(vocab: int, tokens: int):
    """A ``chunk_size`` of the fused cross-entropy that pads nothing:
    ``even_chunk``'s equal parts of whole lane tiles, else equal parts of
    any width (32,784 = 16 x 2,049 rows in two parts of 16,392, where the
    auto plan would take 32,774 and pad a second chunk to 65,548
    columns), else None (the auto plan)."""
    chunk = even_chunk(vocab, tokens)
    if chunk is not None:
        return chunk
    fewest = -(-vocab // max(4096, _CE_CHUNK_ELEM_BUDGET // max(1, tokens)))
    for parts in range(fewest, 2 * fewest + 1):
        if vocab % parts == 0:
            return vocab // parts
    return None


def residual_merge(x, y, res, entry=None):
    """``a * (x + c) + g * (y + d)`` in float32, in x's dtype; ``res``
    holds ``g`` and ``d`` and, where the sublayer owns them, ``a`` and
    ``c``, which ``entry`` hands in otherwise (None: 1 and 0)."""
    f32 = jnp.float32
    kept = x.astype(f32)
    scales = res if entry is None else entry
    if "a" in scales:
        kept = scales["a"].astype(f32) * (kept + scales["c"].astype(f32))
    return (kept + res["g"].astype(f32) * (
        y.astype(f32) + res["d"].astype(f32))).astype(x.dtype)


@dataclass
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40          # the first layers; all are alike
    layer_types: Optional[Tuple[str, ...]] = None   # None: all "hybrid"
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2                   # taps of the depthwise conv
    cca_time1: int = 2                   # taps of the conv within a head
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    # (first, count) of the routed experts held here; None is all
    experts_held: Optional[Tuple[int, int]] = None
    # the picked scores divided by their sum: refused at one pick a token
    renormalize: bool = False
    bias_update_rate: float = 0.001      # gamma of the selection bias
    initializer_range: float = 0.02
    # None: a stack of more than one layer is scanned (one traced body)
    scan_layers: Optional[bool] = None
    bf16: bool = True
    activation_checkpointing: bool = False

    def __post_init__(self):
        n = self.num_hidden_layers
        kinds = tuple(self.layer_types or (HYBRID,) * n)[:n]
        if len(kinds) != n or set(kinds) != {HYBRID}:
            raise ValueError(
                f"layer_types must name the {n} layers kept, every one "
                f"{HYBRID!r} (a sliding layer's window is not written for "
                f"this family): {kinds!r}")
        self.layer_types = kinds
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of the "
                             "key/value heads")
        if self.num_experts_per_tok == 1 and self.renormalize:
            raise ValueError(
                "num_experts_per_tok=1 with renormalize=True: a single "
                "pick's renormalised weight is 1 whatever the router says, "
                "so no gradient reaches the router and it is cut off from "
                "the loss; ZAYA's top-1 weight is the softmax probability "
                "as it is (renormalize=False)")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(self.experts_held)

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32

    @property
    def rotated(self) -> int:
        """Dimensions of a head the rotation turns."""
        return int(self.head_dim * self.partial_rotary_factor)


class ZayaModel(SelectionBiasUpdate, ExpertStack):
    """The decoder over its one stacked group of alike layers; trained
    through ``deepspeed_tpu.initialize`` like LagunaModel."""

    # the scalars of ``__call__``'s dict that the engine sums on the
    # device for whoever reads ``engine.model_counters()``
    aux_counters = (R.M_LOAD_MAX_OVER_MEAN, R.M_ROUTER_STATE_RMS,
                    R.M_CCA_TAU_MEAN, R.M_RESIDUAL_SCALE_MEAN)
    # Even shares of the picks the experts' row buffers hold.  Half the
    # experts are held, so two shares are every pick there is: one trip
    # of the dropless walk whatever the router does, and a step's time
    # does not follow a second trip (models/keye_vl2.py measured its way
    # to the same two)
    dispatch_headroom = 2.0

    # engine paths this model has not been run on, each with its reason;
    # the engine raises NotImplementedError with it at construction (an
    # expert axis larger than one is DroplessMoE's to refuse)
    refuses = {
        "zero3_streaming": (
            "the streamed ZeRO-3 layer scan carries ONE array from layer "
            "to layer and this stack carries two (the stream and the "
            "router's state), and the selection biases are leaves the "
            "optimizer does not own"),
        "pipeline": (
            "the router's state would have to travel from stage to stage "
            "with the stream, and the selection biases move from routing "
            "counts that would have to travel between the stages; no "
            "pipeline module does either yet"),
    }

    def __init__(self, config: ZayaConfig):
        super().__init__(config, DroplessMoE(
            config.hidden_size, config.num_experts,
            config.num_experts_per_tok, config.moe_intermediate_size,
            None, score="softmax", renormalize=config.renormalize,
            experts_held=config.experts_held,
            init_std=config.initializer_range, selection_bias=True,
            # one rank of several: models/glm4_moe_lite.py has the reason
            first_chunk_always=True,
            dispatch_headroom=self.dispatch_headroom, own_router=False))

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng):
        cfg = self.config
        hid, dim, wide = cfg.hidden_size, cfg.head_dim, cfg.router_hidden_size
        heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        channels = (heads + kv) * dim
        keys = iter(jax.random.split(rng, 16))

        def normal(shape):
            return cfg.initializer_range * jax.random.normal(
                next(keys), shape, jnp.float32)

        def uniform(shape, fan_in):
            # torch's default for a Conv1d and a Linear, weights and
            # biases alike: uniform in +- 1 / sqrt(fan in)
            bound = 1.0 / math.sqrt(fan_in)
            return jax.random.uniform(next(keys), shape, jnp.float32,
                                      -bound, bound)

        def merge(owns_entry):
            scales = {"g": jnp.ones((hid,), jnp.float32),
                      "d": jnp.zeros((hid,), jnp.float32)}
            if owns_entry:
                scales.update(a=jnp.ones((hid,), jnp.float32),
                              c=jnp.zeros((hid,), jnp.float32))
            return scales

        return {
            "ln1": jnp.ones((hid,), jnp.float32),
            "attn": {
                # W_q | W_k | W_v1 | W_v2
                "qkv_w": normal((hid, (heads + 2 * kv) * dim)),
                "conv0_w": uniform((channels, cfg.cca_time0), cfg.cca_time0),
                "conv0_b": uniform((channels,), cfg.cca_time0),
                # [head, tap, in, out]: torch's [out, in, tap] a group
                "conv1_w": uniform((heads + kv, cfg.cca_time1, dim, dim),
                                   cfg.cca_time1 * dim),
                "conv1_b": uniform((channels,), cfg.cca_time1 * dim),
                "tau": jnp.ones((kv,), jnp.float32),
                "out_w": normal((heads * dim, hid))},
            "attn_res": merge(False),
            "ln2": jnp.ones((hid,), jnp.float32),
            "router": {
                "down_w": uniform((hid, wide), hid),
                "down_b": uniform((wide,), hid),
                "norm": jnp.ones((wide,), jnp.float32),
                "w1": uniform((wide, wide), wide),
                "b1": uniform((wide,), wide),
                "w2": uniform((wide, wide), wide),
                "b2": uniform((wide,), wide),
                "w3": uniform((wide, cfg.num_experts), wide)},
            "moe": self.moe.init_params(next(keys)),
            "moe_res": merge(True)}

    def init_params(self, rng):
        """Matrices of the model's width normal(0, initializer_range), the
        table too (it is the head); the convs and the router's MLP as
        torch draws a Conv1d and a Linear; gains, ``a``, ``g``, ``tau``
        and ``gamma`` 1; ``c``, ``d`` and the selection biases 0.  A
        layer's weights depend on its published index alone."""
        cfg = self.config
        hid, n = cfg.hidden_size, cfg.num_hidden_layers
        k_wte, k_layers = jax.random.split(rng)
        keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
            jnp.arange(n))
        return {
            "wte": cfg.initializer_range * jax.random.normal(
                k_wte, (cfg.vocab_size, hid), jnp.float32),
            "ln_f": jnp.ones((hid,), jnp.float32),
            "layers": jax.vmap(self._init_layer)(keys),
            # of layers 1 on: the attention sublayer's a and c, and the
            # scale of the router state it reads
            "entry": {
                "a": jnp.ones((n - 1, hid), jnp.float32),
                "c": jnp.zeros((n - 1, hid), jnp.float32),
                "gamma": jnp.ones((n - 1, cfg.router_hidden_size),
                                  jnp.float32)}}

    def param_partition_specs(self):
        """No tensor- or expert-parallel split is written for this family
        yet: every leaf replicated over the model axis (ZeRO shards over
        the data axes as it does for any tree)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def num_params(self) -> int:
        """Every leaf's entries, the ``num_experts`` selection biases a
        layer among them (buffers, not parameters of the count the
        papers give)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    def gates(self):
        """The path of the experts' parameters: [(keys to the stacked
        ``moe`` dict, layers in it)]."""
        return [(("layers", "moe"), self.config.num_hidden_layers)]

    # -- the layer ------------------------------------------------------ #
    def rotary_tables(self, seq, lanes=False):
        """(cos, sin) float32 [seq, rotated / 2]; ``lanes``: (cos, sin,
        rotated / 2) with the tables per lane of a head, as
        ops/rotary.py's kernels read them."""
        cfg = self.config
        i = jnp.arange(cfg.rotated // 2, dtype=jnp.float32)
        cos, sin = rotary_table(
            seq, cfg.rope_theta ** (-2.0 * i / cfg.rotated))
        if lanes:
            return (*lane_tables(cos, sin, cfg.head_dim), cos.shape[-1])
        return cos, sin

    def rotary_plan(self, seq):
        """(positions, heads) of the rotary kernels' block, or None where
        ``apply_rotary`` runs (ops/rotary.py ``rotary_block``)."""
        cfg = self.config
        return rotary_block(seq, cfg.head_dim, cfg.num_attention_heads,
                            cfg.num_key_value_heads)

    def latents(self, p, u):
        """The projections of a layer's normed input: ``q~ [B, S, H D]``,
        ``k~ [B, S, K D]`` and the two value heads ``[B, S, K D]``."""
        cfg = self.config
        dim, heads = cfg.head_dim, cfg.num_attention_heads
        with jax.named_scope("attn_qkv"):
            return jnp.split(
                u @ p["qkv_w"],
                [heads * dim, (heads + cfg.num_key_value_heads) * dim],
                axis=-1)

    def mixed(self, p, q, k, v):
        """ops/cca.py on the projections: the mixed and normed q and k and
        the shifted v, flat as they came."""
        cfg = self.config
        heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        with jax.named_scope("attn_mix"):
            q, k, shifted = cca.mix_heads(q, k, v, p, heads, kv)
        with jax.named_scope("attn_qk_norm"):
            return (*cca.unit_norm_heads(q, k, p["tau"], v.dtype), shifted)

    def _attention(self, p, u, table):
        cfg = self.config
        batch, seq, _ = u.shape
        dim = cfg.head_dim
        heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        # inside "attn" the work is named once more, by part
        # (profiling/scope_map.py PARTS); names only
        with jax.named_scope("attn"):
            q, k, v = self.mixed(p, *self.latents(p, u))
            if self.rotary_plan(seq) is not None:
                with jax.named_scope("attn_rotary"):
                    q, k, v = rotate_qkv(
                        jnp.concatenate([q, k, v], axis=-1), *table, heads,
                        kv)
            else:
                def by_head(t, n):
                    with jax.named_scope("attn_layout"):
                        return t.reshape(batch, seq, n, dim).transpose(
                            0, 2, 1, 3)

                def rotary(t):
                    with jax.named_scope("attn_rotary"):
                        return apply_rotary(t, table)

                q, k = rotary(by_head(q, heads)), rotary(by_head(k, kv))
                v = by_head(v, kv)
            with jax.named_scope("attn_core"):
                a = flash_attention(q, k, v, causal=True,
                                    sm_scale=1.0 / math.sqrt(dim))
            with jax.named_scope("attn_layout"):
                a = a.transpose(0, 2, 1, 3)
            with jax.named_scope("attn_out"):
                return a.reshape(batch, seq, heads * dim) @ p["out_w"]

    def router_state(self, p, gamma, u, before):
        """``r_l`` float32 [..., router width]: the down-projection of
        what the router reads plus ``gamma`` times the layer before's."""
        f32 = jnp.float32
        return (jnp.dot(u.astype(f32), p["down_w"].astype(f32),
                        precision=_HIGHEST) + p["down_b"].astype(f32)
                + gamma.astype(f32) * before)

    def router_logits(self, p, state):
        """f32 [..., E] of the router's MLP on its (normed) state."""
        f32 = jnp.float32

        def dense(t, w, b=None):
            t = jnp.dot(t, p[w].astype(f32), precision=_HIGHEST)
            return t if b is None else t + p[b].astype(f32)

        z = rms_norm(state, p["norm"], self.config.rms_norm_eps)
        z = jax.nn.gelu(dense(z, "w1", "b1"), approximate=False)
        z = jax.nn.gelu(dense(z, "w2", "b2"), approximate=False)
        return dense(z, "w3")

    def _layer(self, p, entry, carry, table, picks=None):
        """((the stream, the router's state) after the layer, the
        experts' Routing)."""
        eps = self.config.rms_norm_eps
        x, before = carry
        with jax.named_scope("layer"):
            y = self._attention(p["attn"], rms_norm(x, p["ln1"], eps), table)
            x = residual_merge(x, y, p["attn_res"], entry)
            u = rms_norm(x, p["ln2"], eps)
            with jax.named_scope("router"):
                state = self.router_state(p["router"], entry["gamma"], u,
                                          before)
                logits = self.router_logits(p["router"], state)
            y, routing = self.moe.apply(p["moe"], u, picks=picks,
                                        logits=logits)
            return (residual_merge(x, y, p["moe_res"]), state), routing

    # -- the stack ------------------------------------------------------ #
    def stack_plan(self, seq):
        """The M_STACK_* fields of this stack."""
        cfg = self.config
        block = self.rotary_plan(seq)
        return {
            R.M_STACK_LAYERS: tuple(
                (i, "cca+experts", 0) for i in range(cfg.num_hidden_layers)),
            R.M_STACK_EXPERTS_HELD: (*cfg.experts_held, cfg.num_experts),
            R.M_STACK_ROTARY: (
                (HYBRID, "kernel", *block) if block else (HYBRID, "xla"),),
            R.M_STACK_CCA: (cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim,
                            cfg.cca_time0, cfg.cca_time1,
                            cfg.router_hidden_size)}

    def entries(self, params):
        """``params["entry"]`` with layer 0's row in front: ``a`` 1 and
        ``c`` 0 (no scaling at the model's first sublayer), ``gamma`` 0
        (it reads no state), so that the layers are alike to the body
        that runs them; [layers, ...] each."""
        return {name: jnp.concatenate(
            [jnp.full((1, *rows.shape[1:]), fill, rows.dtype), rows])
            for (name, fill), rows in (
                (item, params["entry"][item[0]])
                for item in (("a", 1.0), ("c", 0.0), ("gamma", 0.0)))}

    def _run(self, params, input_ids, picks, keep):
        """(the hidden states before the final norm, the last layer's
        router state, ``keep(routing)`` of every layer stacked)."""
        cfg = self.config
        batch, seq = input_ids.shape
        with jax.named_scope("embed"):
            h = params["wte"].astype(cfg.dtype)[input_ids]
        table = self.rotary_tables(seq, lanes=self.rotary_plan(seq)
                                   is not None)
        carry = (h, jnp.zeros((batch, seq, cfg.router_hidden_size),
                              jnp.float32))

        def body(carry, xs):
            p, entry, forced = xs
            carry, routing = self._layer(p, entry, carry, table, forced)
            return carry, keep(routing)

        xs = (params["layers"], self.entries(params), picks)
        wrap = self._layer_wrapper([(body, xs)], carry, self.stack_plan(seq))
        scan = cfg.scan_layers
        if scan is None:
            scan = cfg.num_hidden_layers > 1
        (h, state), kept = run_layer_stack(wrap(body), carry, xs, scan,
                                           with_ys=True)
        return h, state, kept

    def _objective(self, params, input_ids, labels=None, picks=None):
        """(the mean next-token cross-entropy, the counters of
        ``aux_counters``).  The RoutingStats of all layers go to the
        collecting tap as ONE entry, if the engine installed one
        (moe/sharded_moe.py): the sums over the layers, and each layer's
        picks an expert, which the selection biases are moved by."""
        cfg = self.config
        h, state, stats = self._run(params, input_ids, picks, self.moe.stats)
        counts = stats.expert_counts                          # [L, E]
        entry, layers = params["entry"], params["layers"]
        scales = [layers["attn_res"]["g"], layers["moe_res"]["a"],
                  layers["moe_res"]["g"], entry["a"]]
        counters = {
            R.M_LOAD_MAX_OVER_MEAN: jnp.mean(
                jnp.max(counts, axis=-1) / jnp.mean(counts, axis=-1)),
            R.M_ROUTER_STATE_RMS: jnp.sqrt(jnp.mean(jnp.square(state))),
            R.M_CCA_TAU_MEAN: jnp.mean(
                layers["attn"]["tau"].astype(jnp.float32)),
            R.M_RESIDUAL_SCALE_MEAN: (
                sum(jnp.sum(s.astype(jnp.float32)) for s in scales)
                / sum(s.size for s in scales))}
        emit_routing_stats(jax.tree.map(
            lambda a: jnp.sum(a, axis=0), stats)._replace(
            layer_counts=counts))
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"], cfg.rms_norm_eps)
            if labels is None:
                h, labels = h[:, :-1], input_ids[:, 1:]
            h = h.reshape(-1, cfg.hidden_size)
            loss = fused_linear_cross_entropy(
                h, params["wte"].astype(h.dtype).T,
                labels.reshape(-1).astype(jnp.int32),
                head_chunk(cfg.vocab_size, h.shape[0]))
        return loss, counters

    def routing(self, params, input_ids, with_inputs=False):
        """(scores f32 [L, T, E], picks int32 [L, T, 1]) of the L layers
        on ``input_ids``, from the same forward pass as the loss; with
        ``with_inputs`` also what each layer's experts (and its router's
        down-projection) read, [L, T, hidden]."""
        _, _, kept = self._run(
            params, input_ids, None,
            lambda r: (r.scores, r.picks) + ((r.inputs,) * with_inputs))
        return kept

    def loss(self, params, rng, input_ids, labels=None, picks=None):
        """Mean next-token cross-entropy; ``input_ids[:, 1:]`` are the
        targets where `labels` is None.  `rng` is unused (no dropout).
        ``picks`` int32 [L, T, 1] forces every layer's choice."""
        return self._objective(params, input_ids, labels, picks)[0]

    def logits(self, params, input_ids, picks=None, positions=None):
        """f32 [B, S, vocab], or [B, P, vocab] at the ``positions`` (int32
        [P]) alone; ``picks`` as ``loss`` takes them."""
        h, _, _ = self._run(params, input_ids, picks, lambda r: None)
        if positions is not None:
            h = h[:, positions]
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"], self.config.rms_norm_eps)
            return jnp.dot(h, params["wte"].astype(h.dtype).T,
                           preferred_element_type=jnp.float32)

    def __call__(self, params, rng, input_ids, labels=None, picks=None):
        """(L, the counters): the engine differentiates and reports the
        first and sums the scalars of the second (``aux_counters``)."""
        return self._objective(params, input_ids, labels, picks)
