"""Nemotron-H with sparse experts (``model_type: nemotron_h``, NVIDIA
Nemotron-3-Nano-30B-A3B's ``config.json``): a decoder in which every
layer is ONE sublayer, by the character of ``hybrid_override_pattern``
at its index: a Mamba-2 mixer (``M``), a sparse expert layer (``E``) or
grouped-query attention (``*``).  models/granite_hybrid.py is the dense
Mamba-2 hybrid (a mixer AND a gated FFN a layer, one group of B and C);
this one shares its mixer (models/mamba2.py) and its attention.

With ``N`` an RMSNorm with its own gain, no bias anywhere but the
conv's, a bf16 residual:

  model      ``h0 = E[ids]``; layer i: ``h = h + Mixer_i(N_i(h))``;
             ``logits = Nf(h) W_head`` (the head its own matrix, no muP
             multiplier).
  M          models/mamba2.py's mixer: 64 heads of 64 (the inner width
             is heads x head size; ``expand`` is not read), 128 states,
             ``n_groups`` groups of B and C (8 heads a group), the gated
             norm over each group's channels.
  *          ``position_free_attention`` (models/granite_hybrid.py): 32
             query heads on 2 key/value heads of 128, causal, softmax at
             1 / sqrt(head size), NO rotation and no other positional
             operation (the Nemotron-H report: the Mamba layers carry
             the order).
  E          ``moe.DroplessMoE`` on experts that are NOT gated
             (moe/experts.py ``ReluSquaredExpertMLP``: ``(relu(x
             W_up))^2 W_down``): sigmoid scores over all the routed
             experts in float32, the k largest of ``score + bias``
             picked, their scores renormalised and scaled, the held
             experts' part of the sum, one shared expert of its own width
             added once.  The selection bias is a leaf no gradient moves
             and the optimizer does not own (models/glm4_moe_lite.py
             ``SelectionBiasUpdate`` moves it from the step's picks).

How the sublayers are stacked, and why.  The pattern alternates
(``MEMEM*EME...``): a run of like layers, which granite_hybrid.py stacks
and scans, is one layer long here, and the unit that repeats (``MEMEM*E``
with a longer tail) does not divide the published 52.  So every sublayer
is a stacked group of ONE layer (leaves ``layers_00`` ... with a leading
axis of 1, a layer's weights a function of its published index alone),
the groups run unrolled in pattern order, and ONE recomputation budget is
spent over all of them (``ExpertStack._layer_wrapper``: a sublayer keeps
its input, the byte budget decides which named residuals stay, the
experts' row buffers count in every sublayer's working set since any
neighbour may be an ``E``).  A scan over a unit of unlike sublayers would
trace one body for nine shapes of parameters and buy nothing at this
depth; a cut of the model is ``num_hidden_layers`` (the first characters
of the pattern), ``experts_held`` and ``vocab_size``.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe.dropless import DroplessMoE
from ..moe.experts import ReluSquaredExpertMLP
from ..moe.sharded_moe import emit_routing_stats
from ..monitor import record as R
from ..ops.fused_cross_entropy import fused_linear_cross_entropy
from ..ops.normalize import rms_norm
from .glm4_moe_lite import SelectionBiasUpdate
from .granite_hybrid import position_free_attention
from .laguna import EMBEDDING_STD, ExpertStack
from .layer_stack import run_layer_stack
from .mamba2 import Mamba2Mixer

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
KIND_NAMES = {MAMBA: "mamba", EXPERTS: "experts", ATTENTION: "attention"}
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52          # the first characters of the pattern
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8                    # the mixer's groups of B and C
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # (first, count) of the routed experts held here; None is all
    experts_held: Optional[Tuple[int, int]] = None
    bias_update_rate: float = 0.001      # gamma of the selection bias
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # ``rescale_prenorm_residual``: every sublayer's output projection is
    # drawn at initializer_range / sqrt(this many layers), the PUBLISHED
    # depth whatever the cut (a layer's weights depend on its index
    # alone); None: at initializer_range like the rest
    rescale_layers: Optional[int] = 52
    bf16: bool = True
    activation_checkpointing: bool = False

    def __post_init__(self):
        self.hybrid_override_pattern = self.hybrid_override_pattern[
            :self.num_hidden_layers]
        if len(self.hybrid_override_pattern) != self.num_hidden_layers or \
                set(self.hybrid_override_pattern) - set(KIND_NAMES):
            raise ValueError(
                f"hybrid_override_pattern must name {self.num_hidden_layers} "
                f"layers, each one of {''.join(KIND_NAMES)}: "
                f"{self.hybrid_override_pattern!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of the "
                             "key/value heads")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(self.experts_held)

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32

    @property
    def mixer(self) -> Mamba2Mixer:
        return Mamba2Mixer(
            hidden_size=self.hidden_size, n_heads=self.mamba_num_heads,
            d_head=self.mamba_head_dim, d_state=self.ssm_state_size,
            n_groups=self.n_groups, d_conv=self.conv_kernel,
            chunk_size=self.chunk_size, eps=self.layer_norm_epsilon)

    def layers(self):
        """[(group name, kind)] of the kept layers, in order."""
        return [(f"layers_{i:02d}", kind)
                for i, kind in enumerate(self.hybrid_override_pattern)]


class NemotronHModel(SelectionBiasUpdate, ExpertStack):
    """The decoder over its sublayers, each a stacked group of one;
    trained through ``deepspeed_tpu.initialize`` like LagunaModel."""

    # the scalar of ``__call__``'s dict that the engine sums on the
    # device for whoever reads ``engine.model_counters()``
    aux_counters = (R.M_LOAD_MAX_OVER_MEAN,)

    # engine paths this model has not been run on, each with its reason;
    # the engine raises NotImplementedError with it at construction (an
    # expert axis larger than one is DroplessMoE's to refuse)
    refuses = {
        "zero3_streaming": (
            "the streamed ZeRO-3 layer scan walks ONE stacked group of "
            "like layers, and this stack is a pattern of unlike "
            "sublayers whose gathers would have to be chained from one "
            "to the next; the selection biases are leaves the optimizer "
            "does not own besides"),
        "pipeline": (
            "no pipeline module cuts a pattern of unlike sublayers into "
            "stages yet, and the selection biases move from routing "
            "counts that would have to travel between the stages"),
    }

    def __init__(self, config: NemotronHConfig):
        super().__init__(config, DroplessMoE(
            config.hidden_size, config.n_routed_experts,
            config.num_experts_per_tok, config.moe_intermediate_size,
            config.moe_shared_expert_intermediate_size
            * config.n_shared_experts,
            score="sigmoid", renormalize=config.norm_topk_prob,
            scale=config.routed_scaling_factor,
            experts_held=config.experts_held,
            init_std=config.initializer_range, selection_bias=True,
            # one rank of many: models/glm4_moe_lite.py has the reason
            first_chunk_always=True, expert=ReluSquaredExpertMLP))
        self.mixer = config.mixer

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng, kind):
        cfg = self.config
        hid = cfg.hidden_size
        keys = iter(jax.random.split(rng, 8))
        # an output projection's std over the other matrices'
        ratio = 1.0 / math.sqrt(cfg.rescale_layers or 1)

        def normal(shape):
            return cfg.initializer_range * jax.random.normal(
                next(keys), shape, jnp.float32)

        def out_normal(shape):
            return ratio * normal(shape)

        layer = {"ln": jnp.ones((hid,), jnp.float32)}
        if kind == MAMBA:
            layer["mixer"] = self.mixer.init_params(keys, normal, out_normal)
        elif kind == ATTENTION:
            width = cfg.num_attention_heads * cfg.head_dim
            kv = cfg.num_key_value_heads * cfg.head_dim
            layer["attn"] = {"qkv_w": normal((hid, width + 2 * kv)),
                             "out_w": out_normal((width, hid))}
        else:
            moe = self.moe.init_params(next(keys))
            for experts in (moe["experts"], moe.get("shared")):
                if experts is not None:
                    experts["w2"] = ratio * experts["w2"]
            layer["moe"] = moe
        return layer

    def init_params(self, rng):
        """Matrices normal(0, initializer_range), every sublayer's output
        projection at ``initializer_range / sqrt(rescale_layers)``; the
        embedding's rows normal(0, 1) (models/laguna.py has the reason);
        the mixer's own leaves as models/mamba2.py draws them; norm gains
        1; selection biases 0.  A layer's weights depend on its published
        index alone."""
        cfg = self.config
        k_wte, k_head, k_layers = jax.random.split(rng, 3)
        params = {
            "wte": EMBEDDING_STD * jax.random.normal(
                k_wte, (cfg.vocab_size, cfg.hidden_size), jnp.float32),
            "ln_f": jnp.ones((cfg.hidden_size,), jnp.float32),
            "head": cfg.initializer_range * jax.random.normal(
                k_head, (cfg.hidden_size, cfg.vocab_size), jnp.float32)}
        for i, (name, kind) in enumerate(cfg.layers()):
            params[name] = jax.vmap(
                lambda k, kind=kind: self._init_layer(k, kind))(
                jax.random.fold_in(k_layers, i)[None])
        return params

    def param_partition_specs(self):
        """No tensor- or expert-parallel split is written for this family
        yet: every leaf replicated over the model axis (ZeRO shards over
        the data axes as it does for any tree)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    def gates(self):
        """The paths of the expert layers' parameters in gate order:
        [(keys to the stacked ``moe`` dict, layers in it)]."""
        return [((name, "moe"), 1) for name, kind in self.config.layers()
                if kind == EXPERTS]

    # -- the layer ------------------------------------------------------ #
    def _layer(self, p, x, kind, picks=None):
        """(layer output, the expert layer's Routing or None)."""
        cfg = self.config
        with jax.named_scope("layer"):
            u = rms_norm(x, p["ln"], cfg.layer_norm_epsilon)
            if kind == MAMBA:
                return x + self.mixer.apply(p["mixer"], u), None
            if kind == ATTENTION:
                return x + position_free_attention(
                    p["attn"], u, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim,
                    1.0 / math.sqrt(cfg.head_dim)), None
            y, routing = self.moe.apply(p["moe"], u, picks=picks)
            return x + y, routing

    # -- the stack ------------------------------------------------------ #
    def stack_plan(self, batch, seq):
        """The M_STACK_* fields of this stack on [batch, seq] tokens."""
        cfg = self.config
        pattern = cfg.hybrid_override_pattern
        return {
            R.M_STACK_LAYERS: tuple(
                (i, KIND_NAMES[kind], 0) for i, kind in enumerate(pattern)),
            R.M_STACK_EXPERTS_HELD: (*cfg.experts_held,
                                     cfg.n_routed_experts),
            R.M_STACK_SSD: (
                self.mixer.scan_form(), cfg.chunk_size,
                self.mixer.entry_state_bytes(batch, seq),
                ", ".join(pattern), "unrolled", cfg.n_groups,
                self.mixer.conv_form(seq))}

    def _run(self, params, input_ids, picks, keep):
        """The hidden states before the final norm and ``keep(routing)``
        of every expert layer stacked in gate order."""
        cfg = self.config
        with jax.named_scope("embed"):
            h = params["wte"].astype(cfg.dtype)[input_ids]
        groups, gates = [], 0
        for name, kind in cfg.layers():
            sparse = kind == EXPERTS
            forced = picks[gates:gates + 1] if (
                sparse and picks is not None) else None
            gates += sparse

            def body(carry, xs, kind=kind):
                p, forced_picks = xs
                out, routing = self._layer(p, carry, kind, forced_picks)
                return out, None if routing is None else keep(routing)

            groups.append((body, (params[name], forced), sparse))
        wrap = self._layer_wrapper(groups, h,
                                   self.stack_plan(*input_ids.shape))
        kept = []
        for body, xs, sparse in groups:
            h, ys = run_layer_stack(wrap(body), h, xs, False, with_ys=True)
            if sparse:
                kept.append(ys)
        return h, (jax.tree.map(lambda *a: jnp.concatenate(a), *kept)
                   if kept else None)

    def _objective(self, params, input_ids, labels=None, picks=None):
        """(the mean next-token cross-entropy, the counters of
        ``aux_counters``: the picks of the busiest of ALL experts over
        the mean, averaged over the gates).  The RoutingStats of all
        gates go to the collecting tap as ONE entry, if the engine
        installed one (moe/sharded_moe.py): the sums over the gates, and
        each gate's picks an expert, which the selection biases are
        moved by."""
        cfg = self.config
        h, stats = self._run(params, input_ids, picks, self.moe.stats)
        counters = {R.M_LOAD_MAX_OVER_MEAN: jnp.float32(0.0)}
        if stats is not None:
            counts = stats.expert_counts                      # [L, E]
            counters[R.M_LOAD_MAX_OVER_MEAN] = jnp.mean(
                jnp.max(counts, axis=-1) / jnp.mean(counts, axis=-1))
            emit_routing_stats(jax.tree.map(
                lambda a: jnp.sum(a, axis=0), stats)._replace(
                layer_counts=counts))
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"], cfg.layer_norm_epsilon)
            if labels is None:
                h, labels = h[:, :-1], input_ids[:, 1:]
            loss = fused_linear_cross_entropy(
                h.reshape(-1, cfg.hidden_size),
                params["head"].astype(h.dtype),
                labels.reshape(-1).astype(jnp.int32))
        return loss, counters

    def routing(self, params, input_ids, with_inputs=False):
        """(scores f32 [L, T, E], picks int32 [L, T, k]) of the L expert
        layers on ``input_ids``, from the same forward pass as the loss;
        with ``with_inputs`` also what each router read, [L, T, hidden]."""
        _, kept = self._run(
            params, input_ids, None,
            lambda r: (r.scores, r.picks) + ((r.inputs,) * with_inputs))
        return kept

    def loss(self, params, rng, input_ids, labels=None, picks=None):
        """Mean next-token cross-entropy; ``input_ids[:, 1:]`` are the
        targets where `labels` is None.  `rng` is unused (no dropout).
        ``picks`` int32 [L, T, k] forces every expert layer's choice."""
        return self._objective(params, input_ids, labels, picks)[0]

    def logits(self, params, input_ids):
        """f32 [B, S, vocab]."""
        h, _ = self._run(params, input_ids, None, lambda r: None)
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"], self.config.layer_norm_epsilon)
            return (h @ params["head"].astype(h.dtype)).astype(jnp.float32)

    def __call__(self, params, rng, input_ids, labels=None, picks=None):
        """(L, {"load_max_over_mean"}): the engine differentiates and
        reports the first and sums the scalar of the second
        (``aux_counters``)."""
        return self._objective(params, input_ids, labels, picks)
