"""GPT-MoE — decoder LM with gated expert FFNs on alternating layers.

Reference pattern: Megatron-MoE / GShard place a `MoE` layer in the FFN
position of every other transformer layer (deepspeed/moe/layer.py:18 MoE
wraps gate+experts; the 0.5.2-era examples interleave dense and expert
layers).  Here the composition is explicit: dense layers are full
DeepSpeedTransformerLayers; MoE layers are an attention-only layer
(ffn="none") followed by [pre-LN -> top-k gated experts -> dropout ->
residual], with the GShard load-balancing loss summed across MoE layers
and added to the LM loss.

Layers are stored per-layer (a tuple under "h") and executed unrolled —
dense and MoE layers have different param trees, so the homogeneous-stack
scan machinery (layer_stack.py) does not apply.  Expert parallelism rides
the mesh's "expert" axis; everything else composes exactly as GPT2Model
(ZeRO 0-2, TP on the attention/dense layers, dp).
"""

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..moe import MoE
from ..ops.activations import dropout
from ..ops.normalize import fused_layer_norm
from ..ops.transformer import (DeepSpeedTransformerConfig,
                               DeepSpeedTransformerLayer)
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import MODEL_AXIS


@dataclass
class GPTMoEConfig:
    vocab_size: int = 50257
    n_positions: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None
    # --- MoE ---
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    min_capacity: int = 4
    moe_every: int = 2            # layer i is MoE when i % moe_every == 1
    moe_aux_loss_coef: float = 0.01
    # --- shared with GPT2Config ---
    embd_dropout: float = 0.1
    attn_dropout: float = 0.1
    hidden_dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    bf16: bool = True
    attn_layout: str = "bhsd"
    tie_word_embeddings: bool = True
    # chunked fused linear+CE (the LM-head HBM fix — same knobs as
    # GPT2Config): never materializes the [B, S, V] fp32 logits
    fused_loss: bool = True
    fused_loss_chunk: int = 8192

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32

    def is_moe_layer(self, i: int) -> bool:
        """Layer i carries the expert FFN when i % moe_every is the LAST
        slot of its group — moe_every=2 gives layers 1,3,5,... (the GShard
        interleave); moe_every=1 makes EVERY layer MoE."""
        return (self.moe_every > 0 and
                i % self.moe_every == self.moe_every - 1)

    def layer_config(self, ffn: str) -> DeepSpeedTransformerConfig:
        return DeepSpeedTransformerConfig(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            heads=self.num_heads,
            attn_dropout_ratio=self.attn_dropout,
            hidden_dropout_ratio=self.hidden_dropout,
            num_hidden_layers=self.num_layers,
            initializer_range=self.initializer_range,
            layer_norm_eps=self.layer_norm_eps,
            bf16=self.bf16, pre_layer_norm=True, causal=True,
            attn_layout=self.attn_layout, ffn=ffn)

    def num_params(self) -> int:
        dense = DeepSpeedTransformerLayer(self.layer_config("dense"))
        attn_only = DeepSpeedTransformerLayer(self.layer_config("none"))
        h, inter = self.hidden_size, self.intermediate_size
        expert_ffn = self.num_experts * (2 * h * inter + h + inter)
        gate = h * self.num_experts
        n = 0
        for i in range(self.num_layers):
            if self.is_moe_layer(i):
                n += attn_only.num_params() + 2 * h + expert_ffn + gate
            else:
                n += dense.num_params()
        n += 2 * self.hidden_size  # ln_f
        n += (self.vocab_size + self.n_positions) * self.hidden_size
        if not self.tie_word_embeddings:
            n += self.hidden_size * self.vocab_size
        return n


class GPTMoEModel:
    """Decoder LM with expert FFNs on alternating layers."""

    def __init__(self, config: GPTMoEConfig):
        self.config = config
        self.dense_layer = DeepSpeedTransformerLayer(
            config.layer_config("dense"))
        self.attn_layer = DeepSpeedTransformerLayer(
            config.layer_config("none"))
        self.moe = MoE(hidden_size=config.hidden_size,
                       num_experts=config.num_experts, k=config.top_k,
                       capacity_factor=config.capacity_factor,
                       min_capacity=config.min_capacity)

    # -- parameters ---------------------------------------------------- #
    def init_params(self, rng):
        cfg = self.config
        k_wte, k_wpe, k_layers = jax.random.split(rng, 3)
        init = jax.nn.initializers.normal(cfg.initializer_range)
        layer_keys = jax.random.split(k_layers, cfg.num_layers)
        layers = []
        probe = jnp.zeros((1, cfg.hidden_size), jnp.float32)
        for i in range(cfg.num_layers):
            if cfg.is_moe_layer(i):
                ka, km = jax.random.split(layer_keys[i])
                layers.append({
                    "attn": self.attn_layer.init_params(ka),
                    "moe_nw": jnp.ones((cfg.hidden_size,), jnp.float32),
                    "moe_nb": jnp.zeros((cfg.hidden_size,), jnp.float32),
                    "moe": self.moe.init_params(km, probe),
                })
            else:
                layers.append(self.dense_layer.init_params(layer_keys[i]))
        params = {
            "wte": init(k_wte, (cfg.vocab_size, cfg.hidden_size),
                        jnp.float32),
            "wpe": init(k_wpe, (cfg.n_positions, cfg.hidden_size),
                        jnp.float32),
            "h": tuple(layers),
            "ln_f": {"w": jnp.ones((cfg.hidden_size,), jnp.float32),
                     "b": jnp.zeros((cfg.hidden_size,), jnp.float32)},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = init(
                jax.random.fold_in(k_wte, 1),
                (cfg.hidden_size, cfg.vocab_size), jnp.float32)
        return params

    def param_partition_specs(self):
        cfg = self.config
        dense_specs = DeepSpeedTransformerLayer.param_partition_specs(
            "dense")
        attn_specs = DeepSpeedTransformerLayer.param_partition_specs("none")
        layers = []
        for i in range(cfg.num_layers):
            if cfg.is_moe_layer(i):
                layers.append({
                    "attn": attn_specs,
                    "moe_nw": P(), "moe_nb": P(),
                    "moe": self.moe.param_partition_specs(),
                })
            else:
                layers.append(dense_specs)
        specs = {
            "wte": P(MODEL_AXIS, None),
            "wpe": P(),
            "h": tuple(layers),
            "ln_f": {"w": P(), "b": P()},
        }
        if not cfg.tie_word_embeddings:
            specs["lm_head"] = P(None, MODEL_AXIS)
        return specs

    # -- forward ------------------------------------------------------- #
    def hidden_states(self, params, input_ids, rng=None,
                      deterministic: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (h [B, S, H], l_aux_sum) — the summed GShard
        load-balancing loss of every MoE layer (reference: sharded_moe
        l_aux, consumed at moe_aux_loss_coef in loss())."""
        cfg = self.config
        if rng is None:
            deterministic = True
            rng = jax.random.PRNGKey(0)
        r_embd, r_layers = jax.random.split(rng)

        wte = params["wte"].astype(cfg.dtype)
        wpe = params["wpe"].astype(cfg.dtype)
        h = wte[input_ids] + wpe[jnp.arange(input_ids.shape[1])]
        h = dropout(h, cfg.embd_dropout, r_embd, deterministic)

        b, s, hid = h.shape
        l_aux_sum = jnp.float32(0.0)
        layer_rngs = jax.random.split(r_layers, cfg.num_layers)
        for i, lp in enumerate(params["h"]):
            r = None if deterministic else layer_rngs[i]
            if cfg.is_moe_layer(i):
                h = self.attn_layer(lp["attn"], h, rng=r,
                                    deterministic=deterministic)
                moe_in = fused_layer_norm(h, lp["moe_nw"], lp["moe_nb"],
                                          cfg.layer_norm_eps)
                flat = moe_in.reshape(b * s, hid)
                # distinct key: r's children feed the attention dropouts,
                # so the gate's rsample noise gets its own fold
                r_moe = (jax.random.fold_in(r, 13)
                         if r is not None else None)
                out, l_aux, _ = self.moe.apply(
                    lp["moe"], flat, rng=r_moe, train=not deterministic)
                out = out.reshape(b, s, hid).astype(h.dtype)
                out = dropout(out, cfg.hidden_dropout,
                              (jax.random.fold_in(r, 7)
                               if r is not None else jax.random.PRNGKey(0)),
                              deterministic or r is None)
                h = h + out
                l_aux_sum = l_aux_sum + l_aux.astype(jnp.float32)
            else:
                h = self.dense_layer(lp, h, rng=r,
                                     deterministic=deterministic)
        return h, l_aux_sum

    # -- head (shared by logits and loss) ------------------------------ #
    def _final_hidden_and_head(self, params, h):
        h = fused_layer_norm(h, params["ln_f"]["w"], params["ln_f"]["b"],
                             self.config.layer_norm_eps)
        if self.config.tie_word_embeddings:
            head = params["wte"].astype(h.dtype).T
        else:
            head = params["lm_head"].astype(h.dtype)
        return h, head

    def logits(self, params, input_ids, rng=None, deterministic=False):
        h, _ = self.hidden_states(params, input_ids, rng, deterministic)
        h, head = self._final_hidden_and_head(params, h)
        return (h @ head).astype(jnp.float32)

    def loss(self, params, rng, input_ids, labels=None):
        """Next-token CE + moe_aux_loss_coef * summed l_aux (the GShard
        auxiliary loss placement, reference sharded_moe.py top2gating).
        With cfg.fused_loss the head projection and CE fuse into the
        vocab-chunked streaming pass (no [B, S, V] fp32 logits — the same
        LM-head HBM fix as GPT2Model.loss)."""
        cfg = self.config
        h, l_aux = self.hidden_states(params, input_ids, rng,
                                      deterministic=rng is None)
        h, head = self._final_hidden_and_head(params, h)
        if labels is None:
            h, labels = h[:, :-1], input_ids[:, 1:]
        if cfg.fused_loss:
            from ..ops.fused_cross_entropy import fused_linear_cross_entropy
            ce = fused_linear_cross_entropy(
                h.reshape(-1, cfg.hidden_size), head,
                labels.reshape(-1).astype(jnp.int32), cfg.fused_loss_chunk)
        else:
            logits = (h @ head).astype(jnp.float32)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
        return ce + cfg.moe_aux_loss_coef * l_aux

    def __call__(self, params, rng, input_ids, labels=None):
        """Engine entry: loss(params, rng, batch...) like GPT2Model."""
        return self.loss(params, rng, input_ids, labels)
