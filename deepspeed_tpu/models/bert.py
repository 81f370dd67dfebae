"""BERT family — encoder LM (the role bing_bert plays in the reference's
headline benchmarks: BERT-large pretraining, docs/_tutorials/bert-pretraining.md
and the fused-kernel tests tests/unit/modeling.py:1597).

Same TPU structure as GPT-2: stacked layers + lax.scan, fused transformer
body, declarative TP specs.  Loss = masked-LM cross entropy (positions with
label == ignore_index contribute nothing), matching the reference pretraining
objective minus NSP (which modern recipes drop).
"""

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from ..ops.transformer import (DeepSpeedTransformerConfig,
                               DeepSpeedTransformerLayer)
from ..ops.normalize import fused_layer_norm
from ..ops.activations import dropout
from ..parallel.mesh import MODEL_AXIS
from ..runtime.activation_checkpointing.checkpointing import checkpoint_layer


@dataclass
class BertConfig:
    vocab_size: int = 30592          # 30522 padded to a 128 multiple
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_size: int = 1024          # BERT-large defaults
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: Optional[int] = None
    embd_dropout: float = 0.1
    attn_dropout: float = 0.1
    hidden_dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    hidden_act: str = "gelu"         # HF BERT default: exact erf gelu
    initializer_range: float = 0.02
    bf16: bool = True
    # attention kernel layout: "bhsd" (classic) or "bshd" (API
    # convenience; converts at the kernel boundary — a native bshd
    # BlockSpec is Mosaic-illegal, measured round 3)
    attn_layout: str = "bhsd"
    attn_dropout_impl: str = "kernel"  # "kernel" (reference semantics) | "ctx" (cheaper)
    pre_layer_norm: bool = True      # reference supports both (preln/postln)
    activation_checkpointing: bool = False
    sparse_attention: Optional[object] = None  # a SparsityConfig
    ignore_index: int = -100
    # layer-stack execution, same semantics as GPT2Config.scan_layers
    scan_layers: Optional[bool] = None
    # chunked LM-head + CE (ops/fused_cross_entropy.py) — never SAVES the
    # [B, S, V] fp32 logits; None = auto chunk from the transient budget
    fused_loss: bool = True
    fused_loss_chunk: Optional[int] = None

    @property
    def use_scan(self) -> bool:
        from .layer_stack import resolve_use_scan
        return resolve_use_scan(self.scan_layers, self.num_layers)

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32

    def layer_config(self) -> DeepSpeedTransformerConfig:
        return DeepSpeedTransformerConfig(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            heads=self.num_heads,
            attn_dropout_ratio=self.attn_dropout,
            hidden_dropout_ratio=self.hidden_dropout,
            num_hidden_layers=self.num_layers,
            initializer_range=self.initializer_range,
            layer_norm_eps=self.layer_norm_eps,
            bf16=self.bf16,
            pre_layer_norm=self.pre_layer_norm,
            causal=False,
            activation=self.hidden_act,
            sparsity_config=self.sparse_attention,
            attn_layout=self.attn_layout,
            attn_dropout_impl=self.attn_dropout_impl,
        )

    def num_params(self, include_embeddings: bool = True) -> int:
        layer = DeepSpeedTransformerLayer(self.layer_config())
        n = self.num_layers * layer.num_params() + 2 * self.hidden_size
        if include_embeddings:
            n += (self.vocab_size + self.max_position_embeddings +
                  self.type_vocab_size) * self.hidden_size
        return n


class BertModel:
    """Encoder LM over stacked DeepSpeedTransformerLayers (MLM objective)."""

    def __init__(self, config: BertConfig):
        self.config = config
        self.layer = DeepSpeedTransformerLayer(config.layer_config())
        self._remat_budget = None

    def install_remat_budget(self, budget) -> None:
        """Engine hook: the bytes the layer scan's checkpointing may spend
        on saved residuals (checkpointing.RematBudget)."""
        self._remat_budget = budget

    def init_params(self, rng):
        cfg = self.config
        k_wte, k_wpe, k_tte, k_layers = jax.random.split(rng, 4)
        init = jax.nn.initializers.normal(cfg.initializer_range)
        layer_keys = jax.random.split(k_layers, cfg.num_layers)
        stacked = jax.vmap(self.layer.init_params)(layer_keys)
        return {
            "wte": init(k_wte, (cfg.vocab_size, cfg.hidden_size), jnp.float32),
            "wpe": init(k_wpe, (cfg.max_position_embeddings, cfg.hidden_size),
                        jnp.float32),
            "tte": init(k_tte, (cfg.type_vocab_size, cfg.hidden_size),
                        jnp.float32),
            "emb_ln": {"w": jnp.ones((cfg.hidden_size,), jnp.float32),
                       "b": jnp.zeros((cfg.hidden_size,), jnp.float32)},
            "h": stacked,
        }

    def param_partition_specs(self):
        layer_specs = DeepSpeedTransformerLayer.param_partition_specs()
        stacked_specs = {k: P(None, *list(s)) for k, s in layer_specs.items()}
        return {
            "wte": P(MODEL_AXIS, None),
            "wpe": P(),
            "tte": P(),
            "emb_ln": {"w": P(), "b": P()},
            "h": stacked_specs,
        }

    def hidden_states(self, params, input_ids, attention_mask=None,
                      token_type_ids=None, rng=None,
                      deterministic: bool = False):
        cfg = self.config
        b, s = input_ids.shape
        if rng is None:
            deterministic = True
            rng = jax.random.PRNGKey(0)
        r_embd, r_layers = jax.random.split(rng)

        h = (params["wte"].astype(cfg.dtype)[input_ids] +
             params["wpe"].astype(cfg.dtype)[jnp.arange(s)])
        if token_type_ids is not None:
            h = h + params["tte"].astype(cfg.dtype)[token_type_ids]
        h = fused_layer_norm(h, params["emb_ln"]["w"], params["emb_ln"]["b"],
                             cfg.layer_norm_eps)
        h = dropout(h, cfg.embd_dropout, r_embd, deterministic)

        bias = None
        if attention_mask is not None:
            # [B, S] 1/0 mask -> additive [B, 1, 1, S]
            bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0,
                             -1e9).astype(jnp.float32)

        layer_fn = self.layer

        def body(carry, xs):
            layer_params, layer_rng = xs
            out = layer_fn(layer_params, carry, attn_mask=bias, rng=layer_rng,
                           deterministic=deterministic)
            return out, None

        layer_rngs = jax.random.split(r_layers, cfg.num_layers)
        if cfg.activation_checkpointing:
            body = checkpoint_layer(body, self._remat_budget, h,
                                    (params["h"], layer_rngs),
                                    head_width=cfg.vocab_size)
        from .layer_stack import run_layer_stack
        return run_layer_stack(body, h, (params["h"], layer_rngs),
                               cfg.use_scan)

    def mlm_loss(self, params, rng, input_ids, labels,
                 attention_mask=None, token_type_ids=None):
        """Masked-LM loss; positions with labels == ignore_index are
        excluded (reference objective, bing_bert pretraining)."""
        cfg = self.config
        h = self.hidden_states(params, input_ids, attention_mask,
                               token_type_ids, rng)
        if cfg.fused_loss:
            from ..ops.fused_cross_entropy import fused_linear_cross_entropy
            return fused_linear_cross_entropy(
                h.reshape(-1, cfg.hidden_size),
                params["wte"].astype(h.dtype).T,
                labels.reshape(-1).astype(jnp.int32),
                cfg.fused_loss_chunk, cfg.ignore_index)
        logits = (h @ params["wte"].astype(h.dtype).T).astype(jnp.float32)
        valid = labels != cfg.ignore_index
        safe_labels = jnp.where(valid, labels, 0)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits, safe_labels)
        denom = jnp.maximum(jnp.sum(valid), 1)
        return jnp.sum(per_tok * valid) / denom

    def __call__(self, params, rng, input_ids, labels,
                 attention_mask=None, token_type_ids=None):
        return self.mlm_loss(params, rng, input_ids, labels,
                             attention_mask, token_type_ids)
