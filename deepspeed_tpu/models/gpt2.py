"""GPT-2 family — the flagship decoder LM (the role Megatron-GPT2 plays for
the reference's headline ZeRO benchmarks, docs/_tutorials/megatron.md).

TPU-native structure:
  - all transformer layers stored STACKED (leading layer axis) and executed
    with `lax.scan` — one compiled layer body regardless of depth, the
    XLA-friendly analog of the reference's per-layer module list;
  - per-layer activation checkpointing = `jax.checkpoint` around the scanned
    body, keeping the flash kernel's outputs where the engine's byte
    budget admits them (the kernel then runs once a layer) and
    recomputing the rest; the layer's input alone with no budget or
    under the streamed ZeRO-3 scan (runtime/activation_checkpointing/
    checkpointing.py, `checkpoint_layer`);
  - tensor parallelism is declarative: `param_partition_specs` emits
    Megatron-style column/row specs over the "model" mesh axis, vocab-sharded
    embedding included (the role of Megatron's VocabParallelEmbedding).
"""

from dataclasses import dataclass
from typing import Optional

import numpy as np

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
class GPT2Config:
    vocab_size: int = 50304          # 50257 padded to a 128 multiple (MXU)
    n_positions: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None
    embd_dropout: float = 0.1
    attn_dropout: float = 0.1
    hidden_dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    bf16: bool = True
    # attention kernel layout: "bhsd" (classic) or "bshd" (API
    # convenience; converts at the kernel boundary — a native bshd
    # BlockSpec is Mosaic-illegal, measured round 3)
    attn_layout: str = "bhsd"
    attn_dropout_impl: str = "kernel"  # "kernel" (reference semantics) | "ctx" (cheaper)
    activation_checkpointing: bool = False
    sparse_attention: Optional[object] = None  # a SparsityConfig
    tie_word_embeddings: bool = True
    # chunked LM-head + cross-entropy: never SAVES the [B,S,V] fp32 logits
    # (ops/fused_cross_entropy.py); None = auto chunk from the transient
    # budget (largest chunk wins on speed — ops/fused_cross_entropy.py)
    fused_loss: bool = True
    fused_loss_chunk: Optional[int] = None
    # layer-stack execution: None = auto (unrolled up to the measured
    # threshold, scan beyond — see models/layer_stack.py).  ZeRO-3
    # streaming always uses its gather-scan.
    scan_layers: Optional[bool] = None

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
            pre_layer_norm=True,
            causal=True,
            sparsity_config=self.sparse_attention,
            attn_layout=self.attn_layout,
            attn_dropout_impl=self.attn_dropout_impl,
        )

    def num_params(self, include_embeddings: bool = True) -> int:
        layer = DeepSpeedTransformerLayer(self.layer_config())
        n = self.num_layers * layer.num_params() + 2 * self.hidden_size
        if include_embeddings:
            n += (self.vocab_size + self.n_positions) * self.hidden_size
        return n


class GPT2Model:
    """Decoder-only LM over stacked DeepSpeedTransformerLayers."""

    @property
    def sparse_grad_paths(self):
        """engine "sparse_gradients" consumers: row-sparse embedding grads
        are reduced as (indices, values) instead of a dense allreduce
        (reference: engine.py:1729-1792 sparse_allreduce — which applies to
        sparse nn.Embedding grads).  Only valid UNTIED: a tied LM head adds
        a dense d loss/d wte contribution over every vocab row."""
        if self.config.tie_word_embeddings:
            return ()
        return ("wte",)

    def __init__(self, config: GPT2Config):
        self.config = config
        self.layer = DeepSpeedTransformerLayer(config.layer_config())
        self._zero3_stream = None
        self._remat_budget = None

    def install_zero3_streaming(self, stream_ctx) -> None:
        """Engine hook: route the layer-stack scan through the explicit
        ZeRO-3 gather/prefetch executor (runtime/zero/stage3_streaming.py —
        the stage3_max_live_parameters / stage3_prefetch_bucket_size
        consumer; reference stage3.py:294 PartitionedParameterCoordinator)."""
        self._zero3_stream = stream_ctx

    def install_remat_budget(self, budget) -> None:
        """Engine hook: the bytes the layer scan's checkpointing may spend
        on saved residuals (checkpointing.RematBudget)."""
        self._remat_budget = budget

    # -- parameters ---------------------------------------------------- #
    def init_params(self, rng):
        cfg = self.config
        k_wte, k_wpe, k_layers = jax.random.split(rng, 3)
        init = jax.nn.initializers.normal(cfg.initializer_range)
        layer_keys = jax.random.split(k_layers, cfg.num_layers)
        stacked = jax.vmap(self.layer.init_params)(layer_keys)
        params = {
            "wte": init(k_wte, (cfg.vocab_size, cfg.hidden_size), jnp.float32),
            "wpe": init(k_wpe, (cfg.n_positions, cfg.hidden_size),
                        jnp.float32),
            "h": stacked,
            "ln_f": {"w": jnp.ones((cfg.hidden_size,), jnp.float32),
                     "b": jnp.zeros((cfg.hidden_size,), jnp.float32)},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = init(
                jax.random.fold_in(k_wte, 1),
                (cfg.hidden_size, cfg.vocab_size), jnp.float32)
        return params

    def param_partition_specs(self):
        """TP specs: vocab-sharded embeddings + Megatron column/row layer
        splits over the "model" axis."""
        layer_specs = DeepSpeedTransformerLayer.param_partition_specs()
        stacked_specs = {k: P(None, *list(s)) for k, s in layer_specs.items()}
        specs = {
            "wte": P(MODEL_AXIS, None),
            "wpe": P(),
            "h": stacked_specs,
            "ln_f": {"w": P(), "b": P()},
        }
        if not self.config.tie_word_embeddings:
            specs["lm_head"] = P(None, MODEL_AXIS)
        return specs

    # -- forward ------------------------------------------------------- #
    def embed(self, params, input_ids, position_offset=0):
        """Token + position embedding; position_offset supports KV-cache
        decode (inference engine feeds one token at position `pos`)."""
        cfg = self.config
        wte = params["wte"].astype(cfg.dtype)
        wpe = params["wpe"].astype(cfg.dtype)
        pos = position_offset + jnp.arange(input_ids.shape[1])
        return wte[input_ids] + wpe[pos]

    def _head_matrix(self, params, dtype):
        """[H, V] LM projection — tied wte.T or the independent lm_head.
        (The layer-streaming path re-derives the tie from its own group
        split — layerwise_api head_loss_fn.)"""
        if self.config.tie_word_embeddings:
            return params["wte"].astype(dtype).T
        return params["lm_head"].astype(dtype)

    def _final_hidden(self, params, h):
        """Final layer norm shared by head_logits and the fused-loss path."""
        return fused_layer_norm(h, params["ln_f"]["w"], params["ln_f"]["b"],
                                self.config.layer_norm_eps)

    @staticmethod
    def _shift_for_next_token(h, input_ids, labels):
        """Next-token convention: when labels is None, input_ids[:, 1:] are
        the targets and the last hidden column is dropped (keeps the
        attention length unchanged, e.g. divisible by a sparse-attention
        block)."""
        if labels is None:
            return h[:, :-1], input_ids[:, 1:]
        return h, labels

    def head_logits(self, params, h):
        """Final LN + (tied) LM head, fp32 logits."""
        with jax.named_scope("head"):
            h = self._final_hidden(params, h)
            return (h @ self._head_matrix(params, h.dtype)).astype(
                jnp.float32)

    def hidden_states(self, params, input_ids, rng=None,
                      deterministic: bool = False, pld_theta=None):
        """input_ids [B, S] -> pre-head hidden states [B, S, H] (the final
        LN lives in head_logits so the KV-cache decode path shares it).

        pld_theta: progressive-layer-drop keep probability theta(t)
        (reference: runtime/progressive_layer_drop.py injected via
        engine.py:1236).  Layer i keeps its residual branch with
        p_i = 1 - (i/L)(1 - theta) — deeper layers drop more (PLD paper's
        depth schedule) — gated per step inside the scan."""
        cfg = self.config
        if rng is None:
            deterministic = True
            rng = jax.random.PRNGKey(0)
        r_embd, r_layers, r_pld = jax.random.split(rng, 3)

        with jax.named_scope("embed"):
            h = self.embed(params, input_ids)
            h = dropout(h, cfg.embd_dropout, r_embd, deterministic)

        layer_fn = self.layer
        use_pld = pld_theta is not None and not deterministic
        n = cfg.num_layers
        if use_pld:
            keep_probs = 1.0 - (jnp.arange(n, dtype=jnp.float32) / n) * \
                (1.0 - jnp.float32(pld_theta))
            pld_keys = jax.random.split(r_pld, n)

        stream = self._zero3_stream
        # usable() also covers the post-engine life of the model object
        # (stale mesh, batch-1 decode); it is the same predicate scan gates
        # on internally, so the fold below only runs inside the manual
        # region.
        streaming = stream is not None and stream.usable(
            h, params=params["h"])

        def body(carry, xs):
            if use_pld:
                layer_params, layer_rng, keep_p, pld_key = xs
            else:
                layer_params, layer_rng = xs
            if streaming and not deterministic:
                # Inside the manual ZeRO region every shard sees the same
                # layer rng; fold in the shard index so dropout masks stay
                # independent across the batch shards.
                layer_rng = stream.fold_shard_index(layer_rng)
            with jax.named_scope("layer"):
                out = layer_fn(layer_params, carry, rng=layer_rng,
                               deterministic=deterministic)
            if use_pld:
                keep = jax.random.bernoulli(pld_key, keep_p)
                out = jnp.where(keep, out, carry)
            return out, None

        layer_rngs = jax.random.split(r_layers, n)
        extras = ((layer_rngs, keep_probs, pld_keys) if use_pld
                  else (layer_rngs,))
        if cfg.activation_checkpointing:
            # The carried stream takes each layer's VJP from its saved
            # input carry, so a residual kept by the body's policy lives
            # for one layer's VJP only: memory spent, no kernel spared
            # (PERF.md section 7).  It keeps whole-layer recomputation.
            body = checkpoint_layer(
                body, None if streaming else self._remat_budget, h,
                (params["h"],) + extras, head_width=cfg.vocab_size)
        if streaming:
            h = stream.scan(body, h, params["h"], extras,
                            param_tp_specs=self.param_partition_specs()["h"])
        else:
            from .layer_stack import run_layer_stack
            h = run_layer_stack(body, h, (params["h"],) + extras,
                                cfg.use_scan)
        return h

    # -- layer-streaming protocol (ZeRO-Infinity param offload) --------- #
    def layerwise_api(self):
        """Split the model into streaming groups for the layer-streaming
        engine (runtime/zero/infinity.py): embed / one group per layer /
        head.  The reference's analog is the per-submodule fetch units of
        stage3.py:397 fetch_sub_module.

        Tied embeddings: the head group reads `wte` from the EMBED group, so
        wte gradients accumulate from both the embedding lookup and the LM
        head matmul (the reference ties them through the shared Parameter).
        """
        cfg = self.config
        layer = self.layer
        n = cfg.num_layers

        def split(params):
            groups = {"embed": {"wte": params["wte"], "wpe": params["wpe"]}}
            for i in range(n):
                groups[f"layer{i}"] = jax.tree.map(lambda a: a[i],
                                                   params["h"])
            head = {"ln_f": params["ln_f"]}
            if not cfg.tie_word_embeddings:
                head["lm_head"] = params["lm_head"]
            groups["head"] = head
            return groups

        def join(groups):
            params = {
                "wte": groups["embed"]["wte"],
                "wpe": groups["embed"]["wpe"],
                "h": jax.tree.map(
                    lambda *ls: np.stack(ls) if isinstance(
                        ls[0], np.ndarray) else jnp.stack(ls),
                    *[groups[f"layer{i}"] for i in range(n)]),
                "ln_f": groups["head"]["ln_f"],
            }
            if not cfg.tie_word_embeddings:
                params["lm_head"] = groups["head"]["lm_head"]
            return params

        def join_consuming(groups):
            """join, but each numpy layer-group leaf is FREED right after
            its row is copied into the stacked array — the transient is
            one stacked leaf instead of a full second copy of all layer
            tensors.  The streaming engine's optimizer boundary calls
            this on the accumulated grad tier, where the naive join's
            extra full-model copy OOMed a 125 GB host at 4.2B (r4)."""
            layer_groups = [groups[f"layer{i}"] for i in range(n)]
            treedef = jax.tree.structure(layer_groups[0])
            flats = [treedef.flatten_up_to(g) for g in layer_groups]
            out_leaves = []
            for li in range(treedef.num_leaves):
                rows = [flats[i][li] for i in range(n)]
                if isinstance(rows[0], np.ndarray):
                    out = np.empty((n,) + rows[0].shape, rows[0].dtype)
                    for i in range(n):
                        out[i] = rows[i]
                        flats[i][li] = None
                        rows[i] = None
                else:
                    out = jnp.stack(rows)
                out_leaves.append(out)
            for i in range(n):
                groups[f"layer{i}"] = None
            params = {
                "wte": groups["embed"]["wte"],
                "wpe": groups["embed"]["wpe"],
                "h": jax.tree_util.tree_unflatten(treedef, out_leaves),
                "ln_f": groups["head"]["ln_f"],
            }
            if not cfg.tie_word_embeddings:
                params["lm_head"] = groups["head"]["lm_head"]
            return params

        def embed_fn(embed_g, input_ids, rng):
            wte = embed_g["wte"].astype(cfg.dtype)
            wpe = embed_g["wpe"].astype(cfg.dtype)
            h = wte[input_ids] + wpe[jnp.arange(input_ids.shape[1])]
            deterministic = rng is None
            r = rng if rng is not None else jax.random.PRNGKey(0)
            return dropout(h, cfg.embd_dropout, r, deterministic)

        def layer_fn(layer_g, h, rng, layer_idx):
            r = (jax.random.fold_in(rng, layer_idx)
                 if rng is not None else None)
            return layer(layer_g, h, rng=r,
                         deterministic=rng is None)

        def head_loss_fn(head_g, embed_g, h, input_ids, labels):
            hs = fused_layer_norm(h, head_g["ln_f"]["w"],
                                  head_g["ln_f"]["b"], cfg.layer_norm_eps)
            if cfg.tie_word_embeddings:
                head = embed_g["wte"].astype(hs.dtype).T
            else:
                head = head_g["lm_head"].astype(hs.dtype)
            hs, labels = GPT2Model._shift_for_next_token(
                hs, input_ids, labels)
            if cfg.fused_loss:
                from ..ops.fused_cross_entropy import (
                    fused_linear_cross_entropy)
                return fused_linear_cross_entropy(
                    hs.reshape(-1, cfg.hidden_size), head,
                    labels.reshape(-1).astype(jnp.int32),
                    cfg.fused_loss_chunk)
            logits = (hs @ head).astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        return {"split": split, "join": join,
                "join_consuming": join_consuming, "embed_fn": embed_fn,
                "layer_fn": layer_fn, "head_loss_fn": head_loss_fn,
                "num_layers": n}

    def logits(self, params, input_ids, rng=None, deterministic=False,
               pld_theta=None):
        h = self.hidden_states(params, input_ids, rng, deterministic,
                               pld_theta)
        return self.head_logits(params, h)

    def loss(self, params, rng, input_ids, labels=None, pld_theta=None):
        """Next-token cross entropy (fp32 softmax).  When labels is None,
        input_ids[:, 1:] serve as targets; the model runs on the FULL
        sequence and the last logit column is dropped (keeps the attention
        length unchanged, e.g. divisible by a sparse-attention block).

        With cfg.fused_loss (default) the head projection and the CE fuse
        into a vocab-chunked streaming pass that never materializes the
        [B, S, V] fp32 logits — the LM-head HBM fix."""
        cfg = self.config
        if cfg.fused_loss:
            from ..ops.fused_cross_entropy import fused_linear_cross_entropy
            h = self.hidden_states(params, input_ids, rng,
                                   deterministic=rng is None,
                                   pld_theta=pld_theta)
            with jax.named_scope("head"):
                h = self._final_hidden(params, h)
                h, labels2 = self._shift_for_next_token(h, input_ids,
                                                        labels)
                return fused_linear_cross_entropy(
                    h.reshape(-1, cfg.hidden_size),
                    self._head_matrix(params, h.dtype),
                    labels2.reshape(-1).astype(jnp.int32),
                    cfg.fused_loss_chunk)
        logits = self.logits(params, input_ids, rng,
                             deterministic=rng is None,
                             pld_theta=pld_theta).astype(jnp.float32)
        with jax.named_scope("head"):
            logits, labels = self._shift_for_next_token(logits, input_ids,
                                                        labels)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

    # engine entry point: model(params, rng, batch...) -> loss
    def __call__(self, params, rng, input_ids, labels=None, pld_theta=None):
        return self.loss(params, rng, input_ids, labels, pld_theta)
