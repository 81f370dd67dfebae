"""DeepSpeedTransformerLayer — the fused transformer block.

Reference: deepspeed/ops/transformer/transformer.py — DeepSpeedTransformerConfig
(:39), DeepSpeedTransformerLayer (:462, owns attn_qkvw/attn_qkvb/attn_ow/...),
backed by the csrc/transformer CUDA kernels.

TPU-native: the layer is a pure function over a param pytree (same weight
names as the reference for checkpoint parity).  Attention runs the Pallas
flash kernel; LN the fused LN; bias/gelu/dropout chains are left to XLA
fusion.  Tensor parallelism is declared, not coded: `param_partition_specs`
returns the Megatron-style column/row split over the "model" mesh axis and
GSPMD inserts the per-layer collectives.  (Exception: inside shard_map-manual
regions — the gated 1F1B executor — `__call__(tp_axis=...)` runs the same
split with EXPLICIT collectives, the f/g operator pair of
ops/tp_collectives.py, so they stay out of divergent control flow.)
"""

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import MODEL_AXIS
from .activations import bias_gelu, bias_dropout_residual, dropout
from .flash_attention import flash_attention, flash_attention_bsh
from .normalize import fused_layer_norm
from .quant import matmul_maybe_int8
from .tp_collectives import tp_fcast, tp_psum


@dataclass
class DeepSpeedTransformerConfig:
    """Mirror of ops/transformer/transformer.py:39 (CUDA-only knobs dropped,
    TPU knobs added)."""
    batch_size: int = -1
    hidden_size: int = -1
    intermediate_size: int = -1
    heads: int = -1
    attn_dropout_ratio: float = 0.1
    hidden_dropout_ratio: float = 0.1
    num_hidden_layers: int = -1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-5
    seed: int = -1
    fp16: bool = False
    bf16: bool = True
    pre_layer_norm: bool = True
    layer_id: int = 0
    # TPU additions
    causal: bool = False
    # v5e-tuned flash blocks (ops/flash_attention.DEFAULT_BLOCK_*)
    block_q: int = 512
    block_k: int = 1024
    # "auto" = Pallas flash when usable, XLA reference otherwise
    attn_impl: str = "auto"
    # "bhsd" (default): classic [B,H,S,D] kernel layout.  "bshd": API
    # convenience for [B,S,H,D] callers — NOT transpose-free: a native
    # bshd BlockSpec is Mosaic-illegal (measured round 3, v5e), so the
    # layout converts at the Pallas boundary.  Neither layout's
    # transposes are cheap: under "bhsd" they are eight stand-alone
    # copies a layer (q, k, v in, the context out, and their transposes
    # in the backward pass; three more in the recomputation), read by
    # the part metric attn_layout_ms on the v5e (PERF.md section 5,
    # PR 38): 12.5 ms of gpt2-large.s1024's 246.5 ms step (5.1%), 12.2
    # of gpt2-large.s512's 237.6, 50.1 of gpt2-large.gas4's 913.3.
    attn_layout: str = "bhsd"
    # "kernel" = in-kernel attention-probability dropout (reference
    # semantics, ~10% step cost at S=1024); "ctx" = cheap dropout on the
    # attention output (different regularizer) — see __call__
    attn_dropout_impl: str = "kernel"
    # "gelu_new"/"gelu_pytorch_tanh" = tanh approx (the reference kernel's
    # flavor, gelu_kernels.cu:10); "gelu" = exact erf (HF BERT default)
    activation: str = "gelu_new"
    # block-sparse attention: a SparsityConfig routes the layer's attention
    # through SparseSelfAttention (the reference wires this via
    # bert_sparse_self_attention.py:78; here it's one config field)
    sparsity_config: Optional[object] = None
    # "dense" (default) = the fused inter/output FFN; "none" = attention
    # sublayer only (no FFN params) — the GShard/Megatron-MoE pattern
    # replaces the FFN of alternating layers with an expert layer
    # (reference: moe/layer.py MoE wraps the FFN position), so the MoE
    # model composes [attention-only layer] + [gated expert FFN block]
    ffn: str = "dense"

    @property
    def gelu_approximate(self) -> bool:
        if self.activation in ("gelu_new", "gelu_pytorch_tanh",
                               "gelu_python", "gelu_fast"):
            return True
        if self.activation == "gelu":
            return False
        raise ValueError(f"unsupported activation {self.activation!r} — "
                         f"gelu variants only (reference kernel parity)")

    def __post_init__(self):
        if self.intermediate_size == -1 and self.hidden_size != -1:
            self.intermediate_size = 4 * self.hidden_size
        if self.ffn not in ("dense", "none"):
            raise ValueError(
                f"ffn={self.ffn!r}: must be 'dense' or 'none' "
                "(init/forward/specs all key on it)")
        if self.attn_dropout_impl not in ("kernel", "ctx"):
            raise ValueError(
                f"attn_dropout_impl={self.attn_dropout_impl!r}: must be "
                "'kernel' (in-kernel probability dropout, reference "
                "semantics) or 'ctx' (output dropout)")

    @property
    def dtype(self):
        if self.bf16:
            return jnp.bfloat16
        if self.fp16:
            return jnp.float16
        return jnp.float32


class DeepSpeedTransformerLayer:
    """Fused transformer layer (reference: transformer.py:462).

    Weight names follow the reference exactly:
      attn_qkvw [H, 3H], attn_qkvb [3H], attn_ow [H, H], attn_ob [H],
      attn_nw/attn_nb [H] (post-attention LN), inter_w [H, I], inter_b [I],
      output_w [I, H], output_b [H], norm_w/norm_b [H].
    """

    def __init__(self, config: DeepSpeedTransformerConfig):
        self.config = config
        self._sparse_attn = None
        if config.sparsity_config is not None:
            from .sparse_attention import SparseSelfAttention
            self._sparse_attn = SparseSelfAttention(config.sparsity_config)

    # -- parameters ---------------------------------------------------- #
    def init_params(self, rng):
        cfg = self.config
        h, inter = cfg.hidden_size, cfg.intermediate_size
        std = cfg.initializer_range
        keys = jax.random.split(rng, 4)
        init = jax.nn.initializers.normal(std)
        params = {
            "attn_qkvw": init(keys[0], (h, 3 * h), jnp.float32),
            "attn_qkvb": jnp.zeros((3 * h,), jnp.float32),
            "attn_ow": init(keys[1], (h, h), jnp.float32),
            "attn_ob": jnp.zeros((h,), jnp.float32),
            "norm_w": jnp.ones((h,), jnp.float32),
            "norm_b": jnp.zeros((h,), jnp.float32),
        }
        if cfg.ffn == "dense":
            params.update({
                "attn_nw": jnp.ones((h,), jnp.float32),
                "attn_nb": jnp.zeros((h,), jnp.float32),
                "inter_w": init(keys[2], (h, inter), jnp.float32),
                "inter_b": jnp.zeros((inter,), jnp.float32),
                "output_w": init(keys[3], (inter, h), jnp.float32),
                "output_b": jnp.zeros((h,), jnp.float32),
            })
        return params

    @staticmethod
    def param_partition_specs(ffn: str = "dense"):
        """Megatron-style TP: qkv/inter column-split, out/output row-split
        over the "model" axis (the role the external Megatron mpu plays in
        the reference — engine.py:739-770)."""
        specs = {
            "attn_qkvw": P(None, MODEL_AXIS),
            "attn_qkvb": P(MODEL_AXIS),
            "attn_ow": P(MODEL_AXIS, None),
            "attn_ob": P(),
            "norm_w": P(), "norm_b": P(),
        }
        if ffn == "dense":
            specs.update({
                "attn_nw": P(), "attn_nb": P(),
                "inter_w": P(None, MODEL_AXIS),
                "inter_b": P(MODEL_AXIS),
                "output_w": P(MODEL_AXIS, None),
                "output_b": P(),
            })
        return specs

    @staticmethod
    def tp_manual_views(params, heads: int):
        """Rearrange the fused qkv leaves head-major for MANUAL TP.

        Storage keeps the reference's blocked [q|k|v] layout (attn_qkvw
        [..., H, 3H], attn_qkvb [..., 3H]) — HF policy imports, the MP
        resize merge/split (state_dict_factory) and inference all assume
        it.  A contiguous model-axis shard of that layout holds
        MISmatched q/k/v pieces, so the gated executor views them as
        [..., H, heads, 3, d] / [..., heads, 3, d] (a free in-graph
        reshape+swap applied OUTSIDE the shard_map; AD transposes it) —
        any contiguous shard of the heads dim then carries matched head
        groups.  Returns the viewed tree; `tp_manual_unview` restores
        storage layout (for the grads)."""
        p = dict(params)
        w = p["attn_qkvw"]
        d = w.shape[-2] // heads
        p["attn_qkvw"] = w.reshape(
            w.shape[:-1] + (3, heads, d)).swapaxes(-3, -2)
        bias = p["attn_qkvb"]
        p["attn_qkvb"] = bias.reshape(
            bias.shape[:-1] + (3, heads, d)).swapaxes(-3, -2)
        return p

    @staticmethod
    def tp_manual_unview(params):
        """Inverse of tp_manual_views (applied to the grads)."""
        p = dict(params)
        w = p["attn_qkvw"]  # [..., H, heads, 3, d]
        heads, _, d = w.shape[-3:]
        p["attn_qkvw"] = w.swapaxes(-3, -2).reshape(
            w.shape[:-3] + (3 * heads * d,))
        bias = p["attn_qkvb"]
        p["attn_qkvb"] = bias.swapaxes(-3, -2).reshape(
            bias.shape[:-3] + (3 * heads * d,))
        return p

    @staticmethod
    def tp_manual_view_specs(ffn: str = "dense"):
        """param_partition_specs in the tp_manual_views layout: the qkv
        leaves shard on their heads dim; everything else is unchanged
        (attn_ow's row shard is already head-contiguous)."""
        specs = DeepSpeedTransformerLayer.param_partition_specs(ffn)
        specs["attn_qkvw"] = P(None, MODEL_AXIS, None, None)
        specs["attn_qkvb"] = P(MODEL_AXIS, None, None)
        return specs

    def num_params(self):
        h, i = self.config.hidden_size, self.config.intermediate_size
        if self.config.ffn != "dense":
            # qkvw+ow (4h^2) + qkvb+ob (4h) + pre-attn LN (2h)
            return 4 * h * h + 6 * h
        return 4 * h * h + 2 * h * i + 9 * h + i

    # -- forward ------------------------------------------------------- #
    def __call__(self, params, x, attn_mask=None, rng=None,
                 deterministic: bool = False, tp_axis: Optional[str] = None,
                 seq_axis: Optional[str] = None, sp_mode: str = "auto"):
        """x: [B, S, H] -> [B, S, H].  attn_mask: additive [B, 1, 1, S] or
        [B, 1, S, S] bias, like the reference's input_mask.

        tp_axis: MANUAL tensor parallelism — params are LOCAL Megatron
        shards (param_partition_specs layout over that mesh axis) and the
        row-parallel matmul outputs are psum'd explicitly here, instead of
        GSPMD inserting the collectives from sharding annotations.  Used
        inside shard_map-manual regions where GSPMD-placed collectives
        would land in divergent control flow (the gated 1F1B executor's
        per-stage lax.cond branches — one_f_one_b.py).  x and the returned
        activation are replicated over tp_axis.

        seq_axis: MANUAL sequence parallelism — x is the LOCAL sequence
        chunk [B, S_local, H] (global order follows the axis index) and
        attention runs ring or Ulysses over that axis
        (parallel/sequence.py *_inner), with explicit collectives for the
        same divergent-control-flow reason as tp_axis.  Composes with
        tp_axis: local heads × local sequence, ring/all-to-all over seq,
        psums over model.  Restrictions: no sparse attention, no additive
        attn_mask, and the attention-probability ('kernel') dropout falls
        back to output ('ctx') dropout — the ring accumulator has no PRNG
        path.  sp_mode: 'ring' | 'ulysses' | 'allgather' | 'auto'
        (Ulysses when the seq degree divides the local head count —
        heads redistribute across seq peers)."""
        cfg = self.config
        eps = cfg.layer_norm_eps
        heads = cfg.heads
        b, s, h = x.shape
        d = h // heads
        if tp_axis is not None:
            # local heads from the head-major qkv view [H, hl, 3, d]
            # (tp_manual_views — a contiguous model-axis shard of the
            # blocked [q|k|v] layout would hold MISmatched q/k/v pieces)
            heads = params["attn_qkvw"].shape[-3]
        hw = heads * d  # local attention width (== h without tp_axis)
        if seq_axis is not None:
            if self._sparse_attn is not None:
                raise ValueError(
                    "manual sequence parallelism does not support sparse "
                    "attention (layouts are built for the full sequence)")
            if attn_mask is not None:
                raise NotImplementedError(
                    "manual sequence parallelism supports causal masking "
                    "only (additive attn_mask has no ring form here)")
        has_dropout = (cfg.attn_dropout_ratio > 0.0 or
                       cfg.hidden_dropout_ratio > 0.0)
        if rng is None:
            if not deterministic and has_dropout:
                raise ValueError(
                    "transformer layer called in training mode with dropout "
                    "configured but no rng — pass rng= or deterministic=True")
            rng = jax.random.PRNGKey(0)
            deterministic = True
        r_attn, r_hid1, r_hid2 = jax.random.split(rng, 3)
        if tp_axis is not None:
            # decorrelate the attention-probability dropout across head
            # shards (each peer sees only its local heads); the hidden
            # dropouts run AFTER the psums on replicated values and must
            # keep the shared key
            r_attn = jax.random.fold_in(r_attn, lax.axis_index(tp_axis))
        if seq_axis is not None:
            # every dropout acts on chunk-LOCAL values: decorrelate all
            # three keys across sequence peers (a shared key would repeat
            # one mask pattern every S_local positions)
            sidx = lax.axis_index(seq_axis)
            r_attn = jax.random.fold_in(r_attn, sidx)
            r_hid1 = jax.random.fold_in(r_hid1, sidx)
            r_hid2 = jax.random.fold_in(r_hid2, sidx)

        x = x.astype(cfg.dtype)
        residual = x
        if cfg.pre_layer_norm:
            attn_in = fused_layer_norm(x, params["norm_w"], params["norm_b"],
                                       eps)
        else:
            attn_in = x
        if tp_axis is not None:
            attn_in = tp_fcast(attn_in, tp_axis)

        # Inside "attn" the work is named once more, by part
        # (profiling/scope_map.py PARTS): attn_qkv, attn_layout, attn_core,
        # attn_out here.  Names only: the compiled programs are the same.
        with jax.named_scope("attn"), jax.named_scope("attn_qkv"):
            if tp_axis is None:
                qkv = matmul_maybe_int8(attn_in, params["attn_qkvw"]) + \
                    params["attn_qkvb"].astype(attn_in.dtype)
                q, k, v = jnp.split(qkv, 3, axis=-1)
            else:
                # head-major local view: w [H, hl, 3, d], b [hl, 3, d]
                qkv = jnp.einsum(
                    "bsh,hjcd->bsjcd", attn_in,
                    params["attn_qkvw"].astype(attn_in.dtype)) + \
                    params["attn_qkvb"].astype(attn_in.dtype)
                q, k, v = (qkv[..., 0, :].reshape(b, s, hw),
                           qkv[..., 1, :].reshape(b, s, hw),
                           qkv[..., 2, :].reshape(b, s, hw))

        # attention dropout placement (attn_dropout_impl):
        #   "kernel" (default) — probability dropout INSIDE the flash
        #     kernel, the reference's semantics (dropout_kernels.cu
        #     attn-dropout on the softmax output).  Costs O(S^2) PRNG
        #     bits regenerated in both kernels (three then): measured ~10% of
        #     the flagship step on v5e (94.3 nodrop vs 84.7 TFLOPS).
        #   "ctx" — cheap dropout on the attention OUTPUT (O(S*d) bits,
        #     one pass).  Different regularizer than the reference's;
        #     choose it when dropout semantics need not match.
        # Sparse attention always uses ctx dropout (its kernel has no
        # PRNG path yet); r_attn is consumed exactly once on every path.
        kernel_drop = (cfg.attn_dropout_impl == "kernel"
                       and seq_axis is None)
        attn_rate = (0.0 if deterministic or not kernel_drop
                     else cfg.attn_dropout_ratio)

        def attn_seed():
            if attn_rate == 0.0:
                return None
            return jax.random.randint(r_attn, (), 0, 2 ** 31 - 1, jnp.int32)

        def to_heads(t):
            with jax.named_scope("attn_layout"):
                return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

        def from_heads(t):
            # the context's way back, [B,H,S,D] -> [B,S,H*D]: written
            # between two "attn" blocks, so its scope is "layer"; the
            # part counts in either (scope_map.PARTS)
            with jax.named_scope("attn_layout"):
                return t.transpose(0, 2, 1, 3).reshape(b, s, hw)

        if seq_axis is not None:
            # ring / Ulysses attention over the manual seq axis on the
            # local chunk (lazy import: parallel.sequence pulls in
            # flash_attention at module load)
            from ..parallel.sequence import sp_attention_inner

            sp = lax.psum(1, seq_axis)  # static under shard_map
            mode = sp_mode
            if mode == "auto":
                mode = "ulysses" if heads % sp == 0 else "ring"

            with jax.named_scope("attn"):
                qkv_heads = to_heads(q), to_heads(k), to_heads(v)
                with jax.named_scope("attn_core"):
                    ctx = sp_attention_inner(*qkv_heads, mode=mode,
                                             axis_name=seq_axis,
                                             causal=cfg.causal)
            ctx = from_heads(ctx)
            # kernel-dropout fallback: output ('ctx') dropout on the chunk
            ctx = dropout(ctx, cfg.attn_dropout_ratio, r_attn, deterministic)
        elif self._sparse_attn is not None:
            # route the layer's additive mask into SparseSelfAttention's
            # mask features (added round 4): [B,1,1,S] (key padding) ->
            # key_padding_mask 'add'; [1,1,S,S] / [S,S] -> attn_mask
            # 'add'.  A per-batch full [B,1,S,S] mask has no sparse
            # analog (the reference softmax supports 2D attn masks only).
            sparse_kp = sparse_am = None
            if attn_mask is not None:
                if attn_mask.ndim == 4 and attn_mask.shape[1:3] == (1, 1):
                    sparse_kp = attn_mask.reshape(attn_mask.shape[0], s)
                elif (attn_mask.ndim == 4 and attn_mask.shape[0] == 1
                      and attn_mask.shape[1] == 1):
                    sparse_am = attn_mask.reshape(s, s)
                elif attn_mask.ndim == 2:
                    sparse_am = attn_mask
                else:
                    raise NotImplementedError(
                        "sparse attention supports [B,1,1,S] key-padding "
                        "or 2D [S,S] additive masks (reference "
                        "softmax.py:attn_mask is 2D-only); got shape "
                        f"{attn_mask.shape}")

            with jax.named_scope("attn"):
                qkv_heads = to_heads(q), to_heads(k), to_heads(v)
                with jax.named_scope("attn_core"):
                    ctx = self._sparse_attn(*qkv_heads, causal=cfg.causal,
                                            key_padding_mask=sparse_kp,
                                            attn_mask=sparse_am)
            ctx = from_heads(ctx)
            ctx = dropout(ctx, cfg.attn_dropout_ratio, r_attn, deterministic)
        elif cfg.attn_layout == "bshd":
            # [B,S,H] -> [B,S,heads,d] is a free view; the layout
            # conversion to the kernel's [B,H,S,D] happens at the Pallas
            # boundary (a native bshd BlockSpec is Mosaic-illegal —
            # measured round 3; see flash_attention.py::_tile_spec)
            def split_heads(t):
                with jax.named_scope("attn_layout"):
                    return t.reshape(b, s, heads, d)

            with jax.named_scope("attn"):
                qkv_heads = split_heads(q), split_heads(k), split_heads(v)
                with jax.named_scope("attn_core"):
                    ctx = flash_attention_bsh(
                        *qkv_heads, causal=cfg.causal, bias=attn_mask,
                        block_q=cfg.block_q, block_k=cfg.block_k,
                        impl=cfg.attn_impl, dropout_rate=attn_rate,
                        dropout_seed=attn_seed())
            with jax.named_scope("attn_layout"):
                ctx = ctx.reshape(b, s, hw)
            if not kernel_drop:
                ctx = dropout(ctx, cfg.attn_dropout_ratio, r_attn,
                              deterministic)
        else:
            with jax.named_scope("attn"):
                qkv_heads = to_heads(q), to_heads(k), to_heads(v)
                with jax.named_scope("attn_core"):
                    ctx = flash_attention(
                        *qkv_heads, causal=cfg.causal, bias=attn_mask,
                        block_q=cfg.block_q, block_k=cfg.block_k,
                        impl=cfg.attn_impl, dropout_rate=attn_rate,
                        dropout_seed=attn_seed())
            ctx = from_heads(ctx)
            if not kernel_drop:
                ctx = dropout(ctx, cfg.attn_dropout_ratio, r_attn,
                              deterministic)

        # NOTE: "attn" opens as several blocks (the dispatch branches
        # prevent one contiguous region); the scope KEY is identical so
        # module_tree merges them — only the context's way back
        # (from_heads) falls between blocks, to the parent "layer" scope.
        # It is not free: a copy a layer and pass, 7.5 ms an optimizer
        # step of attn_layout_ms's 50.1 in gpt2-large.gas4 and 1.9 of
        # 12.2 in gpt2-large.s512 (PERF.md section 5, PR 38).  It stays
        # where it is so that attn_ms reads what it always read.
        with jax.named_scope("attn"), jax.named_scope("attn_out"):
            attn_out = matmul_maybe_int8(ctx, params["attn_ow"])
            if tp_axis is not None:
                # row-parallel output projection: merge the per-peer
                # partials BEFORE bias/dropout/residual (replicated on)
                attn_out = tp_psum(attn_out, tp_axis)
            attn_out = bias_dropout_residual(
                attn_out, params["attn_ob"].astype(attn_out.dtype),
                residual, cfg.hidden_dropout_ratio, r_hid1, deterministic)

        if cfg.ffn == "none":
            # attention sublayer only — the caller owns the FFN position
            # (MoE expert block); pre-LN residual form required
            if not cfg.pre_layer_norm:
                raise ValueError("ffn='none' requires pre_layer_norm")
            return attn_out

        if cfg.pre_layer_norm:
            mlp_in = fused_layer_norm(attn_out, params["attn_nw"],
                                      params["attn_nb"], eps)
            mlp_residual = attn_out
        else:
            attn_out = fused_layer_norm(attn_out, params["attn_nw"],
                                        params["attn_nb"], eps)
            mlp_in = attn_out
            mlp_residual = attn_out
        if tp_axis is not None:
            mlp_in = tp_fcast(mlp_in, tp_axis)

        with jax.named_scope("mlp"):
            inter = bias_gelu(matmul_maybe_int8(mlp_in, params["inter_w"]),
                              params["inter_b"].astype(mlp_in.dtype),
                              approximate=cfg.gelu_approximate)
            out = matmul_maybe_int8(inter, params["output_w"])
            if tp_axis is not None:
                out = tp_psum(out, tp_axis)
            out = bias_dropout_residual(
                out, params["output_b"].astype(out.dtype), mlp_residual,
                cfg.hidden_dropout_ratio, r_hid2, deterministic)

        if not cfg.pre_layer_norm:
            out = fused_layer_norm(out, params["norm_w"], params["norm_b"],
                                   eps)
        return out
