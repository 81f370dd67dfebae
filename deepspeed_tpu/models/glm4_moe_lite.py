"""GLM-4 MoE Lite (``model_type: glm4_moe_lite``, zai-org GLM-4.7-Flash's
``config.json``): a decoder with latent attention, sparse experts chosen
by a biased score beside a shared one, and a multi-token-prediction
module on the shared embedding and head.

Every layer is ``h = x + Attn(RMSNorm(x)); out = h + FFN(RMSNorm(h))``
without bias.

  attention   latent: ``cq = RMSNorm(u Wqa)`` (``q_lora_rank`` wide),
              ``q = cq Wqb`` as heads of ``nope + rope`` dimensions;
              ``[ckv | k_rope] = u Wkva`` (``kv_lora_rank + rope``),
              ``ckv = RMSNorm(ckv)``, ``[k_nope | v] = ckv Wkvb`` a head.
              Rotary positions on the ``rope`` dimensions of every query
              head and on the ONE ``k_rope`` a position, which all heads
              share; ``k = [k_nope | k_rope]``, softmax(q k^T / sqrt(nope
              + rope)) v, causal, through the flash kernels at a
              query/key head of ``nope + rope`` and a value head of
              ``v_head_dim`` (256 and 256 as published here; 192 on 128
              in models/xing4.py: the kernels take the two sizes apart).
              ``rope_scaling`` of type ``yarn``: the rotated dimensions
              turn at YaRN's blended frequencies (models/laguna.py
              ``yarn_inv_freq``), cos and sin times ``mscale /
              mscale_all_dim`` of YaRN's attention factor ``0.1 m
              ln(factor) + 1``, and the softmax scale times the square
              of that factor at ``mscale_all_dim``.
              Between the flat products and the kernels' head-major
              operands, where the shape is one ``latent_block`` takes
              (ops/latent_layout.py: heads of whole lane tiles whose
              last 64 lanes turn, an even number of them, whole blocks
              of positions, a TPU or the interpreter): one pass that
              rotates, joins the one key to every head and transposes,
              and one pass back for the context; ``kv_b``'s product is
              then taken in three column sets so that every head starts
              on a tile.  Elsewhere (the CPU, the small shapes of the
              tests and of the parity's reference): ``apply_rotary`` on
              the ``rope``-wide slices, the one rotated key broadcast to
              the heads and joined to ``k_nope`` by XLA, under
              ``attn_layout``.  The shape alone decides; the stack's
              log line says which (``rotary: latent kernel (...)`` or
              ``latent xla``).
  FFN         the first ``first_k_dense_replace`` layers a dense gated
              FFN; the others ``moe.DroplessMoE`` with a selection bias:
              sigmoid scores over all E experts in float32, the k
              largest of ``score + bias`` picked, their scores
              renormalised and scaled, the held experts' part of the
              sum, a shared expert once.  The bias has no gradient; it
              is a leaf the optimizer does not own
              (``optimizer_exempt``): after each optimizer step
              ``b_e += gamma sign(mean(c) - c_e)`` from the step's picks
              an expert ``c``.
  prediction  ``num_nextn_predict_layers`` (one) module after the stack:
              ``z_i = [RMSNorm_e(E[t_{i+1}]) | RMSNorm_h(H_i)] Wp`` with
              ``H`` the stack's output before the final norm and ``E``
              the shared embedding, one sparse block of the model's own
              kind with its own weights, ``RMSNorm_s`` and the SHARED
              head, scored on ``t_{i+2}``.  The step trains ``L_main +
              lambda L_mtp``.  Every position runs (the shapes stay whole
              lanes): the positions with no target carry the ignore
              index, and position S-1 reads the row's first token where
              there is no next one; causal attention lets nothing of it
              reach a position that is scored.

TPU-native structure as models/laguna.py's, from which the shared parts
are imported: consecutive layers of one shape are one stacked group run
by one body (layer 0, the sparse layers, the module's block), a cut of
the model is ``num_hidden_layers``, ``experts_held`` and ``vocab_size``.
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
from ..ops.flash_attention import flash_attention
from ..ops.fused_cross_entropy import fused_linear_cross_entropy
from ..ops.latent_layout import (heads_to_flat, latent_block, latent_heads,
                                 latent_tables, split_kv_columns)
from ..ops.normalize import rms_norm
from .laguna import (EMBEDDING_STD, ExpertStack, apply_rotary, gated_ffn,
                     rotary_table, yarn_inv_freq)
from .layer_stack import run_layer_stack

IGNORE = -1          # the label of a position that has no target
MTP = "mtp"          # the prediction module's parameters and its scope


@dataclass
class Glm4MoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    num_hidden_layers: int = 47          # the first layers
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1000000.0
    # None, or the released ``rope_scaling`` of type "yarn": factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    first_k_dense_replace: int = 1
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    # (first, count) of the routed experts held here; None is all
    experts_held: Optional[Tuple[int, int]] = None
    bias_update_rate: float = 0.001      # gamma of the selection bias
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3         # lambda
    initializer_range: float = 0.02
    bf16: bool = True
    activation_checkpointing: bool = False

    def __post_init__(self):
        if self.rope_scaling is not None and self.rope_scaling.get(
                "type", self.rope_scaling.get("rope_type")) != "yarn":
            raise NotImplementedError(
                f"rope_scaling {self.rope_scaling}: none or yarn")
        if self.num_nextn_predict_layers not in (0, 1):
            raise NotImplementedError("one prediction module at most")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotated dimensions pair up")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(self.experts_held)

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32

    @property
    def head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def _yarn_factor(self, mscale):
        """YaRN's attention factor ``0.1 mscale ln(factor) + 1``."""
        factor = self.rope_scaling["factor"]
        return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0

    def rotary_frequencies(self):
        """(the ``rope / 2`` frequencies, what cos and sin are times)."""
        rope, scaling = self.qk_rope_head_dim, self.rope_scaling
        if scaling is None:
            return self.rope_theta ** (
                -2.0 * jnp.arange(rope // 2, dtype=jnp.float32) / rope), 1.0
        return (yarn_inv_freq(
            rope, self.rope_theta, scaling["factor"],
            scaling["original_max_position_embeddings"],
            scaling["beta_fast"], scaling["beta_slow"]),
            self._yarn_factor(scaling.get("mscale", 1))
            / self._yarn_factor(scaling.get("mscale_all_dim", 0)))

    @property
    def softmax_scale(self):
        """1 / sqrt(nope + rope), under YaRN times the square of its
        attention factor at ``mscale_all_dim``."""
        scale = 1.0 / math.sqrt(self.head_dim)
        if self.rope_scaling is not None:
            scale *= self._yarn_factor(
                self.rope_scaling.get("mscale_all_dim", 0)) ** 2
        return scale

    def groups(self):
        """Runs of like layers of the stack: [(name, sparse, first index,
        count)]."""
        dense = min(self.first_k_dense_replace, self.num_hidden_layers)
        runs = [("layers_00", False, 0, dense),
                (f"layers_{dense:02d}", True, dense,
                 self.num_hidden_layers - dense)]
        return [r for r in runs if r[3]]


class SelectionBiasUpdate:
    """The leaves the optimizer does not own, for a model of sparse FFNs
    built with ``selection_bias`` (this one, models/xing4.py,
    models/nemotron_h.py): it has ``gates()``, ``init_params`` and
    ``config.bias_update_rate``, and hands the engine one RoutingStats
    entry a step with each gate's picks an expert (``layer_counts``)."""

    def optimizer_exempt(self):
        """(mask, update) for the engine: the selection biases take no
        optimizer update; after each one ``b_e += gamma sign(mean(c) -
        c_e)`` with ``c`` the gate's picks an expert over the step
        (RoutingStats.layer_counts, summed over its micro-batches)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        mask = jax.tree_util.tree_map_with_path(
            lambda path, _: [getattr(k, "key", None) for k in path][-2:]
            == ["moe", "bias"], shapes)
        gamma = self.config.bias_update_rate

        def update(leaves, stats):
            counts = stats.layer_counts                       # [L, E]
            move = gamma * jnp.sign(
                jnp.mean(counts, axis=-1, keepdims=True) - counts)
            at = 0
            for keys, layers in self.gates():
                moe = leaves
                for key in keys:
                    moe = moe[key]
                moe["bias"] = moe["bias"] + move[at:at + layers]
                at += layers
            return leaves

        return mask, update


class Glm4MoeLiteModel(SelectionBiasUpdate, ExpertStack):
    """The decoder over stacked groups of like layers and its prediction
    module; trained through ``deepspeed_tpu.initialize`` like
    LagunaModel."""

    # the scalars of ``__call__``'s dict that the engine sums on the
    # device for whoever reads ``engine.model_counters()``
    aux_counters = (R.M_MAIN_LOSS, R.M_MTP_LOSS, R.M_LOAD_MAX_OVER_MEAN)
    # even shares of the picks the experts' row buffers hold
    # (``DroplessMoE.dispatch_headroom``; models/xing4.py takes two)
    dispatch_headroom = 1.0

    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__(config, DroplessMoE(
            config.hidden_size, config.n_routed_experts,
            config.num_experts_per_tok, config.moe_intermediate_size,
            config.moe_intermediate_size * config.n_shared_experts,
            score="sigmoid", renormalize=config.norm_topk_prob,
            scale=config.routed_scaling_factor,
            experts_held=config.experts_held,
            init_std=config.initializer_range, selection_bias=True,
            # on one rank of eight the held experts get no pick in some
            # steps and a stray one in others once the routers have
            # learnt the absent experts (PERF.md section 6, PR 42)
            first_chunk_always=True,
            dispatch_headroom=self.dispatch_headroom))

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng, sparse):
        cfg = self.config
        hid, heads = cfg.hidden_size, cfg.num_attention_heads
        keys = jax.random.split(rng, 7)
        std = cfg.initializer_range

        def normal(key, shape):
            return std * jax.random.normal(key, shape, jnp.float32)

        layer = {
            "ln1": jnp.ones((hid,), jnp.float32),
            "attn": {
                "q_a": normal(keys[0], (hid, cfg.q_lora_rank)),
                "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
                "q_b": normal(keys[1], (cfg.q_lora_rank,
                                        heads * cfg.head_dim)),
                "kv_a": normal(keys[2], (
                    hid, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
                "kv_b": normal(keys[3], (cfg.kv_lora_rank, heads * (
                    cfg.qk_nope_head_dim + cfg.v_head_dim))),
                "out_w": normal(keys[4], (heads * cfg.v_head_dim, hid))},
            "ln2": jnp.ones((hid,), jnp.float32)}
        if sparse:
            layer["moe"] = self.moe.init_params(keys[5])
        else:
            layer["ffn"] = {
                "w1": normal(keys[5], (hid, 2 * cfg.intermediate_size)),
                "w2": normal(keys[6], (cfg.intermediate_size, hid))}
        return layer

    def init_params(self, rng):
        cfg = self.config
        hid = cfg.hidden_size
        k_wte, k_head, k_layers, k_mtp = jax.random.split(rng, 4)
        params = {
            "wte": EMBEDDING_STD * jax.random.normal(
                k_wte, (cfg.vocab_size, hid), jnp.float32),
            "ln_f": jnp.ones((hid,), jnp.float32),
            "head": cfg.initializer_range * jax.random.normal(
                k_head, (hid, cfg.vocab_size), jnp.float32)}
        for name, sparse, first, count in cfg.groups():
            # a layer's weights depend on its published index alone
            keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
                first + jnp.arange(count))
            params[name] = jax.vmap(
                lambda k, s=sparse: self._init_layer(k, s))(keys)
        if cfg.num_nextn_predict_layers:
            k_proj, k_block = jax.random.split(k_mtp)
            params[MTP] = {
                "enorm": jnp.ones((hid,), jnp.float32),
                "hnorm": jnp.ones((hid,), jnp.float32),
                # the embedding's half first
                "proj": cfg.initializer_range * jax.random.normal(
                    k_proj, (2 * hid, hid), jnp.float32),
                "block": jax.vmap(lambda k: self._init_layer(k, True))(
                    k_block[None]),
                "norm": jnp.ones((hid,), jnp.float32)}
        return params

    def param_partition_specs(self):
        """No tensor- or expert-parallel split is written for this family
        yet: every leaf replicated over the model axis."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    def gates(self):
        """The paths of the sparse FFNs' parameters in gate order (the
        stack's sparse layers, then the prediction module's block):
        [(keys to the stacked ``moe`` dict, layers in it)]."""
        cfg = self.config
        paths = [((name, "moe"), count)
                 for name, sparse, _, count in cfg.groups() if sparse]
        if cfg.num_nextn_predict_layers:
            paths.append(((MTP, "block", "moe"), 1))
        return paths

    # -- the layer ------------------------------------------------------ #
    def latent_block(self, seq):
        """(positions, heads) of the block in which ops/latent_layout.py's
        kernels carry q, k and v between the flat products and the flash
        kernels at ``seq`` positions, or None where XLA does: the shape
        decides."""
        cfg = self.config
        return latent_block(seq, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.v_head_dim, cfg.num_attention_heads)

    def rotary_plan(self, seq):
        """The M_STACK_ROTARY field: which path the latent heads'
        rotation and layout take."""
        block = self.latent_block(seq)
        return ((("latent", "kernel", *block) if block
                 else ("latent", "xla")),)

    def _heads(self, p, cq, ckv, k_rope, table, kernels):
        """q, k ``[B, H, S, nope + rope]`` and v ``[B, H, S, v_head_dim]``
        from the two latents and the one key a position; ``table``
        (cos, sin) per lane of a head's last tile where the ``kernels``
        run, else ``rotary_table``'s."""
        cfg = self.config
        batch, seq, _ = cq.shape
        heads = cfg.num_attention_heads
        nope, rope, vdim = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.v_head_dim)
        if kernels:
            with jax.named_scope("attn_layout"):
                k_lo, k_hi, v_w = split_kv_columns(p["kv_b"], heads, nope,
                                                   vdim)
            with jax.named_scope("attn_qkv"):
                q = cq @ p["q_b"]
                k_lo, k_hi, v = ckv @ k_lo, ckv @ k_hi, ckv @ v_w
            with jax.named_scope("attn_rotary"):
                return latent_heads(q, k_lo, k_hi, v, k_rope, *table, heads)

        def by_head(t, dim):
            with jax.named_scope("attn_layout"):
                return t.reshape(batch, seq, heads, dim).transpose(0, 2, 1, 3)

        with jax.named_scope("attn_qkv"):
            q = cq @ p["q_b"]
            kv = ckv @ p["kv_b"]
        q = by_head(q, nope + rope)
        kv = by_head(kv, nope + vdim)
        with jax.named_scope("attn_rotary"):
            q_rope = apply_rotary(q[..., nope:], table)
            # one rotated key a position, for every head
            k_rope = apply_rotary(k_rope[:, None], table)
        with jax.named_scope("attn_layout"):
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate([
                kv[..., :nope], jnp.broadcast_to(
                    k_rope, (batch, heads, seq, rope))], axis=-1)
            return q, k, kv[..., nope:]

    def _attention(self, p, u, table):
        cfg = self.config
        batch, seq, _ = u.shape
        heads, eps = cfg.num_attention_heads, cfg.rms_norm_eps
        kernels = self.latent_block(seq) is not None
        with jax.named_scope("attn"):
            with jax.named_scope("attn_latent"):
                cq = rms_norm(u @ p["q_a"], p["q_norm"], eps)
                ckv, k_rope = jnp.split(u @ p["kv_a"], [cfg.kv_lora_rank],
                                        axis=-1)
                ckv = rms_norm(ckv, p["kv_norm"], eps)
            q, k, v = self._heads(p, cq, ckv, k_rope, table, kernels)
            with jax.named_scope("attn_core"):
                a = flash_attention(q, k, v, causal=True,
                                    sm_scale=cfg.softmax_scale)
            with jax.named_scope("attn_layout"):
                if kernels:
                    a = heads_to_flat(a)
                else:
                    a = a.transpose(0, 2, 1, 3).reshape(
                        batch, seq, heads * cfg.v_head_dim)
            with jax.named_scope("attn_out"):
                return a @ p["out_w"]

    def _layer(self, p, x, sparse, table, picks=None):
        """(layer output, the sparse FFN's Routing or None)."""
        eps = self.config.rms_norm_eps
        with jax.named_scope("layer"):
            h = x + self._attention(p["attn"], rms_norm(x, p["ln1"], eps),
                                    table)
            u = rms_norm(h, p["ln2"], eps)
            if not sparse:
                return h + gated_ffn(p["ffn"], u), None
            y, routing = self.moe.apply(p["moe"], u, picks=picks)
            return h + y, routing

    # -- the stack ------------------------------------------------------ #
    def stack_plan(self):
        """The M_STACK_* fields of this stack but the rotation's, which
        the sequence decides (``rotary_plan``)."""
        cfg = self.config
        layers = [(i, "latent+" + ("experts" if sparse else "dense"), 0)
                  for _, sparse, first, count in cfg.groups()
                  for i in range(first, first + count)]
        if cfg.num_nextn_predict_layers:
            layers.append((cfg.num_hidden_layers, "mtp:latent+experts", 0))
        return {
            R.M_STACK_LAYERS: tuple(layers),
            R.M_STACK_EXPERTS_HELD: (*cfg.experts_held,
                                     cfg.n_routed_experts),
            R.M_STACK_LATENT: (cfg.q_lora_rank, cfg.kv_lora_rank,
                               cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                               cfg.v_head_dim, cfg.num_attention_heads),
            R.M_STACK_MTP: (cfg.num_nextn_predict_layers,
                            cfg.mtp_loss_weight)}

    def _bodies(self, params, seq, picks, keep):
        """[(body, stacked xs, sparse, layers)] of the stack's groups and
        then of the prediction module's block, each body ``(carry, xs) ->
        (carry, keep(routing) or None)``."""
        cfg = self.config
        table = rotary_table(seq, *cfg.rotary_frequencies())
        if self.latent_block(seq) is not None:
            table = latent_tables(*table)
        stacks = [(params[name], sparse, count)
                  for name, sparse, _, count in cfg.groups()]
        if cfg.num_nextn_predict_layers:
            stacks.append((params[MTP]["block"], True, 1))
        bodies, sparse_before = [], 0
        for stacked, sparse, count in stacks:
            forced = None
            if sparse and picks is not None:
                forced = picks[sparse_before:sparse_before + count]
            sparse_before += count if sparse else 0

            def body(carry, xs, sparse=sparse):
                p, forced_picks = xs
                out, routing = self._layer(p, carry, sparse, table,
                                           forced_picks)
                return out, self._kept(routing, keep)

            bodies.append((body, (stacked, forced), sparse, count))
        return bodies

    # What a family with another residual path gives of its own
    # (models/xing4.py: several streams, and each sublayer's mixes beside
    # the routing): here one stream, and the routing alone.
    def _carry_in(self, h):
        """The stack's carry from the one stream ``h`` [B, S, hidden]."""
        return h

    def _carry_out(self, carry):
        """The one stream [B, S, hidden] a head reads, from the carry."""
        return carry

    def _kept(self, routing, keep):
        """What a body hands the scan of its layer's ``routing`` (None:
        a dense layer's)."""
        return None if routing is None else keep(routing)

    def _gather(self, kept):
        """The groups' stacked keeps as one, in layer order."""
        return (jax.tree.map(lambda *a: jnp.concatenate(a), *kept)
                if kept else None)

    def _head_loss(self, params, h, labels):
        cfg = self.config
        with jax.named_scope("head"):
            return fused_linear_cross_entropy(
                h.reshape(-1, cfg.hidden_size),
                params["head"].astype(h.dtype),
                labels.reshape(-1).astype(jnp.int32), ignore_index=IGNORE)

    def _run(self, params, input_ids, picks, keep, labels=None):
        """((L_main, L_mtp), ``keep(routing)`` of every gate stacked in
        gate order).  ``labels`` [B, S] are the main head's targets
        (None: the next token); the module's are always two ahead."""
        cfg = self.config
        eps = cfg.rms_norm_eps
        wte = params["wte"].astype(cfg.dtype)
        with jax.named_scope("embed"):
            h = self._carry_in(wte[input_ids])
        seq = input_ids.shape[1]
        bodies = self._bodies(params, seq, picks, keep)
        wrap = self._layer_wrapper(bodies, h, {
            **self.stack_plan(), R.M_STACK_ROTARY: self.rotary_plan(seq)})
        module = bodies.pop() if cfg.num_nextn_predict_layers else None
        kept = []
        for body, xs, sparse, count in bodies:
            # a group of several layers is scanned: one traced body
            h, ys = run_layer_stack(wrap(body), h, xs, count > 1,
                                    with_ys=True)
            if ys is not None:    # a dense layer of one stream keeps none
                kept.append(ys)
        h = self._carry_out(h)

        def shifted(by):
            ahead = jnp.roll(input_ids, -by, axis=1)
            return jnp.where(jnp.arange(input_ids.shape[1]) < (
                input_ids.shape[1] - by), ahead, IGNORE)

        if labels is None:
            labels = shifted(1)
        with jax.named_scope("head"):
            final = rms_norm(h, params["ln_f"], eps)
        main = self._head_loss(params, final, labels)
        mtp = jnp.float32(0.0)
        if module is not None:
            p = params[MTP]
            # every operation of the module lies under this scope, its
            # block's own scopes inside it (profiling/scope_map.py REGIONS)
            with jax.named_scope(MTP):
                with jax.named_scope("embed"):
                    ahead = wte[jnp.roll(input_ids, -1, axis=1)]
                hid = cfg.hidden_size
                proj = p["proj"]
                z = (rms_norm(ahead, p["enorm"], eps) @ proj[:hid]
                     + rms_norm(h, p["hnorm"], eps) @ proj[hid:])
                body, xs, _, _ = module
                z, ys = run_layer_stack(wrap(body), self._carry_in(z), xs,
                                        False, with_ys=True)
                z = self._carry_out(z)
                kept.append(ys)
                with jax.named_scope("head"):
                    z = rms_norm(z, p["norm"], eps)
                mtp = self._head_loss(params, z, shifted(2))
        return (main, mtp), self._gather(kept)

    def _objective(self, params, input_ids, labels=None, picks=None):
        """(L, the counters of ``aux_counters``: L_main, L_mtp and the
        picks of the busiest of ALL experts over the mean, averaged over
        the gates).  The RoutingStats of all gates go to the collecting
        tap as ONE entry, if the engine installed one
        (moe/sharded_moe.py): the sums over the gates, and each gate's
        picks an expert, which the selection biases are moved by."""
        (main, mtp), stats = self._run(params, input_ids, picks,
                                       self.moe.stats, labels)
        return self._counted(main, mtp, stats)

    def _counted(self, main, mtp, stats):
        """``_objective``'s pair from the two terms and the gates'
        stacked RoutingStats (None: no gate)."""
        counters = {R.M_MAIN_LOSS: main, R.M_MTP_LOSS: mtp,
                    R.M_LOAD_MAX_OVER_MEAN: jnp.float32(0.0)}
        if stats is not None:
            counts = stats.expert_counts                      # [L, E]
            counters[R.M_LOAD_MAX_OVER_MEAN] = jnp.mean(
                jnp.max(counts, axis=-1) / jnp.mean(counts, axis=-1))
            emit_routing_stats(jax.tree.map(
                lambda a: jnp.sum(a, axis=0), stats)._replace(
                layer_counts=counts))
        return main + self.config.mtp_loss_weight * mtp, counters

    def loss_terms(self, params, input_ids, labels=None, picks=None):
        """(L, L_main, L_mtp)."""
        objective, counters = self._objective(params, input_ids, labels,
                                              picks)
        return objective, counters[R.M_MAIN_LOSS], counters[R.M_MTP_LOSS]

    def routing(self, params, input_ids, with_inputs=False):
        """(scores f32 [L, T, E], picks int32 [L, T, k]) of the L gates
        (the stack's sparse layers, then the module's block) on
        ``input_ids``, from the same forward pass as the loss; with
        ``with_inputs`` also what each router read, [L, T, hidden]."""
        _, kept = self._run(
            params, input_ids, None,
            lambda r: (r.scores, r.picks) + ((r.inputs,) * with_inputs))
        return kept

    def loss(self, params, rng, input_ids, labels=None, picks=None):
        """The objective ``L_main + lambda L_mtp``, each a mean
        cross-entropy over the positions that have a target.  `rng` is
        unused (no dropout).  ``picks`` int32 [L, T, k] forces every
        gate's choice."""
        return self.loss_terms(params, input_ids, labels, picks)[0]

    def __call__(self, params, rng, input_ids, labels=None, picks=None):
        """(L, {"main_loss", "mtp_loss", "load_max_over_mean"}): the
        engine differentiates and reports the first and sums the scalars
        of the second (``aux_counters``)."""
        return self._objective(params, input_ids, labels, picks)
