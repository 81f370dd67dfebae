"""Xing4.0 (``model_type: xing4_0``, XingChen-AGI Xing4.0-29B-A4B's
``config.json``): GLM-4 MoE Lite's decoder (models/glm4_moe_lite.py:
latent attention, sparse experts chosen by a biased sigmoid beside a
shared one, a multi-token-prediction module on the shared embedding and
head) on a residual path of ``hc_mult`` streams mixed by
manifold-constrained hyper-connections (ops/hyper_connection.py).

  streams     ``X^0`` is the embedding copied into the ``n`` streams.
              Each of a layer's two sublayers ``F`` (attention, then the
              dense FFN or the experts), with hyper-connection
              parameters of its own: ``u = H_pre X``, ``y = F(RMSNorm(
              u))``, ``X' = H_res X + H_post^T y``, the three mixes from
              the streams themselves (the norm of ``vec(X)``, a
              projection, sigmoids, ``hc_sinkhorn_iters`` Sinkhorn
              rounds from ``exp(clamp(.))``).  After the last layer the
              streams are summed; then the final norm and the head.
  attention   as GLM-4 MoE Lite's, with query/key heads of ``nope +
              rope`` (128 + 64 as published) on value heads of
              ``v_head_dim`` (128) through the flash kernels' two head
              sizes, YaRN frequencies on the rotated dimensions and
              YaRN's factor on the softmax scale (``rope_scaling``).
  FFN         the first ``first_k_dense_replace`` layers dense, the
              others ``moe.DroplessMoE`` with the selection bias and its
              update, all GLM-4 MoE Lite's.
  prediction  GLM-4 MoE Lite's module: its projection's output copied
              into ``n`` streams, its block with two hyper-connections
              of its own, the streams summed, its norm, the SHARED head.

The carry of the stack is ``[B, n, S, hidden]`` (streams before
positions: ops/hyper_connection.py says why); a checkpointed layer keeps
it whole, ``n`` widths a token, which the byte budget is told
(``carry_streams``).  A cut of the model is ``num_hidden_layers``,
``first_k_dense_replace``, ``experts_held``, ``vocab_size`` and
``num_nextn_predict_layers``.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..monitor import record as R
from ..ops import hyper_connection as hc_ops
from ..ops.normalize import rms_norm
from .glm4_moe_lite import Glm4MoeLiteConfig, Glm4MoeLiteModel
from .laguna import gated_ffn

# the two sublayers' hyper-connections in a layer's parameters
HC_ATTN, HC_FFN = "hc_attn", "hc_ffn"


@dataclass
class Xing4Config(Glm4MoeLiteConfig):
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # where the mixes start (ops/hyper_connection.py ``init_params``)
    hc_alpha_init: float = 0.01
    hc_res_off_diagonal: float = -8.0

    @property
    def hyper_connection(self):
        return hc_ops.HyperConnection(
            streams=self.hc_mult, sinkhorn_iters=self.hc_sinkhorn_iters,
            eps=self.hc_eps, norm_eps=self.rms_norm_eps,
            clamp=(float(self.mhc_h_res_clamp_min),
                   float(self.mhc_h_res_clamp_max)))


class Xing4Model(Glm4MoeLiteModel):
    """GLM-4 MoE Lite's decoder on hyper-connected streams; trained
    through ``deepspeed_tpu.initialize`` like it."""

    aux_counters = Glm4MoeLiteModel.aux_counters + (
        R.M_HC_ROW_ERR, R.M_HC_COL_ERR, R.M_HC_PRE_MEAN, R.M_HC_POST_MEAN)

    # Two even shares of the picks in the experts' row buffers, as
    # models/keye_vl2.py measured its way to.  At one share a layer's
    # routed rows (0.55 to 0.9 shares by the seed's router, scattered by
    # the step's tokens) overran the buffers in 16% of the layer-steps,
    # each a second trip, and a step's time was the seed's: 245.3 to
    # 255.9 ms over six seeds, quartiles 3.6% of the median apart (my
    # chip runs, PR 58; PERF.md section 6).
    dispatch_headroom = 2.0

    @property
    def carry_streams(self):
        return self.config.hc_mult

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng, sparse):
        cfg = self.config
        layer = super()._init_layer(rng, sparse)
        for i, name in enumerate((HC_ATTN, HC_FFN)):
            layer[name] = hc_ops.init_params(
                jax.random.fold_in(rng, 100 + i), cfg.hyper_connection,
                cfg.hidden_size, cfg.initializer_range, cfg.hc_alpha_init,
                cfg.hc_res_off_diagonal)
        return layer

    # -- the residual path ---------------------------------------------- #
    def _carry_in(self, h):
        """[B, S, hidden] copied into the streams, [B, n, S, hidden]."""
        return jnp.broadcast_to(h[:, None], (
            h.shape[0], self.config.hc_mult, *h.shape[1:]))

    def _carry_out(self, carry):
        with jax.named_scope(hc_ops.SCOPE):
            return jnp.sum(carry.astype(jnp.float32), axis=1).astype(
                carry.dtype)

    def _sublayer(self, p, x, norm, fn):
        """(the streams after sublayer ``fn``, what ``fn`` gave beside
        its output, the sublayer's Mixes)."""
        u, mixed = hc_ops.hc_pre(x, p, self.config.hyper_connection)
        y, beside = fn(rms_norm(u, norm, self.config.rms_norm_eps))
        return hc_ops.hc_post(x, y, mixed), beside, mixed

    def _layer(self, p, x, sparse, table, picks=None):
        """(the streams after the layer, (the sparse FFN's Routing or
        None, the two sublayers' Mixes stacked))."""
        with jax.named_scope("layer"):
            x, _, first = self._sublayer(
                p[HC_ATTN], x, p["ln1"],
                lambda u: (self._attention(p["attn"], u, table), None))
            if sparse:
                def ffn(u):
                    return self.moe.apply(p["moe"], u, picks=picks)
            else:
                def ffn(u):
                    return gated_ffn(p["ffn"], u), None
            x, routing, second = self._sublayer(p[HC_FFN], x, p["ln2"], ffn)
            return x, (routing, jax.tree.map(
                lambda a, b: jnp.stack([a, b]), first, second))

    def _kept(self, routing, keep):
        """``keep`` is a pair here: of the Routing, of the Mixes."""
        (routing, mixed), (keep_routing, keep_mixes) = routing, keep
        return (None if routing is None else keep_routing(routing),
                keep_mixes(mixed))

    def _gather(self, kept):
        """(the gates' keeps, every layer's keeps of its Mixes), each
        stacked in layer order."""
        return (super()._gather([r for r, _ in kept if r is not None]),
                super()._gather([m for _, m in kept]))

    # -- the stack ------------------------------------------------------ #
    def stack_plan(self):
        cfg = self.config
        return {**super().stack_plan(), R.M_STACK_STREAMS: (
            cfg.hc_mult, cfg.hc_sinkhorn_iters,
            float(cfg.mhc_h_res_clamp_min), float(cfg.mhc_h_res_clamp_max))}

    @staticmethod
    def _mix_counters(mixed):
        """[2, 4] of a layer's two sublayers (ops ``mix_counters``)."""
        return jnp.stack([hc_ops.mix_counters(jax.tree.map(
            lambda a, i=i: a[i], mixed)) for i in range(2)])

    def _objective(self, params, input_ids, labels=None, picks=None):
        """GLM-4 MoE Lite's pair, and the hyper-connections' counters
        beside its own: the worst row and column error of ``H_res`` over
        the step's tokens and sublayers, the means of ``H_pre`` and
        ``H_post``."""
        (main, mtp), (stats, mixed) = self._run(
            params, input_ids, picks, (self.moe.stats, self._mix_counters),
            labels)
        objective, counters = self._counted(main, mtp, stats)
        mixed = mixed.reshape(-1, 4)                 # [sublayers, 4]
        return objective, {
            **counters,
            R.M_HC_ROW_ERR: jnp.max(mixed[:, 0]),
            R.M_HC_COL_ERR: jnp.max(mixed[:, 1]),
            R.M_HC_PRE_MEAN: jnp.mean(mixed[:, 2]),
            R.M_HC_POST_MEAN: jnp.mean(mixed[:, 3])}

    def routing(self, params, input_ids, with_inputs=False):
        return self.routing_and_mixes(params, input_ids, with_inputs)[0]

    def routing_and_mixes(self, params, input_ids, with_inputs=False):
        """(``routing``'s result, the Mixes of every sublayer from the
        same forward pass: ``pre`` and ``post`` float32 [L, 2, n, B, S],
        ``res`` [L, 2, n, n, B, S], the L layers in order and then the
        module's block)."""
        _, kept = self._run(
            params, input_ids, None,
            (lambda r: (r.scores, r.picks) + ((r.inputs,) * with_inputs),
             lambda mixed: mixed))
        return kept
