"""Phi-4-mini-flash-reasoning: the SambaY decoder-hybrid-decoder (Ren et
al. 2025, arXiv:2507.06607; the released modeling_phi4flash.py).

A stack of four kinds of layer around one residual stream, every layer
``h = x + Mixer(LN1(x)); out = h + FFN(LN2(h))`` with a gated FFN
(``(up * silu(gate)) W2``), LayerNorm with bias, no positional encoding
at all, a tied head.  By published index i of n = 32 layers:

  self-decoder   i < n/2: even i a Mamba-1 mixer (ops/selective_scan.py),
                 odd i differential attention over the last 512 keys;
  the middle     i = n/2 a Mamba mixer whose scan output ``m`` (before
                 its gate) is kept; i = n/2 + 1 full differential
                 attention whose keys and values are kept;
  cross-decoder  i >= n/2 + 2: even i a gated-memory unit
                 (``(m * silu(x Win)) Wout``), odd i differential
                 cross-attention of its own queries on the kept keys and
                 values.

Differential attention (40 query heads, 20 key/value heads of 64, taken
in adjacent pairs) is four calls of the flash kernels a layer:
``[Att(q1,k1,v1), Att(q1,k1,v2)] - lam [Att(q2,k2,v1), Att(q2,k2,v2)]``,
an RMSNorm over each pair's 128 values, ``(1 - lam0)``; the grouped
key/value heads and the window are the kernels' own (index maps, banded
grid).

TPU-native structure: the (Mamba, window) pairs and the (gated-memory,
cross) pairs are each ONE scanned body over stacked parameters, the two
middle layers stand between them; a cut of the model is two counts
(``self_pairs``, ``cross_pairs``: the first pairs of each decoder) and
a row count, the kept layers keeping their published indices (which set
``lam0``).  ``m`` and the kept keys and values are closed over by the
cross-decoder's body, so their cotangents are the sums over its layers
and the recomputation plan keeps them across the scanned group's
boundary (checkpointing.checkpoint_layers).
"""

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..monitor import record as R
from ..ops.causal_conv import causal_conv
from ..ops.flash_attention import flash_attention
from ..ops.fused_cross_entropy import fused_linear_cross_entropy
from ..ops.normalize import fused_layer_norm
from ..ops.selective_scan import CHUNK, entry_state_bytes, selective_scan
from ..runtime.activation_checkpointing.checkpointing import (
    checkpoint_layers, stack_plan_line)
from ..utils.logging import log_dist
from .laguna import gated_ffn
from .layer_stack import resolve_use_scan, run_layer_stack

@dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32          # the PUBLISHED depth: fixes indices
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: Optional[int] = None        # hidden_size / 16
    # layers kept of the published stack: the first self_pairs (Mamba,
    # window) pairs and the first cross_pairs (gated-memory, cross) pairs;
    # None is all of them (n/4 and n/4 - 1)
    self_pairs: Optional[int] = None
    cross_pairs: Optional[int] = None
    initializer_range: float = 0.02
    bf16: bool = True
    activation_checkpointing: bool = False
    # None: unrolled up to layer_stack's threshold, scanned beyond it
    scan_layers: Optional[bool] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.mb_per_layer != 2 or n % 4:
            raise ValueError("the layer pattern is written for mb_per_layer "
                             "2 and a depth that is a multiple of 4")
        if self.dt_rank is None:
            self.dt_rank = self.hidden_size // 16
        if self.self_pairs is None:
            self.self_pairs = n // 4
        if self.cross_pairs is None:
            self.cross_pairs = n // 4 - 1
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs adjacent heads")

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.hidden_size

    @property
    def middle(self) -> int:
        return self.num_hidden_layers // 2

    def layer_plan(self):
        """[(published index, kind, window or 0)] of the layers kept."""
        mid = self.middle
        plan = []
        for p in range(self.self_pairs):
            plan += [(2 * p, "mamba", 0),
                     (2 * p + 1, "attn", self.sliding_window)]
        plan += [(mid, "mamba+memory", 0), (mid + 1, "attn+kv", 0)]
        for p in range(self.cross_pairs):
            plan += [(mid + 2 + 2 * p, "gmu", 0),
                     (mid + 3 + 2 * p, "cross", 0)]
        return plan

    def lambda_init(self, index):
        """lam0 of the attention layer at PUBLISHED index `index`."""
        return 0.8 - 0.6 * math.exp(-0.3 * index)


class Phi4FlashModel:
    """The decoder over stacked layer pairs; trained through
    ``deepspeed_tpu.initialize`` like GPT2Model."""

    def __init__(self, config: Phi4FlashConfig):
        self.config = config
        self._remat_budget = None
        self._stack_plan_logged = None

    def install_remat_budget(self, budget) -> None:
        """Engine hook: the bytes the layer scans' checkpointing may spend
        on saved residuals (checkpointing.RematBudget)."""
        self._remat_budget = budget

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng, kind):
        cfg = self.config
        hid, inter, di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
        n, hd = cfg.ssm_state, cfg.head_dim
        kv = cfg.num_key_value_heads * hd
        keys = iter(jax.random.split(rng, 16))
        std = cfg.initializer_range

        def normal(shape, scale=std):
            return scale * jax.random.normal(next(keys), shape, jnp.float32)

        def lam_and_norm():
            return {**{name: normal((hd,), 0.1)
                       for name in ("lq1", "lk1", "lq2", "lk2")},
                    "subln_w": jnp.ones((2 * hd,), jnp.float32)}

        if kind == "mamba":
            # dt bias: inverse softplus of steps log-uniform in [1e-3, 0.1]
            dt = jnp.exp(jax.random.uniform(next(keys), (di,), jnp.float32)
                         * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            mixer = {
                "in_w": normal((hid, 2 * di)),
                # torch's Conv1d default: uniform in +- 1/sqrt(taps)
                "conv_w": jax.random.uniform(
                    next(keys), (di, cfg.ssm_conv), jnp.float32, -1.0, 1.0)
                / math.sqrt(cfg.ssm_conv),
                "conv_b": jnp.zeros((di,), jnp.float32),
                "x_w": normal((di, cfg.dt_rank + 2 * n)),
                "dt_w": normal((cfg.dt_rank, di)),
                "dt_b": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                    (di, n)),
                "D": jnp.ones((di,), jnp.float32),
                "out_w": normal((di, hid))}
        elif kind == "gmu":
            mixer = {"in_w": normal((hid, di)), "out_w": normal((di, hid))}
        elif kind == "attn":
            mixer = {"qkv_w": normal((hid, hid + 2 * kv)),
                     "qkv_b": jnp.zeros((hid + 2 * kv,), jnp.float32),
                     "out_w": normal((hid, hid)),
                     "out_b": jnp.zeros((hid,), jnp.float32),
                     **lam_and_norm()}
        else:  # cross
            mixer = {"q_w": normal((hid, hid)),
                     "q_b": jnp.zeros((hid,), jnp.float32),
                     "out_w": normal((hid, hid)),
                     "out_b": jnp.zeros((hid,), jnp.float32),
                     **lam_and_norm()}

        def ln():
            return {"w": jnp.ones((hid,), jnp.float32),
                    "b": jnp.zeros((hid,), jnp.float32)}

        return {"ln1": ln(), "mixer": mixer, "ln2": ln(),
                "ffn": {"w1": normal((hid, 2 * inter)),
                        "w2": normal((inter, hid))}}

    def init_params(self, rng):
        cfg = self.config
        k_wte, k_self, k_mid, k_cross = jax.random.split(rng, 4)

        def pairs(key, count, kinds):
            def one(k):
                k0, k1 = jax.random.split(k)
                return {kinds[0]: self._init_layer(k0, kinds[0]),
                        kinds[1]: self._init_layer(k1, kinds[1])}
            return jax.vmap(one)(jax.random.split(key, count))

        mid = jax.random.split(k_mid)
        return {
            "wte": cfg.initializer_range * jax.random.normal(
                k_wte, (cfg.vocab_size, cfg.hidden_size), jnp.float32),
            "self": pairs(k_self, cfg.self_pairs, ("mamba", "attn")),
            "mid_mamba": self._init_layer(mid[0], "mamba"),
            "mid_attn": self._init_layer(mid[1], "attn"),
            "cross": pairs(k_cross, cfg.cross_pairs, ("gmu", "cross")),
            "ln_f": {"w": jnp.ones((cfg.hidden_size,), jnp.float32),
                     "b": jnp.zeros((cfg.hidden_size,), jnp.float32)},
        }

    def param_partition_specs(self):
        """No tensor-parallel split is written for this family yet: every
        leaf replicated over the model axis (ZeRO shards over the data
        axes as it does for any tree)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    # -- the four mixers ------------------------------------------------ #
    def _mamba(self, p, x):
        """(mixer output, the scan's output y before its gate)."""
        cfg = self.config
        n, rank = cfg.ssm_state, cfg.dt_rank
        with jax.named_scope("ssm"):
            xz = x @ p["in_w"]
            z = xz[..., xz.shape[-1] // 2:]
            # causal depthwise conv, bias and silu over the first half,
            # read where the projection wrote it (ops/causal_conv.py)
            xs = causal_conv(xz, p["conv_w"], p["conv_b"])
            dbc = jnp.dot(xs, p["x_w"], preferred_element_type=jnp.float32)
            dt_low, b_mat, c_mat = jnp.split(dbc, [rank, rank + n], axis=-1)
            dt = jax.nn.softplus(
                jnp.dot(dt_low.astype(xs.dtype), p["dt_w"],
                        preferred_element_type=jnp.float32)
                + p["dt_b"].astype(jnp.float32))
            a_mat = -jnp.exp(p["A_log"].astype(jnp.float32))
            y = selective_scan(xs, dt, a_mat, b_mat, c_mat,
                               p["D"].astype(jnp.float32))
            return (y * jax.nn.silu(z)) @ p["out_w"], y

    def _gmu(self, p, x, memory):
        with jax.named_scope("gmu"):
            return (memory * jax.nn.silu(x @ p["in_w"])) @ p["out_w"]

    def _pairs(self, t, heads):
        """[B, S, heads * d] -> the even and the odd heads, each
        [B, heads / 2, S, d]."""
        batch, seq, _ = t.shape
        with jax.named_scope("attn_layout"):
            t = t.reshape(batch, seq, heads // 2, 2, self.config.head_dim)
            t = t.transpose(3, 0, 2, 1, 4)
            return t[0], t[1]

    def _diff_attention(self, p, q, kv, lam0, window):
        """q [B, S, H]; kv = (k1, k2, v1, v2), each [B, kv_heads / 2, S,
        d]; lam0 a scalar (traced in a scanned stack)."""
        cfg = self.config
        q1, q2 = self._pairs(q, cfg.num_attention_heads)
        k1, k2, v1, v2 = kv
        block = {} if window is None else {
            "window": window, "block_q": window, "block_k": window}

        def att(q_, k_, v_):
            with jax.named_scope("attn_core"):
                return flash_attention(
                    q_, k_, v_, causal=True,
                    sm_scale=1.0 / math.sqrt(cfg.head_dim), **block)

        def pair(a_, b_):
            with jax.named_scope("attn_diff"):
                return jnp.concatenate([a_, b_], axis=-1)

        a1 = pair(att(q1, k1, v1), att(q1, k1, v2))
        a2 = pair(att(q2, k2, v1), att(q2, k2, v2))
        f32 = jnp.float32
        with jax.named_scope("attn_diff"):
            lam = (jnp.exp(jnp.sum(p["lq1"].astype(f32)
                                   * p["lk1"].astype(f32)))
                   - jnp.exp(jnp.sum(p["lq2"].astype(f32)
                                     * p["lk2"].astype(f32))) + lam0)
            a = a1.astype(f32) - lam * a2.astype(f32)      # [B, H/2, S, 2d]
            a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                                  + cfg.layer_norm_eps)
            a = (a * p["subln_w"].astype(f32) * (1.0 - lam0)).astype(
                q.dtype)
        batch, _, seq, _ = a.shape
        with jax.named_scope("attn_layout"):
            a = a.transpose(0, 2, 1, 3).reshape(batch, seq, cfg.hidden_size)
        with jax.named_scope("attn_out"):
            return a @ p["out_w"] + p["out_b"]

    def _attn(self, p, x, lam0, window):
        """(mixer output, (k1, k2, v1, v2))."""
        cfg = self.config
        with jax.named_scope("attn"):
            hid = cfg.hidden_size
            kv_w = cfg.num_key_value_heads * cfg.head_dim
            # inside "attn" the work is named once more, by part
            # (profiling/scope_map.py PARTS); names only
            with jax.named_scope("attn_qkv"):
                qkv = x @ p["qkv_w"] + p["qkv_b"]
                q, k, v = jnp.split(qkv, [hid, hid + kv_w], axis=-1)
            kv = (*self._pairs(k, cfg.num_key_value_heads),
                  *self._pairs(v, cfg.num_key_value_heads))
            return self._diff_attention(p, q, kv, lam0, window), kv

    def _cross(self, p, x, kv, lam0):
        with jax.named_scope("attn"):
            with jax.named_scope("attn_qkv"):
                q = x @ p["q_w"] + p["q_b"]
            return self._diff_attention(p, q, kv, lam0, None)

    def _layer(self, p, x, mixer):
        """One layer around `mixer(p['mixer'], LN1(x)) -> (out, kept)`."""
        eps = self.config.layer_norm_eps
        with jax.named_scope("layer"):
            out, kept = mixer(p["mixer"], fused_layer_norm(
                x, p["ln1"]["w"], p["ln1"]["b"], eps))
            h = x + out
            return h + gated_ffn(p["ffn"], fused_layer_norm(
                h, p["ln2"]["w"], p["ln2"]["b"], eps)), kept

    # -- the stack ------------------------------------------------------ #
    def stack_plan(self, batch, seq):
        """The M_STACK_* fields of this stack on [batch, seq] tokens."""
        cfg = self.config
        item = jnp.dtype(cfg.dtype).itemsize
        kv = batch * cfg.num_key_value_heads * seq * cfg.head_dim * item
        return {
            R.M_STACK_LAYERS: tuple(cfg.layer_plan()),
            R.M_STACK_SCAN_CHUNK: CHUNK,
            R.M_STACK_SCAN_ENTRY_BYTES: entry_state_bytes(
                batch, seq, cfg.d_inner, cfg.ssm_state),
            R.M_STACK_CROSS_LAYER_KEPT: (
                (f"layer {cfg.middle} scan output m",
                 batch * seq * cfg.d_inner * item),
                (f"layer {cfg.middle + 1} keys", kv),
                (f"layer {cfg.middle + 1} values", kv))}

    def hidden_states(self, params, input_ids):
        """input_ids [B, S] -> the hidden states before the final
        LayerNorm, [B, S, H]."""
        cfg = self.config
        with jax.named_scope("embed"):
            h = params["wte"].astype(cfg.dtype)[input_ids]
        mid, window = cfg.middle, cfg.sliding_window

        def lam0s(first, count):
            return jnp.asarray([cfg.lambda_init(first + 2 * p)
                                for p in range(count)], jnp.float32)

        def self_body(carry, xs):
            p, lam0 = xs
            h, _ = self._layer(p["mamba"], carry, self._mamba)
            h, _ = self._layer(p["attn"], h, lambda q, x: self._attn(
                q, x, lam0, window))
            return h, None

        def mid_mamba(carry, p):
            return self._layer(p, carry, self._mamba)

        def mid_attn(carry, p):
            return self._layer(p, carry, lambda q, x: self._attn(
                q, x, cfg.lambda_init(mid + 1), None))

        self_xs = (params["self"], lam0s(1, cfg.self_pairs))
        cross_xs = (params["cross"], lam0s(mid + 3, cfg.cross_pairs))

        def cross_body_of(memory, kv):
            def cross_body(carry, xs):
                p, lam0 = xs
                h, _ = self._layer(p["gmu"], carry, lambda q, x: (
                    self._gmu(q, x, memory), None))
                h, _ = self._layer(p["cross"], h, lambda q, x: (
                    self._cross(q, x, kv, lam0), None))
                return h, None
            return cross_body

        plan = self.stack_plan(*input_ids.shape)
        if cfg.activation_checkpointing:
            # shapes of what the middle layers hand on, for the plan's
            # trace of the cross-decoder's body
            _, memory = jax.eval_shape(mid_mamba, h, params["mid_mamba"])
            _, kv = jax.eval_shape(mid_attn, h, params["mid_attn"])
            placeholders = jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), (memory, kv))
            one = jax.tree.map(lambda a: a[None], (params["mid_mamba"],
                                                   params["mid_attn"]))
            wrap = checkpoint_layers(
                [(self_body, self_xs), (mid_mamba, one[0]),
                 (mid_attn, one[1]),
                 (cross_body_of(*placeholders), cross_xs)],
                self._remat_budget, h, cfg.vocab_size, plan)
        else:
            def wrap(body):
                return body
        budget = self._remat_budget
        if ((budget is None or budget.bytes_limit is None)
                and plan != self._stack_plan_logged):
            # no budget carries the plan to the monitor: say it here
            self._stack_plan_logged = plan
            log_dist(stack_plan_line(plan), ranks=[0])

        use_scan = resolve_use_scan(cfg.scan_layers, len(cfg.layer_plan()))
        h = run_layer_stack(wrap(self_body), h, self_xs, use_scan)
        h, memory = wrap(mid_mamba)(h, params["mid_mamba"])
        h, kv = wrap(mid_attn)(h, params["mid_attn"])
        return run_layer_stack(wrap(cross_body_of(memory, kv)), h, cross_xs,
                               use_scan)

    def _final_hidden(self, params, h):
        return fused_layer_norm(h, params["ln_f"]["w"], params["ln_f"]["b"],
                                self.config.layer_norm_eps)

    def logits(self, params, input_ids):
        h = self.hidden_states(params, input_ids)
        with jax.named_scope("head"):
            h = self._final_hidden(params, h)
            return (h @ params["wte"].astype(h.dtype).T).astype(jnp.float32)

    def loss(self, params, rng, input_ids, labels=None):
        """Mean next-token cross-entropy; ``input_ids[:, 1:]`` are the
        targets where `labels` is None.  `rng` is unused: every dropout
        of the source is 0."""
        cfg = self.config
        h = self.hidden_states(params, input_ids)
        with jax.named_scope("head"):
            h = self._final_hidden(params, h)
            if labels is None:
                h, labels = h[:, :-1], input_ids[:, 1:]
            return fused_linear_cross_entropy(
                h.reshape(-1, cfg.hidden_size),
                params["wte"].astype(h.dtype).T,
                labels.reshape(-1).astype(jnp.int32))

    def __call__(self, params, rng, input_ids, labels=None):
        return self.loss(params, rng, input_ids, labels)
