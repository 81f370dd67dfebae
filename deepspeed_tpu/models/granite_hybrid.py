"""Granite 4.0-H (ibm-granite/granite-4.0-h-micro's ``config.json``,
``model_type: granitemoehybrid`` with no experts: dense): a decoder of
Mamba-2 mixers with one grouped-query attention layer every ten, no
positional operation anywhere, four muP multipliers, a tied head.
models/phi4flash.py is the Mamba-1 hybrid (ops/selective_scan.py); this
is the Mamba-2 one (ops/ssd_scan.py).

With ``r = residual_multiplier`` and ``N*`` an RMSNorm with its own gain:

  model      ``h0 = embedding_multiplier E[ids]``; the layers;
             ``logits = (Nf(h) E^T) / logits_scaling`` (tied table).
  layer      ``h = h + r Mixer(N1(h))``; ``h = h + r FFN(N2(h))``;
             ``FFN(u) = (silu(g) * v) W_out`` with ``[g, v] = u W_in``.
  attention  ``layer_types[i] == "attention"``: ``softmax(
             attention_multiplier q k^T) v``, causal, fewer key/value
             heads than query heads, no bias, NO rotation and no other
             positional operation (``position_embedding_type: nope``).
  mamba      models/mamba2.py's mixer (which has the equations): H
             heads of P channels, N states, ``mamba_n_groups`` groups of
             B and C (one as published), the gated norm over each
             group's channels.

TPU-native structure: ``layer_types`` is cut into runs of like layers
(the cell's: mamba x5, attention, mamba x4), each run ONE stacked group
run by one body (models/layer_stack.py), and one recomputation budget is
spent over all of them (``checkpoint_layers(groups, ...)``).  The scan is
``ops/ssd_scan.py``'s chunked matrix form at ``mamba_chunk_size``; the
attention ``ops/flash_attention.py`` with ``sm_scale =
attention_multiplier`` (``position_free_attention``, which
models/nemotron_h.py runs too); the FFN models/laguna.py's
``gated_ffn``; the head ``ops/fused_cross_entropy.py`` on the transposed
table, the division by ``logits_scaling`` folded into the final norm's
output (a power of two there, exact in any float).
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..monitor import record as R
from ..ops.flash_attention import flash_attention
from ..ops.fused_cross_entropy import even_chunk, fused_linear_cross_entropy
from ..ops.normalize import rms_norm
from ..runtime.activation_checkpointing.checkpointing import (
    checkpoint_layers, stack_plan_line)
from ..utils.logging import log_dist
from .laguna import gated_ffn
from .layer_stack import resolve_use_scan, run_layer_stack
from .mamba2 import Mamba2Mixer, causal_conv, gated_rms_norm  # noqa: F401

MAMBA, ATTENTION = "mamba", "attention"
# the released stack: an attention layer at 5, 15, 25, 35 of 40
PUBLISHED_LAYER_TYPES = tuple(
    ATTENTION if i % 10 == 5 else MAMBA for i in range(40))


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40          # the first layers of layer_types
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    bf16: bool = True
    activation_checkpointing: bool = False
    # None: unrolled up to layer_stack's threshold, scanned beyond it
    scan_layers: Optional[bool] = None

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each '{MAMBA}' or '{ATTENTION}': {self.layer_types}")
        if self.mamba_n_heads * self.mamba_d_head != \
                self.mamba_expand * self.hidden_size:
            # this family's published in_proj is sized by the expansion;
            # the mixer itself (models/mamba2.py) reads heads x head size
            raise ValueError(
                f"the mixer's inner width, mamba_n_heads x mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head}, is not "
                f"mamba_expand x hidden_size = "
                f"{self.mamba_expand * self.hidden_size}")
        if self.hidden_size % self.num_attention_heads or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("attention heads must divide the hidden size "
                             "and be a multiple of the key/value heads")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                f"mamba_n_heads {self.mamba_n_heads} do not divide into "
                f"mamba_n_groups {self.mamba_n_groups}")

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mixer(self) -> Mamba2Mixer:
        return Mamba2Mixer(
            hidden_size=self.hidden_size, n_heads=self.mamba_n_heads,
            d_head=self.mamba_d_head, d_state=self.mamba_d_state,
            n_groups=self.mamba_n_groups, d_conv=self.mamba_d_conv,
            chunk_size=self.mamba_chunk_size, eps=self.rms_norm_eps)

    def runs(self):
        """[(kind, first published index, count)] of the runs of like
        layers, in order."""
        out = []
        for i, kind in enumerate(self.layer_types):
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, i, 1])
        return [tuple(run) for run in out]


def position_free_attention(p, u, heads, kv_heads, dim, sm_scale):
    """Causal grouped-query attention with NO rotation and no other
    positional operation: u [B, S, hidden], ``p["qkv_w"]`` [hidden,
    (heads + 2 kv_heads) dim] (q, then k, then v), ``p["out_w"]``
    [heads dim, hidden]; the parts named under scope ``attn``."""
    batch, seq, _ = u.shape
    with jax.named_scope("attn"):
        with jax.named_scope("attn_qkv"):
            q, k, v = jnp.split(
                u @ p["qkv_w"], [heads * dim, (heads + kv_heads) * dim],
                axis=-1)
        with jax.named_scope("attn_layout"):
            q, k, v = (t.reshape(batch, seq, -1, dim).transpose(
                0, 2, 1, 3) for t in (q, k, v))
        with jax.named_scope("attn_core"):
            a = flash_attention(q, k, v, causal=True, sm_scale=sm_scale)
        with jax.named_scope("attn_layout"):
            a = a.transpose(0, 2, 1, 3).reshape(batch, seq, heads * dim)
        with jax.named_scope("attn_out"):
            return a @ p["out_w"]


class GraniteHybridModel:
    """The decoder over runs of stacked layers; trained through
    ``deepspeed_tpu.initialize`` like GPT2Model."""

    # engine paths this model has not been run on, each with its reason;
    # the engine raises NotImplementedError with it at construction
    refuses = {
        "zero3_streaming": (
            "the streamed ZeRO-3 layer scan walks ONE stacked group, and "
            "this stack is several runs of unlike layers whose gathers "
            "would have to be chained across the runs' boundaries"),
        "pipeline": (
            "no pipeline module cuts a stack of unlike runs into stages "
            "yet, and a stage boundary inside a run would split its "
            "stacked group"),
    }

    def __init__(self, config: GraniteHybridConfig):
        self.config = config
        self.mixer = config.mixer
        self._remat_budget = None
        self._stack_plan_logged = None

    def install_remat_budget(self, budget) -> None:
        """Engine hook: the bytes the layer scans' checkpointing may spend
        on saved residuals (checkpointing.RematBudget)."""
        self._remat_budget = budget

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng, kind):
        cfg = self.config
        hid, inter = cfg.hidden_size, cfg.shared_intermediate_size
        keys = iter(jax.random.split(rng, 8))

        def normal(shape):
            return cfg.initializer_range * jax.random.normal(
                next(keys), shape, jnp.float32)

        if kind == MAMBA:
            mixer = self.mixer.init_params(keys, normal)
        else:
            kv = cfg.num_key_value_heads * cfg.head_dim
            mixer = {"qkv_w": normal((hid, hid + 2 * kv)),
                     "out_w": normal((hid, hid))}
        ones = jnp.ones((hid,), jnp.float32)
        return {"ln1": ones, "mixer": mixer, "ln2": ones,
                "ffn": {"w1": normal((hid, 2 * inter)),
                        "w2": normal((inter, hid))}}

    def init_params(self, rng):
        """Matrices and the table normal(0, initializer_range); conv taps
        uniform, bias 0; ``A_log = log(uniform(1, 16))`` a head; ``D`` 1;
        ``dt_bias`` the inverse softplus of steps log-uniform in [1e-3,
        0.1]; norm gains 1.  A layer's weights depend on its published
        index alone."""
        cfg = self.config
        k_wte, k_layers = jax.random.split(rng)

        def run(kind, first, count):
            keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
                first + jnp.arange(count))
            return jax.vmap(lambda k: self._init_layer(k, kind))(keys)

        return {
            "wte": cfg.initializer_range * jax.random.normal(
                k_wte, (cfg.vocab_size, cfg.hidden_size), jnp.float32),
            "runs": [run(*r) for r in cfg.runs()],
            "ln_f": jnp.ones((cfg.hidden_size,), jnp.float32)}

    def param_partition_specs(self):
        """No tensor-parallel split is written for this family yet: every
        leaf replicated over the model axis (ZeRO shards over the data
        axes as it does for any tree)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    # -- the two mixers ------------------------------------------------- #
    def _mamba(self, p, u):
        return self.mixer.apply(p, u)

    def _attention(self, p, u):
        cfg = self.config
        return position_free_attention(
            p, u, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.attention_multiplier)

    def _layer(self, p, x, mixer):
        cfg = self.config
        eps, r = cfg.rms_norm_eps, cfg.residual_multiplier
        with jax.named_scope("layer"):
            h = x + r * mixer(p["mixer"], rms_norm(x, p["ln1"], eps))
            return h + r * gated_ffn(p["ffn"], rms_norm(h, p["ln2"], eps))

    # -- the stack ------------------------------------------------------ #
    def scan_form(self):
        """``kernel`` where ops/ssd_scan.py's Pallas kernels take this
        model's shapes on this backend, else ``xla``."""
        return self.mixer.scan_form()

    def stack_plan(self, batch, seq):
        """The M_STACK_* fields of this stack on [batch, seq] tokens."""
        cfg = self.config
        use_scan = resolve_use_scan(cfg.scan_layers, cfg.num_hidden_layers)
        return {
            R.M_STACK_LAYERS: tuple(
                (i, kind, 0) for i, kind in enumerate(cfg.layer_types)),
            R.M_STACK_SSD: (
                self.scan_form(), cfg.mamba_chunk_size,
                self.mixer.entry_state_bytes(batch, seq),
                ", ".join(kind + (f" x{count}" if count > 1 else "")
                          for kind, _, count in cfg.runs()),
                "scanned" if use_scan else "unrolled",
                cfg.mamba_n_groups, self.mixer.conv_form(seq))}

    def hidden_states(self, params, input_ids):
        """input_ids [B, S] -> the hidden states before the final norm,
        [B, S, hidden]."""
        cfg = self.config
        with jax.named_scope("embed"):
            h = cfg.embedding_multiplier * params["wte"].astype(
                cfg.dtype)[input_ids]

        def mamba_body(carry, p):
            return self._layer(p, carry, self._mamba), None

        def attention_body(carry, p):
            return self._layer(p, carry, self._attention), None

        bodies = {MAMBA: mamba_body, ATTENTION: attention_body}
        groups = [(bodies[kind], xs)
                  for (kind, _, _), xs in zip(cfg.runs(), params["runs"])]
        plan = self.stack_plan(*input_ids.shape)
        budget = self._remat_budget
        if cfg.activation_checkpointing:
            wrap = checkpoint_layers(groups, budget, h, cfg.vocab_size, plan)
        else:
            def wrap(body):
                return body
        if ((budget is None or budget.bytes_limit is None)
                and plan != self._stack_plan_logged):
            # no budget carries the plan to the monitor: say it here
            self._stack_plan_logged = plan
            log_dist(stack_plan_line(plan), ranks=[0])
        use_scan = resolve_use_scan(cfg.scan_layers, cfg.num_hidden_layers)
        for body, xs in groups:
            h = run_layer_stack(wrap(body), h, xs, use_scan)
        return h

    def _head_input(self, params, h):
        """What the tied table multiplies: the final norm's output over
        ``logits_scaling``."""
        cfg = self.config
        return rms_norm(h, params["ln_f"], cfg.rms_norm_eps) * jnp.asarray(
            1.0 / cfg.logits_scaling, h.dtype)

    def logits(self, params, input_ids):
        """f32 [B, S, vocab]."""
        h = self.hidden_states(params, input_ids)
        with jax.named_scope("head"):
            h = self._head_input(params, h)
            return (h @ params["wte"].astype(h.dtype).T).astype(jnp.float32)

    def loss(self, params, rng, input_ids, labels=None):
        """Mean next-token cross-entropy; ``input_ids[:, 1:]`` are the
        targets where `labels` is None.  `rng` is unused (no dropout)."""
        cfg = self.config
        h = self.hidden_states(params, input_ids)
        with jax.named_scope("head"):
            h = self._head_input(params, h)
            if labels is None:
                h, labels = h[:, :-1], input_ids[:, 1:]
            h = h.reshape(-1, cfg.hidden_size)
            return fused_linear_cross_entropy(
                h, params["wte"].astype(h.dtype).T,
                labels.reshape(-1).astype(jnp.int32),
                even_chunk(cfg.vocab_size, h.shape[0]))

    def __call__(self, params, rng, input_ids, labels=None):
        return self.loss(params, rng, input_ids, labels)
