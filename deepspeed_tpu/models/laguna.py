"""Laguna (poolside Laguna-XS.2's ``config.json``): a decoder of full and
window layers with different numbers of query heads, a per-head output
gate, partial YaRN rotary positions, and sparse experts beside a shared
one.

Every layer is ``h = x + Attn(RMSNorm(x)); out = h + FFN(RMSNorm(h))``
without bias.  By published index i:

  attention   ``layer_types[i]``: "full_attention" (48 query heads here)
              or "sliding_attention" (64, the last ``sliding_window``
              keys, the query's own included), both on 8 key/value heads
              of 128 through the flash kernels' grouped index map and
              banded grid.  Rotary positions, rotate-half over the first
              ``r`` dimensions of a head: sliding layers all 128 at theta
              10,000; full layers half of them with YaRN frequencies and
              the attention factor on cos and sin; each element widened
              to float32, multiplied by float32 tables, rounded once.
              ``g = sigmoid(u Wg)``
              with ``Wg [hidden, heads]`` scales each head's output
              before the output projection.
  FFN         ``mlp_only_layers`` (layer 0) a dense gated FFN; the others
              ``moe.DroplessMoE``: sigmoid scores over all E experts in
              float32, the k largest renormalised and scaled, the held
              experts' part of the sum, a shared expert once.

TPU-native structure: consecutive layers of one shape (attention kind,
head count, FFN kind) are one stacked group run by one body; a cut of
the model is ``num_hidden_layers`` (the first layers, which keep their
published indices), ``experts_held`` (first, count) and ``vocab_size``.
The rotary tables are built once a step outside the bodies, in float32.
Where a head is one lane tile of 128 and the sequence is whole blocks
(ops/rotary.py ``rotary_block``: the shape decides, and the stack's
plan says which), q and k are rotated by ``rotate_qkv``, one Pallas pass
that reads the QKV product flat and writes q, k and v head-major for the
flash kernels, the float32 values in registers and the tables per lane;
its backward pass is the same pass the other way.  Elsewhere
``apply_rotary`` on the transposed halves, which is also the plain
definition the tests hold the kernels to; its float32 tensors are
XLA's to place.  The head is its own matrix, not the embedding table.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..moe.dropless import DroplessMoE
from ..moe.sharded_moe import emit_routing_stats
from ..monitor import record as R
from ..ops.flash_attention import flash_attention
from ..ops.fused_cross_entropy import fused_linear_cross_entropy
from ..ops.normalize import rms_norm
from ..ops.rotary import lane_tables, rotary_block, rotate_qkv
from ..runtime.activation_checkpointing.checkpointing import (
    FFN_PRODUCT_NAME, checkpoint_layers, stack_plan_line)
from ..utils.logging import log_dist
from .layer_stack import run_layer_stack

FULL, SLIDING = "full_attention", "sliding_attention"
# The embedding's rows are normal(0, 1), torch.nn.Embedding's own default,
# whatever ``initializer_range`` gives the matrices: at 0.02 a token's own
# row is a fifth of what attention adds to every position alike (values
# are of unit scale after the norm, and under Zipf ids their average over
# the keys keeps 0.13 of that), every router then reads nearly one vector
# and sends most tokens to the same few experts (PERF.md section 6, PR 36).
EMBEDDING_STD = 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """The ``dim / 2`` YaRN frequencies: below ``low`` the plain ones,
    above ``high`` divided by ``factor``, a linear ramp between; ``low``
    and ``high`` are the dimensions whose wavelength makes ``beta_fast``
    and ``beta_slow`` turns over the ``original`` positions."""
    def dimension(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dimension(beta_fast)), 0)
    high = min(math.ceil(dimension(beta_slow)), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary_table(seq: int, inv_freq, attention_factor: float = 1.0):
    """(cos, sin), each float32 [seq, len(inv_freq)]."""
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return attention_factor * jnp.cos(angle), attention_factor * jnp.sin(
        angle)


def apply_rotary(x, table):
    """x [B, heads, S, D]: rotate-half pairing (i, i + r/2) over the first
    r = 2 x table width dimensions, the rest unchanged; float32 inside.
    The plain form: ops/rotary.py does the same where its kernels run."""
    cos, sin = table
    half = cos.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2, rest = (xf[..., :half], xf[..., half:2 * half],
                    xf[..., 2 * half:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
        axis=-1).astype(x.dtype)


def gated_ffn(p, u):
    """A dense gated FFN, ``(silu(u Wg) * (u Wu)) Wd`` with gate and up
    in one matrix ``w1`` (gate first), under scope ``mlp``.  The first
    product is offered whole to a checkpointed layer's byte budget: kept,
    the backward pass rebuilds ``silu(gate)`` and its product with ``up``
    element-wise and runs no second ``u @ w1``."""
    with jax.named_scope("mlp"):
        gate, up = jnp.split(
            checkpoint_name(u @ p["w1"], FFN_PRODUCT_NAME), 2, axis=-1)
        return (up * jax.nn.silu(gate)) @ p["w2"]


class ExpertStack:
    """What the models of stacked layer groups around a ``DroplessMoE``
    share (``self.config``, ``self.moe``): the engine's byte budget and
    the wrapper every group's body runs in."""

    # What the engine reads before it plans its weight copy
    # (runtime/engine.py ``_plan_weight_copy``): these models' grad
    # programs cast the master themselves.  Launched on the engine's bf16
    # copy, the v5e compiler's program for the same step is slower than
    # the cast it saves (my chip runs, PR 48, PERF.md section 6:
    # ``laguna-xs2.s8k`` 561 to 614 and 633 ms a step with ``moe_ms`` 73.7
    # to 117.8, ``glm47-flash.s8k`` 688.3 to 693.4): with the weights as
    # parameters it prefetches 50 slices of activations to the fast memory
    # where it prefetched 137 (the compiled texts, ahead of time).
    casts_own_weights = ("the routed experts' grad program, which the v5e "
                         "compiler schedules worse on bf16 parameters")

    # residual streams of the stack's carry: 1 is [batch, sequence,
    # hidden]; more (models/xing4.py) [batch, streams, sequence, hidden]
    carry_streams = 1

    def __init__(self, config, moe):
        self.config = config
        self.moe = moe
        self._remat_budget = None
        self._stack_plan_logged = None

    def install_remat_budget(self, budget) -> None:
        """Engine hook: the bytes the layer groups' checkpointing may
        spend on saved residuals (checkpointing.RematBudget)."""
        self._remat_budget = budget

    def experts_held(self):
        """(first, count) of the routed experts this program holds."""
        return self.config.experts_held

    def _layer_wrapper(self, groups, h, plan):
        """What every body of ``groups`` ([(body, stacked xs, ...)]) is
        wrapped in before it runs on ``h``: the byte budget's checkpoint
        policy where ``config.activation_checkpointing`` says so, or
        nothing.  ``plan`` (the model's M_STACK_* fields) gets the sparse
        FFN's row buffers and goes to the budget's log line, or is logged
        here, once, where no budget will.  ``h`` may be a tuple, the
        stream first and then the carries of another width that go from
        layer to layer with it (``checkpoint_layers`` has the rest)."""
        cfg = self.config
        stream = h[0] if isinstance(h, tuple) else h
        tokens = stream.shape[0] * stream.shape[-2]
        plan = {**plan, R.M_STACK_DISPATCH_ROWS: self.moe.capacity(tokens)}
        if cfg.activation_checkpointing:
            wrap = checkpoint_layers(
                [group[:2] for group in groups],
                self._remat_budget, h, cfg.vocab_size, plan,
                extra_working_set=self.moe.working_set_bytes(
                    tokens, stream.dtype.itemsize),
                streams=self.carry_streams)
        else:
            def wrap(body):
                return body
        budget = self._remat_budget
        if ((budget is None or budget.bytes_limit is None)
                and plan != self._stack_plan_logged):
            self._stack_plan_logged = plan
            log_dist(stack_plan_line(plan), ranks=[0])
        return wrap


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40          # the first layers of the pattern
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rms_norm_eps: float = 1e-6
    # by published index; None: [full, sliding, sliding, sliding] repeated
    # with 48 and 64 query heads
    layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    mlp_only_layers: Tuple[int, ...] = (0,)
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    # (first, count) of the routed experts held here; None is all
    experts_held: Optional[Tuple[int, int]] = None
    full_rope_theta: float = 500000.0
    full_partial_rotary_factor: float = 0.5
    yarn_factor: float = 64.0
    yarn_original_max_position_embeddings: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    sliding_rope_theta: float = 10000.0
    initializer_range: float = 0.02
    bf16: bool = True
    activation_checkpointing: bool = False

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if i % 4 == 0 else SLIDING for i in range(n))
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = tuple(
                48 if kind == FULL else 64 for kind in self.layer_types)
        self.layer_types = tuple(self.layer_types)[:n]
        self.num_attention_heads_per_layer = tuple(
            self.num_attention_heads_per_layer)[:n]
        if len(self.layer_types) != n or len(
                self.num_attention_heads_per_layer) != n:
            raise ValueError("layer_types and num_attention_heads_per_layer "
                             f"must cover the {n} layers kept")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"unknown layer type in {self.layer_types}")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("query heads must be a multiple of the "
                             "key/value heads")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(self.experts_held)

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32

    def layer_plan(self):
        """[(published index, attention kind, query heads, sparse)]."""
        return [(i, kind, heads, i not in self.mlp_only_layers)
                for i, (kind, heads) in enumerate(zip(
                    self.layer_types, self.num_attention_heads_per_layer))]

    def groups(self):
        """Runs of consecutive layers of one shape: [(name, kind, heads,
        sparse, first index, count)]."""
        runs = []
        for index, kind, heads, sparse in self.layer_plan():
            if runs and tuple(runs[-1][1:4]) == (kind, heads, sparse):
                runs[-1][5] += 1
            else:
                runs.append([f"layers_{index:02d}", kind, heads, sparse,
                             index, 1])
        return [tuple(r) for r in runs]


class LagunaModel(ExpertStack):
    """The decoder over stacked groups of like layers; trained through
    ``deepspeed_tpu.initialize`` like GPT2Model."""

    def __init__(self, config: LagunaConfig):
        super().__init__(config, DroplessMoE(
            config.hidden_size, config.num_experts,
            config.num_experts_per_tok, config.moe_intermediate_size,
            config.shared_expert_intermediate_size, score="sigmoid",
            renormalize=True, scale=config.moe_routed_scaling_factor,
            experts_held=config.experts_held,
            init_std=config.initializer_range))

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng, heads, sparse):
        cfg = self.config
        hid, dim = cfg.hidden_size, cfg.head_dim
        k_qkv, k_gate, k_out, k_ffn, k_down = jax.random.split(rng, 5)
        std = cfg.initializer_range

        def normal(key, shape):
            return std * jax.random.normal(key, shape, jnp.float32)

        layer = {
            "ln1": jnp.ones((hid,), jnp.float32),
            "attn": {
                "qkv_w": normal(k_qkv, (hid, (
                    heads + 2 * cfg.num_key_value_heads) * dim)),
                "gate_w": normal(k_gate, (hid, heads)),
                "out_w": normal(k_out, (heads * dim, hid))},
            "ln2": jnp.ones((hid,), jnp.float32)}
        if sparse:
            layer["moe"] = self.moe.init_params(k_ffn)
        else:
            layer["ffn"] = {
                "w1": normal(k_ffn, (hid, 2 * cfg.intermediate_size)),
                "w2": normal(k_down, (cfg.intermediate_size, hid))}
        return layer

    def init_params(self, rng):
        cfg = self.config
        k_wte, k_head, k_layers = jax.random.split(rng, 3)
        params = {
            "wte": EMBEDDING_STD * jax.random.normal(
                k_wte, (cfg.vocab_size, cfg.hidden_size), jnp.float32),
            "ln_f": jnp.ones((cfg.hidden_size,), jnp.float32),
            "head": cfg.initializer_range * jax.random.normal(
                k_head, (cfg.hidden_size, cfg.vocab_size), jnp.float32)}
        for name, _, heads, sparse, first, count in cfg.groups():
            # a layer's weights depend on its published index alone
            keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
                first + jnp.arange(count))
            params[name] = jax.vmap(
                lambda k, h=heads, s=sparse: self._init_layer(k, h, s))(keys)
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

    # -- the layer ------------------------------------------------------ #
    def rotary_tables(self, seq, lanes=False):
        """{attention kind: (cos, sin)} for ``seq`` positions, float32
        [seq, r / 2]; ``lanes``: (cos, sin, r / 2) with the tables per
        lane of a head, as ops/rotary.py's kernels read them."""
        cfg = self.config
        dim = cfg.head_dim
        rotated = int(dim * cfg.full_partial_rotary_factor)
        i = jnp.arange(dim // 2, dtype=jnp.float32)
        tables = {
            SLIDING: rotary_table(
                seq, cfg.sliding_rope_theta ** (-2.0 * i / dim)),
            FULL: rotary_table(seq, yarn_inv_freq(
                rotated, cfg.full_rope_theta, cfg.yarn_factor,
                cfg.yarn_original_max_position_embeddings,
                cfg.yarn_beta_fast, cfg.yarn_beta_slow),
                cfg.yarn_attention_factor)}
        if lanes:
            tables = {kind: (*lane_tables(cos, sin, dim), cos.shape[-1])
                      for kind, (cos, sin) in tables.items()}
        return tables

    def rotary_plan(self, seq):
        """{attention kind: (positions, heads) of the rotary kernels'
        block, or None where ``apply_rotary`` runs}: the shape decides
        (ops/rotary.py rotary_block)."""
        cfg = self.config
        return {kind: rotary_block(seq, cfg.head_dim, heads,
                                   cfg.num_key_value_heads)
                for _, kind, heads, _ in cfg.layer_plan()}

    def _attention(self, p, u, kind, heads, table):
        cfg = self.config
        batch, seq, _ = u.shape
        dim, kv = cfg.head_dim, cfg.num_key_value_heads
        # inside "attn" the work is named once more, by part
        # (profiling/scope_map.py PARTS); names only
        with jax.named_scope("attn"):
            with jax.named_scope("attn_qkv"):
                qkv = u @ p["qkv_w"]
            if rotary_block(seq, dim, heads, kv) is not None:
                # the split, the head transpose and the rotation in one
                # pass over qkv (ops/rotary.py); `table` its lane tables
                with jax.named_scope("attn_rotary"):
                    q, k, v = rotate_qkv(qkv, *table, heads, kv)
            else:
                with jax.named_scope("attn_qkv"):
                    q, k, v = jnp.split(
                        qkv, [heads * dim, (heads + kv) * dim], axis=-1)

                def by_head(t, n):
                    with jax.named_scope("attn_layout"):
                        return t.reshape(batch, seq, n, dim).transpose(
                            0, 2, 1, 3)

                def rotary(t):
                    with jax.named_scope("attn_rotary"):
                        return apply_rotary(t, table)

                q = rotary(by_head(q, heads))
                k = rotary(by_head(k, kv))
                v = by_head(v, kv)
            window = cfg.sliding_window
            banded = {"window": window, "block_q": window,
                      "block_k": window} if kind == SLIDING else {}
            with jax.named_scope("attn_core"):
                a = flash_attention(q, k, v, causal=True,
                                    sm_scale=1.0 / math.sqrt(dim), **banded)
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid((u @ p["gate_w"]).astype(jnp.float32))
            with jax.named_scope("attn_layout"):
                a = a.transpose(0, 2, 1, 3)
            with jax.named_scope("attn_gate"):
                a = a * gate[..., None].astype(a.dtype)
            with jax.named_scope("attn_out"):
                return a.reshape(batch, seq, heads * dim) @ p["out_w"]

    _dense_ffn = staticmethod(gated_ffn)

    def _layer(self, p, x, kind, heads, sparse, table, picks=None):
        """(layer output, the sparse FFN's Routing or None)."""
        eps = self.config.rms_norm_eps
        with jax.named_scope("layer"):
            h = x + self._attention(p["attn"], rms_norm(x, p["ln1"], eps),
                                    kind, heads, table)
            u = rms_norm(h, p["ln2"], eps)
            if not sparse:
                return h + self._dense_ffn(p["ffn"], u), None
            y, routing = self.moe.apply(p["moe"], u, picks=picks)
            return h + y, routing

    # -- the stack ------------------------------------------------------ #
    def stack_plan(self):
        """The M_STACK_* fields of this stack."""
        cfg = self.config
        return {
            R.M_STACK_LAYERS: tuple(
                (i, kind + ("+experts" if sparse else "+dense"),
                 cfg.sliding_window if kind == SLIDING else 0)
                for i, kind, _, sparse in cfg.layer_plan()),
            R.M_STACK_EXPERTS_HELD: (*cfg.experts_held, cfg.num_experts)}

    def _run(self, params, input_ids, picks, keep):
        """The hidden states before the final norm and, per sparse layer
        in order, ``keep(routing)`` stacked over the layers."""
        cfg = self.config
        with jax.named_scope("embed"):
            h = params["wte"].astype(cfg.dtype)[input_ids]
        seq = input_ids.shape[1]
        rotary = self.rotary_plan(seq)
        tables = self.rotary_tables(seq, lanes=all(rotary.values()))
        groups, sparse_before = [], 0
        for name, kind, heads, sparse, _, count in cfg.groups():
            forced = None
            if sparse and picks is not None:
                forced = picks[sparse_before:sparse_before + count]
            sparse_before += count if sparse else 0

            def body(carry, xs, kind=kind, heads=heads, sparse=sparse):
                p, forced_picks = xs
                out, routing = self._layer(p, carry, kind, heads, sparse,
                                           tables[kind], forced_picks)
                return out, keep(routing) if sparse else None

            groups.append((body, (params[name], forced), sparse, count))

        wrap = self._layer_wrapper(groups, h, {
            **self.stack_plan(), R.M_STACK_ROTARY: tuple(
                (kind, "kernel", *block) if block else (kind, "xla")
                for kind, block in rotary.items())})
        kept = []
        for body, xs, sparse, count in groups:
            # a group of several layers is scanned: one traced body
            h, ys = run_layer_stack(wrap(body), h, xs, count > 1,
                                    with_ys=True)
            if sparse:
                kept.append(ys)
        return h, (jax.tree.map(lambda *a: jnp.concatenate(a), *kept)
                   if kept else None)

    def hidden_states(self, params, input_ids, picks=None):
        """input_ids [B, S] -> the hidden states before the final norm.
        Every sparse layer's RoutingStats go to the collecting tap, if
        the engine installed one (moe/sharded_moe.py)."""
        h, stats = self._run(params, input_ids, picks, self.moe.stats)
        if stats is not None:
            for i in range(stats.layers.shape[0]):
                emit_routing_stats(jax.tree.map(lambda a: a[i], stats))
        return h

    def routing(self, params, input_ids, with_inputs=False):
        """(scores f32 [L, T, E], picks int32 [L, T, k]) of the L sparse
        layers on ``input_ids``, from the same forward pass as the loss;
        with ``with_inputs`` also what each router read, [L, T, hidden]."""
        _, kept = self._run(
            params, input_ids, None,
            lambda r: (r.scores, r.picks) + ((r.inputs,) * with_inputs))
        return kept

    def loss(self, params, rng, input_ids, labels=None, picks=None):
        """Mean next-token cross-entropy; ``input_ids[:, 1:]`` are the
        targets where `labels` is None.  `rng` is unused (no dropout).
        ``picks`` int32 [L, T, k] forces every sparse layer's choice."""
        cfg = self.config
        h = self.hidden_states(params, input_ids, picks)
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"], cfg.rms_norm_eps)
            if labels is None:
                h, labels = h[:, :-1], input_ids[:, 1:]
            return fused_linear_cross_entropy(
                h.reshape(-1, cfg.hidden_size),
                params["head"].astype(h.dtype),
                labels.reshape(-1).astype(jnp.int32))

    def logits(self, params, input_ids):
        h = self.hidden_states(params, input_ids)
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"], self.config.rms_norm_eps)
            return (h @ params["head"].astype(h.dtype)).astype(jnp.float32)

    def __call__(self, params, rng, input_ids, labels=None, picks=None):
        return self.loss(params, rng, input_ids, labels, picks)
