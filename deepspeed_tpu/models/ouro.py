"""Ouro (ByteDance Ouro-2.6B's ``config.json``, the LoopLM of
arXiv:2510.25741): a decoder whose whole stack of layers is run
``total_ut_steps`` times on the SAME weights, an exit gate that reads
every pass's output, and a loss that weighs the passes' cross-entropies,
token by token, by the distribution of exits the gate gives.

With ``N*`` an RMSNorm with its own gain:

  block       sandwich norms: ``a = x + N2(Attn(N1(x)))``,
              ``y = a + N4(FFN(N3(a)))``, no bias.  ``Attn``: full causal
              attention, as many key/value heads as query heads, heads of
              128, rotate-half rotary over the whole head (each element
              widened to float32, multiplied by float32 tables, rounded
              once).  ``FFN(u) = (silu(u Wg) * (u Wu)) Wd``.
  recurrence  ``h_0 = E[ids]``; ``h_t = Nf(Stack(h_{t-1}))`` for t = 1..T,
              ``Stack`` the layers in order and ``Nf`` the ONE final norm.
              ``h_t`` is what the head and the gate read and what pass
              t + 1 starts from.
  gate        in float32: ``lam_t = sigmoid(h_t . w_g + b_g)`` for t < T;
              exit distribution ``p_t = lam_t prod_{j<t} (1 - lam_j)``,
              ``p_T = prod_{j<T} (1 - lam_j)``.
  loss        ``l_t(i) = CE(softmax(h_t(i) W_head), y_i)``;
              ``L = mean_i [ sum_t p_t(i) l_t(i)
                             + beta KL(p(i) || uniform over T) ]``:
              the paper's stage-one objective with a uniform prior in its
              KL form (``KL = ln T - H(p)``, never negative, so L is never
              under the mix of cross-entropies).

TPU-native structure: the layers are ONE stacked group run by one body
(models/layer_stack.py ``run_layer_recurrence``: the same stacked weights
under a loop of passes, so a weight's gradient is the sum over its
T uses and the engine's one compute-dtype cast of the weights serves all
of them); the byte budget plans T x layers APPLICATIONS (every one keeps
its carry and its flash residuals).  The T passes go over the head in ONE
call of the per-token fused cross-entropy on the ``[T x N, hidden]`` stack
of ``h_t`` (ops/fused_cross_entropy.py: losses ``[T x N]`` out, their
cotangent in, no logits kept); the gate's logit is a float32 multiply and
sum on the vector unit, and the exit distribution a product of sigmoids
(``exit_distribution`` says why).  Where a head is one lane tile and the sequence whole blocks,
q and k are rotated by ``rotate_qkv`` (ops/rotary.py), else by
``apply_rotary``, as in models/laguna.py.  All of the exit work (the T
final norms, the gate, the head passes, the distribution, the KL term)
lies under the named scope ``exit`` (profiling/scope_map.py REGIONS).
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..monitor import record as R
from ..ops.flash_attention import flash_attention
from ..ops.fused_cross_entropy import (even_chunk,
                                       fused_linear_cross_entropy_per_token)
from ..ops.normalize import rms_norm
from ..ops.rotary import lane_tables, rotary_block, rotate_qkv
from ..runtime.activation_checkpointing.checkpointing import (
    checkpoint_layers, stack_plan_line)
from ..utils.logging import log_dist
from .laguna import apply_rotary, gated_ffn, rotary_table
from .layer_stack import run_layer_recurrence

FULL = "full_attention"
IGNORE = -1          # the label of a position that has no target
EXIT = "exit"        # the scope of the exit work (scope_map.REGIONS)


@dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48          # the first layers
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    total_ut_steps: int = 4              # passes of the stack
    exit_kl_weight: float = 0.1          # beta
    initializer_range: float = 0.02
    bf16: bool = True
    activation_checkpointing: bool = False

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("Ouro has as many key/value heads as query "
                             "heads; grouped heads are not written here")
        if self.total_ut_steps < 2:
            raise ValueError("total_ut_steps < 2 is a plain decoder: no "
                             "gate, no exit distribution")

    @property
    def dtype(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32


def exit_distribution(logits):
    """``logits`` f32 [T - 1, ...] of the gate after passes 1..T-1 -> p
    f32 [T, ...]: ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last exit
    taking what is left.  ``1 - lam`` is taken as ``sigmoid(-logit)``, so
    that every factor keeps its relative precision however sure the gate
    is.  Not through ``log_sigmoid`` and a cumulative sum: on the TPU a
    float32 ``log`` is good to 4e-5 and the sum may be taken as a
    default-precision product, which together cost what a bf16 gate
    costs (PERF.md section 6, PR 45)."""
    stay, p = jnp.ones_like(logits[0]), []
    for z in logits:
        p.append(jax.nn.sigmoid(z) * stay)
        stay = stay * jax.nn.sigmoid(-z)
    return jnp.stack(p + [stay])


def kl_to_uniform(p):
    """KL(p || uniform over the T exits) along axis 0; an exit whose
    probability has underflowed adds 0 and takes a gradient of 0, not a
    NaN."""
    floor = jnp.finfo(p.dtype).tiny
    return math.log(p.shape[0]) + jnp.sum(
        p * jnp.log(jnp.maximum(p, floor)), axis=0)


class OuroModel:
    """The looped decoder; trained through ``deepspeed_tpu.initialize``
    like GPT2Model."""

    # engine paths this model cannot run yet, each with its reason; the
    # engine raises NotImplementedError with it at construction
    refuses = {
        "zero3_streaming": (
            "the streamed ZeRO-3 layer scan gathers a group once per use, "
            "and a group shared by every pass would have to be gathered "
            "once, run `passes` times and keep one carry an application"),
        "pipeline": (
            "the pipeline engine hands a micro-batch down the stages once, "
            "and every pass of the recurrence would have to cross all of "
            "them again"),
    }

    def __init__(self, config: OuroConfig):
        self.config = config
        # scalars of the dict ``__call__`` returns beside the loss
        # (engine.model_counters())
        self.aux_counters = (
            R.M_TASK_LOSS, R.M_EXIT_KL, R.M_EXIT_STEP_MEAN) + tuple(
            R.M_EXIT_MASS + str(t)
            for t in range(1, config.total_ut_steps + 1))
        self._remat_budget = None
        self._stack_plan_logged = None

    def install_remat_budget(self, budget) -> None:
        """Engine hook: the bytes the layer scan's checkpointing may
        spend on saved residuals (checkpointing.RematBudget)."""
        self._remat_budget = budget

    # -- parameters ---------------------------------------------------- #
    def _init_layer(self, rng):
        cfg = self.config
        hid, inter = cfg.hidden_size, cfg.intermediate_size
        width = cfg.num_attention_heads * cfg.head_dim
        k_qkv, k_out, k_up, k_down = jax.random.split(rng, 4)

        def normal(key, shape):
            return cfg.initializer_range * jax.random.normal(
                key, shape, jnp.float32)

        ones = jnp.ones((hid,), jnp.float32)
        return {"ln1": ones, "ln2": ones, "ln3": ones, "ln4": ones,
                "attn": {"qkv_w": normal(k_qkv, (hid, 3 * width)),
                         "out_w": normal(k_out, (width, hid))},
                "ffn": {"w1": normal(k_up, (hid, 2 * inter)),
                        "w2": normal(k_down, (inter, hid))}}

    def init_params(self, rng):
        """Matrices and the embedding normal(0, initializer_range), norm
        gains 1, the gate's weights and bias 0: every exit starts at
        lam = 1/2."""
        cfg = self.config
        k_wte, k_head, k_layers = jax.random.split(rng, 3)
        std, hid = cfg.initializer_range, cfg.hidden_size
        # a layer's weights depend on its published index alone
        keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
            jnp.arange(cfg.num_hidden_layers))
        return {
            "wte": std * jax.random.normal(
                k_wte, (cfg.vocab_size, hid), jnp.float32),
            "layers": jax.vmap(self._init_layer)(keys),
            "ln_f": jnp.ones((hid,), jnp.float32),
            "gate": {"w": jnp.zeros((hid,), jnp.float32),
                     "b": jnp.zeros((), jnp.float32)},
            "head": std * jax.random.normal(
                k_head, (hid, cfg.vocab_size), jnp.float32)}

    def param_partition_specs(self):
        """No tensor-parallel split is written for this family yet: every
        leaf replicated over the model axis (ZeRO shards over the data
        axes as it does for any tree)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    # -- the layer ------------------------------------------------------ #
    def rotary_plan(self, seq):
        """(positions, heads) of the rotary kernels' block, or None where
        ``apply_rotary`` runs: the shape decides (ops/rotary.py)."""
        cfg = self.config
        return rotary_block(seq, cfg.head_dim, cfg.num_attention_heads,
                            cfg.num_key_value_heads)

    def _rotary_table(self, seq):
        """The table the layers' rotation reads: ``apply_rotary``'s (cos,
        sin), or the kernels' lane tables and half width."""
        dim = self.config.head_dim
        i = jnp.arange(dim // 2, dtype=jnp.float32)
        cos, sin = rotary_table(seq, self.config.rope_theta ** (
            -2.0 * i / dim))
        if self.rotary_plan(seq) is None:
            return cos, sin
        return (*lane_tables(cos, sin, dim), dim // 2)

    def _attention(self, p, u, table):
        cfg = self.config
        batch, seq, _ = u.shape
        heads, dim = cfg.num_attention_heads, cfg.head_dim
        # inside "attn" the work is named once more, by part
        # (profiling/scope_map.py PARTS); names only
        with jax.named_scope("attn"):
            with jax.named_scope("attn_qkv"):
                qkv = u @ p["qkv_w"]
            if len(table) == 3:
                # the split, the head transpose and the rotation in one
                # pass over qkv (ops/rotary.py); `table` its lane tables
                with jax.named_scope("attn_rotary"):
                    q, k, v = rotate_qkv(qkv, *table, heads, heads)
            else:
                with jax.named_scope("attn_layout"):
                    q, k, v = (t.reshape(batch, seq, heads, dim).transpose(
                        0, 2, 1, 3) for t in jnp.split(qkv, 3, axis=-1))
                with jax.named_scope("attn_rotary"):
                    q, k = apply_rotary(q, table), apply_rotary(k, table)
            with jax.named_scope("attn_core"):
                a = flash_attention(q, k, v, causal=True,
                                    sm_scale=1.0 / math.sqrt(dim))
            with jax.named_scope("attn_layout"):
                a = a.transpose(0, 2, 1, 3).reshape(batch, seq, heads * dim)
            with jax.named_scope("attn_out"):
                return a @ p["out_w"]

    def _layer(self, p, x, table):
        eps = self.config.rms_norm_eps
        with jax.named_scope("layer"):
            a = x + rms_norm(self._attention(
                p["attn"], rms_norm(x, p["ln1"], eps), table), p["ln2"], eps)
            return a + rms_norm(gated_ffn(
                p["ffn"], rms_norm(a, p["ln3"], eps)), p["ln4"], eps)

    # -- the stack ------------------------------------------------------ #
    def stack_plan(self, seq):
        """The M_STACK_* fields of this stack on ``seq`` positions."""
        cfg = self.config
        block = self.rotary_plan(seq)
        return {
            R.M_STACK_LAYERS: tuple(
                (i, FULL, 0) for i in range(cfg.num_hidden_layers)),
            R.M_STACK_PASSES: (cfg.total_ut_steps,
                               cfg.total_ut_steps * cfg.num_hidden_layers),
            R.M_STACK_ROTARY: ((FULL, "kernel", *block) if block
                               else (FULL, "xla"),)}

    def hidden_states(self, params, input_ids):
        """input_ids [B, S] -> ``h_t`` of every pass, [T, B, S, hidden]
        (each after the final norm)."""
        cfg = self.config
        with jax.named_scope("embed"):
            h = params["wte"].astype(cfg.dtype)[input_ids]
        seq = input_ids.shape[1]
        table = self._rotary_table(seq)

        def body(carry, p):
            return self._layer(p, carry, table), None

        plan = self.stack_plan(seq)
        budget = self._remat_budget
        if cfg.activation_checkpointing:
            body = checkpoint_layers(
                [(body, params["layers"])], budget, h, cfg.vocab_size, plan,
                passes=cfg.total_ut_steps)(body)
        if ((budget is None or budget.bytes_limit is None)
                and plan != self._stack_plan_logged):
            self._stack_plan_logged = plan
            log_dist(stack_plan_line(plan), ranks=[0])

        def after_pass(h):
            with jax.named_scope(EXIT), jax.named_scope("head"):
                h = rms_norm(h, params["ln_f"], cfg.rms_norm_eps)
            return h, h

        _, passes = run_layer_recurrence(
            body, h, params["layers"], cfg.total_ut_steps,
            cfg.num_hidden_layers > 1, after_pass)
        return passes

    @staticmethod
    def exit_probabilities(gate, h):
        """The gate's exit distribution p f32 [T, N] from what it reads,
        ``h`` [T - 1, N, hidden] (the passes' outputs but the last) and
        its parameters: float32 on the vector unit, no product's
        precision to set."""
        logits = jnp.sum(
            h.astype(jnp.float32) * gate["w"].astype(jnp.float32),
            axis=-1) + gate["b"].astype(jnp.float32)
        return exit_distribution(logits)

    def exit_terms(self, params, input_ids, labels=None, with_inputs=False):
        """(l f32 [T, N] the passes' cross-entropy of every token, p f32
        [T, N] the exit distribution, valid f32 [N] the tokens that have
        a target).  ``input_ids[:, 1:]`` are the targets where
        ``labels`` is None, and a row's last position has none.  With
        ``with_inputs`` also what the gate and the head read, ``h_t``
        [T, N, hidden]."""
        cfg = self.config
        passes, hid = cfg.total_ut_steps, cfg.hidden_size
        h = self.hidden_states(params, input_ids).reshape(passes, -1, hid)
        if labels is None:
            seq = input_ids.shape[1]
            labels = jnp.where(jnp.arange(seq) < seq - 1,
                               jnp.roll(input_ids, -1, axis=1), IGNORE)
        labels = labels.reshape(-1).astype(jnp.int32)
        tokens = labels.shape[0]
        with jax.named_scope(EXIT), jax.named_scope("head"):
            p = self.exit_probabilities(params["gate"], h[:-1])
            losses = fused_linear_cross_entropy_per_token(
                h.reshape(passes * tokens, hid),
                params["head"].astype(h.dtype), jnp.tile(labels, passes),
                even_chunk(cfg.vocab_size, passes * tokens), IGNORE)
        return (losses.reshape(passes, tokens), p,
                (labels != IGNORE).astype(jnp.float32)) + (h,) * with_inputs

    def _objective(self, params, input_ids, labels=None):
        """(L, the counters of ``aux_counters``)."""
        cfg = self.config
        passes = cfg.total_ut_steps
        losses, p, valid = self.exit_terms(params, input_ids, labels)
        with jax.named_scope(EXIT), jax.named_scope("head"):
            count = jnp.maximum(valid.sum(), 1.0)

            def mean(per_token):
                return jnp.sum(per_token * valid, axis=-1) / count

            task = mean(jnp.sum(p * losses, axis=0))
            kl = mean(kl_to_uniform(p))
            mass = mean(p)
            counters = {
                R.M_TASK_LOSS: task, R.M_EXIT_KL: kl,
                R.M_EXIT_STEP_MEAN: jnp.sum(
                    mass * jnp.arange(1, passes + 1, dtype=jnp.float32)),
                **{R.M_EXIT_MASS + str(t + 1): mass[t]
                   for t in range(passes)}}
            return task + cfg.exit_kl_weight * kl, counters

    def loss(self, params, rng, input_ids, labels=None):
        """The objective (see the module's text).  `rng` is unused (no
        dropout)."""
        return self._objective(params, input_ids, labels)[0]

    def logits(self, params, input_ids):
        """The LAST pass's logits, f32 [B, S, vocab]."""
        h = self.hidden_states(params, input_ids)[-1]
        with jax.named_scope(EXIT), jax.named_scope("head"):
            return (h @ params["head"].astype(h.dtype)).astype(jnp.float32)

    def __call__(self, params, rng, input_ids, labels=None):
        """(L, the exit gate's counters): the engine differentiates and
        reports the first and sums the scalars of the second
        (``aux_counters``)."""
        return self._objective(params, input_ids, labels)
