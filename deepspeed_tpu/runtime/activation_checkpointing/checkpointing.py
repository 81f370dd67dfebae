"""Activation checkpointing (rematerialization).

Reference: deepspeed/runtime/activation_checkpointing/checkpointing.py —
CheckpointFunction:482 (forward :493 / recompute-backward :608), activation
partitioning across model-parallel ranks (partition_activations:364 +
gather_partitioned_activations:256), CPU checkpointing (:469), RNG forking
(CudaRNGStatesTracker:122, model_parallel_cuda_manual_seed:198), configure
(:804); config schema runtime/activation_checkpointing/config.py:103.

TPU-native mapping — the four reference memory knobs become jax.checkpoint
policies instead of hand-managed tensor stashes:
  * plain checkpointing        -> jax.checkpoint(fn): recompute what does
                                  not fit.  The scanned layer of the model
                                  families (checkpoint_layer) keeps the
                                  named residuals a reckoned byte budget
                                  admits (RematBudget: the device's memory
                                  limit, less the engine's own state, less
                                  the working set of the traced shapes):
                                  the flash kernel's out / lse / packed
                                  mask, so the kernel runs once a layer,
                                  then a gated FFN's first product, so
                                  that matmul runs once too.
                                  With no budget (no engine, a backend that
                                  reports no memory limit, nothing fits) it
                                  keeps the layer's input alone and
                                  recomputes everything
  * partition_activations      -> saved residuals stay sharded over the
                                  "model" axis: the policy saves only
                                  outputs already annotated device-local,
                                  and GSPMD keeps them partitioned — no
                                  manual scatter/gather pair needed
  * cpu_checkpointing          -> policy offloads saveables to pinned host
                                  memory (save_and_offload_only_these_names /
                                  offload_dot_* policies)
  * contiguous_checkpointing   -> XLA's allocator already packs remat
                                  buffers; accepted and ignored (logged)
  * RNG fork across MP ranks   -> fold the mesh axis_index into the dropout
                                  key (model_parallel_rng), the counter-based
                                  analog of CudaRNGStatesTracker
"""

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ...analysis.jaxpr_walk import sub_jaxprs
from ...monitor import record as R
from ...ops.flash_attention import RESIDUAL_NAME as FLASH_RESIDUALS_NAME
from ...ops.hyper_connection import INPUT_NAME as HC_INPUT_NAME
from ...ops.hyper_connection import MIX_NAME as HC_MIX_NAME
from ...utils.logging import log_dist
from ...parallel.mesh import MODEL_AXIS

_CONFIG = {
    "partition_activations": False,
    "contiguous_memory_optimization": False,
    "cpu_checkpointing": False,
    "number_checkpoints": None,
    "synchronize_checkpoint_boundary": False,
    "profile": False,
    "configured": False,
}


def configure(mpu_=None, deepspeed_config=None,
              partition_activations: Optional[bool] = None,
              contiguous_checkpointing: Optional[bool] = None,
              num_checkpoints: Optional[int] = None,
              checkpoint_in_cpu: Optional[bool] = None,
              synchronize: Optional[bool] = None,
              profile: Optional[bool] = None) -> None:
    """Reference: checkpointing.py:804 configure().  Accepts either explicit
    flags or a DeepSpeedConfig with an activation_checkpointing section."""
    cfg = None
    if deepspeed_config is not None:
        cfg = getattr(deepspeed_config, "activation_checkpointing_config",
                      None) or (deepspeed_config.get(
                          "activation_checkpointing")
                          if isinstance(deepspeed_config, dict) else None)
    if cfg is not None and not isinstance(cfg, dict):
        import dataclasses
        if dataclasses.is_dataclass(cfg):
            cfg = dataclasses.asdict(cfg)
        else:
            cfg = {k: getattr(cfg, k) for k in dir(cfg)
                   if not k.startswith("_") and not callable(
                       getattr(cfg, k))}
    if isinstance(cfg, dict):
        _CONFIG["partition_activations"] = bool(
            cfg.get("partition_activations", False))
        _CONFIG["contiguous_memory_optimization"] = bool(
            cfg.get("contiguous_memory_optimization", False))
        _CONFIG["cpu_checkpointing"] = bool(
            cfg.get("cpu_checkpointing", False))
        _CONFIG["number_checkpoints"] = cfg.get("number_checkpoints")
        _CONFIG["profile"] = bool(cfg.get("profile", False))
    for key, val in (("partition_activations", partition_activations),
                     ("contiguous_memory_optimization",
                      contiguous_checkpointing),
                     ("number_checkpoints", num_checkpoints),
                     ("cpu_checkpointing", checkpoint_in_cpu),
                     ("synchronize_checkpoint_boundary", synchronize),
                     ("profile", profile)):
        if val is not None:
            _CONFIG[key] = val
    if _CONFIG["contiguous_memory_optimization"]:
        log_dist("activation checkpointing: contiguous_memory_optimization "
                 "is implicit under XLA's arena allocator", ranks=[0])
    _CONFIG["configured"] = True


def is_configured() -> bool:
    return _CONFIG["configured"]


def reset() -> None:
    for k in _CONFIG:
        _CONFIG[k] = False if isinstance(_CONFIG[k], bool) else None
    _CONFIG["configured"] = False


def get_partition_policy():
    """The jax.checkpoint policy implied by the configured knobs."""
    if _CONFIG["cpu_checkpointing"]:
        # save matmul outputs, parked in pinned host memory (the reference's
        # checkpoint_in_cpu path :469)
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
    if _CONFIG["partition_activations"]:
        # save only matmul outputs (they carry the model-axis sharding, so
        # the saved residuals stay partitioned across MP ranks)
        return jax.checkpoint_policies.dots_saveable
    return jax.checkpoint_policies.nothing_saveable


# What a checkpointed layer may keep, in the order a budget admits it.
# What each name costs and buys on the v5e (PERF.md section 6, PR 33 and
# PR 46):
# 1. The flash kernel's residuals, 20 ms a GB: 15.3 ms off GPT-2 large's
#    267 ms step at B=4, S=1,024 for 0.77 GB, the step as steady as it
#    was.
# 2. A gated FFN's first product, gate and up together before the split
#    (models/laguna.py ``gated_ffn``): a kept byte spares ``contraction
#    width`` FLOPs of the recomputation pass.  Contracting over 2,560 in
#    an unrolled stack (Phi-4-mini-flash, six products of 8,192 x
#    20,480), 13.8 ms a GB: 27.7 ms off a 478.1 ms step for 2.01 GB, the
#    products plain buffers that stay alive.  Over 2,048 in a scanned
#    stack (Ouro, 32 products of 4,096 x 11,264), 7.1 ms a GB: 20.9 ms
#    off 561.3 for 2.95 GB; 33.9 ms of recomputation spared, 9.2 paid
#    reading each product back out of its [8, ...] stack (a slice of its
#    own at 640 GB/s) and 4.2 filling the stacks before the loop; the
#    forward matmul writes its slice from its own fusion.  Whole and not
#    ``up * silu(gate)``: half the bytes would slip under the 505 MB that
#    Laguna-XS.2's plan has left and move a program that must not move.
# GPT-2's MLP is NOT offered the name.  Contracting over 1,280, its
# pre-activation's recomputation costs 7.3 ms a GB and the scan's write
# of the kept value into its [36, ...] stack cost 7.1: 4.4 ms for 1.51 GB
# at B=4, S=1,024 (the attention projection's 0.38 GB bought 1.7, the QKV
# output's 1.13 cost 2.2), with one long step in a run in three (PR 33).
# ROADMAP S2b has what is left of the matmul outputs.
# 3. (ops/indexed_attention.py) the alignment term's value and its
#    gradients, then the restricted attention's (out, lse): each spares
#    the backward pass a second run of its kernel.
# 4. (ops/hyper_connection.py) a hyper-connection's sublayer input ``u``
#    = ``H_pre X``, one width a token: last, at the flash residuals'
#    bytes it spares the recomputation pass one read of the streams.
FFN_PRODUCT_NAME = "ffn_gate_up"
RESIDUAL_ORDER = (FLASH_RESIDUALS_NAME, FFN_PRODUCT_NAME, "dsa_align",
                  "dsa_residuals", HC_INPUT_NAME)
# Names kept wherever a layer offers them, with a budget, with none that
# fits and with none at all (the CPU, a streamed ZeRO-3, a device that
# reports no limit): a few integers a token, and what a recomputation
# could not be trusted to find again (moe/dropless.py: a top-k recomputed
# in other fusions may flip a near tie, and the backward pass must
# differentiate the forward's picks; ops/indexed_attention.py: the packed
# keep-set of a learned selection, a bit a pair, for the same reason);
# and what costs as little and spares a pass over the whole carry
# (ops/hyper_connection.py: the sums of a sublayer's projection to its
# mixes and the streams' mean square, 25 float32 a token at 4 streams,
# without which the recomputation pass reads every stream twice more for
# the norm and the projection).  A
# body that offers none of them lowers to what ``jax.checkpoint(body)``
# lowers to (tests/unit/test_remat_policy.py).
ALWAYS_KEPT = ("routing_picks", "dsa_keep", HC_MIX_NAME)
_KEEP_ALWAYS = functools.partial(
    jax.checkpoint,
    policy=jax.checkpoint_policies.save_only_these_names(*ALWAYS_KEPT))

# The allowance for what a device holds in a step beside the engine's
# state and the saved residuals, in three terms (working_set_bytes).
# LAYER_WIDTHS: one layer's forward and backward pass, in activations of
# the model's width a token, beside the layer-input carry every layer
# keeps.  The v5e compiler's temporaries for GPT-2 large's whole-layer-
# recomputation grad program, less the compute-dtype copy of the weights,
# come to 308 to 370 KB a token at 4,096 to 16,384 tokens (compiled ahead
# of time, PERF.md section 6, PR 33); 36 carries, 32 more widths and the
# head's fp32 logits make 375.  MARGIN_BYTES: what the allocator held on
# the chip that no program of the step asked for (0.42 GB in the
# benchmark's cells: what ran before the engine was built).
LAYER_WIDTHS = 32
MARGIN_BYTES = 420_000_000


def working_set_bytes(tokens: int, width: int, num_layers: int,
                      head_width: int, itemsize: int,
                      cast_bytes: int = 0, streams: int = 1) -> int:
    """Bytes a device holds in a step beside the engine's state and the
    saved residuals: ``cast_bytes`` (the compute-dtype copy of the
    weights where the grad program casts it; 0 where the engine keeps
    the copy, which is then among its state), and for each of the
    device's ``tokens`` a layer-input carry
    a layer, LAYER_WIDTHS more activations of ``width`` and a row of fp32
    logits ``head_width`` wide; MARGIN_BYTES on top.  It grows with the
    batch, the width and the depth as the grad program does, so a job
    larger than the measured ones is refused its residuals before it is
    refused its memory.  GPT-2 large at 4,096 tokens: 3,505,530,112, where
    the chip's programs reserved 2.4 to 2.9 GB beside their residuals and
    0.42 GB was held beside them.  ``streams``: the residual streams a
    carry holds (models/xing4.py), each ``width`` wide: the carries cost
    that many widths a token, the layer's own pass LAYER_WIDTHS of one
    (its sublayers run on one stream's width)."""
    return (cast_bytes + MARGIN_BYTES + tokens * (
        (num_layers * streams + LAYER_WIDTHS) * width * itemsize
        + 4 * head_width))


def _tiled_bytes(shape, dtype) -> int:
    """Bytes of one saved array as the TPU compiler lays it out in HBM:
    the minor dimension padded to 128 lanes.  Read off the v5e compiler's
    buffer assignment for GPT-2 large's grad program (PR 33): the scan's
    stack of the flash output, bf16[36,4,20,1024,64] tiled T(8,128)(2,1),
    takes 754,974,720 B, twice its elements' bytes; the log-sum-exp
    (f32[36,4,20,1024], 1,024 minor) takes its elements' bytes, the
    compiler ordering the other dimensions so that no tile of 8 or 16
    rows is padded."""
    dims = list(shape)
    if dims:
        dims[-1] = -(-dims[-1] // 128) * 128
    return math.prod(dims) * jnp.dtype(dtype).itemsize


def device_bytes_limit(device) -> Optional[int]:
    """The backend's memory limit for ``device``; None where it reports
    none (the CPU)."""
    return (device.memory_stats() or {}).get("bytes_limit")


class RematBudget:
    """Bytes a device may spend on a layer scan's saved residuals, from
    steady quantities only: the backend's ``bytes_limit``, less
    ``state_bytes`` (what the engine itself placed there: parameters,
    optimizer state, gradient buffers, the compute-dtype copy of the
    weights where the apply program writes it; summed from its pytrees),
    less the working set of the traced program (``working_set_bytes`` of
    its shapes and of ``cast_bytes``, that copy where the grad program
    casts it; tests hand a fixed ``working_set`` in).  Never the
    allocator's ``bytes_in_use``: it holds whatever ran before and would
    give two runs of one job two programs.  ``bytes_limit`` None (a
    backend that reports no limit) means no budget.  ``batch_shards``:
    the ways the batch is split over devices, to turn a traced global
    shape into bytes a device."""

    def __init__(self, bytes_limit: Optional[int], state_bytes: int = 0,
                 batch_shards: int = 1, cast_bytes: int = 0,
                 working_set: Optional[int] = None):
        self.bytes_limit = bytes_limit
        self.state_bytes = int(state_bytes)
        self.batch_shards = int(batch_shards)
        self.cast_bytes = int(cast_bytes)
        self.working_set = working_set
        self.plan: Optional[Dict[str, Any]] = None  # the last one made
        self._pending: Optional[Dict[str, Any]] = None  # not yet taken

    def bytes(self, working_set: int) -> int:
        return max(0, int(self.bytes_limit) - self.state_bytes - working_set)

    def note_plan(self, plan: Dict[str, Any]) -> None:
        """Log a plan and queue it for the monitor, once per distinct
        plan (a model is traced several times for one program)."""
        if plan == self.plan:
            return
        self.plan = self._pending = plan
        log_dist(
            "activation checkpointing: layer scan keeps "
            f"{list(plan[R.M_REMAT_KEPT]) or 'the layer input only'} of "
            f"{list(plan[R.M_REMAT_OFFERED])}: "
            f"{plan[R.M_REMAT_KEPT_BYTES_PER_LAYER]:,} B a layer, "
            f"{plan[R.M_REMAT_KEPT_BYTES]:,} B over "
            f"{_layers_phrase(plan)}{_by_name_phrase(plan)}, under a budget "
            f"of {plan[R.M_REMAT_BUDGET_BYTES]:,} B (limit "
            f"{plan[R.M_REMAT_BYTES_LIMIT]:,} - state "
            f"{plan[R.M_REMAT_STATE_BYTES]:,} - working set "
            f"{plan[R.M_REMAT_WORKING_SET_BYTES]:,}"
            + (f", {plan[R.M_REMAT_SIDE_CARRY_BYTES]:,} B a layer of it a "
               "side carry" if R.M_REMAT_SIDE_CARRY_BYTES in plan else "")
            + ")", ranks=[0])
        if R.M_STACK_LAYERS in plan:
            log_dist(stack_plan_line(plan), ranks=[0])

    def take_plan(self) -> Optional[Dict[str, Any]]:
        """The plan, if the monitor has not had it yet."""
        plan, self._pending = self._pending, None
        return plan


def _layers_phrase(plan: Dict[str, Any]) -> str:
    """``N layers``, or where the stack runs several times on the same
    weights its applications and what they are made of."""
    layers, passes = plan[R.M_REMAT_LAYERS], plan.get(R.M_REMAT_PASSES, 1)
    if passes == 1:
        return f"{layers} layers"
    return (f"{layers} layer applications ({layers // passes} layers x "
            f"{passes} passes)")


def _by_name_phrase(plan: Dict[str, Any]) -> str:
    """`` (name N B, name M B)`` where several names are kept."""
    by_name = plan.get(R.M_REMAT_KEPT_BYTES_BY_NAME)
    if not by_name:
        return ""
    return " (" + ", ".join(f"{n} {b:,} B" for n, b in by_name) + ")"


def offered_residuals(body: Callable, *args,
                      batch_shards: int = 1) -> Dict[str, int]:
    """``{checkpoint_name: bytes a device}`` of one call of ``body``, off
    its jaxpr: every named value's tiled size, the names inside a
    ``shard_map`` at their local shape, the others (traced at the global
    batch) divided by ``batch_shards``."""
    offered: Dict[str, int] = {}

    def walk(jaxpr, local):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name":
                aval = eqn.outvars[0].aval
                size = _tiled_bytes(aval.shape, aval.dtype)
                offered[eqn.params["name"]] = offered.get(
                    eqn.params["name"], 0) + (
                    size if local else size // batch_shards)
            for sub in sub_jaxprs(eqn):
                walk(sub.jaxpr, local or eqn.primitive.name == "shard_map")

    walk(jax.make_jaxpr(body)(*args).jaxpr, False)
    return offered


def saved_residual_names(offered: Dict[str, int], num_layers: int,
                         budget_bytes: int) -> Tuple[str, ...]:
    """The longest prefix of RESIDUAL_ORDER (names not offered skipped)
    whose bytes over ``num_layers`` layers fit ``budget_bytes``."""
    kept, total = [], 0
    for name in RESIDUAL_ORDER:
        if name not in offered:
            continue
        total += offered[name] * num_layers
        if total > budget_bytes:
            break
        kept.append(name)
    return tuple(kept)


def checkpoint_layer(body: Callable, budget: Optional[RematBudget],
                     carry, stacked_xs, head_width: int) -> Callable:
    """``jax.checkpoint(body)`` for the layer ``body(carry, xs)`` that
    ``run_layer_stack`` runs over the leading axis of ``stacked_xs``,
    keeping the named residuals ``budget`` admits beside the working set
    of a step on ``carry`` ([batch, sequence, width]) with a head
    ``head_width`` wide.  No budget, or none that fits:
    ``jax.checkpoint(body)`` as it always was, the layer's input kept and
    the rest recomputed."""
    return checkpoint_layers([(body, stacked_xs)], budget, carry,
                             head_width)(body)


def stack_plan_line(plan: Dict[str, Any]) -> str:
    """The log line of a stack of unlike layers (the M_STACK_* fields)."""
    line = "layer stack: " + ", ".join(
        f"{i}:{kind}" + (f"(window {w})" if w else "")
        for i, kind, w in plan[R.M_STACK_LAYERS])
    if R.M_STACK_SCAN_CHUNK in plan:
        line += (
            f"; selective scan in chunks of {plan[R.M_STACK_SCAN_CHUNK]}, "
            f"{plan[R.M_STACK_SCAN_ENTRY_BYTES]:,} B of entry states a call; "
            "kept across layers: " + ", ".join(
                f"{name} {size:,} B"
                for name, size in plan[R.M_STACK_CROSS_LAYER_KEPT]))
    if R.M_STACK_EXPERTS_HELD in plan:
        first, count, of = plan[R.M_STACK_EXPERTS_HELD]
        line += (f"; routed experts {first} to {first + count - 1} of {of} "
                 "held here")
    if R.M_STACK_LATENT in plan:
        q, kv, nope, rope, v, heads = plan[R.M_STACK_LATENT]
        line += (f"; latent attention: queries through {q}, keys and "
                 f"values through {kv}, {heads} heads of {nope} + {rope} "
                 f"rotated (one rotated key a position) and values of {v}")
    if R.M_STACK_STREAMS in plan:
        streams, rounds, low, high = plan[R.M_STACK_STREAMS]
        line += (f"; {streams} residual streams mixed by hyper-connections "
                 f"({rounds} Sinkhorn rounds from logits clamped to "
                 f"[{low:g}, {high:g}])")
    if R.M_STACK_CCA in plan:
        heads, kv, size, taps0, taps1, wide = plan[R.M_STACK_CCA]
        line += (f"; compressed convolutional attention: {heads} query "
                 f"heads on {kv} key/value heads of {size}, mixed by a "
                 f"depthwise conv of {taps0} taps and a conv within a head "
                 f"of {taps1}; the router carries a state of {wide} from "
                 "layer to layer")
    if R.M_STACK_MTP in plan:
        modules, weight = plan[R.M_STACK_MTP]
        line += (f"; {modules} multi-token-prediction module(s), loss "
                 f"weight {weight}")
    if R.M_STACK_PASSES in plan:
        passes, applications = plan[R.M_STACK_PASSES]
        line += (f"; run {passes} times on the same weights: "
                 f"{applications} layer applications a step")
    if R.M_STACK_INDEXER in plan:
        heads, size, topk, form = plan[R.M_STACK_INDEXER]
        line += (f"; indexer: {heads} heads of {size} on one key head, "
                 f"{topk} keys kept a query; index, select, core and "
                 f"align: {form}")
    if R.M_STACK_ROTARY in plan:
        line += "; rotary: " + ", ".join(
            f"{kind} {path}" + (
                f" (blocks of {block[0]} positions x {block[1]} heads)"
                if block else "")
            for kind, path, *block in plan[R.M_STACK_ROTARY])
    if R.M_STACK_SSD in plan:
        form, chunk, entry_bytes, runs, mode, groups, conv = plan[
            R.M_STACK_SSD]
        line += (f"; runs of like layers: {runs}, {mode}; state-space "
                 f"duality scan: {form} in chunks of {chunk}"
                 + (f" on {groups} groups of B and C" if groups > 1 else "")
                 + f", {entry_bytes:,} B of chunk-entry states a layer; "
                 f"conv: {conv}")
    return line


def checkpoint_layers(groups, budget: Optional[RematBudget], carry,
                      head_width: int,
                      stack_plan: Optional[Dict[str, Any]] = None,
                      extra_working_set: int = 0, passes: int = 1,
                      streams: int = 1):
    """``checkpoint_layer`` for a model whose stack is several scanned
    groups of unlike layers: ``groups`` is a list of ``(body,
    stacked_xs)``, every body taking the same ``carry``; one budget is
    spent over all of them (a name is kept in every group or in none) and
    one plan is noted, ``stack_plan`` (the model's M_STACK_* fields)
    riding on it; ``extra_working_set``: bytes a layer of this model
    holds that ``working_set_bytes`` does not know of (a sparse layer's
    rows); ``passes``: how many times the whole stack runs on the same
    weights (models/layer_stack.py ``run_layer_recurrence``): every
    APPLICATION of a layer keeps its carry and its residuals, so offered
    and kept bytes, the working set's carries and M_REMAT_LAYERS count
    applications, and M_REMAT_PASSES rides on the plan (1: today's plan,
    the field left out).  Returns the wrapper for every body
    of the stack.  ``carry`` may be a tuple: its first entry the stream(s)
    and the others SIDE carries of widths of their own, handed from layer
    to layer and kept a layer like the stream (models/zaya.py: the
    router's state, float32 [tokens, 256]); they are charged to the
    working set at their tiled bytes a layer, and
    M_REMAT_SIDE_CARRY_BYTES rides on the plan (no side carry: today's
    plan, the field left out).  What
    a wrapped body closes over (another layer's output that this one
    reads; the plan may be made on a stand-in of its shape) is an input
    of the checkpointed body: kept, never recomputed."""
    if budget is None or budget.bytes_limit is None:
        return _KEEP_ALWAYS
    shape = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), carry)
    carry, *sides = carry if isinstance(carry, tuple) else (carry,)
    side_bytes = sum(_tiled_bytes(a.shape, a.dtype)
                     for a in sides) // budget.batch_shards
    steps = [passes * jax.tree.leaves(xs)[0].shape[0] for _, xs in groups]
    offers = [offered_residuals(
        body, shape, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), xs),
        batch_shards=budget.batch_shards) for body, xs in groups]
    # bytes of a name over the whole stack, as if it were one layer's
    offered = {name: sum(o.get(name, 0) * n for o, n in zip(offers, steps))
               for o in offers for name in o}
    num_layers = sum(steps)
    working_set = budget.working_set
    if working_set is None:
        working_set = working_set_bytes(
            math.prod(carry.shape[:-1]) // streams // budget.batch_shards,
            carry.shape[-1], num_layers, head_width, carry.dtype.itemsize,
            budget.cast_bytes, streams)
    working_set += extra_working_set + num_layers * side_bytes
    kept = saved_residual_names(offered, 1, budget.bytes(working_set))
    by_name = tuple((name, offered[name]) for name in kept)
    budget.note_plan({
        R.M_REMAT_OFFERED: tuple(n for n in RESIDUAL_ORDER if n in offered),
        R.M_REMAT_KEPT: kept,
        R.M_REMAT_KEPT_BYTES_PER_LAYER: max(
            sum(o.get(name, 0) for name in kept) for o in offers),
        R.M_REMAT_KEPT_BYTES: sum(size for _, size in by_name),
        **({R.M_REMAT_KEPT_BYTES_BY_NAME: by_name} if len(kept) > 1 else {}),
        R.M_REMAT_LAYERS: num_layers,
        R.M_REMAT_BUDGET_BYTES: budget.bytes(working_set),
        R.M_REMAT_BYTES_LIMIT: int(budget.bytes_limit),
        R.M_REMAT_STATE_BYTES: budget.state_bytes,
        R.M_REMAT_WORKING_SET_BYTES: working_set,
        **({R.M_REMAT_PASSES: passes} if passes != 1 else {}),
        **({R.M_REMAT_SIDE_CARRY_BYTES: side_bytes} if sides else {}),
        **(stack_plan or {})})
    if not kept:
        return _KEEP_ALWAYS
    return functools.partial(
        jax.checkpoint, policy=jax.checkpoint_policies.save_only_these_names(
            *kept, *ALWAYS_KEPT))


def checkpoint(function: Callable, *args) -> Any:
    """Reference CheckpointFunction.apply: run `function` now, recompute in
    backward under the configured policy."""
    return jax.checkpoint(function, policy=get_partition_policy())(*args)


class CheckpointFunction:
    """API-parity shim (reference: checkpointing.py:482)."""

    @staticmethod
    def apply(function, *args):
        return checkpoint(function, *args)


def model_parallel_rng(rng, axis_name: str = MODEL_AXIS):
    """Per-MP-rank dropout key — the CudaRNGStatesTracker analog
    (reference :122 / model_parallel_cuda_manual_seed :198): fold the mesh
    position into the counter-based key inside shard_map/jit."""
    try:
        idx = lax.axis_index(axis_name)
    except NameError:
        return rng
    return jax.random.fold_in(rng, idx)
