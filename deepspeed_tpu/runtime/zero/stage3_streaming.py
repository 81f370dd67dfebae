"""ZeRO-3 explicit parameter streaming — gather-at-use with live-set control.

Reference semantics: stage3.py's PartitionedParameterCoordinator (:294) keeps
at most ``stage3_max_live_parameters`` gathered at once, prefetches the next
``stage3_prefetch_bucket_size`` elements ahead of use (PrefetchCoordinator
:169), and releases each submodule's params after use (:460).  The reference
implements this with per-module torch hooks and hand-scheduled NCCL
all-gathers.

TPU recasting: for stacked-layer models (leaves ``[L, ...]`` scanned with
``lax.scan``), the live-set control is a *program structure*, not a hook
protocol.  The layer stack runs inside a partial-manual ``jax.shard_map``
over the ZeRO ("data","expert") axes:

  - each scan step ``lax.all_gather``\\ s exactly one layer group's shards
    (tiled) — the gather-at-use of stage3.py:522 ``_all_gather``;
  - when the scan step ends, XLA frees the gathered buffer — the release of
    stage3.py:460 ``release_sub_module``;
  - the group size is chosen so ``layers_per_step × params_per_layer ≤
    stage3_max_live_parameters`` — max-live honored by construction;
  - with prefetch enabled (``stage3_prefetch_bucket_size`` covering a
    group) the scan carries a double buffer: the gather for group ``i+1``
    is ISSUED into the scan carry before group ``i``'s compute and
    consumed one iteration later, so the gather's issue→first-consume
    distance spans a full group of MXU work — overlap as a
    *program-graph property* (T3, arXiv:2401.16677) that the Schedule
    Auditor verifies statically,
    rather than a scheduling opportunity XLA may or may not take.  The
    backward re-gather sweep is double-buffered the same way.  This is
    the role of PrefetchCoordinator's trace-based lookahead, without
    needing a trace (the scan order IS the trace).  A bucket under one
    layer group (0) gathers each group at use instead;
  - the backward of a tiled all-gather over the ZeRO axes is a
    reduce-scatter with fp32 accumulation regardless of compute dtype
    (stage3.py:1908 grad partitioning, tightened).  Its dense form
    (``comm/low_bandwidth.dense_psum_scatter``) leaves as ``W - 1``
    shifted collective-permutes of the owners' chunks IN THE GRADIENT'S
    OWN DTYPE, widened and summed in source order on arrival: the TPU's
    compiler runs a reduce-scatter synchronously with nothing beside it
    (95 ms of GPT-2 XL's 1,064 ms step on four chips) and permutes
    asynchronously.  Under the carried stream a group's stacks are
    scattered at the top of the NEXT group's backward and land under it
    (``_build_carried_stream``); gathering at use, the gather's own
    transpose (``_ag_bwd``) sends a group's inside the scan's transposed
    body.

Tensor-parallel ("model") and any other non-ZeRO axes stay *automatic*
(GSPMD) inside the region — explicit ZeRO streaming composes with
declarative TP.

Residuals: gathered layer groups are NEVER saved for the backward, or
the saved stack would be the full unsharded model and max_live would
bound nothing.  With prefetch that is structural — the hand-written
VJP's residuals are each layer's input activation carry plus the
sharded inputs, and the backward re-gathers (``_build_carried_stream``);
gathering at use, the ``zero3_gathered`` checkpoint-name policy (see
``gather_group``) does the same job through the remat machinery.
Tested by test_zero3_streaming.py::test_backward_regathers_instead_of_saving
and ::test_carried_residuals_are_per_layer_carries.
"""

import logging
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec

from ...ops.collective_matmul import fcm_all_gather, fcm_reduce_scatter
from ...parallel.mesh import MeshContext, ZERO_AXES
from ...monitor import trace as host_trace
from ...utils.logging import log_dist
from ..comm.low_bandwidth import (dense_psum_scatter, dense_scatter_wire,
                                  largest_divisor_at_most,
                                  low_bandwidth_all_gather,
                                  quantized_gather_saves_bytes,
                                  quantized_psum_scatter)
from .partition import (filter_spec_axes, resolve_hpz_axes,
                        zero_partition_spec)


@dataclass(frozen=True)
class StreamPlan:
    """How the layer stack is grouped and prefetched.

    ``prefetch`` is the structure applied: the double-buffered scan
    carry (gather for group i+1 issued under group i's compute, in both
    the forward and the backward re-gather sweep), or each group
    gathered at use.  ``forfeited`` records WHY a requested prefetch
    degraded to gathers at use (surfaced by the Schedule Auditor's
    overlap report and logged once at trace time)."""
    layers_per_step: int
    prefetch: bool
    num_layers: int
    params_per_layer: int
    forfeited: Optional[str] = None

    @property
    def live_parameters(self) -> int:
        """Worst-case simultaneously-gathered parameter count."""
        mult = 2 if self.prefetch else 1
        return mult * self.layers_per_step * self.params_per_layer


def plan_layer_streaming(num_layers: int, params_per_layer: int,
                         max_live_parameters: int,
                         prefetch_bucket_size: int) -> StreamPlan:
    """Consume the stage-3 knobs into a concrete (group, prefetch) plan.

    ``stage3_max_live_parameters`` bounds the gathered set (reference
    zero/config.py ``max_live_parameters``); ``stage3_prefetch_bucket_size``
    enables lookahead when it covers at least one more layer group: the
    gather for group i+1 then rides the scan carry, issue→first-consume
    spans a full group of MXU work, and the only constraint is >= 2
    groups (any divisor group count works).  Otherwise each group is
    gathered at use.
    """
    base_budget = max(1, int(max_live_parameters) // max(
        1, params_per_layer))
    # a bucket smaller than one layer group is the documented prefetch
    # OFF switch (no forfeit); a bucket that ASKS for prefetch which the
    # live-parameter budget then cannot honor is a loud forfeit below
    wants = int(prefetch_bucket_size) >= params_per_layer
    forfeited = None
    if wants and base_budget < 2:
        forfeited = (
            f"stage3_max_live_parameters holds {base_budget} layer(s) — "
            "a double buffer needs at least 2 (current + prefetched "
            "group)")
    elif wants:
        # live set holds current + prefetched group
        budget = base_budget // 2
        candidates = [g for g in range(1, budget + 1)
                      if num_layers % g == 0 and num_layers // g >= 2]
        if candidates:
            return StreamPlan(layers_per_step=max(candidates),
                              prefetch=True, num_layers=num_layers,
                              params_per_layer=params_per_layer)
        forfeited = (
            f"{num_layers} layer(s) cannot form >= 2 groups within "
            f"the double-buffer budget of {budget} group(s)")
    g = largest_divisor_at_most(num_layers, base_budget)
    return StreamPlan(layers_per_step=g, prefetch=False,
                      num_layers=num_layers,
                      params_per_layer=params_per_layer,
                      forfeited=forfeited)


def _jaxpr_has_pallas(jaxpr) -> bool:
    """Recursively walk a jaxpr (and every sub-jaxpr riding in eqn
    params) for pallas primitives."""
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            return True
        for v in eqn.params.values():
            for sub in jax.tree.leaves(
                    v, is_leaf=lambda x: hasattr(x, "jaxpr") or
                    hasattr(x, "eqns")):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns") and _jaxpr_has_pallas(inner):
                    return True
    return False


def _body_uses_pallas(body, init_carry, p_tree, p_leaves, extra_xs) -> bool:
    """Abstractly trace ONE layer application of the user body and report
    whether it contains a pallas_call (which the shard_map vma analysis
    cannot see through).  Tracing failures — e.g. a body that needs the
    live mesh context — return True so check_vma stays conservatively
    off."""
    try:
        layer0 = p_tree.unflatten(
            [jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype) for leaf in p_leaves])
        extras0 = jax.tree.map(
            lambda e: jax.ShapeDtypeStruct(e.shape[1:], e.dtype), extra_xs)
        carry0 = jax.tree.map(
            lambda c: jax.ShapeDtypeStruct(c.shape, c.dtype), init_carry)
        jaxpr = jax.make_jaxpr(
            lambda c, leaf, e: body(c, (leaf,) + tuple(e)))(
            carry0, layer0, extras0)
        return _jaxpr_has_pallas(jaxpr.jaxpr)
    except Exception:  # noqa: BLE001 — conservative on any trace failure
        return True


def _restrict_to_manual(spec: PartitionSpec, manual: frozenset
                        ) -> PartitionSpec:
    """Strip non-manual axes from a spec (shard_map in_specs may only name
    manual axes; auto axes ride along on the array sharding)."""
    return filter_spec_axes(spec, manual.__contains__)


def _gather_dims(spec: PartitionSpec, manual: frozenset):
    """[(dim, (axes...)), ...] — where tiled all-gathers must run."""
    out = []
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a in manual)
        if kept:
            out.append((dim, kept))
    return out


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _all_gather_f32grad(x, axes, dim):
    """Tiled all-gather whose transpose accumulates in float32.

    Forward: identical to ``lax.all_gather(tiled=True)`` — shards move at
    their native width (bf16 gathers cost bf16 bytes).  Backward:
    ``dense_psum_scatter``: the owners' chunks of the layer gradient
    travel as shifted permutes at the gradient's width and are summed in
    fp32 on arrival (the native collective, where a leaf keeps it, is
    promoted to fp32 BEFORE the ``psum_scatter`` and demoted after), so
    the cross-shard gradient reduction accumulates in fp32 regardless of
    compute dtype (the reference reduces fp16 grads natively,
    stage3.py:1908; fp32 accumulation strictly tightens that).  Either
    form keeps half-precision reduction collectives, on which XLA-CPU's
    AllReducePromotion pass hard-aborts ('Invalid binary instruction
    opcode copy'), out of the manual region — bf16 streaming runs
    identically on CPU and TPU."""
    return lax.all_gather(x, axes, axis=dim, tiled=True)


def _ag_fwd(x, axes, dim):
    return _all_gather_f32grad(x, axes, dim), None


def _ag_bwd(axes, dim, _, g):
    return (dense_psum_scatter(g, axes, dim),)


_all_gather_f32grad.defvjp(_ag_fwd, _ag_bwd)


def _index_tree(tree, i):
    """Dynamic per-group slice of a ``[steps, ...]``-stacked pytree."""
    return jax.tree.map(
        lambda leaf: lax.dynamic_index_in_dim(leaf, i, keepdims=False), tree)


def _layer_of(full, extras, j):
    """Layer ``j``'s leaves and extras out of one ``[g, ...]`` group."""
    return [leaf[j] for leaf in full], jax.tree.map(lambda e: e[j], extras)


def _body_closes_over_tracers(body) -> bool:
    """True when the user body (or a callable it closes over, two levels
    deep) captures live JAX tracers.  Neither structure differentiates
    such a body — shard_map cannot transpose captured tracers
    (NotImplementedError gathering at use), and the carried custom_vjp
    differentiates only its explicit inputs (UnexpectedTracerError) —
    both failures surface deep inside grad with no hint at the cause,
    so scan() detects the capture up front and logs the actionable
    diagnosis: thread those values through ``stacked_params`` /
    ``extra_xs``.  (Forward-only use still works: the captured value
    rides the region as a replicated const.)"""
    seen = set()

    def has_tracer(v):
        try:
            return any(isinstance(leaf, jax.core.Tracer)
                       for leaf in jax.tree.leaves(v))
        except Exception:  # noqa: BLE001 — exotic leaves: assume clean
            return False

    def check(fn, depth):
        if depth > 2 or not callable(fn) or id(fn) in seen:
            return False
        seen.add(id(fn))
        fn = getattr(fn, "__func__", fn)  # unwrap bound methods
        for cell in getattr(fn, "__closure__", None) or ():
            try:
                v = cell.cell_contents
            except ValueError:  # empty cell
                continue
            if isinstance(v, jax.core.Tracer) or has_tracer(v):
                return True
            if callable(v) and check(v, depth + 1):
                return True
        return False

    return check(body, 0)


def _build_carried_stream(steps: int, g: int, gather_group, run_layer,
                          scatter_grads):
    """Carried double-buffer executor with a hand-scheduled VJP.

    Program structure::

        forward:  full(0) = gather(group 0)                 # prologue
                  scan i = 0 .. S-2, carry (act, full(i)):
                      issue gather(group i+1)  -> next carry
                      act = compute(act, full(i))           # a FULL group
                                                            # of MXU slack
                  act = compute(act, full(S-1))             # epilogue
        backward: re-gather(S-1), issue re-gather(S-2)      # prologue
                  reverse scan i = S-2 .. 1, carry (cot, full(i)):
                      issue re-gather(group i-1) -> next carry
                      cot = vjp(compute)(cot) @ full(i)
                  cot = vjp(compute)(cot) @ full(0)         # epilogue

    Why a custom VJP instead of the ``zero3_gathered`` checkpoint-name
    policy alone: a gathered buffer riding a ``lax.scan`` carry is a
    *body input* of every step, and scan partial evaluation demands body
    inputs as stacked residuals — the name policy only prunes values
    produced INSIDE the rematerialized body, so the naive carried scan
    saves ``steps x group`` = the full unsharded model and defeats
    ``stage3_max_live_parameters`` outright (verified: the stacked
    ``[S, full]`` residual appears in the grad jaxpr).  Hand-writing the
    VJP extends the policy's intent across the carry: gathered buffers
    are dropped from residuals entirely and RE-GATHERED in the backward
    (the reference's backward re-fetch, stage3.py:546
    PreBackwardFunction) — and the re-gathers get their own carried
    double buffer, so the backward's wire hides under the backward's
    compute exactly like the forward's.

    The residuals saved are the INPUT activation carry of every LAYER —
    the forward scan's ``ys``, ``[S-1, g, ...]`` per carry leaf, and the
    epilogue group's ``[g, ...]``: ``num_layers`` carries of one
    batch-shard activation each — plus the sharded inputs.  That is what
    a plain
    ``lax.scan`` over ``jax.checkpoint(body)`` saves, so the streamed and
    the non-streamed engine paths hold the same thing.  The backward
    takes each layer's VJP from its own saved carry, so each layer's
    internals are recomputed ONCE: two layer forwards a step for every
    group size, whether or not ``body`` is itself checkpointed (a
    checkpointed body's ``jax.vjp`` has no live primal output and the
    rematerialization is its one recomputation; a plain body's
    ``jax.vjp`` runs it once and keeps one layer's internals at a
    time).  Peak gathered memory stays at ``2 x layers_per_step x
    params_per_layer``.

    Where the gradients leave.  ``scatter_grads(g_full) -> g_shards`` is
    the exact transpose of ``gather_group``'s wire (qwZ/qgZ aware) for a
    group's ``[g, ...]`` stacks.  It is issued ONE GROUP LATE: group
    ``i+1``'s stacks are scattered at the top of group ``i``'s backward
    and what they bring is held by ``_finish_rows``' barrier after that
    group's last layer, so a dense leaf's shifted permutes
    (``dense_psum_scatter``) have sixteen layers of matmuls to travel
    under.  Issued after its own group and held by nothing, every
    group's scatter sinks to the end of the program, nothing beside it
    (where the parent's synchronous reduce-scatters sat); spread over a
    whole backward they stall the prefetch's all-gathers, which share
    the links (PERF.md section 6, PR 59: four placements on the chip).
    Group 0's own scatter is the tail: only the embeddings' gradients
    are left to run beside it.

    ``steps`` must be >= 2 (a plan that prefetches guarantees it).
    ``gather_group(shards) -> full``, ``run_layer(act, layer_leaves,
    layer_extras) -> act`` for ONE of a group's ``g`` layers and
    ``scatter_grads`` come from the enclosing
    :meth:`Zero3StreamContext.scan` trace.
    """

    if steps < 2:
        raise ValueError(
            f"carried prefetch needs >= 2 layer groups, got {steps} — "
            "plan_layer_streaming should have forfeited the prefetch")

    # a list of leaves is a pytree: _index_tree slices shard groups too
    _group_shards = _index_tree

    def _set_row(stacked, leaves, j):
        """Layer ``j``'s leaves into ``[g, ...]`` stacks, written as the
        backward yields them (last layer first)."""
        if stacked is None:
            stacked = [jnp.zeros((g,) + leaf.shape, leaf.dtype)
                       for leaf in leaves]
        return [buf.at[j].set(leaf) for buf, leaf in zip(stacked, leaves)]

    def _finish_rows(g_c, held):
        """What ``held`` lists is computed before the next layer's
        backward (which needs ``g_c``) starts: this layer's rows, and
        after a group's last layer the shards the group before it sent
        for.  Left free, XLA fuses each weight-gradient matmul into its
        row update and sinks the chain to the group's end, every layer's
        recomputed activations live until then (+7 GB of temporaries
        for GPT-2 XL at g=16).  A barrier gives all it holds one
        varying-axes type, so under ``check_vma`` it holds the arrays of
        ``g_c``'s type only."""
        vma = jax.typeof(jax.tree.leaves(g_c)[0]).vma
        same = [jax.typeof(buf).vma == vma for buf in held]
        g_c, out = lax.optimization_barrier(
            (g_c, [buf for buf, h in zip(held, same) if h]))
        out = iter(out)
        return g_c, [next(out) if h else buf for buf, h in zip(held, same)]

    def _run_group(c, full, extras_i):
        """A gathered group's ``g`` layers.  Returns the output carry and
        the layers' INPUT carries, stacked ``[g, ...]``."""
        ins = []
        for j in range(g):
            ins.append(c)
            c = run_layer(c, *_layer_of(full, extras_i, j))
        return c, jax.tree.map(lambda *leaves: jnp.stack(leaves), *ins)

    def _forward(c0, params_g, extras_g):
        first = gather_group(_group_shards(params_g, 0))

        def fbody(carry, i):
            c, cur = carry
            # issue i+1's gather BEFORE group i's compute: the result is
            # consumed next iteration (carried), so its wire has the
            # whole group's MXU work as slack
            nxt = gather_group(_group_shards(params_g, i + 1))
            c, ins = _run_group(c, cur, _index_tree(extras_g, i))
            return (c, nxt), ins

        (c, last), c_ins = lax.scan(
            fbody, (c0, first), jnp.arange(steps - 1))
        c_fin, c_ins_last = _run_group(
            c, last, _index_tree(extras_g, steps - 1))
        return c_fin, (c_ins, c_ins_last)

    @jax.custom_vjp
    def carried(c0, params_g, extras_g):
        return _forward(c0, params_g, extras_g)[0]

    def carried_fwd(c0, params_g, extras_g):
        c_fin, saved = _forward(c0, params_g, extras_g)
        # residuals: every layer's input carry (the scan's ys for groups
        # 0 .. S-2, the epilogue group's own stack; c_ins[0, 0] IS c0) +
        # the SHARDED inputs — never a gathered buffer
        return c_fin, (saved, params_g, extras_g)

    def carried_bwd(res, g_out):
        (c_ins, c_ins_last), params_g, extras_g = res
        ex_leaves = jax.tree.leaves(extras_g)
        ex_tree = jax.tree.structure(extras_g)
        is_float = [jnp.issubdtype(leaf.dtype, jnp.inexact)
                    for leaf in ex_leaves]

        def float_only(g_ex):
            return [leaf for leaf, f in zip(jax.tree.leaves(g_ex), is_float)
                    if f]

        def group_vjp(i, carry_in, full, g_c, owed=None):
            """Group ``i``'s layers in reverse, each from its own saved
            carry ``carry_in(j)``; per-layer cotangents restacked to the
            ``[g, ...]`` leaves ``scatter_grads`` (and the extras) take.
            ``owed``: the full-width stacks of the group whose backward
            ran before this one's.  Their scatter is issued here and has
            this group's whole backward to land under; returned last."""
            extras_i = _index_tree(extras_g, i)
            flying = scatter_grads(owed) if owed is not None else []
            g_full = g_ex = None
            for j in reversed(range(g)):
                _, vjp_fn = jax.vjp(run_layer, carry_in(j),
                                    *_layer_of(full, extras_i, j))
                g_c, g_layer, g_ex_j = vjp_fn(g_c)
                g_full = _set_row(g_full, g_layer, j)
                g_ex = _set_row(g_ex, float_only(g_ex_j), j)
                g_c, held = _finish_rows(
                    g_c, g_full + (flying if j == 0 else []))
                g_full, landed = held[:len(g_full)], held[len(g_full):]
            return g_c, g_full, g_ex, landed

        def saved_carry(stack, *group):
            """``j -> `` layer ``j``'s carry out of a stack of them, one
            slice each (no ``[g, ...]`` group copy)."""
            return lambda j: jax.tree.map(lambda buf: buf[group + (j,)],
                                          stack)

        # group S-1: backward re-fetch, with S-2's re-gather issued
        # BEFORE the transposed compute (the backward's own prologue
        # double buffer)
        full_last = gather_group(_group_shards(params_g, steps - 1))
        full_prev = gather_group(_group_shards(params_g, steps - 2))
        g_c, g_full, g_ex_last, _ = group_vjp(
            steps - 1, saved_carry(c_ins_last), full_last, g_out)

        def bbody(carry, i):
            g_c, cur, owed = carry
            nxt = gather_group(_group_shards(params_g, i - 1))
            g_c, g_full, g_ex, landed = group_vjp(
                i, saved_carry(c_ins, i), cur, g_c, owed)
            # ys: group i+1's shards, landed under group i's backward
            return (g_c, nxt, g_full), (landed, g_ex)

        (g_c, cur0, g_full), (g_sh_upper, g_ex_mid) = lax.scan(
            bbody, (g_c, full_prev, g_full), jnp.arange(1, steps - 1),
            reverse=True)

        # group 0: consumes the last carried re-gather; group 1's shards
        # land under it, and its own scatter is the tail
        g_c0, g_full, g_ex0, g_sh1 = group_vjp(
            0, saved_carry(c_ins, 0), cur0, g_c, g_full)
        g_sh0 = scatter_grads(g_full)

        g_params = [jnp.concatenate([a[None], b[None], upper], axis=0)
                    for a, b, upper in zip(g_sh0, g_sh1, g_sh_upper)]
        out_ex, fi = [], 0
        for leaf, f in zip(ex_leaves, is_float):
            if f:
                out_ex.append(jnp.concatenate(
                    [g_ex0[fi][None], g_ex_mid[fi], g_ex_last[fi][None]],
                    axis=0))
                fi += 1
            else:
                # integer / PRNG-key extras take the conventional float0
                # cotangent
                out_ex.append(np.zeros(jnp.shape(leaf), jax.dtypes.float0))
        return g_c0, g_params, jax.tree.unflatten(ex_tree, out_ex)

    carried.defvjp(carried_fwd, carried_bwd)
    return carried


class Zero3StreamContext:
    """Installable streaming executor for stacked-layer models.

    The engine builds one of these when zero stage 3 runs with explicit
    gathering, and hands it to the model via ``install_zero3_streaming``.
    The model then calls :meth:`scan` instead of ``lax.scan`` for its layer
    stack; everything else about the model is unchanged.
    """

    def __init__(self, mesh_ctx: MeshContext, max_live_parameters: int,
                 prefetch_bucket_size: int,
                 persistence_threshold: int = 0,
                 low_bandwidth=None):
        self.ctx = mesh_ctx
        self.max_live_parameters = int(max_live_parameters)
        self.prefetch_bucket_size = int(prefetch_bucket_size)
        self.persistence_threshold = int(persistence_threshold)
        self.axis_sizes = {a: mesh_ctx.axis_size(a) for a in ZERO_AXES}
        self.manual = frozenset(
            a for a in ZERO_AXES if mesh_ctx.axis_size(a) > 1)
        self._plan_logged = False
        # ZeRO++-style low-bandwidth collectives (config.py
        # ZeroLowBandwidthConfig; comm/low_bandwidth.py): qwZ quantizes
        # the weight gathers, qgZ the grad reduce-scatters, hpZ confines
        # the hot-loop gathers to a sub-mesh via a secondary partition.
        self.lbc = (low_bandwidth if low_bandwidth is not None and
                    getattr(low_bandwidth, "enabled", False) else None)
        # T3-style fused collective-matmul (ops/collective_matmul.py):
        # the qwZ/qgZ transports move per-tile over a ring instead of as
        # one monolithic collective — the Schedule Auditor classifies
        # the per-tile wire as fused/hidden (docs/fused_collective_
        # matmul.md)
        self.fcm = bool(self.lbc is not None and getattr(
            self.lbc, "fused_collective_matmul", False))
        self.param_manual = self.manual
        self.param_axis_sizes = dict(self.axis_sizes)
        # last StreamPlan actually applied by scan() — set during
        # tracing, so the Schedule Auditor (analysis/auditor.py) can
        # name the streamed scan's structure in overlap findings
        self.last_plan: Optional[StreamPlan] = None
        # the backward's wire as scan() last planned it (_grad_wire)
        self.last_grad_wire: Optional[dict] = None
        if self.lbc is not None and self.lbc.hpz_group_size > 1:
            hpz = resolve_hpz_axes(self.axis_sizes,
                                   self.lbc.hpz_group_size)
            self.param_manual = frozenset(hpz) & self.manual
            self.param_axis_sizes = {
                a: (self.axis_sizes[a] if a in self.param_manual else 1)
                for a in ZERO_AXES}

    @property
    def active(self) -> bool:
        """Streaming is a no-op on a 1-way ZeRO mesh."""
        return bool(self.manual)

    def fold_shard_index(self, key):
        """Fold the ZeRO shard index into an rng key — models call this on
        per-layer dropout keys inside the streamed region so masks stay
        independent across batch shards.  Only legal inside the manual
        region (scan body); callers must gate on :meth:`usable`."""
        for ax in sorted(self.manual):
            key = jax.random.fold_in(key, lax.axis_index(ax))
        return key

    def usable(self, init_carry, carry_batch_dim: int = 0,
               params=None) -> bool:
        """True when :meth:`scan` will actually stream.  Models MUST gate
        both the scan call and any fold_shard_index use on this — it is the
        same predicate scan applies internally (scan falls back to a plain
        lax.scan when it is False).

        Streaming cannot apply when: 1-way ZeRO mesh, the global mesh has
        moved on since install (the model object outlives the engine —
        e.g. reused for inference), or the batch doesn't divide the ZeRO
        world (batch-1 decode).

        Half precision streams on every backend: the gather's transpose
        (``_all_gather_f32grad``) is permutes or an fp32 collective,
        never a half-precision reduction, which sidesteps the XLA-CPU
        AllReducePromotion abort that used to force a GSPMD fallback
        here."""
        del params  # kept for call-site compatibility
        if not self.active:
            return False
        from ...parallel import mesh as mesh_mod
        cur = mesh_mod.get_mesh_context(required=False)
        if cur is None or cur.mesh is not self.ctx.mesh:
            return False
        zero_world = int(np.prod([self.axis_sizes[a] for a in self.manual]))
        for leaf in jax.tree.leaves(init_carry):
            shape = getattr(leaf, "shape", ())
            if len(shape) <= carry_batch_dim or \
                    shape[carry_batch_dim] % zero_world != 0:
                return False
        return True

    # ------------------------------------------------------------------ #
    def _per_layer_zero_spec(self, leaf, tp_spec: Optional[PartitionSpec]
                             ) -> PartitionSpec:
        """ZeRO spec of ONE layer's slice (shape ``leaf.shape[1:]``) — the
        same decision function as ZeroPartitioner (partition.py), applied
        per-layer so the stream always shards within a layer and never
        across the layer axis (a layer-axis shard could not be gathered
        one group at a time).  When the engine's stacked-tree placement
        picked a different dim, shard_map simply reshards at entry.

        With hpZ on, ``param_axis_sizes`` confines the spec to the
        sub-mesh axes: the region entry reshard materializes the
        SECONDARY weight copy (one gather over the slow axes for the
        whole grouped stack, amortized across the scan — ZeRO++ hpZ's
        secondary allocation), and every hot-loop gather below stays
        within the fast sub-mesh."""
        tp_inner = (PartitionSpec(*list(tp_spec)[1:])
                    if tp_spec is not None else None)
        return zero_partition_spec(tuple(leaf.shape[1:]),
                                   self.param_axis_sizes,
                                   self.persistence_threshold, tp_inner)

    def _leaf_wire_bits(self, leaf, dim):
        """Per-leaf, per-direction quantization decision ``(qwz, qgz)``:
        a direction keeps its configured bits only when the narrowed
        payload actually beats the wire it replaces — a skinny leaf
        (bias gathered one layer at a time) would pay more in fp32
        block scales than it saves, so it degrades to 0 (dense) per
        direction.  The forward compares against the leaf's native
        width; the backward against fp32, the native reduce-scatter's
        wire for every float dtype (the dense fallback's permutes move a
        half gradient at its own width, so for a half leaf this gate
        lets qgZ in a little earlier than bytes alone would)."""
        lbc = self.lbc
        if lbc is None or not jnp.issubdtype(leaf.dtype, jnp.floating):
            return 0, 0
        qwz = lbc.qwz_bits if (lbc.qwz_bits and quantized_gather_saves_bytes(
            leaf.shape, dim, leaf.dtype, lbc.qwz_bits, lbc.block_size)
        ) else 0
        qgz = lbc.qgz_bits if (lbc.qgz_bits and quantized_gather_saves_bytes(
            leaf.shape, dim, jnp.float32, lbc.qgz_bits, lbc.block_size)
        ) else 0
        return qwz, qgz

    def _gather_leaf(self, leaf, axes, dim):
        """One tiled all-gather: quantized wire per direction when it
        pays (``_leaf_wire_bits``), the fp32-transpose gather
        otherwise.  With ``fused_collective_matmul`` on, float leaves
        route through the per-tile ring transport instead — bitwise the
        same values, but the wire moves tile-by-tile under the
        consuming compute and classifies as fused/hidden in the
        Schedule Auditor's overlap report."""
        qwz, qgz = self._leaf_wire_bits(leaf, dim)
        if self.fcm and jnp.issubdtype(leaf.dtype, jnp.floating):
            return fcm_all_gather(leaf, axes, dim, qwz, qgz,
                                  self.lbc.block_size)
        if qwz or qgz:
            return low_bandwidth_all_gather(leaf, axes, dim, qwz, qgz,
                                            self.lbc.block_size)
        return _all_gather_f32grad(leaf, axes, dim)

    def plan_for(self, stacked_params: Any) -> StreamPlan:
        leaves = jax.tree.leaves(stacked_params)
        num_layers = int(leaves[0].shape[0])
        per_layer = sum(
            int(np.prod(leaf.shape[1:])) for leaf in leaves)
        return plan_layer_streaming(num_layers, per_layer,
                                    self.max_live_parameters,
                                    self.prefetch_bucket_size)

    def _leaf_transpose_plan(self, local_shape, dtype, dims):
        """Static transpose schedule of ``gather_group``'s wire for one
        leaf: ``[(dim, axes, qgz_bits), ...]`` in FORWARD gather order.
        The quantization decision replays ``_gather_leaf``'s per-step
        ``_leaf_wire_bits`` on the simulated intermediate shapes, so the
        carried backward's hand-applied scatter moves exactly the bytes
        ``low_bandwidth_all_gather``'s own transpose would (qgZ
        quantized reduce-scatter when configured and paying, the fp32
        promote-reduce-demote otherwise)."""
        shape = list(local_shape)
        plan = []
        for dim, axes in dims:
            leaf = jax.ShapeDtypeStruct(tuple(shape), dtype)
            _qwz, qgz = self._leaf_wire_bits(leaf, dim + 1)
            # the transpose wire depends only on qgz: both _lbag_bwd
            # (qwz path) and _ag_bwd (dense path) fall back to
            # dense_psum_scatter when qgz == 0
            plan.append((dim + 1, tuple(axes), qgz))
            world = int(np.prod([self.param_axis_sizes[a] for a in axes]))
            shape[dim + 1] *= world
        return plan

    def _leaf_wire_form(self, local_shape, dtype, plan_k):
        """How one gathered leaf's gradient leaves, from shapes alone:
        ``(form, {form: bytes one shard sends a group})`` with ``form``
        the widest of its scattered dimensions' (``"quantized"``, then
        ``"native"``, then ``"permute"``; ``dense_scatter_wire``), or
        None for a leaf gathered over no axis, whose gradient is the
        region boundary's psum."""
        if not plan_k:
            return None, {}
        shape = list(local_shape)
        worlds_of = [[self.param_axis_sizes[a] for a in axes]
                     for _d, axes, _q in plan_k]
        for (d, _axes, _q), worlds in zip(plan_k, worlds_of):
            shape[d] *= int(np.prod(worlds))
        sent = {}
        for (d, _axes, qgz), worlds in zip(reversed(plan_k),
                                           reversed(worlds_of)):
            if qgz:
                sent.setdefault("quantized", 0)
            else:
                form, nbytes = dense_scatter_wire(shape, dtype, d, worlds)
                sent[form] = sent.get(form, 0) + nbytes
            shape[d] //= int(np.prod(worlds))
        return next(f for f in ("quantized", "native", "permute")
                    if f in sent), sent

    def _grad_wire(self, wires, steps):
        """The counter of the backward's wire, over the leaves'
        ``_leaf_wire_form``: how many of a group's gathered leaves leave
        as shifted permutes, as the native reduce-scatter and quantized,
        and the bytes one shard sends a step in the two dense forms."""
        out = {"permuted_leaves": 0, "native_leaves": 0,
               "quantized_leaves": 0, "permute_bytes_per_step": 0,
               "native_bytes_per_step": 0}
        for form, sent in wires:
            if form is None:
                continue
            out[{"permute": "permuted_leaves", "native": "native_leaves",
                 "quantized": "quantized_leaves"}[form]] += 1
            for dense in ("permute", "native"):
                out[f"{dense}_bytes_per_step"] += sent.get(dense, 0) * steps
        return out

    # ------------------------------------------------------------------ #
    def scan(self, body, init_carry, stacked_params: Any, extra_xs: Any,
             param_tp_specs: Any = None, carry_batch_dim: int = 0):
        """Drop-in for ``lax.scan(body, init, (params, *extras))`` where
        ``body(carry, (layer_params, *layer_extras)) -> (carry, None)``.

        stacked_params: pytree of ``[L, ...]`` leaves to ZeRO-stream.
        extra_xs: pytree of ``[L, ...]`` leaves passed through replicated
        (layer RNGs, PLD keep-probabilities, ...).
        param_tp_specs: optional matching tree of tensor-parallel
        PartitionSpecs for the stacked leaves (layer axis included).
        carry_batch_dim: dimension of each carry leaf sharded over the ZeRO
        axes (the batch dimension).
        """
        if not self.usable(init_carry, carry_batch_dim,
                           params=stacked_params):
            carry, _ = lax.scan(
                lambda c, xs: body(c, xs),
                init_carry, (stacked_params,) + tuple(extra_xs))
            return carry

        plan = self.plan_for(stacked_params)
        if not self._plan_logged and _body_closes_over_tracers(body):
            # neither structure can DIFFERENTIATE a body that captures
            # traced values (shard_map cannot transpose captured
            # tracers; the carried custom_vjp differentiates only its
            # explicit inputs) — both failures are opaque deep inside
            # grad, so name the fix up front.  Forward-only use works.
            log_dist(
                "ZeRO-3 streaming: the scan body closes over traced "
                "values — gradients cannot flow to them through the "
                "streamed region (expect UnexpectedTracerError / "
                "NotImplementedError under grad); thread those values "
                "through stacked_params/extra_xs instead",
                ranks=[0], level=logging.WARNING)
        self.last_plan = plan
        if plan.forfeited and not self._plan_logged:
            # requested overlap fell back to serialized gathers — a
            # capacity fallback the operator should see once, loudly
            try:
                from ..resilience.degradation import record as degrade
                degrade("zero3_prefetch", "overlapped", "serialized",
                        plan.forfeited)
            except Exception:  # pragma: no cover — partial install
                pass
        mesh = self.ctx.mesh
        manual = self.manual
        g = plan.layers_per_step
        steps = plan.num_layers // g

        # -- sharding specs for every shard_map operand ----------------- #
        if param_tp_specs is None:
            param_tp_specs = jax.tree.map(lambda _: None, stacked_params)
        tp_list = jax.tree.leaves(
            param_tp_specs,
            is_leaf=lambda x: x is None or isinstance(x, PartitionSpec))
        p_leaves, p_tree = jax.tree_util.tree_flatten(stacked_params)
        if len(tp_list) != len(p_leaves):
            raise ValueError("param_tp_specs must mirror stacked_params")
        p_manual = self.param_manual  # == manual unless hpZ restricts it
        inner_specs = [self._per_layer_zero_spec(leaf, s)
                       for leaf, s in zip(p_leaves, tp_list)]
        in_param_specs = [
            PartitionSpec(None, *list(_restrict_to_manual(s, p_manual)))
            for s in inner_specs]
        gathers = [_gather_dims(s, p_manual) for s in inner_specs]
        # A leaf not gathered over EVERY manual axis enters the region
        # replicated along the uncovered axes, so its gradient is a psum
        # over those axes at the shard_map transpose boundary.  Such
        # half-precision leaves are widened to fp32 at entry (cast back to
        # their dtype at use) so that psum accumulates in fp32 — matching
        # _all_gather_f32grad's fp32 accumulation for the gathered dims,
        # and keeping every reduction collective the region emits out of
        # XLA-CPU's half-precision AllReducePromotion abort.  Without hpZ
        # the uncovered leaves are the ones too small to shard further, so
        # the widened transfer is noise.  With hpZ EVERY leaf is uncovered
        # by design (gathers stop at param_manual; the slow outer axes
        # reduce grads once at the boundary) — the fp32 widening then
        # doubles the once-per-step entry reshard, a deliberate trade: the
        # hot-loop per-layer gathers, which hpZ is buying back, stay at
        # the quantized/native width, and the boundary grad psum must be
        # fp32 anyway (accumulation + the XLA-CPU abort above).
        leaf_dtypes = [leaf.dtype for leaf in p_leaves]

        def _covered_axes(dims):
            cov = set()
            for _, axes in dims:
                cov.update(axes)
            return cov

        widen = [
            _covered_axes(dims) != set(manual) and
            jnp.issubdtype(dt, jnp.floating) and jnp.dtype(dt).itemsize < 4
            for dims, dt in zip(gathers, leaf_dtypes)]

        def group_leaf(leaf):
            return leaf.reshape((steps, g) + tuple(leaf.shape[1:]))

        grouped_params = [
            group_leaf(leaf.astype(jnp.float32) if w else leaf)
            for leaf, w in zip(p_leaves, widen)]
        grouped_extras = jax.tree.map(group_leaf, extra_xs)
        # the group reshape shifts every dim by one: shift specs too
        def shift(spec):
            return PartitionSpec(None, *list(spec))
        in_specs_params = [shift(s) for s in in_param_specs]

        carry_spec = jax.tree.map(
            lambda c: PartitionSpec(
                *([None] * carry_batch_dim),
                tuple(sorted(manual, key=ZERO_AXES.index))),
            init_carry)
        extras_specs = jax.tree.map(lambda _: PartitionSpec(), grouped_extras)

        # The transpose of gather_group's wire, leaf by leaf, replaying
        # the qwZ/qgZ decisions _gather_leaf makes from the LOCAL
        # (in-region) shard shapes: what the carried backward applies by
        # hand, and what the gather's own VJP does where groups are
        # gathered at use.
        def local_group_shape(k):
            shape = [g] + list(p_leaves[k].shape[1:])
            for d, axes in gathers[k]:
                world = int(np.prod(
                    [self.param_axis_sizes[a] for a in axes]))
                shape[d + 1] //= world
            return shape

        local_shapes = [local_group_shape(k) for k in range(len(p_leaves))]
        wire_dtypes = [jnp.float32 if w else dt
                       for w, dt in zip(widen, leaf_dtypes)]
        transpose_plans = [
            self._leaf_transpose_plan(shape, dt, dims)
            for shape, dt, dims in zip(local_shapes, wire_dtypes, gathers)]
        wires = [self._leaf_wire_form(shape, dt, plan_k)
                 for shape, dt, plan_k in zip(local_shapes, wire_dtypes,
                                              transpose_plans)]
        self.last_grad_wire = self._grad_wire(wires, steps)
        host_trace.mark("zero3.grad_wire", **self.last_grad_wire)
        if not self._plan_logged:
            lb = ""
            if self.lbc is not None:
                # key off the CONFIG, not param_manual == manual: a
                # group size equal to the full ZeRO world is a
                # configured (degenerate) hpZ, not "off"
                hpz = (sorted(self.param_manual)
                       if self.lbc.hpz_group_size > 1 else "off")
                lb = (f", low_bandwidth: qwz={self.lbc.qwz_bits}b "
                      f"qgz={self.lbc.qgz_bits}b hpz={hpz}"
                      f"{' fcm' if self.fcm else ''}")
            saved = ""
            if plan.prefetch:
                # the carried VJP's residuals: every layer's input carry
                zero_world = int(np.prod(
                    [self.axis_sizes[a] for a in self.manual]))
                carry_bytes = sum(
                    int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
                    for leaf in jax.tree.leaves(init_carry)) // zero_world
                saved = (f", backward saves {plan.num_layers} layer-input "
                         f"carries ({plan.num_layers * carry_bytes:,} B "
                         f"per shard)")
            wire = self.last_grad_wire
            grads = (f", gradients leave a group as permutes for "
                     f"{wire['permuted_leaves']} leaves "
                     f"({wire['permute_bytes_per_step']:,} B sent per "
                     f"shard and step) and as the native reduce-scatter "
                     f"for {wire['native_leaves']} "
                     f"({wire['native_bytes_per_step']:,} B)")
            if wire["quantized_leaves"]:
                grads += f", quantized for {wire['quantized_leaves']}"
            log_dist(
                f"ZeRO-3 streaming: {plan.num_layers} layers in groups of "
                f"{plan.layers_per_step}, prefetch={plan.prefetch}, "
                f"live<= {plan.live_parameters:,} "
                f"params (max_live={self.max_live_parameters:,}){saved}"
                f"{grads}{lb}",
                ranks=[0])
            if plan.forfeited:
                log_dist(
                    f"ZeRO-3 streaming: prefetch FORFEITED — "
                    f"{plan.forfeited}; falling back to serialized "
                    f"at-use gathers ({plan.num_layers} layers in groups "
                    f"of {plan.layers_per_step})",
                    ranks=[0], level=logging.WARNING)
            self._plan_logged = True

        def gather_group(shards):
            """all-gather one layer group's param shards into full arrays.
            The +1 dim shift accounts for the group dimension.  Gathered
            values are checkpoint-named so the step's remat policy DROPS
            them from the saved residuals: without this, lax.scan's VJP
            would stack every step's gathered group — the full unsharded
            model — as a residual, defeating max_live entirely.  Backward
            re-gathers instead (exactly the reference's backward re-fetch,
            stage3.py:546 PreBackwardFunction)."""
            full = []
            for leaf, dims, dt, w in zip(shards, gathers, leaf_dtypes,
                                         widen):
                for dim, axes in dims:
                    leaf = self._gather_leaf(leaf, axes, dim + 1)
                if w:
                    leaf = leaf.astype(dt)
                full.append(checkpoint_name(leaf, "zero3_gathered"))
            return full

        def run_layer(carry, layer_leaves, layer_extras):
            """One layer of a gathered group: leaves and extras without
            the group dimension."""
            carry, _ = body(carry, (p_tree.unflatten(layer_leaves),) +
                            tuple(layer_extras))
            return carry

        if plan.prefetch:
            # Carried double-buffer prefetch (_build_carried_stream): the
            # gather for group i+1 rides the scan carry, issued under
            # group i's compute, and the hand-written VJP re-gathers in a
            # reverse scan with its own carried double buffer — gathered
            # buffers never become scan residuals (the naive carried
            # structure would stack the full unsharded model; see the
            # builder's docstring), preserving StreamPlan.live_parameters'
            # 2x bound.
            block = self.lbc.block_size if self.lbc is not None else 0
            fcm = self.fcm

            def scatter_grads(g_full):
                out = []
                for gk, plan_k, w in zip(g_full, transpose_plans, widen):
                    if w:  # transpose of gather_group's cast-back to dt
                        gk = gk.astype(jnp.float32)
                    for d, axes, qgz in reversed(plan_k):
                        if qgz and fcm:
                            # per-tile ring scatter: the backward GEMM's
                            # epilogue wire, classified fused/hidden
                            gk = fcm_reduce_scatter(gk, axes, d,
                                                    bits=qgz, block=block)
                        elif qgz:
                            gk = quantized_psum_scatter(gk, axes, d,
                                                        bits=qgz,
                                                        block=block)
                        else:  # with the knob or without: one dense path
                            gk = dense_psum_scatter(gk, axes, d)
                    out.append(gk)
                return out

            carried = _build_carried_stream(steps, g, gather_group,
                                            run_layer, scatter_grads)

            def region_fn(carry, params_grouped, extras_grouped):
                return carried(carry, params_grouped, extras_grouped)
        else:
            def step(c, xs):
                shards, extras_g = xs
                full = gather_group(shards)
                for j in range(g):  # unrolled over the gathered group
                    c = run_layer(c, *_layer_of(full, extras_g, j))
                return c, None

            # Save every intermediate EXCEPT the gathered params:
            # activations are stored as usual (no recompute tax), only the
            # all-gathers rerun in backward.
            step = jax.checkpoint(
                step,
                policy=jax.checkpoint_policies.
                save_anything_except_these_names("zero3_gathered"))

            def region_fn(carry, params_grouped, extras_grouped):
                carry, _ = lax.scan(
                    step, carry, (params_grouped, extras_grouped))
                return carry

        # check_vma SCOPED (advisor r3): pallas_call outputs carry no
        # varying-mesh-axes metadata, so the vma analysis rejects any
        # Pallas kernel (flash attention, Pallas LN) inside the manual
        # region at trace time — but a Pallas-FREE body (CPU sim, XLA
        # dispatch, custom models) keeps the analysis ON, catching
        # cross-shard replication bugs where it can.  Detection traces
        # the user body once abstractly and walks the jaxpr for pallas
        # primitives; an untraceable body (needs the mesh context)
        # conservatively keeps the analysis off.
        check_vma = not _body_uses_pallas(body, init_carry, p_tree,
                                          p_leaves, extra_xs)
        streamed = jax.shard_map(
            region_fn, mesh=mesh,
            in_specs=(carry_spec, in_specs_params, extras_specs),
            out_specs=carry_spec, axis_names=set(manual),
            check_vma=check_vma)
        return streamed(init_carry, grouped_params, grouped_extras)
