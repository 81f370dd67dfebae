"""Fused whole-step train program: scan-based gradient accumulation plus
the optimizer/loss-scale update in ONE compiled XLA program.

Motivation (docs/fused_step.md): the modular forward/backward/step protocol
dispatches ``2N+1`` XLA programs per optimizer step at ``gas=N`` (N grad
programs, the accumulation adds, then the apply), with the accumulated
gradients round-tripping through HBM between programs and the Python loop
fencing every microbatch.  Fusing the whole step into one program lets
XLA's latency-hiding scheduler overlap microbatch *i*'s gradient collective
(pmean / reduce-scatter, emitted from the output shardings) with microbatch
*i+1*'s compute — the T3-style compute/communication overlap
(arXiv:2401.16677) with no hand scheduling — and the grad buffers become
program-internal scratch that never leaves the program.

Structure of the emitted program::

    scan over [gas] microbatches:
        loss, grads = loss_and_grads(params, scaler, rng_i, microbatch_i)
        acc += grads                     # donated carry, in-place
    (in-program, optional) loss-only sentinel observe -> healthy flag
    unscale -> overflow check -> optax update -> per-leaf select skip
    loss-scale transition                # select form, fuses into epilogue

The scan body IS the engine's existing grad program (``_loss_and_grads`` —
including the sparse-gradients shard_map region and the ZeRO-3 streamed
layer scan, which simply nests: scan-in-scan, or scan-in-scan-in-scan
with the carried double-buffer prefetch of zero/stage3_streaming.py,
whose hand-written VJP guarantees gathered layer groups never stack as
residuals of THIS outer scan either), and the epilogue IS the engine's
existing apply program (``_apply_core``), so the fused path is
numerically the modular path with the host removed from the middle.

The engine builds this only when ``fused_step.enabled`` is set AND no
host-interactive feature is active (``fused_fallback_reason``); everything
else — host bookkeeping, fp16 ``skipped_steps``, boundary logging — stays
in ``engine._fused_train_batch``.
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# matches the host sentinel's zscore floor (resilience/sentinel.py)
_VAR_FLOOR = 1e-12


class FusedSentinelState(NamedTuple):
    """Device-resident mirror of the host sentinel's loss EWMA
    (resilience/sentinel.py _EwmaStat) so loss-only monitoring runs INSIDE
    the fused program: the k-sigma/non-finite verdict gates the apply via
    the same per-leaf select predicate as the fp16 overflow skip, with no
    host round-trip.  Counters/budget/abort stay host-side — the engine
    drains the returned flags at logging boundaries."""
    mean: jnp.ndarray       # f32 — EWMA mean of the per-step mean loss
    var: jnp.ndarray        # f32 — EWMA variance
    count: jnp.ndarray      # i32 — clean observations folded in


def sentinel_state_from_host(sentinel, mesh_ctx) -> FusedSentinelState:
    """Seed the device EWMA from the host sentinel (fresh engine or
    checkpoint load: ``load_state_dict`` already ran)."""
    stat = sentinel.loss_stat
    state = FusedSentinelState(
        mean=jnp.asarray(stat.mean if stat.mean is not None else 0.0,
                         jnp.float32),
        var=jnp.asarray(stat.var, jnp.float32),
        count=jnp.asarray(stat.count, jnp.int32))
    return jax.device_put(state, mesh_ctx.replicated())


def sentinel_state_to_host(state: FusedSentinelState, sentinel) -> None:
    """Fold the device EWMA back into the host sentinel (checkpoint save:
    ``state_dict`` must capture what the program learned)."""
    import numpy as np
    count = int(np.asarray(state.count))
    sentinel.loss_stat.count = count
    sentinel.loss_stat.mean = (float(np.asarray(state.mean))
                               if count > 0 else None)
    sentinel.loss_stat.var = float(np.asarray(state.var))


def fused_fallback_reason(engine) -> Optional[str]:
    """Why the fused path cannot serve this engine (None = it can).

    The fused program is one dispatch with no host in the loop, so any
    feature that needs the host BETWEEN microbatches or between the grads
    and the apply forces the modular loop.  This is the documented
    fallback matrix (docs/fused_step.md)."""
    cfg = engine.config
    if getattr(engine, "_custom_grad_program", None) is not None:
        return ("a custom grad program (pipeline 1F1B executor) schedules "
                "its own step")
    if engine._offload_enabled:
        return "offload_optimizer steps on the host (CPU/NVMe Adam)"
    if cfg.quantize_training_enabled:
        return "MoQ quantize-training runs host-scheduled post-step programs"
    if cfg.eigenvalue_config.enabled:
        return "eigenvalue curvature probes re-run the loss between steps"
    if cfg.pld_config.enabled:
        return "progressive_layer_drop injects per-step host state (theta)"
    if cfg.curriculum_config.enabled:
        return "curriculum_learning re-truncates the batch per step"
    if cfg.flops_profiler_config.enabled:
        return "flops_profiler arms the modular forward at profile_step"
    if engine.sentinel is not None:
        if engine.sentinel.policy == "rewind":
            return ("sentinel policy 'rewind' restores host checkpoints "
                    "mid-run")
        if engine.sentinel.monitor_grad_norm:
            return ("sentinel grad-norm monitoring reads accumulated grads "
                    "on the host (set resilience.sentinel.monitor_grad_norm "
                    "= false for in-program loss-only monitoring)")
    return None


def build_fused_step(engine, onebit=None):
    """Compile the fused whole-step program for `engine`.

    Signature of the returned jitted callable::

        (params, opt_state, scaler_state, sent_state, rng,
         batch_args, batch_kwargs)
          -> (params', opt_state', scaler_state', sent_state',
              mean_loss, overflow, (flagged, nonfinite))

    ``batch_args``/``batch_kwargs`` carry a leading ``[gas]`` microbatch
    axis on every leaf (dataloader.stack_microbatches).  params/opt_state
    are donated and alias the outputs; grad buffers are program-internal.

    ``onebit`` (engine._onebit_get_programs) selects the compressed-phase
    twin: the scan body is the phase-B grad program (local [W, ...]
    stacked grads — no dense allreduce) and the epilogue the phase-B
    apply (packed-sign momentum sync, wire-error state threaded through
    as a donated carry).  The onebit build returns a dict
    {fn, raw, donate_argnums, label} and does NOT touch the engine's
    telemetry attributes — the engine installs them at the phase switch.
    The onebit callable's signature gains the wire-error carry::

        (params, opt_state, scaler_state, sent_state, wire_error, rng,
         batch_args, batch_kwargs)
          -> (params', opt_state', scaler_state', sent_state',
              wire_error', mean_loss, overflow, (flagged, nonfinite))
    """
    gas = engine.gradient_accumulation_steps()
    loss_and_grads = (onebit["loss_and_grads"] if onebit is not None
                      else engine._loss_and_grads)
    # MoE routing stats (monitor.moe): the scan body's aux RoutingStats
    # ride out as stacked scan outputs and are summed over the [gas]
    # axis IN-program — the accumulator crosses the microbatch scan
    # without a host touch (docs/telemetry.md).  The onebit tier disables
    # MoE telemetry at init, so the onebit build never threads stats.
    moe_stats = (getattr(engine, "_moe_stats_enabled", False)
                 and onebit is None)
    apply_core = (onebit["apply_core"] if onebit is not None
                  else engine._apply_core)
    if apply_core is None:  # pragma: no cover — guarded by fallback_reason
        raise RuntimeError("fused_step requires the compiled apply path")
    compute_dtype = engine.compute_dtype
    grads_half = engine._grads_half

    sentinel = engine.sentinel
    sent_on = sentinel is not None
    if sent_on:
        alpha = float(sentinel.loss_stat.alpha)
        k_sigma = float(sentinel.k_sigma)
        warmup = int(sentinel.warmup_steps)
        warn_policy = sentinel.policy == "warn"
        skip_policy = sentinel.policy == "skip_step"

    def _grad_dtype(p):
        if jnp.issubdtype(p.dtype, jnp.floating):
            return compute_dtype if grads_half else p.dtype
        return p.dtype

    def _sentinel_observe(state: FusedSentinelState, loss):
        """In-program mirror of TrainingSentinel.observe for the loss
        stream: non-finite always flags; k-sigma flags after warmup.  The
        baseline adapts on clean steps, and (warn policy only) on finite
        spikes — matching the host sentinel's train-through rule; a
        non-finite observation never drags the EWMA."""
        nonfinite = ~jnp.isfinite(loss)
        # count > 0 mirrors the host sentinel's mean-is-None guard: the
        # very first observation can never be a k-sigma spike (the device
        # mean is a placeholder 0.0 until something is observed), even
        # with warmup_steps = 0
        warmed = (state.count >= warmup) & (state.count > 0)
        z = jnp.abs(loss - state.mean) / jnp.sqrt(
            jnp.maximum(state.var, _VAR_FLOOR))
        spike = warmed & (z > k_sigma) & ~nonfinite
        flagged = nonfinite | spike
        adapt = ~flagged | (spike if warn_policy else jnp.asarray(False))
        first = state.count == 0
        diff = loss - state.mean
        incr = alpha * diff
        new_mean = jnp.where(first, loss, state.mean + incr)
        new_var = jnp.where(first, 0.0,
                            (1.0 - alpha) * (state.var + diff * incr))
        new_state = FusedSentinelState(
            mean=jnp.where(adapt, new_mean, state.mean),
            var=jnp.where(adapt, new_var, state.var),
            count=jnp.where(adapt, state.count + 1, state.count))
        return flagged, nonfinite, new_state

    def fused_step(params, opt_state, scaler_state, sent_state, rng,
                   batch_args, batch_kwargs, wire_error=None):
        rngs = jax.random.split(rng, gas)
        if onebit is not None:
            # phase-B grads are worker-stacked: [W, ...] per leaf
            wn = onebit["world"]
            zeros = jax.tree.map(
                lambda p: jnp.zeros((wn,) + tuple(p.shape),
                                    _grad_dtype(p)), params)
        else:
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, _grad_dtype(p)), params)

        def body(carry, xs):
            acc, loss_sum = carry
            r, mb_args, mb_kwargs = xs
            if moe_stats:
                loss, grads, stats = loss_and_grads(
                    params, scaler_state, r, *mb_args, **mb_kwargs)
            else:
                loss, grads = loss_and_grads(params, scaler_state, r,
                                             *mb_args, **mb_kwargs)
                stats = None
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_sum + loss.astype(jnp.float32)), stats

        (grads, loss_sum), stats_stack = lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)),
            (rngs, batch_args, batch_kwargs))
        # stacked [gas, ...] RoutingStats -> one per-step sum (None
        # passes through tree.map untouched: a dense model under
        # monitor.moe, or moe_stats off)
        moe_out = jax.tree.map(lambda x: x.sum(axis=0), stats_stack)
        mean_loss = loss_sum / gas

        healthy = jnp.asarray(True)
        flagged = jnp.asarray(False)
        nonfinite = jnp.asarray(False)
        new_sent = sent_state
        if sent_on:
            flagged, nonfinite, new_sent = _sentinel_observe(sent_state,
                                                             mean_loss)
            if skip_policy:
                # rides the same select machinery as the overflow skip; a
                # NaN loss also NaNs the grads, so the apply's own finite
                # check would catch it even without the sentinel
                healthy = ~flagged
        if onebit is not None:
            (new_params, new_opt, new_scaler, overflow,
             new_wire) = apply_core(params, opt_state, scaler_state,
                                    grads, wire_error, healthy)
            return (new_params, new_opt, new_scaler, new_sent, new_wire,
                    mean_loss, overflow, (flagged, nonfinite))
        new_params, new_opt, new_scaler, overflow = apply_core(
            params, opt_state, scaler_state, grads, healthy)
        out = (new_params, new_opt, new_scaler, new_sent, mean_loss,
               overflow, (flagged, nonfinite))
        if moe_stats:
            out = out + (moe_out,)
        return out

    replicated = engine.mesh_ctx.replicated()
    sent_shardings = jax.tree.map(lambda _: replicated,
                                  engine._fused_sent_state)
    if onebit is not None:
        # positional wire-error carry (donation needs a positional slot)
        def fused_step_onebit(params, opt_state, scaler_state, sent_state,
                              wire_error, rng, batch_args, batch_kwargs):
            return fused_step(params, opt_state, scaler_state, sent_state,
                              rng, batch_args, batch_kwargs,
                              wire_error=wire_error)

        donate = (0, 1, 4)
        out_shardings = (engine.param_shardings, replicated, replicated,
                         sent_shardings, onebit["wire_sharding"],
                         replicated, replicated, (replicated, replicated))
        return {
            "fn": jax.jit(fused_step_onebit, out_shardings=out_shardings,
                          donate_argnums=donate),
            "raw": fused_step_onebit,
            "donate_argnums": donate,
        }
    # The un-jitted body, the donation facts, and the scan structure are
    # recorded on the engine for the Program Auditor (analysis/
    # auditor.py), which traces this exact program abstractly and audits
    # donation + schedule against what is actually dispatched.
    engine._fused_step_raw = fused_step
    engine._fused_donate_argnums = (0, 1)
    engine._fused_scan_info = {"gas_scan_length": gas}
    out_shardings = (engine.param_shardings, engine.opt_shardings,
                     replicated, sent_shardings, replicated, replicated,
                     (replicated, replicated))
    if moe_stats:
        # prefix sharding broadcasts over the RoutingStats pytree (or
        # over None when the model has no MoE layers)
        out_shardings = out_shardings + (replicated,)
    return jax.jit(
        fused_step,
        out_shardings=out_shardings,
        donate_argnums=engine._fused_donate_argnums)
