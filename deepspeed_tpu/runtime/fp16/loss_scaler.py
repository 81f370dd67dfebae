"""Static and dynamic loss scaling as jit-compatible state.

Reference: deepspeed/runtime/fp16/loss_scaler.py:221 (LossScaler /
DynamicLossScaler).  The reference mutates python attributes per step; here the
scaler is split into a static config (python, closed over by the compiled
step) and an array-only pytree state updated functionally inside the jitted
optimizer step — overflow-skip / halve / double all trace into one XLA program
with no host round-trips.
"""

from dataclasses import dataclass
from typing import NamedTuple

import jax.numpy as jnp


@dataclass(frozen=True)
class LossScalerConfig:
    """Static scaler configuration (not part of the traced state)."""
    dynamic: bool = False
    scale_window: int = 1000
    scale_factor: float = 2.0
    min_loss_scale: float = 1.0
    init_hysteresis: int = 2
    init_scale: float = 1.0


class LossScaleState(NamedTuple):
    """Array-only pytree state for (dynamic) loss scaling."""
    loss_scale: jnp.ndarray    # f32 scalar — current scale
    good_steps: jnp.ndarray    # i32 — consecutive overflow-free steps
    hysteresis: jnp.ndarray    # i32 — remaining tolerated overflows


def create_loss_scaler(fp16_config=None, static_scale: float = 1.0):
    """Build (config, state) from an FP16Config (reference keys: loss_scale /
    initial_scale_power / loss_scale_window / hysteresis / min_loss_scale)."""
    if fp16_config is not None and fp16_config.enabled:
        if fp16_config.dynamic_loss_scale:
            cfg = LossScalerConfig(
                dynamic=True,
                scale_window=int(fp16_config.loss_scale_window),
                min_loss_scale=float(fp16_config.min_loss_scale),
                init_hysteresis=int(fp16_config.hysteresis),
                init_scale=2.0 ** fp16_config.initial_scale_power)
        else:
            cfg = LossScalerConfig(dynamic=False,
                                   init_scale=float(fp16_config.loss_scale))
    else:
        cfg = LossScalerConfig(dynamic=False, init_scale=static_scale)
    state = LossScaleState(
        loss_scale=jnp.asarray(cfg.init_scale, jnp.float32),
        good_steps=jnp.asarray(0, jnp.int32),
        hysteresis=jnp.asarray(cfg.init_hysteresis, jnp.int32))
    return cfg, state


def update_loss_scale(cfg: LossScalerConfig, state: LossScaleState,
                      overflow) -> LossScaleState:
    """One scaler transition (reference: loss_scaler.py update_scale):

    - overflow & hysteresis exhausted → scale = max(scale/factor, min), reset
      good-step counter
    - overflow & hysteresis left      → burn one hysteresis credit
    - clean step                      → good_steps += 1; after scale_window
      consecutive clean steps, scale *= factor and hysteresis resets

    Select form (jnp.where), not lax.cond: the transition is three scalar
    selects, and a cond would keep both branches' operands alive across the
    branch boundary — that blocks XLA from fusing the scaler update into
    the apply program's epilogue, the same donation/aliasing argument as
    the engine's per-leaf overflow skip.
    """
    if not cfg.dynamic:
        return state
    overflow = jnp.asarray(overflow)

    exhausted = state.hysteresis <= 1
    of_scale = jnp.where(
        exhausted,
        jnp.maximum(state.loss_scale / cfg.scale_factor, cfg.min_loss_scale),
        state.loss_scale)
    of_hyst = jnp.where(exhausted, state.hysteresis, state.hysteresis - 1)

    grow = (state.good_steps + 1) % cfg.scale_window == 0
    clean_scale = jnp.where(grow, state.loss_scale * cfg.scale_factor,
                            state.loss_scale)
    clean_hyst = jnp.where(grow,
                           jnp.asarray(cfg.init_hysteresis, jnp.int32),
                           state.hysteresis)

    return LossScaleState(
        loss_scale=jnp.where(overflow, of_scale, clean_scale),
        good_steps=jnp.where(overflow, jnp.zeros_like(state.good_steps),
                             state.good_steps + 1),
        hysteresis=jnp.where(overflow, of_hyst, clean_hyst))


# API-parity shims (reference exposes these names).
class LossScalerBase:
    def __init__(self, cur_scale):
        self.cur_scale = cur_scale

    @property
    def loss_scale(self):
        return self.cur_scale

    def backward(self, loss):  # pragma: no cover — functional API instead
        raise NotImplementedError(
            "deepspeed_tpu computes grads functionally; use the engine")


class LossScaler(LossScalerBase):
    """Static scaler shim."""


class DynamicLossScaler(LossScalerBase):
    """Dynamic scaler shim; real logic lives in LossScaleState."""

    def __init__(self, init_scale=2 ** 32, scale_factor=2.0, scale_window=1000,
                 min_scale=1, delayed_shift=1, consecutive_hysteresis=False):
        super().__init__(init_scale)
        self.scale_factor = scale_factor
        self.scale_window = scale_window
        self.min_scale = min_scale
        self.delayed_shift = delayed_shift
        self.consecutive_hysteresis = consecutive_hysteresis
