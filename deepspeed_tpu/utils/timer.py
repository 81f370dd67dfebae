"""The throughput timer (reference: deepspeed/utils/timer.py:19).

Where the reference synchronizes CUDA streams, we synchronize XLA's async
dispatch queue: `_device_sync` runs a trivial computation and blocks on it,
which (by in-order execution per device) drains previously dispatched work.
"""

import time

from .logging import log_dist, logger

# one debug line per process, not one per timed step
_sync_failure_logged = False


def _device_sync():
    """Drain the XLA dispatch queue.  Failures are narrowed: only the
    expected benign cases (no jax installed: ImportError; backend not
    initialized / torn down mid-exit: RuntimeError) are swallowed — and
    even those are logged once at debug, because a sync that silently
    fails times the queue depth as ~0 and every derived number lies."""
    global _sync_failure_logged
    try:
        import jax
        import jax.numpy as jnp
        jnp.zeros(()).block_until_ready()
        # effects_barrier waits for any outstanding host callbacks too
        jax.effects_barrier()
    except (ImportError, RuntimeError) as e:
        if not _sync_failure_logged:
            _sync_failure_logged = True
            logger.debug(f"timer device sync unavailable "
                         f"({type(e).__name__}: {e}) — timings will not "
                         "drain the dispatch queue")


class ThroughputTimer:
    """Samples/sec tracking (reference: deepspeed/utils/timer.py
    ThroughputTimer).

    Unlike the reference (which cuda-synchronizes every step), the device
    queue is drained only at `steps_per_output` window boundaries: a per-step
    sync serializes host dispatch against device compute.
    Two semantic consequences: per-step variance is lost, and the window
    includes inter-step host time (dataloader etc.) the reference's
    start/stop bracketing excluded — i.e. this reports DELIVERED end-to-end
    throughput, which is lower than the reference's device-only number when
    a slow input pipeline isn't hidden by the dispatch queue.
    """

    def __init__(self, batch_size, num_workers, start_step=2,
                 steps_per_output=50, monitor_memory=False, logging_fn=None):
        self.start_time = 0.0
        self.end_time = 0.0
        self.started = False
        self.batch_size = max(1, batch_size)
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.total_timed_steps = 0
        self.window_steps = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or log_dist
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step and self.start_time == 0.0:
            # first timed step: drain the queue once so the window starts
            # from an idle device, then let dispatch run free
            _device_sync()
            self.start_time = time.time()
            self.window_steps = 0

    def stop(self, global_step=False, report_speed=True):
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        if not global_step:
            return
        self.global_step_count += 1
        if self.start_time <= 0:
            return
        self.window_steps += 1
        if self.global_step_count % self.steps_per_output != 0:
            return
        window_rate = self._close_window()
        if report_speed:
            self.logging(
                "epoch={}/micro_step={}/global_step={}, "
                "RunningAvgSamplesPerSec={:.6g}, CurrSamplesPerSec={:.6g}".format(
                    self.epoch_count, self.micro_step_count,
                    self.global_step_count, self.avg_samples_per_sec(),
                    window_rate))

    def _close_window(self):
        """Drain the device queue, fold the open window into the running
        totals, and start the next window.  Returns the closed window's
        global samples/sec (all workers, same units as the running avg)."""
        _device_sync()
        self.end_time = time.time()
        duration = self.end_time - self.start_time
        self.total_elapsed_time += duration
        self.total_timed_steps += self.window_steps
        rate = (self.batch_size * self.num_workers * self.window_steps /
                max(duration, 1e-12))
        self.start_time = self.end_time  # next window starts synced
        self.window_steps = 0
        return rate

    def avg_samples_per_sec(self):
        if self.window_steps > 0:
            # fold the open partial window in — otherwise short runs
            # (< steps_per_output steps) would have no data at all
            self._close_window()
        if self.total_timed_steps > 0:
            samples_per_step = self.batch_size * self.num_workers
            avg_time_per_step = (self.total_elapsed_time /
                                 self.total_timed_steps)
            return samples_per_step / avg_time_per_step
        return float("-inf")
