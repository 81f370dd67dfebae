"""ZeRO-Infinity layer-streaming engine — train models whose parameters do
not fit in HBM.

Reference: the stage-3 + NVMe composition — parameters paged from NVMe at
fetch time (runtime/swap_tensor/partitioned_param_swapper.py:36, wired at
stage3.py:932), gradients partitioned to CPU/NVMe (stage3.py:2088), and
optimizer states swapped around a sub_group-wise step (stage3.py:2777,
2633-2686).  That is the reference's "40B params on one V100" story
(SURVEY.md).

TPU recasting (no autograd hooks; a Python-driven streaming step around
small jitted programs):

  HBM      : boundary activations + at most TWO layer groups of params at a
             time (current + async prefetch) — never the whole model;
  host/NVMe: compute-dtype parameter groups (PartitionedParamSwapper when
             offload_param.device == "nvme"; host arrays for "cpu"), fp32
             gradient accumulators, and the fp32 master + Adam moments
             owned by the host/NVMe optimizer tier (zero/offload.py,
             swap_tensor/optimizer_swapper.py);
  step     : forward streams layer groups up through the loss (head runs
             fused with value_and_grad so the loss cotangent is ready);
             backward re-streams the groups in reverse, rematerializing
             each layer's forward with jax.vjp from its saved input;
             the optimizer sweep then pipelines NVMe master/moment reads,
             native host Adam, and write-backs leaf by leaf.

The model opts in by exposing `layerwise_api()` (models/gpt2.py) — the
split/join of its params into ordered streaming groups plus pure embed /
layer / head-loss functions.  `deepspeed_tpu.initialize` dispatches here
when `zero_optimization.offload_param` is configured on such a model.
"""

import json
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...config import DeepSpeedConfig
from ...utils.logging import log_dist
from ...utils.timer import ThroughputTimer
from ..engine import resolve_mesh_ctx

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
SWEEP_RESULTS_PATH = os.environ.get(
    "DS_AIO_SWEEP_RESULTS",
    os.path.join(_REPO_ROOT, "benchmarks", "aio_sweep_results.txt"))


def load_sweep_ceiling(backend: str,
                       path: str = None) -> Optional[Dict[str, float]]:
    """Measured read/write GB/s ceiling for `backend` from the aio sweep
    artifact (benchmarks/aio_sweep_results.txt `aio_best_config` line) —
    the denominator of the engine's achieved-bytes/s honesty report.
    Returns None when no sweep has been run on this host."""
    path = path or SWEEP_RESULTS_PATH
    best = None
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if row.get("metric") == "aio_best_config":
                    best = row  # later lines win (append-only artifact)
    except OSError:
        return None
    if best is None:
        return None
    ceilings = best.get("ceilings")
    if ceilings is not None:
        if backend in ceilings:
            return {"read_gbps": float(ceilings[backend]["read_gbps"]),
                    "write_gbps": float(ceilings[backend]["write_gbps"])}
        # the sweep never measured THIS backend — no ceiling, rather
        # than another backend's number as a false denominator
        return None
    # pre-backend-axis artifact: one global best
    if "read_gbps" in best:
        return {"read_gbps": float(best["read_gbps"]),
                "write_gbps": float(best["write_gbps"])}
    return None


class _HostFetch:
    """swap_in handle for host-RAM parameter groups (no NVMe tier): the
    'read' is free, so it is all hidden and zero bytes."""

    def __init__(self, tree):
        self._tree = tree
        self.nbytes = 0
        self.hidden_s = 0.0
        self.exposed_s = 0.0

    def wait(self, copy: bool = True):
        return self._tree


class ZeroInfinityEngine:
    """forward/backward/step protocol over streamed parameter groups."""

    def __init__(self, model=None, config=None, model_parameters=None,
                 optimizer=None, lr_scheduler=None, mesh=None, rng=None,
                 training_data=None, collate_fn=None, mpu=None,
                 param_partition_specs=None):
        if not hasattr(model, "layerwise_api"):
            raise ValueError(
                "offload_param requires a model exposing layerwise_api() "
                "(streaming groups) — GPT2Model does; see models/gpt2.py")
        if optimizer is not None:
            raise ValueError(
                "offload_param drives the host/NVMe optimizer tier — a "
                "client optax optimizer cannot be streamed")
        self.module = model
        self.mesh_ctx = resolve_mesh_ctx(config, mesh)
        dp = self.mesh_ctx.data_parallel_world_size
        self.config = (config if isinstance(config, DeepSpeedConfig)
                       else DeepSpeedConfig(config, world_size=dp))
        if self.config.fp16.enabled:
            raise ValueError(
                "the streaming engine is bf16/fp32-native; use bf16 instead "
                "of fp16 (dynamic loss scaling is unnecessary on TPU)")
        self.compute_dtype = (jnp.bfloat16 if self.config.bf16.enabled
                              else jnp.float32)

        api = model.layerwise_api()
        self._split = api["split"]
        self._join = api["join"]
        # memory-lean variant (frees group leaves as it stacks); models
        # that don't provide one fall back to the plain join
        self._join_consuming = api.get("join_consuming", api["join"])
        self._embed_fn = api["embed_fn"]
        self._layer_fn = api["layer_fn"]
        self._head_loss_fn = api["head_loss_fn"]
        self.num_layers = api["num_layers"]
        self._order = (["embed"] +
                       [f"layer{i}" for i in range(self.num_layers)] +
                       ["head"])

        if model_parameters is None:
            raise ValueError("model_parameters is required")

        # ---- host/NVMe tiers ----------------------------------------- #
        zc = self.config.zero_config
        op = zc.offload_param
        import ml_dtypes  # bf16 numpy dtype
        self._np_dtype = (ml_dtypes.bfloat16
                          if self.compute_dtype == jnp.bfloat16
                          else np.float32)
        # cast straight to the compute numpy dtype — no transient fp32 copy
        # of the full model (this engine exists because the model is big)
        groups_compute = self._split(jax.tree.map(
            lambda a: np.asarray(a).astype(self._np_dtype)
            if np.issubdtype(np.asarray(a).dtype, np.floating) or
            str(np.asarray(a).dtype) == "bfloat16" else np.asarray(a),
            model_parameters))
        self._use_nvme_params = op is not None and op.device == "nvme"
        # swap-in look-ahead: how many window buffers the sweeps may hold
        # in flight (2 = double buffer; < 2 serializes reads at use).
        # Validated against buffer_count at the config boundary.
        self._prefetch_depth = (int(op.prefetch_depth)
                                if op is not None else 0)
        if self._use_nvme_params:
            from ..swap_tensor.partitioned_param_swapper import (
                PartitionedParamSwapper)
            swap_dir = os.path.join(
                op.nvme_path or "/tmp/deepspeed_tpu_nvme", "zero_stage_3",
                "params")
            self._swapper = PartitionedParamSwapper(
                swap_dir, groups_compute,
                buffer_count=max(2, op.buffer_count),
                aio_config=self.config.aio_config,
                retry_policy=self.config.resilience_config
                .build_retry_policy())
            for name, tree in groups_compute.items():
                self._swapper.write(name, tree, async_op=True)
            self._swapper.flush_writes()
            self._swapper.snapshot_stats()  # init writes are not step I/O
            self._swapper.drain_write_events()  # ...nor step trace spans
            self._host_groups = None
        else:
            self._swapper = None
            self._host_groups = groups_compute

        # fp32 master + moments: NVMe or host Adam tier.  The fp32 tree is
        # consumed by the tier's constructor (NVMe writes it to files and
        # drops it; host keeps it — that IS the master copy).
        oo = zc.offload_optimizer
        full_f32 = jax.tree.map(lambda a: np.asarray(a, np.float32),
                                model_parameters)
        if oo is not None and oo.device == "nvme":
            from ..swap_tensor import create_nvme_offload_optimizer
            self._opt = create_nvme_offload_optimizer(
                full_f32, self.config,
                gradient_clipping=self.config.gradient_clipping)
        else:
            from .offload import HostOffloadOptimizer
            self._opt = HostOffloadOptimizer(
                full_f32, self.config.optimizer_name or "adam",
                self.config.optimizer_params,
                gradient_clipping=self.config.gradient_clipping)
        del full_f32

        # ---- compiled programs --------------------------------------- #
        cdt = self.compute_dtype

        def cast(tree):
            return jax.tree.map(
                lambda a: a.astype(cdt) if jnp.issubdtype(
                    jnp.asarray(a).dtype, jnp.floating) else jnp.asarray(a),
                tree)

        self._jit_embed = jax.jit(
            lambda e, ids, r: self._embed_fn(cast(e), ids, r))
        self._jit_layer = jax.jit(
            lambda p, h, r, i: self._layer_fn(cast(p), h, r, i))

        def head_valgrad(head_g, embed_g, h, ids, labels):
            def f(hg, eg, hh):
                return self._head_loss_fn(cast(hg), cast(eg), hh, ids,
                                          labels)
            (loss), grads = jax.value_and_grad(f, argnums=(0, 1, 2))(
                head_g, embed_g, h)
            return loss, grads

        self._jit_head = jax.jit(head_valgrad)

        def layer_vjp(p, x, ct, r, i):
            _, vjp = jax.vjp(lambda pp, xx: self._layer_fn(cast(pp), xx,
                                                           r, i), p, x)
            return vjp(ct)

        self._jit_layer_vjp = jax.jit(layer_vjp)

        def embed_vjp(e, ids, ct, r):
            def f(eg):
                h = self._embed_fn(cast(eg), ids, r)
                return jnp.vdot(h.astype(jnp.float32),
                                ct.astype(jnp.float32))
            return jax.grad(f)(e)

        self._jit_embed_vjp = jax.jit(embed_vjp)

        # ---- bookkeeping --------------------------------------------- #
        self.lr_scheduler = lr_scheduler
        self.training_dataloader = self._configure_dataloader(
            training_data, collate_fn)
        self.global_steps = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._rng = rng if rng is not None else jax.random.PRNGKey(42)
        self._grad_groups: Optional[Dict[str, Any]] = None
        self._acts = None
        self._pending = None
        self._last_loss = None
        self.max_live_param_groups = 0
        self._live_now = 0
        # cross-sweep carries: each sweep's FIRST swap-in is issued at the
        # tail of the adjacent sweep (backward's first group under the
        # head compute, next forward's embed under the optimizer sweep) —
        # without these the first read of every sweep is structurally
        # serialized
        self._fwd_carry = None
        self._bwd_carry = None
        # ---- swap-overlap accounting (per optimizer-step window) ----- #
        self._swap_events: List[Dict[str, float]] = []
        self._step_t0: Optional[float] = None
        self.last_swap_stats: Optional[Dict[str, Any]] = None
        self.serialized_swap_steps = 0
        backend = (self._swapper.write_handle.backend_name
                   if self._swapper is not None else "none")
        self.aio_backend = backend
        self.sweep_ceiling = (load_sweep_ceiling(backend)
                              if self._swapper is not None else None)
        self.tput_timer = ThroughputTimer(
            batch_size=self.config.train_micro_batch_size_per_gpu,
            num_workers=dp,
            steps_per_output=self.config.steps_per_print)
        # ---- runtime telemetry monitor (docs/telemetry.md) ------------ #
        # The streaming engine has no static roofline (its step is a
        # host-driven sweep, not one traced program) — reconciliation
        # here is the SWAP lane: achieved GB/s + overlap vs the aio
        # sweep ceiling, which _finalize_swap_stats measures per step.
        self.monitor = None
        self._monitor_seq = None
        if self.config.monitor_config.enabled and (
                jax.process_index() == 0 or
                self.config.monitor_config.fleet or
                self.config.monitor_config.heartbeat):
            from ...monitor import TrainingMonitor
            self.monitor = TrainingMonitor(
                self.config.monitor_config,
                steps_per_print=self.config.steps_per_print,
                predictions=None,
                boundary_fn=self._monitor_boundary_reads,
                swap_stats_fn=lambda: self.last_swap_stats,
                process_index=jax.process_index(),
                world_size=jax.process_count(),
                meta={"engine": type(self).__name__,
                      "params_on": ("nvme" if self._use_nvme_params
                                    else "host"),
                      "aio_backend": self.aio_backend,
                      "prefetch_depth": self._prefetch_depth,
                      "sweep_ceiling": self.sweep_ceiling})
        n_params = sum(int(np.prod(np.shape(leaf)))
                       for leaf in jax.tree.leaves(model_parameters))
        log_dist(
            f"ZeroInfinityEngine: {n_params:,} params in "
            f"{len(self._order)} streamed groups, params_on="
            f"{'nvme' if self._use_nvme_params else 'host'}, "
            f"optimizer={type(self._opt).__name__}, "
            f"aio_backend={self.aio_backend}, "
            f"prefetch_depth={self._prefetch_depth}"
            + (f", sweep_ceiling={self.sweep_ceiling['read_gbps']:.2f}GB/s "
               "read" if self.sweep_ceiling else ""), ranks=[0])

    # ------------------------------------------------------------------ #
    def _configure_dataloader(self, training_data, collate_fn):
        """Same per-process sharding contract as DeepSpeedEngine
        (runtime/engine.py _configure_dataloader)."""
        if training_data is None:
            return None
        from ..dataloader import DeepSpeedDataLoader
        nproc = jax.process_count()
        dp = self.mesh_ctx.data_parallel_world_size
        per_process = (self.config.train_micro_batch_size_per_gpu *
                       dp) // nproc
        return DeepSpeedDataLoader(
            training_data, batch_size=per_process, collate_fn=collate_fn,
            data_parallel_world_size=nproc,
            data_parallel_rank=jax.process_index())

    @property
    def optimizer(self):
        return self._opt

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self.gradient_accumulation_steps() == 0

    def estimate_memory(self):
        """Per-tier byte estimate: HBM holds only the streaming window."""
        group_bytes = {}
        for name in self._order:
            tree = self._group_host(name)
            group_bytes[name] = sum(
                np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree))
        total = sum(group_bytes.values())
        hbm_window = 2 * max(group_bytes.values())
        n = sum(int(np.prod(np.shape(leaf))) for name in self._order
                for leaf in jax.tree.leaves(self._group_host(name)))
        return {
            "hbm_param_window": hbm_window,
            "host_or_nvme_params": total,
            "grads_fp32_host": 4 * n,
            "optimizer_fp32_nvme_or_host": 12 * n,
            "total_hbm_params": hbm_window,   # vs 2n/4n resident baselines
        }

    # ------------------------------------------------------------------ #
    def _group_host(self, name: str):
        if self._swapper is not None:
            return self._swapper.get(name)
        return self._host_groups[name]

    def _release_device(self, ref):
        """Callers MUST rebind: ``p = self._release_device(p)`` — deleting a
        local alias alone would keep the device arrays alive and push peak
        residency past the 2-group window."""
        self._live_now -= 1
        del ref
        return None

    # ---- carried swap-in machinery ----------------------------------- #
    # The sweeps walk a fetch PLAN (ordered group names).  _take(pos)
    # first issues the next prefetch_depth-1 plan positions' NVMe reads,
    # THEN waits for position pos — so group i+1's disk read runs while
    # group i's wait returns (usually instantly, read done under the
    # previous group's compute) and its jitted compute dispatches.  The
    # in-flight handles live in `inflight`, the sweep's carry — the PR 7
    # carried-double-buffer discipline one tier down, with two (or
    # prefetch_depth) pinned window buffers instead of HBM gather slots.

    def _swap_in(self, name: str):
        if self._swapper is not None:
            return self._swapper.swap_in(name)
        return _HostFetch(self._host_groups[name])

    def _sweep_state(self, plan: List[str]):
        return {"plan": plan, "inflight": {}}

    def _take(self, st, pos: int, extra: int = 0):
        """Device params for plan position `pos`; issues the look-ahead.
        `extra` widens it when upcoming positions are consumed by ONE
        compute (the head + tied-embed pair) — without it the pair's
        second read could only start after the first's wait."""
        plan, inflight = st["plan"], st["inflight"]
        if self._prefetch_depth >= 2:
            for k in range(pos, min(pos + self._prefetch_depth, len(plan))):
                if k not in inflight:
                    inflight[k] = self._swap_in(plan[k])
        handle = inflight.pop(pos, None)
        if handle is None:
            # prefetch disabled (or depth exhausted): pay the read inline
            handle = self._swap_in(plan[pos])
        tree = handle.wait()
        if self._prefetch_depth >= 2 and extra:
            # the widened tail issues AFTER the wait: `tree` is a detached
            # copy, so pos's window slot is evictable and the pair fits
            # even in a two-buffer window
            ahead = self._prefetch_depth + extra
            for k in range(pos + 1, min(pos + ahead, len(plan))):
                if k not in inflight:
                    inflight[k] = self._swap_in(plan[k])
        if handle.nbytes:
            # t_issue/t_done are absolute perf_counter stamps: the monitor
            # trace exporter turns the window into a Perfetto span
            self._swap_events.append({
                "name": plan[pos], "bytes": float(handle.nbytes),
                "hidden_s": handle.hidden_s, "exposed_s": handle.exposed_s,
                "t_issue": handle.t_issue,
                "t_done": (handle.t_issue + handle.hidden_s +
                           handle.exposed_s)})
        self._live_now += 1
        self.max_live_param_groups = max(self.max_live_param_groups,
                                         self._live_now)
        return jax.tree.map(jnp.asarray, tree)

    def _release_group(self, name: str) -> None:
        if self._swapper is not None:
            self._swapper.release(name)

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # ------------------------------------------------------------------ #
    _trace = bool(int(os.environ.get("DS_INFINITY_TRACE", "0")))

    def _t(self, msg):
        if self._trace:
            import time as _time
            print(f"[inf-trace] {msg} @{_time.time():.1f}", flush=True)

    def forward(self, input_ids, labels=None):
        """Stream groups forward; returns the loss.  The head runs fused
        with value_and_grad so backward() starts with the cotangent ready
        (the reference's PreBackwardFunction re-fetch begins the same way,
        stage3.py:546).

        The fetch plan is carried: _take(i) issues layer i+1's (and, at
        depth > 2, further) NVMe reads BEFORE waiting on layer i, so the
        disk streams the next group while this group's compute holds the
        device — swap-in latency hides under MXU work instead of
        serializing the sweep."""
        self.tput_timer.start()
        if self.monitor is not None:
            self.monitor.mark_step_start()
            self._monitor_seq = int(np.shape(input_ids)[-1])
        if self._step_t0 is None:
            self._step_t0 = time.perf_counter()
        self._t("fwd start")
        rng = self._next_rng() if self._is_dropout_mode() else None
        ids = jnp.asarray(input_ids)
        lbl = None if labels is None else jnp.asarray(labels)

        plan = (["embed"] + [f"layer{i}" for i in range(self.num_layers)]
                + ["head", "embed"])
        st = self._sweep_state(plan)
        if self._fwd_carry is not None:     # issued under the last step()
            st["inflight"][0] = self._fwd_carry
            self._fwd_carry = None
        embed_g = self._take(st, 0)
        h = self._jit_embed(embed_g, ids, rng)
        acts = [h]
        # release the embed group during the layer sweep — the head step
        # re-fetches it (tied wte); peak device residency stays at 2 groups
        embed_g = self._release_device(embed_g)
        self._release_group("embed")
        for i in range(self.num_layers):
            # on the last layer the look-ahead covers BOTH head groups —
            # jit_head consumes head + tied embed in one compute, so the
            # pair must stream together under this layer's window
            extra = 1 if i == self.num_layers - 1 else 0
            p = self._take(st, 1 + i, extra=extra)
            h = self._jit_layer(p, h, rng, jnp.int32(i))
            acts.append(h)
            p = self._release_device(p)
            self._release_group(f"layer{i}")

        self._t("fwd layers done")
        head_g = self._take(st, 1 + self.num_layers)
        embed_g = self._take(st, 2 + self.num_layers)
        loss, (g_head, g_embed_head, dh) = self._jit_head(
            head_g, embed_g, h, ids, lbl)
        head_g = self._release_device(head_g)
        embed_g = self._release_device(embed_g)
        self._release_group("head")
        self._release_group("embed")
        if self._prefetch_depth >= 2 and self._swapper is not None:
            # backward's first group streams in under the head compute
            self._bwd_carry = self._swap_in(f"layer{self.num_layers - 1}")
        self._t("fwd head done")
        self._acts = acts
        self._pending = {"rng": rng, "ids": ids, "dh": dh,
                         "g_head": g_head, "g_embed_head": g_embed_head}
        self._last_loss = loss
        return loss

    __call__ = forward

    def _is_dropout_mode(self) -> bool:
        cfg = getattr(self.module, "config", None)
        if cfg is None:
            return False
        return any(getattr(cfg, k, 0.0) > 0.0 for k in
                   ("embd_dropout", "attn_dropout", "hidden_dropout"))

    def backward(self, loss=None):
        """Re-stream groups in reverse; accumulate fp32 grads on host
        (the reference partitions grads to CPU/NVMe — stage3.py:2088).

        Gradient fetches are PIPELINED one group behind the compute: the
        device->host copy of layer i+1's grads is started asynchronously
        (copy_to_host_async) and materialized while layer i's vjp runs,
        so transfer overlaps compute instead of serializing it (the
        reference overlaps the same way on a side CUDA stream,
        stage2.py:1326; VERDICT r2 weak #7).  Device residency: the params
        window + up to TWO grad groups transiently (the in-flight copy
        and the one the running vjp is producing) — size beyond-HBM
        configs accordingly."""
        assert self._pending is not None, "backward() before forward()"
        pend, acts = self._pending, self._acts
        rng, ids, dh = pend["rng"], pend["ids"], pend["dh"]

        def acc(name, tree):
            host = jax.tree.map(lambda a: np.asarray(a, np.float32), tree)
            if self._grad_groups is None:
                self._grad_groups = {}
            if name in self._grad_groups:
                self._grad_groups[name] = jax.tree.map(
                    np.add, self._grad_groups[name], host)
            else:
                self._grad_groups[name] = host

        def start_copy(name, tree):
            for leaf in jax.tree.leaves(tree):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
            return (name, tree)

        self._t("bwd start")
        inflight = start_copy("head", pend["g_head"])
        plan = ([f"layer{i}" for i in reversed(range(self.num_layers))]
                + ["embed"])
        st = self._sweep_state(plan)
        if self._bwd_carry is not None:     # issued under the head compute
            st["inflight"][0] = self._bwd_carry
            self._bwd_carry = None
        for pos, i in enumerate(reversed(range(self.num_layers))):
            p = self._take(st, pos)
            gp, dh = self._jit_layer_vjp(p, acts[i], dh, rng, jnp.int32(i))
            # materialize the PREVIOUS group (its async copy overlapped
            # this vjp's dispatch) before starting the next copy — one
            # d2h copy in flight at a time
            acc(*inflight)
            inflight = start_copy(f"layer{i}", gp)
            p = self._release_device(p)
            self._release_group(f"layer{i}")
            self._t(f"bwd layer{i} done")

        embed_g = self._take(st, self.num_layers)
        g_embed = self._jit_embed_vjp(embed_g, ids, dh, rng)
        g_embed = jax.tree.map(jnp.add, g_embed,
                               jax.tree.map(jnp.asarray,
                                            pend["g_embed_head"]))
        acc(*inflight)
        acc("embed", g_embed)
        embed_g = self._release_device(embed_g)
        self._release_group("embed")
        if self._prefetch_depth >= 2 and self._swapper is not None:
            # next forward's embed streams in under the optimizer sweep
            # (write() keeps the pending slot coherent when the step
            # rewrites the group's file)
            self._fwd_carry = self._swap_in("embed")
        self._acts = None
        self._pending = None
        self.micro_steps += 1
        return loss if loss is not None else self._last_loss

    def step(self):
        """Optimizer sweep at the accumulation boundary: the host/NVMe tier
        pipelines master/moment reads, native Adam, and write-backs leaf by
        leaf (reference: stage3.py:2777 sub_group step)."""
        if not self.is_gradient_accumulation_boundary():
            return
        assert self._grad_groups is not None, "step() before backward()"
        gas = self.gradient_accumulation_steps()
        # consuming join: each layer-group grad leaf is freed as its row
        # is copied into the stacked layout, so the join transient is one
        # stacked leaf — the naive join's full second copy (~17 GB on a
        # 4.2B model) OOMed a 125 GB host at exactly this point (r4)
        self._t("step join start")
        box = [self._join_consuming(self._grad_groups)]
        self._grad_groups = None  # leaves now owned by the box alone
        lr = None
        if self.lr_scheduler is not None:
            lr = float(self.lr_scheduler.lr_at(self._opt.step_count()))
        # ownership-box call: apply takes the tree out of the box, so the
        # native sweep can free each grad leaf right after its update
        self._t("step apply start")
        new_host = self._opt.apply(box, 1.0 / gas, lr,
                                   self.compute_dtype, boxed=True)
        self._t("step apply done")
        overflow = new_host is None
        if not overflow:
            # astype(copy=False): the emit_bf16 path already returns the
            # store dtype — an unconditional astype here was a second
            # full-model copy at exactly the step's memory peak
            new_groups = self._split(jax.tree.map(
                lambda a: np.asarray(a).astype(self._np_dtype, copy=False)
                if np.issubdtype(np.asarray(a).dtype, np.floating) or
                str(np.asarray(a).dtype) == "bfloat16" else np.asarray(a),
                new_host))
            if self._swapper is not None:
                for name, tree in new_groups.items():
                    self._swapper.write(name, tree, async_op=True)
                self._swapper.flush_writes()
            else:
                self._host_groups = new_groups
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
        else:
            self.skipped_steps += 1
        self.global_steps += 1
        self.tput_timer.stop(global_step=True)
        self._finalize_swap_stats()
        if self.monitor is not None:
            from ...monitor import record as mrec
            tokens = (self.config.train_batch_size * self._monitor_seq
                      if self._monitor_seq else None)
            self.monitor.end_step(
                self.global_steps, loss=self._last_loss, tokens=tokens,
                counters={mrec.F_SKIPPED_STEPS: self.skipped_steps,
                          mrec.F_DISPATCHES_PER_STEP: None},
                # THIS step's swap stats are already host data — records
                # carry per-step values, not the window boundary's
                swap=self.last_swap_stats)
        if self.global_steps % self.config.steps_per_print == 0:
            stats = self.last_swap_stats or {}
            extra = ""
            if stats.get("read_bytes"):
                extra = (f", swap_read={stats['read_gbps']:.2f}GB/s"
                         + (f" ({stats['read_vs_ceiling']:.0%} of sweep "
                            "ceiling)" if stats.get("read_vs_ceiling")
                            is not None else "")
                         + f", overlap={stats['overlap_fraction']:.0%}")
            log_dist(f"step={self.global_steps}, "
                     f"loss={float(self._last_loss):.6f}{extra}", ranks=[0])

    # ------------------------------------------------------------------ #
    def _finalize_swap_stats(self):
        """Fold the step window's swap-in handle timings into the honesty
        report: achieved bytes/s (lower bound — per-group issue->done
        windows), the bytes-weighted overlap fraction (how much of the
        swap traffic hid under compute), and the serialized-swap-in
        finding (auditor-style WARNING: prefetch was configured but a
        group's read was paid inline on the critical path)."""
        events, self._swap_events = self._swap_events, []
        t0, self._step_t0 = self._step_t0, None
        if (self.monitor is not None and self.monitor.trace_active
                and self._swapper is not None):
            # the step's I/O timeline becomes Perfetto spans: swap-in
            # issue→done windows (+ exposed-wait tails) and the write-
            # back issue→flush windows
            self.monitor.trace.add_swap_read_events(
                events, step=self.global_steps)
            self.monitor.trace.add_swap_write_events(
                self._swapper.drain_write_events(), step=self.global_steps)
        if self._swapper is None:
            self.last_swap_stats = None
            return
        io = self._swapper.snapshot_stats()
        read_bytes = sum(e["bytes"] for e in events)
        hidden_s = sum(e["hidden_s"] for e in events)
        exposed_s = sum(e["exposed_s"] for e in events)
        overlap_bytes = sum(
            e["bytes"] * (e["hidden_s"] / (e["hidden_s"] + e["exposed_s"]))
            for e in events if e["hidden_s"] + e["exposed_s"] > 0)
        serialized = [e["name"] for e in events
                      if e["exposed_s"] > max(e["hidden_s"], 1e-4)]
        window_s = hidden_s + exposed_s
        stats: Dict[str, Any] = {
            "aio_backend": self.aio_backend,
            "prefetch_depth": self._prefetch_depth,
            "read_bytes": read_bytes,
            "read_exposed_s": exposed_s,
            "read_hidden_s": hidden_s,
            # lower bound: per-group issue->done windows overlap each
            # other at depth > 2, so the true device-side rate is >= this
            "read_gbps": (read_bytes / window_s / 1e9) if window_s else 0.0,
            "overlap_bytes": overlap_bytes,
            "overlap_fraction": (overlap_bytes / read_bytes
                                 if read_bytes else 1.0),
            "serialized_swap_ins": serialized,
            "serialized_reads_inline": io.get("serialized_reads", 0.0),
            "write_bytes": io.get("write_bytes", 0.0),
            "write_exposed_s": io.get("write_wait_s", 0.0),
            "step_wall_s": (time.perf_counter() - t0) if t0 else 0.0,
        }
        if self.sweep_ceiling is not None and stats["read_gbps"]:
            stats["sweep_read_gbps"] = self.sweep_ceiling["read_gbps"]
            stats["read_vs_ceiling"] = (stats["read_gbps"] /
                                        self.sweep_ceiling["read_gbps"])
        else:
            stats["read_vs_ceiling"] = None
        opt_stats = getattr(self._opt, "last_sweep_stats", None)
        if opt_stats is not None:
            stats["optimizer_sweep"] = dict(opt_stats)
        if serialized and self._prefetch_depth >= 2:
            self.serialized_swap_steps += 1
            log_dist(
                f"[infinity-schedule] WARNING: {len(serialized)} serialized "
                f"swap-in(s) this step ({', '.join(serialized[:4])}"
                f"{'...' if len(serialized) > 4 else ''}) — the NVMe read "
                "was paid on the critical path despite prefetch_depth="
                f"{self._prefetch_depth}.  The disk is slower than the "
                "per-group compute window; raise the group size, deepen "
                "the prefetch, or check the aio backend "
                f"({self.aio_backend}) against the sweep ceiling.",
                ranks=[0])
        self.last_swap_stats = stats

    def swap_stats(self) -> Optional[Dict[str, Any]]:
        """Swap-overlap report for the last completed optimizer step."""
        return self.last_swap_stats

    def _monitor_boundary_reads(self) -> Dict[str, Any]:
        """Flush-boundary reads for the monitor (host-side: the streaming
        optimizer tier owns its step count as a plain int)."""
        lr = None
        if self.lr_scheduler is not None:
            try:
                lr = float(self.lr_scheduler.lr_at(self._opt.step_count()))
            except Exception:  # noqa: BLE001
                lr = None
        return {"lr": lr, "loss_scale": None}

    # ------------------------------------------------------------------ #
    def module_state_dict(self):
        """Consolidated fp32 master weights (from the optimizer tier)."""
        return self._opt.master_params

    def save_checkpoint(self, save_dir, tag=None, client_state=None):
        from .. import checkpoint as ckpt_mod
        tag = tag or f"global_step{self.global_steps}"
        client = dict(client_state or {})
        client.update({"global_steps": self.global_steps,
                       "micro_steps": self.micro_steps,
                       "skipped_steps": self.skipped_steps,
                       # bit-exact dropout resume (same as DeepSpeedEngine)
                       "engine_rng": np.asarray(
                           jax.random.key_data(self._rng)).tolist(),
                       "engine_rng_impl": str(
                           jax.random.key_impl(self._rng))})
        return ckpt_mod.save_checkpoint_state(
            save_dir, tag, module_state={"module": self.module_state_dict()},
            optimizer_state={"optimizer": self._opt.state_dict()},
            client_state=client)

    def load_checkpoint(self, load_dir, tag=None):
        from .. import checkpoint as ckpt_mod
        module_tmpl = {"module": self.module_state_dict()}
        opt_tmpl = {"optimizer": self._opt.state_dict()}
        module_state, opt_state, client = ckpt_mod.load_checkpoint_state(
            load_dir, tag, module_tmpl, opt_tmpl)
        self._opt.load_state_dict(opt_state["optimizer"])
        master = module_state["module"]
        self._opt.load_master_params(master)
        new_groups = self._split(jax.tree.map(
            lambda a: np.asarray(a, np.float32).astype(self._np_dtype),
            master))
        if self._swapper is not None:
            for name, tree in new_groups.items():
                self._swapper.write(name, tree, async_op=True)
            self._swapper.flush_writes()
            # restore writes are not step I/O: keep them out of the next
            # step's trace (same exclusion as the init write-back)
            self._swapper.drain_write_events()
        else:
            self._host_groups = new_groups
        self.global_steps = client.get("global_steps", 0)
        self.micro_steps = client.get("micro_steps", 0)
        self.skipped_steps = client.get("skipped_steps", 0)
        if client.get("engine_rng") is not None:
            try:
                self._rng = jax.random.wrap_key_data(
                    jnp.asarray(np.asarray(client["engine_rng"],
                                           np.uint32)),
                    impl=client.get("engine_rng_impl", "threefry2x32"))
            except Exception as e:  # noqa: BLE001 — old/foreign ckpt
                log_dist(f"engine_rng restore skipped: {e}", ranks=[0])
        return load_dir, client
