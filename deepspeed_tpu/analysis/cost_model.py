"""Static step-time model — a roofline lower bound for the traced step.

The audit report carries this lower bound next to the lockstep
signature and wire bytes, and the monitor reconciles measured windows
against it, so "the step should be ~X ms" is a number computed
from the program, not a hope.  Three terms, each a genuine lower bound:

  compute   total jaxpr flops (profiling/flops_profiler walk, scan trip
            counts multiplied in) at the configured peak
  memory    program I/O bytes — every input read and output written at
            least once, whatever XLA fuses in between — at HBM bandwidth
  comm      overlap-adjusted wire time: the hidden fraction of each
            collective (analysis/overlap.py) rides under compute, the
            exposed remainder is added on top.  Fused collective-matmul
            transports (per-tile wire under the producer/consumer GEMM,
            ops/collective_matmul.py) are hidden by construction — they
            price entirely in the hidden lane and are broken out as
            ``wire_bytes_fused`` for attribution
  swap      offload-tier traffic (params/optimizer state streamed from
            NVMe) at the MEASURED aio sweep ceiling, not HBM speed — a
            double-buffered stream (prefetch/pipeline depth >= 2) rides
            under compute like hidden comm, a serialized one is added
            on top like exposed comm

    t_lb = max(compute, memory, hidden_comm, swap_hidden)
           + exposed_comm + swap_exposed

The model is deliberately optimistic (true lower bound): measured step
time below it means the model's hardware constants are wrong; measured
far above it bounds how much the schedule is leaving on the table.
"""

from typing import Any, Dict, List, Optional

from .. import constants as C
from .jaxpr_walk import as_jaxpr, aval_bytes
from .overlap import CollectiveOverlap


def program_io_bytes(closed_jaxpr) -> int:
    """Bytes the program must move through HBM at least once: every
    input read, every output written."""
    jx = as_jaxpr(closed_jaxpr)
    return (sum(aval_bytes(v) for v in jx.invars)
            + sum(aval_bytes(v) for v in jx.constvars)
            + sum(aval_bytes(v) for v in jx.outvars))


def per_lane_predictions(step_time: Dict[str, Any]) -> Dict[str, Any]:
    """Normalize a step-time payload (build_step_time_model output) into
    the per-lane form the runtime monitor's reconciliation
    (monitor/reconcile.py) and the autotuner consume: one entry per
    cost-model lane plus the binding term and the lower bound itself.
    Single-sourced
    here so the static and measured halves can never disagree on lane
    names."""
    return {
        "compute": step_time["t_compute_s"],
        "memory": step_time["t_memory_s"],
        "hidden_comm": step_time["t_comm_hidden_s"],
        "exposed_comm": step_time["t_comm_exposed_s"],
        "swap": step_time.get("t_swap_s", 0.0),
        "bound": step_time["bound"],
        "predicted_step_time_lb_s": step_time["predicted_step_time_lb_s"],
    }


def hw_constants(cfg) -> Dict[str, float]:
    """The hardware model under the canonical names (C.ANALYSIS_HW_KEYS)
    — what the report payload publishes and what a calibration file
    overrides.  Single-sourced so the two sides can never drift."""
    return {C.ANALYSIS_HW_PEAK_TFLOPS: cfg.hw_peak_tflops,
            C.ANALYSIS_HW_HBM_GBPS: cfg.hw_hbm_gbps,
            C.ANALYSIS_HW_ICI_GBPS: cfg.hw_ici_gbps}


def swap_lane(zero_cfg, aio_cfg, param_bytes: int,
              opt_state_bytes: int) -> Optional[Dict[str, Any]]:
    """Per-step offload-tier traffic model for NVMe-backed configs.

    A streamed config's params never sit in HBM: the step must READ them
    from NVMe every forward (and again on the backward re-fetch) and
    WRITE the updated values back; an NVMe optimizer sweep reads and
    writes its state every step.  Pricing that traffic at HBM speed made
    a streamed config rank identically to a resident one — here it moves
    at the MEASURED aio sweep ceiling for the configured backend
    (runtime/zero/infinity.load_sweep_ceiling), falling back to a
    conservative default when no sweep artifact exists on this host.

    Returns None when neither offload target is NVMe (host-RAM tiers are
    treated as free, matching infinity.py's _HostFetch); otherwise a dict
    build_step_time_model folds into the lower bound: hidden time when
    the tier is double-buffered (prefetch_depth / pipeline_depth >= 2),
    exposed time when serialized.
    """
    op = zero_cfg.offload_param
    oo = zero_cfg.offload_optimizer
    nvme_param = op is not None and op.device == C.OFFLOAD_NVME_DEVICE
    nvme_opt = oo is not None and oo.device == C.OFFLOAD_NVME_DEVICE
    if not nvme_param and not nvme_opt:
        return None

    from ..runtime.zero.infinity import load_sweep_ceiling
    backend = aio_cfg.backend if aio_cfg is not None else (
        C.AIO_BACKEND_DEFAULT)
    ceiling = load_sweep_ceiling(backend)
    if ceiling is None and backend == C.AIO_BACKEND_AUTO:
        # auto resolves per-host; take the best measured backend rather
        # than no ceiling at all
        for b in (C.AIO_BACKEND_IO_URING, C.AIO_BACKEND_BATCHED,
                  C.AIO_BACKEND_THREADPOOL):
            ceiling = load_sweep_ceiling(b)
            if ceiling is not None:
                break
    if ceiling is not None:
        read_gbps = ceiling["read_gbps"]
        write_gbps = ceiling["write_gbps"]
        source = f"sweep_ceiling:{backend}"
    else:
        read_gbps = write_gbps = C.AUTOTUNE_NVME_FALLBACK_GBPS
        source = "fallback_default"

    t_hidden = t_exposed = 0.0
    read_bytes = write_bytes = 0
    if nvme_param:
        # forward read + backward re-fetch; updated params written back
        r, w = 2 * param_bytes, param_bytes
        t = r / (read_gbps * 1e9) + w / (write_gbps * 1e9)
        if op.prefetch_depth >= 2:
            t_hidden += t
        else:
            t_exposed += t
        read_bytes += r
        write_bytes += w
    if nvme_opt:
        # the sweep reads and writes every state leaf once per step
        r = w = opt_state_bytes
        t = r / (read_gbps * 1e9) + w / (write_gbps * 1e9)
        if getattr(oo, "pipeline_depth", 2) >= 2:
            t_hidden += t
        else:
            t_exposed += t
        read_bytes += r
        write_bytes += w
    return {"t_hidden_s": t_hidden, "t_exposed_s": t_exposed,
            "read_bytes": int(read_bytes), "write_bytes": int(write_bytes),
            "read_gbps": read_gbps, "write_gbps": write_gbps,
            "source": source}


def build_step_time_model(total_flops: int, io_bytes: int,
                          records: List[CollectiveOverlap],
                          cfg,
                          swap: Optional[Dict[str, Any]] = None,
                          hlo_only_wire_bytes: int = 0
                          ) -> Dict[str, Any]:
    """Combine the roofline terms into the report payload.

    ``records`` must already be the per-OPTIMIZER-STEP set (the auditor
    repeats the modular grad program's records gas times, matching the
    wire-byte accounting).  ``swap`` is an optional offload-tier traffic
    model (``swap_lane``): its hidden time joins the max() roofline, its
    exposed time is added on top like exposed comm.
    ``hlo_only_wire_bytes`` is per-step wire the HLO-level SPMD audit
    found that the jaxpr accounting never saw (compiler-inserted
    collectives; analysis/hlo_audit.py) — no overlap record exists for
    it, so it prices fully EXPOSED: the lower bound must stop
    undercounting the compiled program's wire."""
    peak_flops_s = cfg.hw_peak_tflops * 1e12
    hbm_bw = cfg.hw_hbm_gbps * 1e9
    wire_bw = cfg.hw_ici_gbps * 1e9

    t_compute = total_flops / peak_flops_s
    t_memory = io_bytes / hbm_bw
    hidden_bytes = sum(r.wire_bytes * r.mult * r.hidden_fraction
                       for r in records)
    exposed_bytes = sum(r.wire_bytes * r.mult * (1.0 - r.hidden_fraction)
                        for r in records)
    # fused collective-matmul transports (per-tile wire under the
    # producer/consumer GEMM) ride at hidden_fraction 1.0 — broken out
    # so the reconciliation can attribute a fused config's win to the
    # hidden-comm lane explicitly
    fused_bytes = sum(r.wire_bytes * r.mult for r in records
                      if getattr(r, "fused", False))
    t_hidden = hidden_bytes / wire_bw
    t_exposed = (exposed_bytes + hlo_only_wire_bytes) / wire_bw
    t_swap_hidden = float(swap["t_hidden_s"]) if swap else 0.0
    t_swap_exposed = float(swap["t_exposed_s"]) if swap else 0.0

    terms = {"compute": t_compute, "memory": t_memory,
             "hidden_comm": t_hidden, "swap": t_swap_hidden}
    bound = max(terms, key=terms.get)
    t_lb = terms[bound] + t_exposed + t_swap_exposed
    out = {
        "flops_per_step": int(total_flops),
        "io_bytes_per_step": int(io_bytes),
        "wire_bytes_hidden": int(hidden_bytes),
        "wire_bytes_exposed": int(exposed_bytes),
        "wire_bytes_fused": int(fused_bytes),
        "wire_bytes_hlo_only": int(hlo_only_wire_bytes),
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_comm_hidden_s": t_hidden,
        "t_comm_exposed_s": t_exposed,
        "t_swap_s": t_swap_hidden + t_swap_exposed,
        "bound": bound,
        "predicted_step_time_lb_s": t_lb,
        "hw": {"peak_tflops": cfg.hw_peak_tflops,
               "hbm_gbps": cfg.hw_hbm_gbps,
               "ici_gbps": cfg.hw_ici_gbps},
    }
    if swap is not None:
        out["swap"] = dict(swap)
    return out
