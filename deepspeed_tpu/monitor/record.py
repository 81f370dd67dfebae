"""Step-record schema — the single source of metric field names.

One optimizer step produces one structured record.  Every consumer —
the JSONL/CSV/TensorBoard writers and the reconciliation report —
imports these names instead of spelling its own, so a field rename is a
one-file change.

The record is assembled with BOUNDARY-ONLY host reads: per-step fields
are either pure host state (wall time, counters) or device scalar
*references* that the MetricsStream batches into one fetch at the flush
window boundary (monitor.py).  Nothing in this module syncs the device
per step — the PR-3 async host loop's no-hot-loop-sync guarantee is the
design constraint the whole subsystem is built around.
"""

from typing import Any, Dict, Optional

# Schema version of the record stream.  v1 (PR 9) had no host identity;
# v2 (PR 10) adds host / process_index / world_size to every
# single-host-attributable record plus the fleet/health record kinds
# (the `fleet` aggregate carries world_size and per-host columns — it
# describes the whole fleet, so a single host identity would mislead).
# The version rides every meta record and the trace file's otherData so
# a consumer can tell which era a stream came from.
SCHEMA_VERSION = 2

# ---- record kinds ---------------------------------------------------- #
KIND_STEP = "step"
KIND_RECONCILE = "reconcile"
KIND_META = "meta"
# fleet-aggregation kinds (monitor/fleet.py): one record per host per
# flush window, one fleet-aggregate record per window, and structured
# health events (monitor/health.py)
KIND_FLEET_HOST = "fleet_host"
KIND_FLEET = "fleet"
KIND_HEALTH = "health"
# MoE routing telemetry (monitor/moe.py): one record per flush window
# summarizing the device-resident RoutingStats accumulator — expert
# popularity, drop/overflow accounting, router entropy/confidence
KIND_MOE = "moe"
# resilience plane (runtime/resilience): a fired chaos-injected fault
# (post-mortems separate injected from organic failures) and a fallback-
# ladder step-down from the degradation registry
KIND_CHAOS = "chaos"
KIND_DEGRADATION = "degradation"
# what the host thread waited for (monitor/trace.py): one record per
# program JAX was asked to compile, from the import of the package on
# (program, trace_s, lower_s, backend_s, outcome compiled | fetched |
# uncached, fetch_s, saved_s, during, step), and one per optimizer step
# that took over twice the median of the last 32 (runtime/engine.py
# _note_step_interval)
KIND_COMPILE = "compile"
KIND_SLOW_STEP = "slow_step"

# ---- per-step field names (the schema) ------------------------------- #
F_KIND = "kind"
F_STEP = "step"
F_LOSS = "loss"
F_LR = "lr"
F_LOSS_SCALE = "loss_scale"
F_WALL_TIME_S = "wall_time_s"
F_TOKENS_PER_SEC = "tokens_per_sec"
F_MEM_PEAK_BYTES = "mem_peak_bytes"
F_MEM_IN_USE_BYTES = "mem_in_use_bytes"
F_MEM_SOURCE = "mem_source"
F_SKIPPED_STEPS = "skipped_steps"
F_SENTINEL_ANOMALIES = "sentinel_anomalies"
F_SENTINEL_SKIPS = "sentinel_skips"
F_RETRACES = "retraces"
F_DISPATCHES_PER_STEP = "dispatches_per_step"
# cumulative transient-I/O retries absorbed by the RetryPolicy
# (resilience/retry.py) — nonzero means the run rode out real faults
F_IO_RETRIES = "io_retries"
# programs JAX was asked to compile during the step (measured; the
# compile record has each), and launches of the grad program that
# stalled on the host (engine LAUNCH_STALL_S)
F_COMPILES = "compiles"
F_STALLED_LAUNCHES = "stalled_launches"
F_SWAP_READ_GBPS = "swap_read_gbps"
F_SWAP_OVERLAP_FRACTION = "swap_overlap_fraction"
F_SWAP_READ_VS_CEILING = "swap_read_vs_ceiling"
# host identity (schema v2): populated on every record, single-host runs
# included — a merged multi-host JSONL stream stays attributable per line
F_HOST = "host"
F_PROCESS_INDEX = "process_index"
F_WORLD_SIZE = "world_size"
# per-step host-gap: wall time between the previous step's end_step and
# this step's first forward (dataloader / host work the device waits on)
F_HOST_GAP_S = "host_gap_s"

# CSV column order; JSONL records carry the same names (plus any
# engine-specific extras, which CSV drops — CSV is the fixed-width view).
# Schema v2 appends the identity + host-gap columns after the v1 set, so
# v1 tooling reading by position keeps working on the shared prefix.
STEP_RECORD_FIELDS = (
    F_STEP, F_LOSS, F_LR, F_LOSS_SCALE, F_WALL_TIME_S, F_TOKENS_PER_SEC,
    F_MEM_PEAK_BYTES, F_MEM_IN_USE_BYTES, F_MEM_SOURCE,
    F_SKIPPED_STEPS, F_SENTINEL_ANOMALIES, F_SENTINEL_SKIPS, F_RETRACES,
    F_DISPATCHES_PER_STEP,
    F_SWAP_READ_GBPS, F_SWAP_OVERLAP_FRACTION, F_SWAP_READ_VS_CEILING,
    F_HOST_GAP_S, F_HOST, F_PROCESS_INDEX, F_WORLD_SIZE,
    # appended after the released v2 set (position-readers keep their
    # shared prefix): retry counters ride every step record
    F_IO_RETRIES,
    # PR 56
    F_COMPILES, F_STALLED_LAUNCHES,
)

# ---- recomputation-plan fields of a meta record ---------------------- #
# What the checkpointed layer scan keeps (runtime/activation_checkpointing/
# checkpointing.py checkpoint_layer): one meta record per traced plan,
# at the first flush boundary after the trace.
M_REMAT_OFFERED = "remat_offered"            # names the layer offers
M_REMAT_KEPT = "remat_kept"                  # the prefix the budget admits
M_REMAT_KEPT_BYTES_PER_LAYER = "remat_kept_bytes_per_layer"
M_REMAT_KEPT_BYTES = "remat_kept_bytes"      # over all layers, a device
# its parts, [[name, bytes]]; on the record only where several names are
# kept (one name's bytes are M_REMAT_KEPT_BYTES)
M_REMAT_KEPT_BYTES_BY_NAME = "remat_kept_bytes_by_name"
M_REMAT_LAYERS = "remat_layers"
M_REMAT_BUDGET_BYTES = "remat_budget_bytes"  # limit - state - working set
M_REMAT_BYTES_LIMIT = "remat_bytes_limit"
M_REMAT_STATE_BYTES = "remat_state_bytes"
M_REMAT_WORKING_SET_BYTES = "remat_working_set_bytes"
# times the whole stack runs on the same weights; on the record only where
# it is not 1, and then M_REMAT_LAYERS and the bytes count applications
M_REMAT_PASSES = "remat_passes"
# bytes a layer of what the stack's carry holds beside its stream(s), a
# device (models/zaya.py: the router's state); on the record only where
# there is such a carry, and then M_REMAT_WORKING_SET_BYTES counts it
M_REMAT_SIDE_CARRY_BYTES = "remat_side_carry_bytes"

# ---- the plan of a stack of unlike layers, on the same meta record --- #
# (models/phi4flash.py; checkpointing.checkpoint_layers carries it)
M_STACK_LAYERS = "stack_layers"      # [[published index, kind, window|0]]
M_STACK_SCAN_CHUNK = "stack_scan_chunk"           # positions a chunk
M_STACK_SCAN_ENTRY_BYTES = "stack_scan_entry_state_bytes"  # a scan call
M_STACK_CROSS_LAYER_KEPT = "stack_cross_layer_kept"  # [[name, bytes]]
# (models/laguna.py) the routed experts this program holds of those the
# router scores: [first, count, of]
M_STACK_EXPERTS_HELD = "stack_experts_held"
# rows of a sparse layer's row buffers: the held experts' even share of
# the picks, which the dispatch walks in as many chunks as it takes
M_STACK_DISPATCH_ROWS = "stack_dispatch_rows"
# which rotation of q and k each attention kind runs: [[kind, "kernel",
# positions, heads a block]] (ops/rotary.py) or [[kind, "xla"]]
M_STACK_ROTARY = "stack_rotary"
# (models/glm4_moe_lite.py) latent attention's widths: [query latent,
# key/value latent, unrotated and rotated dimensions of a query and key
# head, value head, heads]
M_STACK_LATENT = "stack_latent_attention"
# multi-token prediction: [modules, the weight of their loss]
M_STACK_MTP = "stack_prediction_modules"
# (models/ouro.py) a stack run several times on the same weights:
# [passes, layer applications a step]
M_STACK_PASSES = "stack_passes"
# (models/keye_vl2.py) attention over keys a learned indexer chooses:
# [indexer heads, their size, keys kept a query, "kernels" or "xla"
# (ops/indexed_attention.py: which form index, select, core and align run)]
M_STACK_INDEXER = "stack_indexer"
# (models/granite_hybrid.py, models/nemotron_h.py) Mamba-2 mixers on
# ops/ssd_scan.py: ["kernel" or "xla", positions a chunk, bytes of
# chunk-entry states a layer's scan saves, the runs of like layers ("mamba
# x5, attention, mamba x4"; "M, E, M, E, M, *, E, M, E" where every layer
# is one sublayer), "scanned" or "unrolled", groups of B and C, "kernel" or
# "xla" again for the conv before the scan (ops/causal_conv.py)]
M_STACK_SSD = "stack_ssd"
# (models/xing4.py) a residual path of several streams mixed by
# hyper-connections (ops/hyper_connection.py): [streams, Sinkhorn rounds,
# the clamp's two ends]
M_STACK_STREAMS = "stack_streams"
# (models/zaya.py) compressed convolutional attention and the router
# that carries a state: [query heads, key/value heads, their size, taps
# of the depthwise conv, taps of the conv within a head, the router
# state's width]
M_STACK_CCA = "stack_cca"

# ---- fleet field names (fleet.py / health.py payloads) --------------- #
FL_WINDOW_START = "window_start_step"
FL_WINDOW_END = "window_end_step"
FL_HOSTS = "hosts"
FL_STEP_TIME_MEAN_S = "step_time_mean_s"
FL_STEP_TIME_MAX_S = "step_time_max_s"
FL_STEP_TIME_MIN_S = "step_time_min_s"
FL_STEP_TIME_MEDIAN_S = "step_time_median_s"
FL_STEP_TIME_P99_S = "step_time_p99_s"
FL_LOSS_MEAN = "loss_mean"
FL_LOSS_SPREAD = "loss_spread"
FL_HOST_GAP_MEAN_S = "host_gap_mean_s"
FL_SWAP_READ_GBPS = "swap_read_gbps"
FL_SWAP_EXPOSED_S = "swap_exposed_mean_s"
FL_PER_HOST = "per_host"
# MoE routing slots (fleet.py moe_* vector fields; absent on dense runs)
FL_MOE_DROP_FRAC = "moe_drop_frac"
FL_MOE_LOCAL_LOAD = "moe_local_load"
FL_MOE_LOAD_MAX = "moe_local_load_max"
# health-event field names (health.py)
H_EVENT = "event"
H_STEP = "step"
H_LANE = "lane"
H_RATIO = "ratio"
H_ZSCORE = "zscore"
H_DETAIL = "detail"
H_METRIC = "metric"
H_SPREAD = "spread"
EVENT_STRAGGLER = "straggler"
EVENT_DIVERGENCE = "divergence"
# MoE health events (health.py MoE rules, ISSUE 15)
EVENT_DEAD_EXPERT = "dead_expert"
EVENT_ROUTER_COLLAPSE = "router_collapse"
EVENT_EP_IMBALANCE = "ep_imbalance"

# ---- MoE routing field names (monitor/moe.py payload) ----------------- #
M_WINDOW_START = "window_start_step"
M_WINDOW_END = "window_end_step"
M_STEPS = "steps"
M_EXPERTS = "num_experts"
M_LAYERS_PER_STEP = "layers_per_step"
M_TOKENS_PER_STEP = "tokens_per_step"
M_DROP_FRAC = "drop_fraction"
M_COUNTS = "expert_counts"
M_OVERFLOW = "overflow_counts"
M_IMBALANCE = "imbalance"          # hottest / mean routed count
M_MIN_COUNT_FRAC = "min_count_frac"  # coldest / fair share
M_ENTROPY = "router_entropy"       # normalized [0, 1] (1 = uniform)
M_CONFIDENCE = "router_confidence"  # mean raw top-k gate mass per token
M_LAUX = "l_aux_mean"              # per gate invocation
M_LOCAL_LOAD = "local_expert_load"  # this host's load vs fair share
# a layer that holds a range of the experts its router scores
# (moe/dropless.py), per sparse layer and optimizer step:
M_HELD_RANGE = "held_experts"          # [first, past the last]
M_HELD_ROWS_MAX = "held_rows_max"      # rows of the busiest held expert
M_HELD_ROWS_MEAN = "held_rows_mean"    # rows a held expert, on average
M_HELD_PICK_SHARE = "held_pick_share"  # of the k x tokens picks: landed here
M_DISPATCH_CHUNKS = "dispatch_chunks"  # passes over the row buffers, a layer
# (a model that emits them: models/glm4_moe_lite.py) picks of the busiest
# of ALL experts over the mean, averaged over gates and steps; the
# objective's two terms, averaged over micro-batches
M_LOAD_MAX_OVER_MEAN = "load_max_over_mean"
M_MAIN_LOSS = "main_loss"
M_MTP_LOSS = "mtp_loss"
M_POPULARITY = "popularity"        # embedded ExpertPopularitySnapshot
# (a model that emits them: models/ouro.py) the exit gate's counters,
# averaged over micro-batches: the objective's two terms (the exit
# distribution's mix of the passes' cross-entropies; KL(p || uniform)),
# the mean pass of exit sum_t t p_t, and M_EXIT_MASS + "1".."T", the mean
# probability of each exit
M_TASK_LOSS = "task_loss"
M_EXIT_KL = "exit_kl"
M_EXIT_STEP_MEAN = "exit_step_mean"
M_EXIT_MASS = "exit_mass_"
# (models/keye_vl2.py) beside M_MAIN_LOSS: the indexer's alignment term
# summed over the layers, and the pairs the selection kept over the causal
# pairs (min(t + 1, topk) summed over t, over S (S + 1) / 2)
M_INDEX_LOSS = "index_loss"
M_KEPT_SHARE = "kept_share"
# (models/xing4.py) the hyper-connections' mixes over a step's tokens
# and sublayers, averaged over micro-batches: the worst |row sum - 1|
# and |column sum - 1| of H_res after its Sinkhorn rounds (columns are
# normalised last), the means of H_pre (1 / streams at the start) and
# of H_post (1 at the start)
M_HC_ROW_ERR = "hc_res_row_err_max"
M_HC_COL_ERR = "hc_res_col_err_max"
M_HC_PRE_MEAN = "hc_pre_mean"
M_HC_POST_MEAN = "hc_post_mean"
# (models/zaya.py) averaged over micro-batches: the rms of the router's
# carried state after the last layer; the mean of the key heads' learned
# temperatures (1 at the start); the mean of the residual merges' scales
# ``a`` and ``g`` (1 at the start)
M_ROUTER_STATE_RMS = "router_state_rms"
M_CCA_TAU_MEAN = "cca_tau_mean"
M_RESIDUAL_SCALE_MEAN = "residual_scale_mean"

# ---- reconciliation field names (reconcile.py payload) --------------- #
R_WINDOW_START = "window_start_step"
R_WINDOW_END = "window_end_step"
R_MEASURED_STEP_S = "measured_step_time_s"
R_PREDICTED_STEP_S = "predicted_step_time_lb_s"
R_STEP_RATIO = "step_time_ratio"
R_LANES = "lanes"
R_ATTRIBUTION = "attribution"
R_MEASURED_HBM = "measured_hbm_peak_bytes"
R_PREDICTED_HBM = "predicted_hbm_peak_bytes"
R_HBM_RATIO = "hbm_ratio"
R_SWAP_GBPS = "swap_read_gbps"
R_SWAP_CEILING_GBPS = "swap_ceiling_gbps"
R_SWAP_VS_CEILING = "swap_read_vs_ceiling"
R_OVERLAP_FRACTION = "swap_overlap_fraction"
R_FLAGS = "flags"


def device_memory() -> Dict[str, Any]:
    """Measured memory high-water, one bounded read.

    Prefers the accelerator's own allocator stats
    (``jax.local_devices()[0].memory_stats()`` — peak_bytes_in_use is the
    HBM high-water the liveness estimator predicts).  CPU backends
    usually report no allocator stats; there the process RSS high-water
    (``ru_maxrss``) stands in, labeled via ``mem_source`` so a record
    never passes host RSS off as device HBM."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:  # noqa: BLE001 — monitoring must never crash a step
        stats = {}
    peak = stats.get("peak_bytes_in_use")
    if peak:
        return {F_MEM_PEAK_BYTES: int(peak),
                F_MEM_IN_USE_BYTES: int(stats.get("bytes_in_use", 0)),
                F_MEM_SOURCE: "device"}
    try:
        import resource
        import sys
        ru = resource.getrusage(resource.RUSAGE_SELF)
        # linux reports ru_maxrss in KiB; macOS/BSD report bytes
        unit = 1024 if sys.platform.startswith("linux") else 1
        return {F_MEM_PEAK_BYTES: int(ru.ru_maxrss) * unit,
                F_MEM_IN_USE_BYTES: None,
                F_MEM_SOURCE: "host_rss"}
    except Exception:  # noqa: BLE001
        return {F_MEM_PEAK_BYTES: None, F_MEM_IN_USE_BYTES: None,
                F_MEM_SOURCE: "unavailable"}


def identity(process_index: Optional[int] = None,
             world_size: Optional[int] = None,
             host: Optional[str] = None) -> Dict[str, Any]:
    """The host-identity triple every v2 record carries.  Defaults are
    resolved from the running process (jax process index/count + the
    hostname) so single-host runs populate them too."""
    if process_index is None or world_size is None:
        try:
            import jax
            if process_index is None:
                process_index = jax.process_index()
            if world_size is None:
                world_size = jax.process_count()
        except Exception:  # noqa: BLE001 — identity must never crash
            process_index = process_index or 0
            world_size = world_size or 1
    if host is None:
        import socket
        try:
            host = socket.gethostname()
        except Exception:  # noqa: BLE001
            host = f"host{process_index}"
    return {F_HOST: host, F_PROCESS_INDEX: int(process_index),
            F_WORLD_SIZE: int(world_size)}


def make_step_record(step: int, loss: Optional[float], wall_s: float,
                     tokens: Optional[int], counters: Dict[str, Any],
                     boundary: Dict[str, Any],
                     memory: Dict[str, Any],
                     swap: Optional[Dict[str, Any]] = None,
                     extra: Optional[Dict[str, Any]] = None,
                     host_gap_s: Optional[float] = None
                     ) -> Dict[str, Any]:
    """Assemble one step record from already-fetched host values."""
    rec: Dict[str, Any] = {F_KIND: KIND_STEP, F_STEP: int(step)}
    rec[F_LOSS] = loss
    rec[F_HOST_GAP_S] = (round(float(host_gap_s), 6)
                         if host_gap_s is not None else None)
    rec[F_WALL_TIME_S] = round(float(wall_s), 6) if wall_s else wall_s
    rec[F_TOKENS_PER_SEC] = (round(tokens / wall_s, 1)
                             if tokens and wall_s and wall_s > 0 else None)
    rec[F_LR] = boundary.get("lr")
    rec[F_LOSS_SCALE] = boundary.get("loss_scale")
    rec.update(memory)
    for k in (F_SKIPPED_STEPS, F_SENTINEL_ANOMALIES, F_SENTINEL_SKIPS,
              F_RETRACES, F_DISPATCHES_PER_STEP, F_IO_RETRIES,
              F_COMPILES, F_STALLED_LAUNCHES):
        rec[k] = counters.get(k)
    if swap:
        rec[F_SWAP_READ_GBPS] = swap.get("read_gbps")
        rec[F_SWAP_OVERLAP_FRACTION] = swap.get("overlap_fraction")
        rec[F_SWAP_READ_VS_CEILING] = swap.get("read_vs_ceiling")
    else:
        rec[F_SWAP_READ_GBPS] = None
        rec[F_SWAP_OVERLAP_FRACTION] = None
        rec[F_SWAP_READ_VS_CEILING] = None
    if extra:
        rec.update(extra)
    return rec
