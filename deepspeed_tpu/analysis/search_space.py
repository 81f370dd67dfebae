"""Config-autotuner search space — enumerate the REAL decision space.

The knobs that decide a training config's step time are not free-form:
they are the axes this repo actually implements and benchmarks — mesh
factorization (data x model x expert over a fixed chip count), ZeRO
stage 1/2/3 with the stage-3 resident-vs-streamed split and its
prefetch mode + group size (docs/zero3_streaming.md), gas/micro splits
of a FIXED global batch (the batch is a hyperparameter, its split is a
schedule choice), the ZeRO++ transport knobs qwZ/qgZ/hpZ
(docs/low_bandwidth_collectives.md), per-tile fused collective-matmul
transports (docs/fused_collective_matmul.md — candidate names carry an
``fcm`` tag), and the offload tier with its prefetch/pipeline depths
(docs/zero_infinity.md).

Enumeration is deterministic (nested loops in a documented order, names
encode every knob) and GATED so the product only contains meaningful
points: stage-3 streaming knobs collapse for stages 1/2, qwZ/hpZ only
modulate streamed stage-3 gathers, qgZ needs a stage >= 2 grad
reduce-scatter, and the NVMe tier needs streamed stage 3.  Structural
infeasibilities — a global batch the data world cannot divide, an
elasticity block that rejects the world size — are recorded as pruned
candidates with reasons, never silently skipped.
"""

import copy
import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

from .. import constants as C


class AutotuneError(RuntimeError):
    """Search-configuration or search-execution failure."""


@dataclass
class Candidate:
    """One enumerated point: a engine-ready engine config + the flat knob
    summary the leaderboard reports."""
    name: str
    config: Dict[str, Any]
    knobs: Dict[str, Any]


@dataclass
class Pruned:
    """A candidate rejected by a hard constraint, with provenance: which
    pruning stage killed it and why — the empty-search diagnosis is
    built from these."""
    name: str
    stage: str  # "batch" | "hbm_floor" | "trace" | "auditor" | "emit_gate"
    reason: str


@dataclass
class SearchSpace:
    """Resolved enumeration output."""
    candidates: List[Candidate] = field(default_factory=list)
    pruned: List[Pruned] = field(default_factory=list)
    n_enumerated: int = 0


def mesh_factorizations(chips: int, model_sizes, expert_sizes
                        ) -> List[Tuple[int, int, int]]:
    """(data, model, expert) factorizations of `chips` with the model /
    expert axes drawn from the configured choice lists."""
    out = []
    for m in sorted(set(int(v) for v in model_sizes)):
        for e in sorted(set(int(v) for v in expert_sizes)):
            if m < 1 or e < 1 or chips % (m * e) != 0:
                continue
            out.append((chips // (m * e), m, e))
    return out


def batch_splits(global_batch: int, dp_world: int,
                 micro_filter=None) -> List[Tuple[int, int]]:
    """(micro, gas) divisor splits of the fixed global batch over the
    data-parallel world (data x expert axes)."""
    if global_batch % dp_world != 0:
        return []
    per_replica = global_batch // dp_world
    splits = []
    for micro in range(1, per_replica + 1):
        if per_replica % micro != 0:
            continue
        if micro_filter is not None and micro not in micro_filter:
            continue
        splits.append((micro, per_replica // micro))
    return splits


def _deep_merge(dst: Dict[str, Any], overlay: Dict[str, Any]) -> None:
    for k, v in overlay.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _deep_merge(dst[k], v)
        else:
            dst[k] = copy.deepcopy(v)


def _candidate_name(stage, streamed, bucket, micro, gas, data,
                    model, expert, qwz, qgz, hpz, offload,
                    pdepth, odepth, multi_bucket, fcm=False,
                    onebit=False) -> str:
    bits = [f"z{stage}" + ("s" if streamed else "")]
    if streamed and multi_bucket:
        bits.append(f"g{bucket}")
    bits.append(f"b{micro}x{gas}")
    bits.append(f"d{data}m{model}e{expert}")
    if qwz:
        bits.append(f"qwz{qwz}")
    if qgz:
        bits.append(f"qgz{qgz}")
    if hpz:
        bits.append(f"hpz{hpz}")
    if fcm:
        bits.append("fcm")
    if onebit:
        bits.append("1bit")
    if offload == C.AUTOTUNING_OFFLOAD_TIER_NVME:
        # the depth axes only modulate the NVMe tier; the cpu tier has
        # no depth knob to encode
        bits.append(f"off-{offload}{pdepth}")
    elif offload != C.AUTOTUNING_OFFLOAD_TIER_NONE:
        bits.append(f"off-{offload}")
    return "-".join(bits)


def _build_config(base: Dict[str, Any], *, stage, streamed,
                  bucket, micro, gas, data, model, expert, qwz, qgz,
                  hpz, offload, pdepth, odepth,
                  fixed, fcm=False, onebit=False) -> Dict[str, Any]:
    raw = copy.deepcopy(base)
    # candidates are engine-ready engine configs: the search description
    # itself must not ride along
    raw.pop(C.AUTOTUNING, None)
    raw[C.MESH] = {C.MESH_DATA_AXIS: data, C.MESH_MODEL_AXIS: model,
                   C.MESH_EXPERT_AXIS: expert}
    dp_world = data * expert  # MeshContext.data_parallel_world_size
    raw[C.TRAIN_BATCH_SIZE] = micro * gas * dp_world
    raw[C.TRAIN_MICRO_BATCH_SIZE_PER_GPU] = micro
    raw[C.GRADIENT_ACCUMULATION_STEPS] = gas

    zo = dict(raw.get(C.ZERO_OPTIMIZATION) or {})
    zo[C.ZERO_OPTIMIZATION_STAGE] = stage
    for key in (C.ZERO_OPTIMIZATION_PARAM_PERSISTENCE_THRESHOLD,
                C.ZERO_OPTIMIZATION_MAX_LIVE_PARAMETERS,
                C.ZERO_OPTIMIZATION_PREFETCH_BUCKET_SIZE,
                C.ZERO_OPTIMIZATION_LOW_BANDWIDTH,
                C.ZERO_OPTIMIZATION_OFFLOAD_PARAM,
                C.ZERO_OPTIMIZATION_OFFLOAD_OPTIMIZER):
        zo.pop(key, None)
    if streamed:
        zo[C.ZERO_OPTIMIZATION_PARAM_PERSISTENCE_THRESHOLD] = 0
        zo[C.ZERO_OPTIMIZATION_MAX_LIVE_PARAMETERS] = bucket
        zo[C.ZERO_OPTIMIZATION_PREFETCH_BUCKET_SIZE] = bucket
    lb = {}
    if qwz:
        lb[C.LOW_BANDWIDTH_QWZ_BITS] = qwz
    if qgz:
        lb[C.LOW_BANDWIDTH_QGZ_BITS] = qgz
    if hpz:
        lb[C.LOW_BANDWIDTH_HPZ_GROUP_SIZE] = hpz
    if fcm:
        lb[C.LOW_BANDWIDTH_FCM] = True
    if onebit:
        lb[C.LOW_BANDWIDTH_ONEBIT] = True
        # the wire format IS the onebit optimizer's error-feedback
        # momentum: swap the base optimizer for its onebit counterpart,
        # keeping lr/betas/... (docs/onebit.md)
        opt = copy.deepcopy(base.get(C.OPTIMIZER) or {})
        name = str(opt.get("type") or "").lower()
        opt["type"] = "OneBitLamb" if "lamb" in name else "OneBitAdam"
        opt.setdefault("params", {})
        raw[C.OPTIMIZER] = opt
    if lb:
        zo[C.ZERO_OPTIMIZATION_LOW_BANDWIDTH] = lb
    if offload == C.AUTOTUNING_OFFLOAD_TIER_CPU:
        zo[C.ZERO_OPTIMIZATION_OFFLOAD_OPTIMIZER] = {
            C.OFFLOAD_OPTIMIZER_DEVICE: C.OFFLOAD_CPU_DEVICE}
    elif offload == C.AUTOTUNING_OFFLOAD_TIER_NVME:
        zo[C.ZERO_OPTIMIZATION_OFFLOAD_PARAM] = {
            C.OFFLOAD_PARAM_DEVICE: C.OFFLOAD_NVME_DEVICE,
            C.OFFLOAD_PARAM_PREFETCH_DEPTH: pdepth}
        zo[C.ZERO_OPTIMIZATION_OFFLOAD_OPTIMIZER] = {
            C.OFFLOAD_OPTIMIZER_DEVICE: C.OFFLOAD_NVME_DEVICE,
            C.OFFLOAD_OPTIMIZER_PIPELINE_DEPTH: odepth}
    raw[C.ZERO_OPTIMIZATION] = zo
    if fixed:
        _deep_merge(raw, fixed)
    return raw


def enumerate_candidates(base: Dict[str, Any], tune_cfg,
                         chips: int,
                         global_batch: int) -> SearchSpace:
    """Walk the gated cartesian product and return candidates plus the
    structurally-pruned points (batch-indivisible worlds, elasticity
    rejections).  Raises AutotuneError when the space exceeds
    ``autotuning.max_candidates`` — an oversized search must be narrowed
    explicitly, not silently truncated."""
    space = SearchSpace()
    seen: Dict[str, str] = {}

    meshes = mesh_factorizations(chips, tune_cfg.mesh_model,
                                 tune_cfg.mesh_expert)
    if not meshes:
        raise AutotuneError(
            f"no (data, model, expert) factorization of {chips} chips "
            f"admits model sizes {list(tune_cfg.mesh_model)} x expert "
            f"sizes {list(tune_cfg.mesh_expert)}")
    multi_bucket = len(set(tune_cfg.stage3_bucket_sizes)) > 1
    elastic = base.get(C.ELASTICITY)

    # the 1-bit wire axis (docs/onebit.md) is gated at the BASE config:
    # gradient clipping / sparse gradients conflict with the tier for
    # every candidate, so an infeasible base yields ONE pruned record
    # instead of a trace-prune per enumerated point
    onebit_axis = tuple(sorted(set(bool(v) for v in tune_cfg.onebit)))
    if True in onebit_axis:
        reason = None
        if float(base.get(C.GRADIENT_CLIPPING) or 0.0) > 0:
            reason = (f"base config sets {C.GRADIENT_CLIPPING}="
                      f"{base.get(C.GRADIENT_CLIPPING)}; global-norm "
                      "clipping needs the dense gradient the 1-bit tier "
                      "removes")
        elif base.get(C.SPARSE_GRADIENTS):
            reason = (f"base config sets {C.SPARSE_GRADIENTS}; both "
                      "features rewrite the data-parallel grad "
                      "reduction")
        if reason is not None:
            space.n_enumerated += 1
            space.pruned.append(Pruned(name="1bit", stage="batch",
                                       reason=reason))
            onebit_axis = tuple(v for v in onebit_axis if not v) or \
                (False,)

    streamed_possible = 3 in tune_cfg.zero_stages and any(
        v == C.AUTOTUNING_STAGE3_VARIANT_STREAMED
        for v in tune_cfg.stage3_variants)

    for (data, model, expert) in meshes:
        dp_world = data * expert
        # hpZ divisibility depends only on (hpz, dp_world): check it
        # once per mesh so an indivisible group size yields ONE pruned
        # record, not one per unrelated knob combination
        mesh_hpzs = []
        for hpz in tune_cfg.hpz_group_sizes:
            if (streamed_possible and hpz and hpz > 1
                    and dp_world % hpz != 0):
                space.n_enumerated += 1
                space.pruned.append(Pruned(
                    name=f"hpz{hpz}-d{data}m{model}e{expert}",
                    stage="batch",
                    reason=f"hpz_group_size {hpz} does not divide dp "
                           f"world {dp_world}"))
            else:
                mesh_hpzs.append(hpz)
        splits = batch_splits(global_batch, dp_world,
                              tune_cfg.micro_batches)
        if not splits:
            space.n_enumerated += 1
            space.pruned.append(Pruned(
                name=f"d{data}m{model}e{expert}", stage="batch",
                reason=(f"global batch {global_batch} has no "
                        f"(micro, gas) split over dp world {dp_world}"
                        + (f" admitted by micro_batches="
                           f"{list(tune_cfg.micro_batches)}"
                           if tune_cfg.micro_batches else ""))))
            continue
        if elastic is not None:
            # elasticity batch-triple validity is a hard constraint: the
            # candidate must survive a fleet resize contract, not just
            # divide today's world (reuses the elasticity solver)
            from ..elasticity import (ElasticityError,
                                      compute_elastic_config)
            try:
                compute_elastic_config({C.ELASTICITY: elastic},
                                       world_size=dp_world)
            except ElasticityError as e:
                space.n_enumerated += 1
                space.pruned.append(Pruned(
                    name=f"d{data}m{model}e{expert}", stage="batch",
                    reason=f"elasticity rejects dp world {dp_world}: "
                           f"{e}"))
                continue

        for stage in tune_cfg.zero_stages:
            if stage == 3:
                variants = [
                    v == C.AUTOTUNING_STAGE3_VARIANT_STREAMED
                    for v in tune_cfg.stage3_variants]
            else:
                variants = [False]
            for streamed in variants:
                buckets = (tune_cfg.stage3_bucket_sizes if streamed
                           else (None,))
                # qwZ/hpZ modulate the streamed stage-3 weight gathers;
                # qgZ needs the stage >= 2 grad reduce-scatter; the
                # fused collective-matmul rides the streamed transports
                qwzs = tune_cfg.qwz_bits if streamed else (0,)
                hpzs = tuple(mesh_hpzs) if streamed else (0,)
                qgzs = tune_cfg.qgz_bits if stage >= 2 else (0,)
                fcms = (tuple(sorted(set(
                    tune_cfg.fused_collective_matmul)))
                    if streamed else (False,))
                for (bucket, micro_gas, qwz, qgz, hpz, fcm,
                     offload) in itertools.product(
                        buckets, splits, qwzs, qgzs, hpzs, fcms,
                        tune_cfg.offload):
                    micro, gas = micro_gas
                    if (offload == C.AUTOTUNING_OFFLOAD_TIER_NVME
                            and not streamed):
                        # NVMe params = the ZeRO-Infinity layer-streaming
                        # engine; only the streamed stage-3 shape maps
                        continue
                    pdepths = (tune_cfg.nvme_prefetch_depths
                               if offload == C.AUTOTUNING_OFFLOAD_TIER_NVME
                               else (None,))
                    odepths = (tune_cfg.opt_pipeline_depths
                               if offload == C.AUTOTUNING_OFFLOAD_TIER_NVME
                               else (None,))
                    # the 1-bit wire replaces the DATA-parallel grad
                    # allreduce of a resident stage <= 2 engine: ZeRO-3
                    # streaming has no whole-grad allreduce, offloaded
                    # optimizer state cannot host the packed momentum,
                    # non-data axes shard the grads it syncs, and qgZ
                    # already rewrites the same reduction
                    onebits = (onebit_axis
                               if (stage <= 2 and not streamed
                                   and offload ==
                                   C.AUTOTUNING_OFFLOAD_TIER_NONE
                                   and model == 1 and expert == 1
                                   and not qgz)
                               else (False,))
                    for pdepth, odepth, onebit in itertools.product(
                            pdepths, odepths, onebits):
                        space.n_enumerated += 1
                        name = _candidate_name(
                            stage, streamed, bucket, micro, gas,
                            data, model, expert, qwz, qgz, hpz,
                            offload, pdepth, odepth, multi_bucket,
                            fcm=fcm, onebit=onebit)
                        cfg = _build_config(
                            base, stage=stage, streamed=streamed,
                            bucket=bucket, micro=micro,
                            gas=gas, data=data, model=model,
                            expert=expert, qwz=qwz, qgz=qgz, hpz=hpz,
                            offload=offload, pdepth=pdepth,
                            odepth=odepth, fixed=tune_cfg.fixed,
                            fcm=fcm, onebit=onebit)
                        import json as _json
                        key = _json.dumps(cfg, sort_keys=True)
                        if key in seen:
                            continue  # knob gating can fold two points
                        seen[key] = name
                        space.candidates.append(Candidate(
                            name=name, config=cfg,
                            knobs={
                                "zero_stage": stage,
                                "streamed": streamed,
                                "stage3_bucket": bucket,
                                "micro_batch": micro, "gas": gas,
                                "mesh": {"data": data, "model": model,
                                         "expert": expert},
                                "qwz_bits": qwz, "qgz_bits": qgz,
                                "hpz_group_size": hpz,
                                "fused_collective_matmul": bool(fcm),
                                "onebit": bool(onebit),
                                "offload": offload,
                                "nvme_prefetch_depth": pdepth,
                                "opt_pipeline_depth": odepth,
                            }))
    if len(space.candidates) > tune_cfg.max_candidates:
        raise AutotuneError(
            f"search space has {len(space.candidates)} candidates, over "
            f"autotuning.max_candidates={tune_cfg.max_candidates} — "
            "narrow the axes (zero_stages, stage3_bucket_sizes, qwz_bits, "
            "micro_batches, ...) or raise the cap explicitly; the "
            "autotuner never truncates silently")
    return space


def nearest_divisor_worlds(global_batch: int, chips: int,
                           k: int = 3) -> List[int]:
    """Chip counts nearest to `chips` whose dp world divides the global
    batch — what an all-pruned-at-batch search suggests (reuses the
    elasticity module's nearest-world helper)."""
    from ..elasticity import nearest_valid_world_sizes
    divisors = [w for w in range(1, global_batch + 1)
                if global_batch % w == 0]
    return nearest_valid_world_sizes(divisors, chips, k=k)
