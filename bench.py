"""Benchmark entry point: prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Flagship bench: GPT-2 (124M) causal-LM training throughput on one chip under
the engine (ZeRO config, bf16, fused Pallas attention).  North star per
BASELINE.json: tokens/sec/chip + MFU.

vs_baseline: achieved model TFLOPS/chip divided by the reference's best
published single-device number — BERT-large pretrain at 64 TFLOPS on 1xV100
(BASELINE.md).  >1.0 means this framework extracts more absolute model FLOPs
from one TPU chip than reference DeepSpeed did from one V100.

The row runs in this process on the device JAX reports; the printed row
is stamped with that device (platform, device_kind, device count).  Any
exception exits non-zero with its traceback, and a platform other than
``tpu`` is refused unless ``JAX_PLATFORMS`` names ``cpu`` explicitly (CI
smoke: such a row's numbers are not device metrics).

Ladder: `python bench.py --config
{gpt2|gpt2_gas4|gpt2_gas4_fused|gpt2_zero3_stream|
gpt2_zero3_stream_carried|gpt2_zero3_stream_fcm|bert_z2|bert_s512|
decode|moe|gpt_moe|longseq|sparse_longseq|offload|infinity}` selects
other BASELINE.md anchor points; default is the flagship gpt2.  The
gas4 pair A/Bs the fused whole-step program (1 dispatch/step) against
the modular loop (2N); the zero3_stream pair A/Bs the carried
double-buffer prefetch against serialized at-use gathers; the fcm row
A/Bs the per-tile fused collective-matmul transports against the
modular qwZ/qgZ collectives in one row (all three need a >1-chip ZeRO
world).
DS_BENCH_ITERS overrides the timing iteration count (CI smoke).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REFERENCE_TFLOPS = 64.0  # BASELINE.md: BERT-large seq128, 1xV100


def _emit(payload):
    print(json.dumps(payload))
    sys.stdout.flush()


def _git_head():
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or None
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return None


def _peak_tflops():
    """bf16 peak of the device the row runs on; raises on a device_kind
    the peaks table does not hold (deepspeed_tpu/utils/chip.py)."""
    from deepspeed_tpu.utils.chip import device_peaks, device_summary

    return device_peaks(device_summary()["kind"])["bf16_tflops"]


def _time_steps(step, warmup=3, iters=30, align=1, final_sync=None):
    """align: round the (possibly DS_BENCH_ITERS-overridden) iteration
    count UP to a multiple of this, so windows that must hold whole
    optimizer steps (gradient accumulation) stay aligned under overrides.

    final_sync: optional callable forced INSIDE the timed window after the
    last step.  The loss fetch only forces work the loss depends on — the
    LAST optimizer update (post-loss) is outside that chain, which
    understates per-step optimizer cost when the window holds few
    optimizer steps (the gas-amortization row holds only 2)."""
    iters = max(1, int(os.environ.get("DS_BENCH_ITERS", iters)))
    if align > 1:
        iters = align * -(-iters // align)
    warmup = min(warmup, iters)
    for _ in range(warmup):
        loss = step()
    float(loss)  # scalar fetch: forces the chain the loss depends on
    if final_sync is not None:
        final_sync()
    t0 = time.time()
    for _ in range(iters):
        loss = step()
    final_loss = float(loss)  # forces the whole dependent chain
    if final_sync is not None:
        final_sync()
    return time.time() - t0, final_loss, iters


def _program_audit_fields(engine, measured_step_s=None):
    """Static-audit provenance for a ladder row: the collective-lockstep
    signature and trip-weighted wire bytes/step of the exact programs
    this row dispatches (docs/program_auditor.md).  A perf regression
    that changes PROGRAM SHAPE (dense fallback reappearing, a collective
    reordered) then shows as a signature/wire diff in the row JSON, not
    just a slower number.  Best-effort: rows must never fail on an audit
    bug.

    With ``measured_step_s`` the row also embeds the monitor's
    reconciliation summary (monitor/reconcile.py — the same math the
    runtime telemetry subsystem runs per window, docs/telemetry.md):
    measured step time vs the roofline lower bound with per-lane
    attribution, and measured memory vs the liveness estimate, so a
    slow row carries why it was slow."""
    out = {}
    if measured_step_s is not None:
        # per-host spread + straggler verdict (degenerate on 1 host).
        # Hoisted OUTSIDE the audit try: the allgather inside must run
        # on every host even when the audit throws on one of them —
        # were it downstream of the audit, a host-local audit error
        # would skip this host's exchange while every peer blocks in
        # the timeout-less collective
        out.update(_fleet_summary_fields(
            measured_step_s,
            ep_imbalance_ratio=engine.config.monitor_config.moe.
            ep_imbalance_ratio))
    try:
        from deepspeed_tpu.analysis import audit_engine
        report = audit_engine(engine, multihost=False)
        lb = report.predicted_step_time_lb_s
        out.update({
            "lockstep_signature": (report.signature or "")[:16],
            "wire_bytes_per_step": report.wire_bytes_per_step,
            "audit_findings": report.counts(),
            # schedule provenance (docs/program_auditor.md, round 10):
            # predicted-vs-measured rides every row, so a perf PR's
            # claim is checkable against the static model
            "overlap_efficiency": round(report.overlap_efficiency, 4),
            "peak_hbm_bytes": report.peak_hbm_bytes,
            "predicted_step_time_lb": (round(lb, 6)
                                       if lb is not None else None),
        })
        if report.hlo:
            # HLO-level SPMD cross-check (analysis/hlo_audit.py, round
            # 18; runs when analysis.hlo_audit is on): the row carries
            # the compiled program's wire story next to the jaxpr's, so
            # a divergence regression is diffable from the row JSON
            ratio = report.hlo_divergence_ratio
            if ratio is not None:
                # "inf" as a string: json.dumps would emit the bare
                # token `Infinity`, which is not RFC-8259 JSON and
                # breaks non-Python consumers of the JSONL ladder
                # (matches cli.py's golden-payload spelling)
                ratio = ("inf" if ratio == float("inf")
                         else round(ratio, 4))
            out.update({
                "hlo_wire_bytes_per_step": report.hlo_wire_bytes_per_step,
                "hlo_collective_count": report.hlo_collective_count,
                "hlo_divergence_ratio": ratio,
                "n_silent_reshards": report.hlo["n_silent_reshards"],
            })
        if measured_step_s is not None and report.step_time is not None:
            out["reconciliation"] = _reconciliation_summary(
                report, measured_step_s)
    except Exception as e:  # noqa: BLE001 — provenance is best-effort
        out["lockstep_signature"] = f"audit-failed: {e}"[:80]
    out.update(_resilience_fields(engine))
    return out


def _resilience_fields(engine):
    """Resilience provenance for a ladder row (docs/resilience.md):
    which fallback tiers this process ran on (degradation registry) and
    the I/O retry tally, so a row produced under degraded conditions —
    python-tier aio, jsonl-tier metrics, retried swap writes — carries
    that context next to its numbers instead of looking like a clean
    regression.  Best-effort, like the audit fields."""
    out = {}
    try:
        from deepspeed_tpu.runtime.resilience.degradation import \
            get_registry
        events = get_registry().events()
        if events:
            out["degradation_events"] = events
        policy = getattr(engine, "_retry_policy", None)
        if policy is not None:
            snap = policy.snapshot()
            if snap.get("attempts"):
                out["retry_counters"] = snap
    except Exception:  # noqa: BLE001 — provenance is best-effort
        pass
    return out


def _fleet_summary_fields(measured_step_s, final_loss=None,
                          swap=None, ep_imbalance_ratio=None):
    """Per-host attribution for a ladder row (monitor/fleet.py).

    On a multihost run every process reaches this point in lockstep (the
    whole bench row is lockstep), so the one fixed-shape allgather here
    is safe — the row then lands with the per-host step-time spread and
    a one-shot straggler verdict, so a slow POD number names the slow
    HOST (ROADMAP items 1/3/5's on-chip runs).  A single-process run
    records the degenerate 1-host summary: the field shape is identical,
    so downstream tooling never branches.  Best-effort like the audit
    fields — a row must never fail on its own telemetry."""
    try:
        import jax
        from deepspeed_tpu.monitor import (FleetAggregator,
                                           straggler_verdict,
                                           summarize_fleet)
        agg = FleetAggregator(process_index=jax.process_index(),
                              process_count=jax.process_count())
        summary = {
            "last_step": 0,
            "steps": 1,
            "step_time_mean_s": measured_step_s,
            "step_time_max_s": measured_step_s,
            "loss_mean": final_loss,
        }
        if swap:
            summary["swap_read_gbps"] = swap.get("read_gbps")
            summary["swap_exposed_mean_s"] = (
                (swap.get("read_exposed_s") or 0.0)
                + (swap.get("write_exposed_s") or 0.0))
        matrix = agg.exchange(summary)
        hosts = agg.host_names()
        fleet = summarize_fleet(matrix)
        fleet.pop("window_end_step", None)
        fleet["host_names"] = hosts
        verdict_kw = {}
        if ep_imbalance_ratio is not None:
            # the engine's configured monitor.moe gate — keeps the row's
            # one-shot verdict lane-consistent with the live detector
            verdict_kw["ep_imbalance_ratio"] = float(ep_imbalance_ratio)
        fleet["straggler"] = straggler_verdict(matrix, hosts,
                                               **verdict_kw)
        return {"fleet": fleet}
    except Exception as e:  # noqa: BLE001 — provenance is best-effort
        return {"fleet": {"error": f"{e}"[:80]}}


def _reconciliation_summary(report, measured_step_s):
    """Monitor-schema reconciliation payload for one measured row (single-
    sourced field names: deepspeed_tpu.monitor.record / reconcile)."""
    from deepspeed_tpu.analysis import per_lane_predictions
    from deepspeed_tpu.monitor import (Bands, bare_summary, device_memory,
                                       reconcile_window)
    from deepspeed_tpu.monitor import record as mrec
    mem = device_memory()
    return bare_summary(reconcile_window(
        {"step_time_s": measured_step_s,
         "hbm_peak_bytes": mem.get(mrec.F_MEM_PEAK_BYTES),
         "mem_source": mem.get(mrec.F_MEM_SOURCE)},
        {"predicted_step_time_lb_s": report.predicted_step_time_lb_s,
         "lanes": per_lane_predictions(report.step_time),
         "peak_hbm_bytes": report.peak_hbm_bytes},
        Bands()))


def bench_gpt2(batch=8, metric="gpt2_124m_train_tokens_per_sec_1chip",
               hidden=768, layers=12, heads=12, remat=False,
               grads_half=False):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    # ad-hoc probe overrides (memory-fit experiments without editing the
    # committed row configs); every active override is echoed into the
    # result row so a leftover env var can never silently pollute the
    # canonical ladder
    def _env_flag(name):
        return os.environ[name] not in ("", "0", "false", "False", "no")

    overrides = {}
    if "DS_BENCH_BATCH" in os.environ:
        batch = int(os.environ["DS_BENCH_BATCH"])
        overrides["DS_BENCH_BATCH"] = batch
    if "DS_BENCH_REMAT" in os.environ:
        remat = _env_flag("DS_BENCH_REMAT")
        overrides["DS_BENCH_REMAT"] = remat
    if "DS_BENCH_GRADS_BF16" in os.environ:
        grads_half = _env_flag("DS_BENCH_GRADS_BF16")
        overrides["DS_BENCH_GRADS_BF16"] = grads_half
    seq = 1024
    # DS_BENCH_ATTN_LAYOUT=bshd A/Bs the transpose-free kernel layout
    # without a code change (default stays the Mosaic-proven bhsd)
    cfg = GPT2Config(n_positions=seq, bf16=True,
                     hidden_size=hidden, num_layers=layers, num_heads=heads,
                     activation_checkpointing=remat,
                     attn_layout=os.environ.get("DS_BENCH_ATTN_LAYOUT",
                                                "bhsd"))
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    config = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True, "grads_in_compute_dtype": grads_half},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params)

    rng = np.random.RandomState(0)
    # loss() runs attention on the full length and shifts on logits, so the
    # input length IS the attention length (keep it = n_positions)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss

    dt, final_loss, n = _time_steps(step)
    tokens_per_sec = n * batch * seq / dt
    tflops = tokens_per_sec * cfg.flops_per_token() / 1e12
    peak = _peak_tflops()
    return {
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip": round(tflops, 2),
        "mfu": round(tflops / peak, 4),
        "final_loss": round(final_loss, 4),
        "batch": batch,
        **_program_audit_fields(engine, measured_step_s=dt / n),
        **({"probe_overrides": overrides} if overrides else {}),
    }


def _bench_gpt2_gas(fused, gas=4, batch=8):
    """Flagship shape at gas=4: the dispatch-amortization A/B.  `fused`
    routes the whole optimizer step through the single-program
    fused-step path (scan-based accumulation + in-program apply,
    docs/fused_step.md) via engine.train_batch; the modular row drives
    the same train_batch API down the 2N-dispatch forward/backward/step
    loop.  Same model/optimizer/precision as the flagship row, so
    fused/modular quantifies the dispatch+HBM-roundtrip tax directly."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    seq = 1024
    cfg = GPT2Config(n_positions=seq, bf16=True)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    config = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "fused_step": {"enabled": fused},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params)
    if fused and engine._fused_step_fn is None:  # pragma: no cover
        raise RuntimeError(
            f"fused row fell back to modular: {engine.fused_step_reason}")
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)

    def batch_iter():
        while True:
            yield (ids,)

    it = batch_iter()

    def step():
        return engine.train_batch(it)  # one optimizer step (gas micros)

    # final_sync: the loss fetch only forces work the loss depends on —
    # the window's LAST optimizer apply (post-loss) would go untimed on
    # the modular side and bias the A/B (same fix as the offload gas row)
    import jax.numpy as jnp

    def param_sync():
        leaf = jax.tree.leaves(engine.params)[0]
        float(jnp.asarray(leaf).ravel()[0])

    dt, final_loss, n = _time_steps(step, warmup=2, iters=8,
                                    final_sync=param_sync)
    tokens_per_sec = n * gas * batch * seq / dt
    tflops = tokens_per_sec * cfg.flops_per_token() / 1e12
    peak = _peak_tflops()
    kind = "fused" if fused else "modular"
    return {
        "metric": f"gpt2_124m_gas{gas}_{kind}_train_tokens_per_sec_1chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip": round(tflops, 2),
        "mfu": round(tflops / peak, 4),
        "gradient_accumulation_steps": gas,
        "dispatches_per_step": 1 if fused else 2 * gas,
        "final_loss": round(final_loss, 4),
        **_program_audit_fields(engine, measured_step_s=dt / n),
    }


def bench_gpt2_gas4():
    return _bench_gpt2_gas(fused=False)


def bench_gpt2_gas4_fused():
    return _bench_gpt2_gas(fused=True)


def bench_gpt2_onebit(batch=8, freeze=2, seq=1024):
    """1-bit optimizer A/B (ISSUE 16): OneBitAdam with the compressed
    wire tier (zero_optimization.low_bandwidth.onebit, docs/onebit.md)
    against a dense-Adam twin on the identical model/data/ZeRO stage.
    The timed window measures the STEADY-STATE compressed phase — the
    warmup steps and the one planned phase-switch retrace run untimed —
    and the row embeds both phases' wire accounting from per-phase
    audits, so the measured delta is attributable to the wire the tier
    removed.  Hard gates: the phase switch must cost EXACTLY one
    planned retrace (RecompileGuard counters), and the 1-bit run's
    final loss must land inside a 10% band around the dense twin's
    (post-freeze sign+scale momentum is an approximation — the band is
    the pinned contract, bitwise identity is only promised for warmup).
    Requires a >1-device data world: on a single chip the tier is inert
    and the row would silently measure dense Adam."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    mesh = ds.initialize_mesh(data=-1)
    dp = mesh.data_parallel_world_size
    if dp < 2:
        raise RuntimeError(
            f"gpt2_onebit needs a >1-device data world (the 1-bit tier "
            f"is inert on {dp} device) — run on a multichip host")
    cfg = GPT2Config(n_positions=seq, bf16=True)
    model = GPT2Model(cfg)
    micro = max(1, batch // dp)
    global_batch = micro * dp
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      size=(global_batch, seq)).astype(np.int32)

    def batch_iter():
        while True:
            yield (ids,)

    def run(onebit):
        params = model.init_params(jax.random.PRNGKey(0))
        config = {
            "train_micro_batch_size_per_gpu": micro,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            # warn mode arms the RecompileGuard (the retrace-count gate)
            # without failing the build on advisory findings
            "analysis": {"mode": "warn"},
            "steps_per_print": 10 ** 9,
        }
        if onebit:
            config["optimizer"] = {
                "type": "OneBitAdam",
                "params": {"lr": 6e-4, "freeze_step": freeze}}
            config["zero_optimization"]["low_bandwidth"] = {
                "onebit": True}
        else:
            config["optimizer"] = {"type": "Adam", "params": {"lr": 6e-4}}
        engine, _, _, _ = ds.initialize(model=model, config=config,
                                        model_parameters=params)
        it = batch_iter()
        # untimed: the warmup steps, the freeze-boundary switch, and one
        # compressed step to absorb the phase-B compile — the timed
        # window then measures the steady-state program only (the dense
        # twin runs the same untimed prefix so the A/B stays aligned)
        for _ in range(freeze + 1):
            engine.train_batch(it)

        def step():
            return engine.train_batch(it)

        import jax.numpy as jnp

        def param_sync():
            leaf = jax.tree.leaves(engine.params)[0]
            float(jnp.asarray(leaf).ravel()[0])

        dt, final_loss, n = _time_steps(step, warmup=1, iters=8,
                                        final_sync=param_sync)
        return engine, dt, final_loss, n

    e_1bit, dt_1bit, loss_1bit, n_1bit = run(onebit=True)
    if e_1bit._onebit_phase != "compressed":
        raise RuntimeError(
            "gpt2_onebit: engine never entered the compressed phase "
            f"(phase={e_1bit._onebit_phase!r}, freeze_step={freeze})")
    counters = (e_1bit._recompile_guard.counters()
                if e_1bit._recompile_guard is not None else {})
    planned = int(counters.get("planned_retraces", -1))
    if planned != 1:
        raise RuntimeError(
            f"gpt2_onebit: the warmup->compressed switch must cost "
            f"exactly ONE planned retrace, guard saw {counters}")

    # per-phase wire accounting (docs/onebit.md): the jaxpr numbers for
    # both phase programs plus the HLO cross-check when it lowers —
    # best-effort like every audit field, the row never fails on it
    phases = {}
    try:
        from deepspeed_tpu.analysis import audit_engine
        for phase in ("warmup", "compressed"):
            rep = audit_engine(e_1bit, multihost=False, phase=phase,
                               hlo=True)
            phases[f"wire_bytes_{phase}"] = rep.wire_bytes_per_step
            if rep.hlo:
                phases[f"hlo_wire_bytes_{phase}"] = (
                    rep.hlo["hlo_wire_bytes_per_step"])
            phases[f"lockstep_signature_{phase}"] = (
                rep.signature or "")[:16]
    except Exception as e:  # noqa: BLE001 — provenance is best-effort
        phases["phase_audit_error"] = f"{e}"[:120]

    e_dense, dt_dense, loss_dense, n_dense = run(onebit=False)
    band = 0.10
    if abs(loss_1bit - loss_dense) > band * max(1.0, abs(loss_dense)):
        raise RuntimeError(
            f"gpt2_onebit loss left the parity band: 1bit="
            f"{loss_1bit:.6f} vs dense={loss_dense:.6f} (band {band:.0%})"
            " — the compressed momentum changed the trajectory, not "
            "just the wire")

    tokens_per_sec = n_1bit * global_batch * seq / dt_1bit
    tokens_dense = n_dense * global_batch * seq / dt_dense
    tflops = tokens_per_sec * cfg.flops_per_token() / 1e12
    peak = _peak_tflops()
    return {
        "metric": "gpt2_124m_onebit_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip": round(tflops / dp, 2),
        "mfu": round(tflops / (peak * dp), 4),
        "data_world": dp,
        "freeze_step": freeze,
        "planned_retraces": planned,
        "final_loss": round(loss_1bit, 4),
        "dense_tokens_per_sec": round(tokens_dense, 1),
        "dense_final_loss": round(loss_dense, 4),
        "onebit_speedup": round(tokens_per_sec / tokens_dense, 4),
        "loss_parity_band": band,
        **phases,
        **_program_audit_fields(e_1bit,
                                measured_step_s=dt_1bit / n_1bit),
    }


def _zero3_stream_setup(row_name, batch, seq=1024):
    """Shared scaffolding of the zero3_stream rows (the carried pair
    and the fcm A/B): mesh + >1-device guard + model + data.  Requires
    a >1-device ZeRO world — on a single chip the streamed region
    cannot engage and the row raises rather than publish a non-streamed
    number."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    mesh = ds.initialize_mesh(data=-1)
    zero_world = mesh.data_parallel_world_size
    if zero_world < 2:
        raise RuntimeError(
            f"{row_name} needs a >1-device ZeRO world (explicit "
            f"streaming is a no-op on {zero_world} device) — run on a "
            "multichip host")
    cfg = GPT2Config(n_positions=seq, bf16=True)
    model = GPT2Model(cfg)
    per_layer = sum(
        int(np.prod(leaf.shape[1:])) for leaf in jax.tree.leaves(
            model.init_params(jax.random.PRNGKey(0))["h"]))
    rng = np.random.RandomState(0)
    global_batch = max(1, batch // zero_world) * zero_world
    ids = rng.randint(0, cfg.vocab_size,
                      size=(global_batch, seq)).astype(np.int32)
    return mesh, zero_world, cfg, model, per_layer, ids, global_batch


def _zero3_stream_run(setup, batch, carried, low_bandwidth=None,
                      row_name="zero3_stream"):
    """Build + time ONE streamed engine at the A/B-pinned group size
    (both modes plan groups of 2 layers — carried halves its budget for
    the prefetched group: 4x/2 -> 2; off takes 2x directly — so every
    A/B over this helper holds gather granularity fixed and varies only
    the schedule/transport).  Returns (dt, final_loss, n, plan, engine)
    and raises loudly when the requested plan did not engage."""
    import jax
    import deepspeed_tpu as ds

    mesh, zero_world, cfg, model, per_layer, ids, _ = setup
    zero_cfg = {
        "stage": 3,
        "stage3_param_persistence_threshold": 0,
        "stage3_max_live_parameters": (4 * per_layer if carried
                                       else 2 * per_layer),
        "stage3_prefetch_bucket_size": (2 * per_layer if carried
                                        else 0),
        "stage3_prefetch_mode": "carried" if carried else "off",
    }
    if low_bandwidth is not None:
        zero_cfg["low_bandwidth"] = dict(low_bandwidth)
    config = {
        "train_micro_batch_size_per_gpu": max(1, batch // zero_world),
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": zero_cfg,
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=config,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh)

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss

    dt, final_loss, n = _time_steps(step)
    plan = engine._zero3_stream.last_plan
    if plan is None or (carried and plan.mode != "carried"):
        raise RuntimeError(
            f"{row_name} row fell back to plan={plan} — the streamed "
            "region did not engage" +
            (" the carried prefetch" if carried else ""))
    return dt, final_loss, n, plan, engine


def _bench_gpt2_zero3_stream(carried, batch=8):
    """Streamed-ZeRO-3 A/B (ISSUE 7): the carried double-buffer prefetch
    (stage3_prefetch_mode=carried — layer i+1's gather issued into the
    scan carry under layer i's compute, backward re-gather likewise)
    against the serialized at-use gather baseline, same model/precision
    and the SAME group size (2 layers/gather — see _zero3_stream_run;
    the carried row legitimately holds two groups live, that IS the
    double buffer), so the measured delta isolates the prefetch, not a
    gather-granularity change.  Every row embeds overlap_efficiency /
    peak_hbm_bytes / predicted_step_time_lb from the static Schedule
    Auditor, so the measured delta is attributable against the model's
    prediction."""
    seq = 1024
    setup = _zero3_stream_setup("gpt2_zero3_stream", batch, seq)
    _, zero_world, cfg, _, _, _, global_batch = setup
    dt, final_loss, n, plan, engine = _zero3_stream_run(
        setup, batch, carried)
    tokens_per_sec = n * global_batch * seq / dt
    tflops = tokens_per_sec * cfg.flops_per_token() / 1e12
    peak = _peak_tflops()
    kind = "carried" if carried else "serialized"
    return {
        "metric": f"gpt2_124m_zero3_stream_{kind}_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip": round(tflops / zero_world, 2),
        "mfu": round(tflops / (peak * zero_world), 4),
        "final_loss": round(final_loss, 4),
        "zero_world": zero_world,
        "stream_plan": {"layers_per_step": plan.layers_per_step,
                        "prefetch": plan.prefetch, "mode": plan.mode},
        **_program_audit_fields(engine, measured_step_s=dt / n),
    }


def bench_gpt2_zero3_stream():
    return _bench_gpt2_zero3_stream(carried=False)


def bench_gpt2_zero3_stream_carried():
    return _bench_gpt2_zero3_stream(carried=True)


def bench_gpt2_zero3_stream_fcm(batch=8):
    """Fused-collective-matmul A/B (ISSUE 13): the per-tile fused qwZ/qgZ
    transports (ops/collective_matmul.py, low_bandwidth.fused_collective_
    matmul) against the modular monolithic collectives, at the IDENTICAL
    group size (g=2, carried prefetch in both modes — _zero3_stream_run)
    and identical qwZ/qgZ bits (8/8) — the measured delta isolates the
    per-tile transport schedule, nothing else.  Both runs' losses must
    agree (the fused gather is bitwise-identical and the fused scatter
    keeps the modular accumulation-order contract; only dense-fallback
    skinny leaves may reassociate) — the row fails loudly if they don't,
    and embeds overlap_efficiency + the exposed/hidden comm lanes for
    BOTH modes so the reconciliation attributes the win.  Requires a
    >1-device ZeRO world, like the carried pair."""
    seq = 1024
    setup = _zero3_stream_setup("gpt2_zero3_stream_fcm", batch, seq)
    _, zero_world, cfg, _, _, _, global_batch = setup

    def run(fcm):
        dt, final_loss, n, plan, engine = _zero3_stream_run(
            setup, batch, carried=True,
            low_bandwidth={"qwz_bits": 8, "qgz_bits": 8,
                           "fused_collective_matmul": bool(fcm)},
            row_name=f"gpt2_zero3_stream_fcm (fcm={fcm})")
        if fcm and not engine._zero3_stream.fcm:
            raise RuntimeError(
                "zero3_stream_fcm: fused_collective_matmul did not "
                "engage on the streaming context")
        audit = _program_audit_fields(engine, measured_step_s=dt / n)
        return dt, final_loss, n, plan, audit

    dt_mod, loss_mod, n_mod, plan_mod, audit_mod = run(fcm=False)
    dt_fcm, loss_fcm, n_fcm, plan_fcm, audit_fcm = run(fcm=True)
    if plan_fcm.layers_per_step != plan_mod.layers_per_step:
        raise RuntimeError(
            f"A/B group sizes diverged: fused g={plan_fcm.layers_per_step}"
            f" vs modular g={plan_mod.layers_per_step}")
    # identical-loss gate: same init, same data, same quantizers — only
    # dense-fallback skinny leaves may reassociate their fp32 grad sums
    if not np.isclose(loss_fcm, loss_mod, rtol=1e-2, atol=1e-3):
        raise RuntimeError(
            f"fused-vs-modular loss divergence: fcm={loss_fcm:.6f} vs "
            f"modular={loss_mod:.6f} — the fused transport changed the "
            "numerics, not just the schedule")

    tokens_per_sec = n_fcm * global_batch * seq / dt_fcm
    tokens_mod = n_mod * global_batch * seq / dt_mod
    tflops = tokens_per_sec * cfg.flops_per_token() / 1e12
    peak = _peak_tflops()

    def _lanes(audit):
        rec = (audit.get("reconciliation") or {})
        lanes = rec.get("lanes") or {}
        return {"exposed_comm": lanes.get("exposed_comm"),
                "hidden_comm": lanes.get("hidden_comm"),
                "overlap_efficiency": audit.get("overlap_efficiency")}

    return {
        "metric": "gpt2_124m_zero3_stream_fcm_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip": round(tflops / zero_world, 2),
        "mfu": round(tflops / (peak * zero_world), 4),
        "final_loss": round(loss_fcm, 4),
        "zero_world": zero_world,
        "stream_plan": {"layers_per_step": plan_fcm.layers_per_step,
                        "prefetch": plan_fcm.prefetch,
                        "mode": plan_fcm.mode, "fcm": True},
        "modular_tokens_per_sec": round(tokens_mod, 1),
        "modular_final_loss": round(loss_mod, 4),
        "fcm_speedup": round(tokens_per_sec / tokens_mod, 4),
        "lanes_modular": _lanes(audit_mod),
        "lanes_fcm": _lanes(audit_fcm),
        **audit_fcm,
    }


def bench_smoke():
    """Tiny end-to-end smoke row (2-layer GPT-2-shape, seq 128): exercises
    the full bench main path — backend init, engine build, compiled
    train loop, JSON contract — in under a minute on any backend.  For
    CI and verify drives; NOT a performance anchor (vs_baseline 0)."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    batch, seq = 4, 128
    cfg = GPT2Config(n_positions=seq, hidden_size=128, num_layers=2,
                     num_heads=4, vocab_size=2048, bf16=True)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    config = {
        "train_micro_batch_size_per_gpu": batch,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss

    dt, final_loss, n = _time_steps(step, warmup=1, iters=5)
    return {
        "metric": "smoke_tiny_gpt2_train_tokens_per_sec",
        "value": round(n * batch * seq / dt, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "final_loss": round(final_loss, 4),
        **_program_audit_fields(engine, measured_step_s=dt / n),
    }


def bench_bert_z2(batch=32, seq=128, baseline=272.0,
                  metric="bert_large_z2_samples_per_sec_1chip",
                  remat=False):
    """BERT-large-class encoder, ZeRO-2 — BASELINE.md anchor rows.

    seq=128 vs the reference's 272 samples/s and seq=512 vs its 52
    samples/s (docs/_tutorials/bert-pretraining.md:387, 1x V100)."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import BertConfig, BertModel
    cfg = BertConfig(max_position_embeddings=seq, hidden_size=1024,
                     num_layers=24, num_heads=16, bf16=True,
                     activation_checkpointing=remat)
    model = BertModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    config = {
        "train_micro_batch_size_per_gpu": batch,
        "optimizer": {"type": "Lamb", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    labels = ids  # full-position MLM — throughput accounting only

    def step():
        loss = engine.forward(ids, labels)
        engine.backward(loss)
        engine.step()
        return loss

    dt, final_loss, n = _time_steps(step)
    samples_per_sec = n * batch / dt
    tflops = n * batch * seq * cfg.flops_per_token(seq) / dt / 1e12
    return {
        "metric": metric,
        "value": round(samples_per_sec, 1),
        "unit": "samples/s",
        "vs_baseline": round(samples_per_sec / baseline, 3),
        "batch": batch, "seq_len": seq,
        "tflops_per_chip": round(tflops, 2),
        "mfu": round(tflops / _peak_tflops(), 4),
        "final_loss": round(final_loss, 4),
    }


def bench_decode():
    """Inference decode tokens/s on GPT-2 124M (KV-cache scan decode),
    bf16 and int8 — plus the int8 accuracy delta (greedy-token agreement
    vs the bf16 engine on the same weights, the serving-accuracy check the
    reference's int8 path implies — module_quantize.py)."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    batch, prompt, gen = 8, 128, 128
    cfg = GPT2Config(n_positions=prompt + gen, bf16=True)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, prompt)).astype(np.int32)
    iters = max(1, int(os.environ.get("DS_BENCH_ITERS", 5)))

    def run(dtype):
        engine = ds.init_inference(model=model, model_parameters=params,
                                   dtype=dtype)
        out = engine.generate(ids, max_new_tokens=gen)  # compile
        np.asarray(out)
        t0 = time.time()
        for _ in range(iters):
            out = engine.generate(ids, max_new_tokens=gen)
        toks = np.asarray(out)
        dt = time.time() - t0
        return iters * batch * gen / dt, toks

    tps_bf16, toks_bf16 = run("bf16")
    tps_int8, toks_int8 = run("int8")
    # generate() returns the NEW tokens only: [B, gen]
    agree = float((toks_bf16 == toks_int8).mean())
    return {
        "metric": "gpt2_124m_decode_tokens_per_sec_1chip",
        "value": round(tps_bf16, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # no reference decode anchor on this hw class
        "batch": batch, "prompt": prompt, "gen": gen,
        "int8_tokens_per_sec": round(tps_int8, 1),
        "int8_greedy_token_agreement": round(agree, 4),
    }


def _moe_routing_summary(engine, hot_k=4):
    """Drain the engine's device-resident RoutingStats accumulator ONCE
    (post-run — never per step) and summarize it in the row: drop
    fraction, imbalance max/mean, entropy, popularity top-k — what the
    ROUTER was doing, in the row itself (ISSUE 15)."""
    if not getattr(engine, "_moe_stats_enabled", False):
        return None
    raw = engine._monitor_moe_stats()
    # the throwaway monitor dir (mkdtemp in the row's config) has served
    # its purpose once the accumulator is drained — close the monitor
    # and remove the dir so repeated ladder runs don't litter /tmp
    try:
        if engine.monitor is not None:
            out_dir = engine.monitor.out_dir
            engine.monitor.close()
            import shutil
            shutil.rmtree(out_dir, ignore_errors=True)
    except Exception:  # noqa: BLE001 — telemetry cleanup is best-effort
        pass
    if raw is None:
        return None
    from deepspeed_tpu.monitor import record as mrec
    from deepspeed_tpu.monitor.moe import MoeRoutingAggregator
    agg = MoeRoutingAggregator(hot_k=hot_k)
    rec = agg.observe_window(raw, None, None)
    if rec is None:
        return None
    snap = rec.get(mrec.M_POPULARITY) or {}
    return {
        "drop_fraction": rec.get(mrec.M_DROP_FRAC),
        "imbalance_max_mean": rec.get(mrec.M_IMBALANCE),
        "min_count_frac": rec.get(mrec.M_MIN_COUNT_FRAC),
        "router_entropy": rec.get(mrec.M_ENTROPY),
        "router_confidence": rec.get(mrec.M_CONFIDENCE),
        "l_aux_mean": rec.get(mrec.M_LAUX),
        "tokens_per_step": rec.get(mrec.M_TOKENS_PER_STEP),
        "popularity_top_k": snap.get("hot"),
        "hit_rate_under_k": snap.get("hit_rate_under_k"),
    }


def bench_moe():
    """GPT-2-small + MoE FFN throughput on one chip (GShard top-2 gating;
    the BASELINE.md GPT-MoE ladder point, single-chip anchor)."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.moe import MoE

    batch, seq, d = 8, 1024, 768
    n_experts, top_k = 4, 2
    mesh = ds.initialize_mesh(data=-1)
    moe = MoE(hidden_size=d, num_experts=n_experts, k=top_k,
              capacity_factor=1.25)
    rng = jax.random.PRNGKey(0)
    x0 = jnp.zeros((batch * seq, d), jnp.bfloat16)
    moe_params = moe.init_params(rng, x0)
    head = jax.random.normal(jax.random.PRNGKey(1), (d, d),
                             jnp.float32) * 0.02
    params = {"moe": moe_params, "head": head}

    def model(p, rng, x, y):
        h, l_aux, _ = moe.apply(p["moe"], x, rng=rng)
        pred = h @ p["head"].astype(h.dtype)
        return jnp.mean((pred.astype(jnp.float32) - y) ** 2) + 0.01 * l_aux

    config = {
        "train_micro_batch_size_per_gpu": batch * seq,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        # routing-stats accumulator (ISSUE 15): huge write_interval so
        # no mid-run flush consumes it — the row fetches it ONCE at the
        # end and embeds the summary next to the active-FLOPs comparator
        "monitor": {"enabled": True,
                    "output_path": tempfile.mkdtemp(
                        prefix="ds_bench_moe_monitor_"),
                    "writers": ["jsonl"], "write_interval": 10 ** 9,
                    "reconcile": False, "moe": {"enabled": True}},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params, mesh=mesh)
    rng_np = np.random.RandomState(0)
    # Device-resident batch, placed ONCE: unlike the token-id benches
    # (32 KB/step), this bench feeds 50 MB of fp32 activations — re-staging
    # them per step would time the host-to-device copy, not the MoE layer.
    import jax as _jax
    xb = _jax.device_put(rng_np.randn(batch * seq, d).astype(np.float32))
    yb = _jax.device_put(rng_np.randn(batch * seq, d).astype(np.float32))

    def step():
        loss = engine.forward(xb, yb)
        engine.backward(loss)
        engine.step()
        return loss

    dt, final_loss, n = _time_steps(step)
    tokens_per_sec = n * batch * seq / dt
    routing = _moe_routing_summary(engine, hot_k=n_experts)
    # active FLOPs/token: top_k routed ExpertMLPs + gate + the d x d
    # head, Megatron 6N accounting — same axis as the dense rows
    # (VERDICT r4 weak #4: MoE rows need a comparator)
    d_ff = moe.deepspeed_moe.expert.d_ff
    active = (top_k * (2 * d * d_ff + d_ff + d) + d * n_experts + d * d)
    tflops = tokens_per_sec * 6 * active / 1e12
    return {
        "metric": "moe_top2_train_tokens_per_sec_1chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip_active": round(tflops, 2),
        "num_experts": n_experts, "final_loss": round(final_loss, 4),
        "routing": routing,
    }


def bench_gpt_moe():
    """GPT-MoE model family: GPT-2-small backbone with 8-expert top-2
    FFNs on alternating layers (~323M params, ~153M active/token at
    top-2) on one chip — the Megatron-MoE/GShard interleave as a first-class model
    (models/gpt_moe.py), complementing the single-layer `moe` row."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPTMoEConfig, GPTMoEModel

    batch, seq = 8, 1024
    mesh = ds.initialize_mesh(data=-1)
    cfg = GPTMoEConfig(n_positions=seq, bf16=True, num_experts=8, top_k=2,
                       moe_every=2)
    model = GPTMoEModel(cfg)
    engine, _, _, _ = ds.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": batch,
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 6e-4, "weight_decay": 0.1}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2},
                "monitor": {"enabled": True,
                            "output_path": tempfile.mkdtemp(
                                prefix="ds_bench_gptmoe_monitor_"),
                            "writers": ["jsonl"],
                            "write_interval": 10 ** 9,
                            "reconcile": False,
                            "moe": {"enabled": True}},
                "steps_per_print": 10 ** 9},
        mesh=mesh)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss

    dt, final_loss, n = _time_steps(step, warmup=2, iters=10)
    tokens_per_sec = n * batch * seq / dt
    routing = _moe_routing_summary(engine, hot_k=4)
    # ACTIVE-FLOPs accounting (GPTMoEConfig.flops_per_token): TFLOPS/MFU
    # land on the same Megatron-style axis as the dense rows, so the MoE
    # row finally has a comparator — vs_baseline keys on the shared
    # 64-TFLOPS anchor like every dense row (VERDICT r4 weak #4)
    tflops = tokens_per_sec * cfg.flops_per_token() / 1e12
    return {
        "metric": "gpt_moe_8e_top2_train_tokens_per_sec_1chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip_active": round(tflops, 2),
        "mfu_active": round(tflops / _peak_tflops(), 4),
        "num_experts": 8, "top_k": 2,
        "total_params": cfg.num_params(),
        "final_loss": round(final_loss, 4),
        "routing": routing,
    }


def _run_longseq(model_cfg, batch=2, seq=8192):
    """Shared S=8192 GPT-2 training row (dense and sparse variants differ
    ONLY in model_cfg, keeping the two rows comparable by construction).
    Returns (tokens_per_sec, dense_equiv_tflops, final_loss)."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Model

    model = GPT2Model(model_cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    config = {
        "train_micro_batch_size_per_gpu": batch,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, model_cfg.vocab_size,
                      size=(batch, seq)).astype(np.int32)

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss

    dt, final_loss, n = _time_steps(step, warmup=2, iters=10)
    tokens_per_sec = n * batch * seq / dt
    tflops = tokens_per_sec * model_cfg.flops_per_token() / 1e12
    return tokens_per_sec, tflops, final_loss


def bench_longseq():
    """GPT-2 124M at S=8192, batch 2 — EXACT causal attention at 8x the
    reference's practical sequence length on one chip, enabled by the O(S)
    flash kernel (the reference's long-seq story is block-sparse
    approximation, README.md:19 'up to 6x faster, ~10x longer'; this row
    is the exact-attention counterpart)."""
    from deepspeed_tpu.models import GPT2Config

    seq = 8192
    cfg = GPT2Config(n_positions=seq, bf16=True)
    tokens_per_sec, tflops, final_loss = _run_longseq(cfg, seq=seq)
    return {
        "metric": "gpt2_124m_seq8192_train_tokens_per_sec_1chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip": round(tflops, 2),
        "mfu": round(tflops / _peak_tflops(), 4),
        "seq_len": seq,
        "final_loss": round(final_loss, 4),
    }


def bench_sparse_longseq():
    """GPT-2 124M at S=8192 with BigBird block-sparse attention (block=512,
    3-block sliding window + global + random) via the Pallas block-sparse
    flash kernel — the reference's actual long-seq mechanism ('up to 6.2x
    faster vs dense', README.md:19; Triton kernels matmul.py:749).
    Comparable to the `longseq` row: same model/batch/seq (via
    _run_longseq), attention swapped dense->sparse.  tokens/s counts real
    tokens; tflops uses the DENSE flops_per_token so vs_baseline stays
    comparable (the sparse row's win shows up as tokens/s, not as
    inflated utilization)."""
    from deepspeed_tpu.models import GPT2Config
    from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                    SparseSelfAttention)

    seq = 8192
    sparse = BigBirdSparsityConfig(
        num_heads=12, block=512, num_random_blocks=1,
        num_sliding_window_blocks=3, num_global_blocks=1)
    cfg = GPT2Config(n_positions=seq, bf16=True, sparse_attention=sparse)
    tokens_per_sec, tflops, final_loss = _run_longseq(cfg, seq=seq)
    density = SparseSelfAttention(sparse).density(seq)
    return {
        "metric": "gpt2_124m_seq8192_sparse_train_tokens_per_sec_1chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip_dense_equiv": round(tflops, 2),
        "seq_len": seq,
        "attn_density": round(density, 4),
        "final_loss": round(final_loss, 4),
    }


def bench_offload():
    """GPT-2 124M, ZeRO-2 + host-offloaded optimizer (native C++ host Adam
    — the DeepSpeedCPUAdam role).  Same model/step as the flagship gpt2
    config, so value/72k-ish quantifies the offload tax directly
    (reference framing: ZeRO-Offload trades step time for HBM,
    docs/_posts/2020-09-09-ZeRO-Offload.md).

    DS_BENCH_GAS=N (default 1) measures the gradient-accumulation
    amortization: grads cross device->host only at the boundary, so the
    per-token offload tax divides by N."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    batch, seq = 8, 1024
    gas = max(1, int(os.environ.get("DS_BENCH_GAS", 1)))
    cfg = GPT2Config(n_positions=seq, bf16=True)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    config = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {
            "stage": 2,
            "offload_optimizer": {"device": "cpu"}},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss

    # align warmup/iters to the accumulation boundary so the timed window
    # holds a WHOLE number of optimizer steps (amortization measured
    # fairly): iters is rounded UP to a multiple of gas, a DS_BENCH_ITERS
    # override is re-rounded inside _time_steps (align=gas), and the
    # window's LAST optimizer update is forced by a param fetch
    # (final_sync) — the loss fetch alone leaves it outside the clock
    import jax.numpy as jnp

    def param_sync():
        leaf = jax.tree.leaves(engine.params)[0]
        float(jnp.asarray(leaf).ravel()[0])

    iters = gas * max(2, -(-10 // gas)) if gas > 1 else 10
    dt, final_loss, n = _time_steps(step, warmup=max(2, gas),
                                    iters=iters, align=gas,
                                    final_sync=param_sync)
    tokens_per_sec = n * batch * seq / dt
    tflops = tokens_per_sec * cfg.flops_per_token() / 1e12
    return {
        "metric": ("gpt2_124m_offload_cpu_adam_tokens_per_sec_1chip"
                   if gas == 1 else
                   f"gpt2_124m_offload_cpu_adam_gas{gas}_tokens_per_sec_1chip"),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip": round(tflops, 2),
        "gradient_accumulation_steps": gas,
        "final_loss": round(final_loss, 4),
    }


def bench_infinity():
    """ZeRO-Infinity layer streaming on one chip: GPT-2 124M with params
    AND optimizer states on NVMe (the BASELINE.md max-model-per-chip
    ladder point — throughput of the streamed step)."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    batch, seq = 4, 1024
    mesh = ds.initialize_mesh(data=1, devices=jax.devices()[:1])
    cfg = GPT2Config(n_positions=seq, bf16=True)
    model = GPT2Model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": batch,
        "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "nvme",
                              "nvme_path": "/tmp/ds_tpu_bench_nvme"},
            "offload_optimizer": {"device": "nvme",
                                  "nvme_path": "/tmp/ds_tpu_bench_nvme"}},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=config,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss

    dt, final_loss, n = _time_steps(step, warmup=2, iters=8)
    tokens_per_sec = n * batch * seq / dt
    tflops = tokens_per_sec * cfg.flops_per_token() / 1e12
    return {
        "metric": "gpt2_124m_infinity_nvme_tokens_per_sec_1chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tflops / REFERENCE_TFLOPS, 3),
        "tflops_per_chip": round(tflops, 2),
        "hbm_groups_resident": engine.max_live_param_groups,
        "final_loss": round(final_loss, 4),
    }


def bench_infinity_stream():
    """ZeRO-Infinity NVMe streaming A/B (ISSUE 8): carried double-buffer
    prefetch (offload_param.prefetch_depth=2 — group i+1's NVMe read
    issued under group i's compute, cross-sweep carries included) against
    the serialized swap-at-use baseline (prefetch_depth=0), same tiny GPT
    model/precision so the loss trajectories must match exactly and the
    measured delta isolates the swap schedule.  CPU-runnable: the streamed
    step is host-driven, so the overlap property is measurable anywhere.
    Embeds the achieved read GB/s (lower bound — per-group issue->done
    windows), the bytes-weighted overlap fraction for BOTH modes, and the
    aio_sweep ceiling the achieved rate is compared against (the engine's
    honesty report, runtime/zero/infinity.py _finalize_swap_stats).

    On a CPU-only host vs_baseline (wall A/B) sits near 1.0: the 'device'
    compute runs on the same cores the aio pool reads with, so there is
    no idle accelerator time to hide the reads under — the
    overlap_bytes ratio is the schedule property this row pins; the wall
    win appears when compute is on-chip (ROADMAP item 3 acceptance)."""
    import shutil
    import tempfile

    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    batch, seq, steps = 2, 256, 4
    cfg = GPT2Config(n_positions=seq, hidden_size=256, num_layers=8,
                     num_heads=8, vocab_size=8192, bf16=False,
                     embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)

    def run(prefetch_depth):
        ds.reset_mesh_context()
        mesh = ds.initialize_mesh(data=1, devices=jax.devices()[:1])
        model = GPT2Model(cfg)
        nvme_dir = tempfile.mkdtemp(prefix="ds_tpu_infstream_")
        config = {
            "train_micro_batch_size_per_gpu": batch,
            "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
            "zero_optimization": {
                "stage": 3,
                "offload_param": {"device": "nvme", "nvme_path": nvme_dir,
                                  "buffer_count": 2,
                                  "prefetch_depth": prefetch_depth},
                "offload_optimizer": {"device": "nvme",
                                      "nvme_path": nvme_dir}},
            "steps_per_print": 10 ** 9,
        }
        engine, _, _, _ = ds.initialize(
            model=model, config=config,
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            mesh=mesh, rng=jax.random.PRNGKey(9))
        losses, stats = [], []
        t0 = None
        for k in range(steps + 1):  # step 0 is compile warmup, untimed
            if k == 1:
                t0 = time.time()
            loss = engine.forward(ids)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
            if k >= 1:
                stats.append(engine.swap_stats())
        dt = time.time() - t0
        backend = engine.aio_backend
        ceiling = engine.sweep_ceiling
        shutil.rmtree(nvme_dir, ignore_errors=True)
        agg = {
            "read_bytes_per_step": np.mean([s["read_bytes"] for s in stats]),
            "overlap_bytes_per_step": np.mean(
                [s["overlap_bytes"] for s in stats]),
            "overlap_fraction": float(np.mean(
                [s["overlap_fraction"] for s in stats])),
            "read_gbps": float(np.mean([s["read_gbps"] for s in stats])),
            "read_exposed_s": float(np.mean(
                [s["read_exposed_s"] for s in stats])),
            "write_bytes_per_step": np.mean(
                [s["write_bytes"] for s in stats]),
            "write_exposed_s": float(np.mean(
                [s["write_exposed_s"] for s in stats])),
            "serialized_swap_ins_last": stats[-1]["serialized_swap_ins"],
        }
        return losses, dt, agg, backend, ceiling

    losses_on, dt_on, on, backend, ceiling = run(prefetch_depth=2)
    losses_off, dt_off, off, _, _ = run(prefetch_depth=0)
    if not np.allclose(losses_on, losses_off, rtol=0, atol=1e-6):
        raise RuntimeError(
            f"prefetch changed the loss trajectory: {losses_on} vs "
            f"{losses_off} — the swap schedule must be compute-invariant")
    tokens_per_sec = steps * batch * seq / dt_on
    overlap_ratio = (on["overlap_bytes_per_step"] /
                     max(off["overlap_bytes_per_step"], 1.0))
    return {
        "metric": "gpt2_tiny_infinity_stream_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        # A/B against the serialized baseline, not a hardware anchor
        "vs_baseline": round(dt_off / dt_on, 3),
        "steps": steps, "batch": batch, "seq_len": seq,
        "aio_backend": backend,
        "read_gbps": round(on["read_gbps"], 3),
        "sweep_read_ceiling_gbps": (round(ceiling["read_gbps"], 2)
                                    if ceiling else None),
        "read_vs_ceiling": (round(on["read_gbps"] / ceiling["read_gbps"], 4)
                            if ceiling else None),
        "read_bytes_per_step": int(on["read_bytes_per_step"]),
        "write_bytes_per_step": int(on["write_bytes_per_step"]),
        "write_exposed_s": round(on["write_exposed_s"], 4),
        "overlap_fraction_on": round(on["overlap_fraction"], 4),
        "overlap_fraction_off": round(off["overlap_fraction"], 4),
        "overlap_bytes_ratio": round(overlap_ratio, 2),
        "serialized_swap_ins_last": on["serialized_swap_ins_last"],
        "loss_trajectory_match": True,
        "final_loss": round(losses_on[-1], 4),
        "reconciliation": _swap_reconciliation(on, ceiling,
                                               dt_on / steps),
        **_fleet_summary_fields(
            dt_on / steps, final_loss=float(losses_on[-1]),
            swap={"read_gbps": on["read_gbps"],
                  "read_exposed_s": on["read_exposed_s"],
                  "write_exposed_s": on["write_exposed_s"]}),
    }


def _swap_reconciliation(agg, ceiling, measured_step_s):
    """Swap-lane reconciliation for the streaming row (same math/field
    names as the runtime monitor's per-window report — the streaming
    engine has no static roofline, so the comparison is achieved GB/s +
    overlap vs the aio sweep ceiling)."""
    from deepspeed_tpu.monitor import Bands, bare_summary, reconcile_window
    swap = {"read_gbps": agg["read_gbps"],
            "overlap_fraction": agg["overlap_fraction"],
            "read_exposed_s": agg["read_exposed_s"],
            "write_exposed_s": agg["write_exposed_s"]}
    if ceiling:
        swap["sweep_read_gbps"] = ceiling["read_gbps"]
        swap["read_vs_ceiling"] = agg["read_gbps"] / ceiling["read_gbps"]
    return bare_summary(reconcile_window(
        {"step_time_s": measured_step_s, "swap": swap}, None, Bands()))


def bench_bert_s512():
    """BERT-large ZeRO-2 at seq 512 — BASELINE.md row 2 (52 samples/s).

    remat=True: 24 layers of S=512 attention activations blow past HBM
    without per-layer rematerialization (measured: ResourceExhausted at
    B=16 without it); the reference's seq-512 recipe likewise leans on
    its activation-checkpointing tier."""
    return bench_bert_z2(batch=16, seq=512, baseline=52.0,
                         metric="bert_large_z2_s512_samples_per_sec_1chip",
                         remat=True)


def bench_gpt2_b16():
    """Flagship shape at batch 16 — the MFU-ceiling probe (the b=8 row
    may be underfeeding the MXU; same model/optimizer/zero config)."""
    return bench_gpt2(batch=16,
                      metric="gpt2_124m_b16_train_tokens_per_sec_1chip")


def bench_gpt2_b32():
    return bench_gpt2(batch=32,
                      metric="gpt2_124m_b32_train_tokens_per_sec_1chip")


def bench_gpt2_medium():
    """GPT-2 medium (355M): the MFU-scaling showcase — the 124M flagship
    is overhead-bound (small matmuls); at 355M the same engine should
    clear 50% MFU.  No reference-baseline row (vs_baseline keys on the
    same 64-TFLOPS anchor for cross-size comparability).

    remat=True since the round-5 OOM (ResourceExhausted in the optimizer
    apply): fp32 master+moments ~4.3 GB +
    bf16 params/grads ~1.4 GB leave no room for 24 layers of un-rematted
    B8 S1024 activations next to the apply working set on a 16 GB chip."""
    return bench_gpt2(metric="gpt2_355m_train_tokens_per_sec_1chip",
                      hidden=1024, layers=24, heads=16, remat=True)


def bench_gpt2_large():
    """GPT-2 large (774M) with remat: fp32 master+moments ~9.3 GB under
    ZeRO-2 on one 16 GB chip — the single-chip memory-discipline
    showcase.

    batch=4 + grads_in_compute_dtype since the round-5 OOM at B=8
    (ResourceExhausted in the optimizer apply): bf16 grad buffers halve
    the ~3.1 GB bf16 params+grads tier and the smaller batch halves the
    rematted activation floor, fitting the apply working set."""
    return bench_gpt2(metric="gpt2_774m_train_tokens_per_sec_1chip",
                      hidden=1280, layers=36, heads=20, remat=True,
                      batch=4, grads_half=True)


def bench_autotune():
    """Ladder ingestion of one autotune leaderboard row (docs/
    autotuner.md — ROADMAP item 5's "validate on chip once" half).
    DS_BENCH_AUTOTUNE_RESULTS names the autotune_results.json a search
    emitted (default autotune_out/autotune_results.json) and
    DS_BENCH_AUTOTUNE_RANK picks the leaderboard entry (default 1); one
    bench invocation per rank turns the top-K into a ladder.  The row
    runs the emitted bench-ready config VERBATIM on the exact model
    shape the search ranked, and embeds the search's prediction next to
    the measurement — _program_audit_fields' reconciliation then feeds
    `python -m deepspeed_tpu.analysis calibrate --records <row.json>`,
    closing the calibration loop."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.analysis.autotuner import (RESULTS_FILENAME,
                                                  validate_results)
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    results_path = os.environ.get(
        "DS_BENCH_AUTOTUNE_RESULTS",
        os.path.join("autotune_out", RESULTS_FILENAME))
    rank = int(os.environ.get("DS_BENCH_AUTOTUNE_RANK", "1"))
    with open(results_path) as f:
        payload = json.load(f)
    validate_results(payload)
    entry = next((e for e in payload["leaderboard"]
                  if e["rank"] == rank), None)
    if entry is None:
        raise RuntimeError(
            f"no rank {rank} in {results_path} (leaderboard has "
            f"{len(payload['leaderboard'])} entries)")
    cfg_path = os.path.join(os.path.dirname(os.path.abspath(results_path)),
                            entry["config_file"])
    with open(cfg_path) as f:
        config = json.load(f)

    chips = int(payload["chips"])
    if jax.device_count() != chips:
        # the emitted config pins a mesh factorization of `chips`; a
        # different world would silently build a different program than
        # the one the search ranked
        raise RuntimeError(
            f"autotune row wants the searched {chips}-chip mesh, "
            f"backend has {jax.device_count()} device(s) — rerun the "
            f"search with --chips {jax.device_count()} or run on the "
            "searched slice")
    mk = payload["model"]
    mcfg = GPT2Config(
        hidden_size=mk["hidden"], num_layers=mk["layers"],
        num_heads=mk["heads"], n_positions=mk["seq"],
        vocab_size=mk["vocab"],
        bf16=bool(config.get("bf16", {}).get("enabled", False)))
    model = GPT2Model(mcfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params)

    micro = engine.train_micro_batch_size_per_gpu()
    gas = engine.gradient_accumulation_steps()
    dp = engine.mesh_ctx.data_parallel_world_size
    seq = mk["seq"]
    rng = np.random.RandomState(0)
    ids = rng.randint(0, mk["vocab"],
                      size=(micro * dp, seq)).astype(np.int32)

    def batch_iter():
        while True:
            yield (ids,)

    it = batch_iter()

    def step():
        return engine.train_batch(it)  # one optimizer step (gas micros)

    import jax.numpy as jnp

    def param_sync():
        leaf = jax.tree.leaves(engine.params)[0]
        float(jnp.asarray(leaf).ravel()[0])

    dt, final_loss, n = _time_steps(step, warmup=2, iters=8,
                                    final_sync=param_sync)
    tokens_per_step = gas * micro * dp * seq
    measured_step_s = dt / n
    predicted = float(entry["predicted_step_time_lb_s"])
    return {
        "metric": "autotune_candidate_train_tokens_per_sec",
        "value": round(n * tokens_per_step / dt, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # candidate rows compare to their siblings
        "autotune_rank": rank,
        "autotune_name": entry["name"],
        "autotune_results": os.path.abspath(results_path),
        "autotune_predicted_step_time_lb_s": predicted,
        "autotune_measured_over_predicted": round(
            measured_step_s / predicted, 3) if predicted > 0 else None,
        "final_loss": round(final_loss, 4),
        **_program_audit_fields(engine, measured_step_s=measured_step_s),
    }


BENCHES = {"gpt2": bench_gpt2, "smoke": bench_smoke,
           "autotune": bench_autotune,
           "gpt2_gas4": bench_gpt2_gas4,
           "gpt2_gas4_fused": bench_gpt2_gas4_fused,
           "gpt2_onebit": bench_gpt2_onebit,
           "gpt2_zero3_stream": bench_gpt2_zero3_stream,
           "gpt2_zero3_stream_carried": bench_gpt2_zero3_stream_carried,
           "gpt2_zero3_stream_fcm": bench_gpt2_zero3_stream_fcm,
           "gpt2_b16": bench_gpt2_b16, "gpt2_b32": bench_gpt2_b32,
           "gpt2_medium": bench_gpt2_medium, "gpt2_large": bench_gpt2_large,
           "bert_z2": bench_bert_z2, "bert_s512": bench_bert_s512,
           "decode": bench_decode, "moe": bench_moe,
           "gpt_moe": bench_gpt_moe,
           "longseq": bench_longseq, "sparse_longseq": bench_sparse_longseq,
           "offload": bench_offload,
           "infinity": bench_infinity,
           "infinity_stream": bench_infinity_stream}
METRIC_NAMES = {  # the metric each row reports (pinned by test_bench_harness)
    "autotune": ("autotune_candidate_train_tokens_per_sec", "tokens/s"),
    "gpt2": ("gpt2_124m_train_tokens_per_sec_1chip", "tokens/s"),
    "gpt2_gas4": ("gpt2_124m_gas4_modular_train_tokens_per_sec_1chip",
                  "tokens/s"),
    "gpt2_gas4_fused": ("gpt2_124m_gas4_fused_train_tokens_per_sec_1chip",
                        "tokens/s"),
    "gpt2_onebit": ("gpt2_124m_onebit_train_tokens_per_sec", "tokens/s"),
    "gpt2_zero3_stream": ("gpt2_124m_zero3_stream_serialized_train_tokens"
                          "_per_sec", "tokens/s"),
    "gpt2_zero3_stream_carried": ("gpt2_124m_zero3_stream_carried_train_"
                                  "tokens_per_sec", "tokens/s"),
    "gpt2_zero3_stream_fcm": ("gpt2_124m_zero3_stream_fcm_train_tokens"
                              "_per_sec", "tokens/s"),
    "gpt2_b16": ("gpt2_124m_b16_train_tokens_per_sec_1chip", "tokens/s"),
    "gpt2_b32": ("gpt2_124m_b32_train_tokens_per_sec_1chip", "tokens/s"),
    "gpt2_medium": ("gpt2_355m_train_tokens_per_sec_1chip", "tokens/s"),
    "gpt2_large": ("gpt2_774m_train_tokens_per_sec_1chip", "tokens/s"),
    "smoke": ("smoke_tiny_gpt2_train_tokens_per_sec", "tokens/s"),
    "bert_z2": ("bert_large_z2_samples_per_sec_1chip", "samples/s"),
    "bert_s512": ("bert_large_z2_s512_samples_per_sec_1chip", "samples/s"),
    "decode": ("gpt2_124m_decode_tokens_per_sec_1chip", "tokens/s"),
    "moe": ("moe_top2_train_tokens_per_sec_1chip", "tokens/s"),
    "gpt_moe": ("gpt_moe_8e_top2_train_tokens_per_sec_1chip",
                "tokens/s"),
    "longseq": ("gpt2_124m_seq8192_train_tokens_per_sec_1chip",
                "tokens/s"),
    "sparse_longseq": ("gpt2_124m_seq8192_sparse_train_tokens_per_sec_1chip",
                       "tokens/s"),
    "offload": ("gpt2_124m_offload_cpu_adam_tokens_per_sec_1chip",
                "tokens/s"),
    "infinity": ("gpt2_124m_infinity_nvme_tokens_per_sec_1chip",
                 "tokens/s"),
    "infinity_stream": ("gpt2_tiny_infinity_stream_tokens_per_sec",
                        "tokens/s"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2", choices=sorted(BENCHES))
    args = ap.parse_args()

    from deepspeed_tpu.utils.chip import device_summary, enable_compile_cache

    enable_compile_cache()
    device = device_summary()
    asked = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    if device["platform"] != "tpu" and "cpu" not in asked:
        sys.exit(f"bench.py measures a TPU and jax reports platform="
                 f"{device['platform']!r}; set JAX_PLATFORMS=cpu to run a "
                 "row on the CPU on purpose (its numbers are not device "
                 "metrics)")
    payload = BENCHES[args.config]()
    payload["platform"] = device["platform"]
    payload["device_kind"] = device["kind"]
    payload["device_count"] = device["count"]
    payload["commit"] = _git_head()
    payload["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime())
    _emit(payload)


if __name__ == "__main__":
    main()
