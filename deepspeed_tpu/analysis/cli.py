"""CLI: lint a config + model pair on CPU without touching an accelerator.

    python -m deepspeed_tpu.analysis --config ds_config.json \
        [--model gpt2] [--hidden 64 --layers 2 --heads 4 --seq 64 \
         --vocab 256] [--devices 1] [--mode off|warn|error] \
        [--json] [--dump-sequence] [--update-golden]

Builds the model and engine on the CPU backend, traces the step
program(s) abstractly, runs every static lint rule plus the schedule
analyses (lockstep signature, overlap, HBM liveness, step-time lower
bound), prints the findings, and exits nonzero when the effective
``analysis.mode`` is ``"error"`` and error-severity findings exist — the
CI contract.  The model defaults to a tiny GPT-2 shape: the lint is
about PROGRAM STRUCTURE (which the config decides), not model scale.

``--devices N`` simulates an N-device CPU mesh (the streamed-ZeRO-3
configs need a real ZeRO world to engage their explicit collectives);
``--mode`` overrides the config's ``analysis.mode`` (the CI gate runs
every example config under ``--mode error``); ``--update-golden``
regenerates the checked-in golden files for the known example configs
instead of hand-editing them.

Subcommands (docs/autotuner.md):

    python -m deepspeed_tpu.analysis tune --config base.json \
        --chips N [--hbm-budget-mb M] [--top-k K] [--calibration FILE] \
        [--out DIR] [--json] [--update-golden]

runs the offline cost-model-driven config search (analysis/autotuner.py)
and emits the top-K engine-ready configs + autotune_results.json;

    python -m deepspeed_tpu.analysis calibrate --records FILE \
        [--base-config base.json] [--out hw_calibration.json] [--json]

fits the hw_{peak_tflops,hbm_gbps,ici_gbps} constants from
measured-vs-predicted reconciliation windows (monitor JSONL records or
a JSON row that embeds a reconciliation), writing the calibration file
the next ``tune --calibration`` loads.
"""

import argparse
import json
import os
import sys
from pathlib import Path

# Known example config -> golden file regenerated by --update-golden.
# Tests pin these files; the CLI is the single writer.
GOLDEN_MAP = {
    "gpt2_analysis.json": "gpt2_lockstep_signature.json",
    "gpt2_zero3_stream_analysis.json": "gpt2_zero3_stream_schedule.json",
    "gpt2_zero3_stream_fcm.json": "gpt2_zero3_stream_fcm_schedule.json",
    "gpt2_hlo_audit.json": "gpt2_hlo_audit.json",
}
# configs whose golden embeds HLO-cross-check fields: --update-golden
# (and the regen test) must run them with the audit on
HLO_GOLDEN_CONFIGS = ("gpt2_hlo_audit.json",)

# tune --update-golden: example search config -> pinned leaderboard
TUNE_GOLDEN_MAP = {
    "gpt2_autotune.json": "gpt2_autotune_leaderboard.json",
}

_TUNE_GOLDEN_COMMENT = (
    "Golden leaderboard of the example autotune search "
    "(docs/examples/gpt2_autotune.json, 8 simulated chips): candidate "
    "ORDERING and names are pinned exactly; predicted_step_time_lb_s "
    "values are band-tolerant in the test (the static model is "
    "deterministic, but jaxpr shapes may drift slightly across jax "
    "versions). If the ordering diffs, the cost model or the search "
    "space changed — re-pin only if that is intended. Regenerate with: "
    "python -m deepspeed_tpu.analysis tune --config "
    "docs/examples/gpt2_autotune.json --update-golden")

_LOCKSTEP_COMMENT = (
    "Collective-lockstep signature of the default gpt2 config "
    "(docs/examples/gpt2_analysis.json): ZeRO stage 2, modular step. "
    "Stage <= 2 emits NO explicit collectives (GSPMD inserts the "
    "data-parallel reduction from shardings), so this is the combine of "
    "two empty collective sequences over [grad_step, apply_step] - "
    "stable across model sizes and backends. If this test diffs, the "
    "step program GAINED or LOST an explicit collective: re-pin only if "
    "that is intended on every host of a pod. Regenerate with: python "
    "-m deepspeed_tpu.analysis --config docs/examples/gpt2_analysis"
    ".json --update-golden")

_FCM_SCHEDULE_COMMENT = (
    "Schedule golden of the FUSED-collective-matmul streamed-ZeRO-3 "
    "gpt2 config (docs/examples/gpt2_zero3_stream_fcm.json, --devices "
    "8): with zero_optimization.low_bandwidth.fused_collective_matmul "
    "on, every qwZ weight gather and qgZ grad scatter in the hot loop "
    "moves per-TILE over a ring (ops/collective_matmul.py) and the "
    "overlap classifier pins them fused/hidden — n_fused > 0 with "
    "n_serialized_hot_loop == 0 and wire_bytes_exposed_hot_loop == 0 "
    "is the ISSUE 13 acceptance bar (exposed-comm lane ~ 0 in "
    "predicted_step_time_lb's attribution), enforced by the config's "
    "require_overlap + mode=error CI gate. Regenerate with: python -m "
    "deepspeed_tpu.analysis --config docs/examples/"
    "gpt2_zero3_stream_fcm.json --devices 8 --update-golden")

_HLO_AUDIT_COMMENT = (
    "HLO-level SPMD cross-check golden of the default gpt2 config with "
    "analysis.hlo_audit + require_spmd_match on (docs/examples/"
    "gpt2_hlo_audit.json, --devices 8): every program is lowered "
    "through XLA's SPMD partitioner (compile-only) and the compiled "
    "collectives are reconciled against the jaxpr wire accounting "
    "(analysis/hlo_audit.py).  n_silent_reshards == 0 with a "
    "divergence_ratio of 1.0 is the ISSUE 14 acceptance bar: the "
    "compiler inserted NO gather the sharding contract does not "
    "predict, and the jaxpr and HLO accountings agree on the traced "
    "wire.  hlo_wire_bytes_per_step > wire_bytes_per_step is EXPECTED "
    "(GSPMD's data-parallel grad combine and ZeRO's param re-gather "
    "are wire the jaxpr never counted — now priced in the exposed-comm "
    "lane).  If this diffs, the compiled wire story changed: re-pin "
    "only after reading the per-target breakdown (--json .hlo). "
    "Regenerate with: python -m deepspeed_tpu.analysis --config "
    "docs/examples/gpt2_hlo_audit.json --devices 8 --update-golden")

_SCHEDULE_COMMENT = (
    "Schedule golden of the streamed-ZeRO-3 gpt2 config "
    "(docs/examples/gpt2_zero3_stream_analysis.json, --devices 8): the "
    "explicit per-layer gathers plus their overlap classification under "
    "the carried prefetch (docs/zero3_streaming.md). "
    "n_serialized_hot_loop == 0 with n_carried > 0 pins the carried "
    "double-buffer schedule — every hot-loop weight gather is issued one "
    "group ahead through the scan carry, forward and backward — and "
    "overlap_efficiency must stay strictly above the pre-carried "
    "serialized baseline (frozen in gpt2_zero3_stream_schedule_"
    "serialized.json). If n_serialized_hot_loop regresses above 0 the "
    "config's require_overlap gate fails CI. Regenerate with: python "
    "-m deepspeed_tpu.analysis --config docs/examples/"
    "gpt2_zero3_stream_analysis.json --devices 8 --update-golden")


def golden_dir() -> Path:
    override = os.environ.get("DS_ANALYSIS_GOLDEN_DIR")
    if override:
        return Path(override)
    return Path(__file__).resolve().parents[2] / "tests" / "unit" / "golden"


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.analysis",
        description="Static Program/Schedule Auditor: lint a DeepSpeed-"
                    "TPU config + model pair (host-syncs, donation "
                    "misses, collective lockstep, dtype hazards, comm "
                    "budget, overlap, HBM liveness, step-time bound).")
    p.add_argument("--config", required=True,
                   help="DeepSpeed JSON config path")
    _add_model_args(p)
    p.add_argument("--devices", type=int, default=1,
                   help="simulated CPU device count (streamed-ZeRO-3 "
                        "configs need a >1 ZeRO world to engage)")
    p.add_argument("--mode", default=None,
                   choices=("off", "warn", "error"),
                   help="override the config's analysis.mode (the CI "
                        "gate runs every example under --mode error)")
    p.add_argument("--json", action="store_true",
                   help="emit the full report as JSON on stdout "
                        "(includes overlap, liveness, and step-time)")
    p.add_argument("--dump-sequence", action="store_true",
                   help="print the ordered collective sequence, the "
                        "per-collective overlap records, and the top "
                        "live buffers at the HBM peak")
    p.add_argument("--hlo-audit", action="store_true",
                   help="additionally lower every program through XLA's "
                        "SPMD partitioner (compile-only) and cross-check "
                        "the jaxpr wire story against the compiled "
                        "collectives (analysis/hlo_audit.py) — the CI "
                        "gate posture; implied by analysis.hlo_audit")
    p.add_argument("--update-golden", action="store_true",
                   help="regenerate the checked-in golden file for this "
                        "example config (tests/unit/golden/) instead of "
                        "hand-editing it")
    return p


def _golden_payload(golden_name: str, report) -> dict:
    if golden_name == "gpt2_lockstep_signature.json":
        return {
            "_comment": _LOCKSTEP_COMMENT,
            "signature": report.signature,
            "collective_count": len(report.collective_sequence),
        }
    if golden_name == "gpt2_hlo_audit.json":
        hlo = report.hlo or {}
        ratio = hlo.get("divergence_ratio", 1.0)
        return {
            "_comment": _HLO_AUDIT_COMMENT,
            "signature": report.signature,
            "wire_bytes_per_step": int(report.wire_bytes_per_step),
            "hlo_wire_bytes_per_step": hlo.get("hlo_wire_bytes_per_step"),
            "hlo_collective_count": hlo.get("hlo_collective_count"),
            "hlo_only_wire_bytes_per_step": hlo.get(
                "hlo_only_wire_bytes_per_step"),
            "divergence_ratio": (round(ratio, 4)
                                 if ratio != float("inf") else "inf"),
            "n_silent_reshards": hlo.get("n_silent_reshards"),
            "waivers": {
                label: t.get("waivers", [])
                for label, t in sorted(hlo.get("targets", {}).items())},
        }
    ov = report.overlap
    if golden_name == "gpt2_zero3_stream_fcm_schedule.json":
        st = report.step_time or {}
        # hot-loop exposure only: top-level collectives (the optimizer's
        # dispatch-boundary psums) are serialized by the dispatch anyway
        # and identical between the fused and modular configs — the
        # acceptance bar is the HOT LOOP's qwZ/qgZ wire
        exposed_hot = sum(
            int(r["wire_bytes"] * r["mult"]
                * (1.0 - r["hidden_fraction"]))
            for r in ov.get("records", []) if r["loop_depth"] > 0)
        return {
            "_comment": _FCM_SCHEDULE_COMMENT,
            "signature": report.signature,
            "collective_count": len(report.collective_sequence),
            "overlap": {
                "n_collectives": ov.get("n_collectives", 0),
                "n_serialized_hot_loop": ov.get("n_serialized_hot_loop",
                                                0),
                "n_carried": ov.get("n_carried", 0),
                "n_fused": ov.get("n_fused", 0),
                "overlap_efficiency": round(report.overlap_efficiency, 4),
            },
            "wire_bytes_exposed_hot_loop": exposed_hot,
            "wire_bytes_fused": int(st.get("wire_bytes_fused", 0)),
        }
    return {
        "_comment": _SCHEDULE_COMMENT,
        "signature": report.signature,
        "collective_count": len(report.collective_sequence),
        "overlap": {
            "n_collectives": ov.get("n_collectives", 0),
            "n_serialized_hot_loop": ov.get("n_serialized_hot_loop", 0),
            "n_carried": ov.get("n_carried", 0),
            "overlap_efficiency": round(report.overlap_efficiency, 4),
        },
    }


def _add_model_args(p: argparse.ArgumentParser) -> None:
    """The tiny trace-model shape (shared by lint and tune: the audit is
    about PROGRAM STRUCTURE, not model scale)."""
    p.add_argument("--model", default=None, choices=("gpt2", "gpt_moe"),
                   help="model family to trace (default gpt2, or the "
                        "config's top-level _trace_model hint — the MoE "
                        "example configs carry one so the CI gate "
                        "traces the program they describe)")
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--vocab", type=int, default=256)


def _resolve_trace_model(args, raw):
    """(family, extra-kwargs) for the trace model: an explicit --model
    wins; otherwise the config's top-level ``_trace_model`` hint (a
    family string, or an object with family + MoE knobs) decides —
    unknown keys at the config top level are ignored by the engine, so
    the hint rides inside the example configs themselves."""
    hint = raw.get("_trace_model")
    extra = {}
    if isinstance(hint, dict):
        extra = {k: v for k, v in hint.items() if k != "family"}
        hint = hint.get("family")
    family = args.model or hint or "gpt2"
    if family not in ("gpt2", "gpt_moe"):
        raise SystemExit(
            f"_trace_model names unknown family {family!r} "
            "(known: gpt2, gpt_moe)")
    return family, extra


def _build_trace_model(args, raw, bf16: bool):
    """Build the tiny trace model + params for the lint."""
    import jax
    family, extra = _resolve_trace_model(args, raw)
    if family == "gpt_moe":
        from deepspeed_tpu.models import GPTMoEConfig, GPTMoEModel
        cfg = GPTMoEConfig(
            hidden_size=args.hidden, num_layers=args.layers,
            num_heads=args.heads, n_positions=args.seq,
            vocab_size=args.vocab, bf16=bf16,
            num_experts=int(extra.get("num_experts", 4)),
            top_k=int(extra.get("top_k", 2)),
            moe_every=int(extra.get("moe_every", 2)),
            capacity_factor=float(extra.get("capacity_factor", 1.25)))
        model = GPTMoEModel(cfg)
    else:
        from deepspeed_tpu.models import GPT2Config, GPT2Model
        cfg = GPT2Config(
            hidden_size=args.hidden, num_layers=args.layers,
            num_heads=args.heads, n_positions=args.seq,
            vocab_size=args.vocab, bf16=bf16)
        model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def build_tune_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.analysis tune",
        description="Offline cost-model-driven config search "
                    "(docs/autotuner.md): enumerate the decision space, "
                    "prune hard constraints, trace survivors on a "
                    "simulated mesh, rank by the static step-time lower "
                    "bound, emit top-K engine-ready configs + "
                    "autotune_results.json.")
    p.add_argument("--config", required=True,
                   help="base DeepSpeed JSON config (its `autotuning` "
                        "block sets the search axes; CLI flags win)")
    p.add_argument("--chips", type=int, default=None,
                   help="simulated chip count to search over (default: "
                        "autotuning.chips)")
    p.add_argument("--global-batch", type=int, default=None,
                   help="fixed global batch the (micro, gas) splits "
                        "factor (default: autotuning.global_batch, then "
                        "the base config's train_batch_size)")
    p.add_argument("--hbm-budget-mb", type=float, default=None,
                   help="per-chip HBM budget candidates must fit "
                        "(default: autotuning.hbm_budget_mb, then "
                        "analysis.hbm_budget_mb)")
    p.add_argument("--top-k", type=int, default=None,
                   help="engine-ready configs to emit (default: "
                        "autotuning.top_k)")
    p.add_argument("--calibration", default=None,
                   help="hw-calibration file from the `calibrate` "
                        "subcommand (default: "
                        "autotuning.calibration_file)")
    p.add_argument("--out", default="autotune_out",
                   help="output directory for the top-K configs + "
                        "autotune_results.json (default: autotune_out)")
    _add_model_args(p)
    p.add_argument("--json", action="store_true",
                   help="emit the full autotune_results payload as JSON "
                        "on stdout")
    p.add_argument("--update-golden", action="store_true",
                   help="regenerate the checked-in golden leaderboard "
                        "for this example search config")
    return p


def build_calibrate_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.analysis calibrate",
        description="Fit the canonical hardware constants "
                    "hw_{peak_tflops,hbm_gbps,ici_gbps} from measured-vs-"
                    "predicted reconciliation windows (monitor JSONL "
                    "records, a JSON row that embeds a reconciliation, "
                    "or a bare window "
                    "list), writing the calibration file the next "
                    "`tune --calibration` loads.")
    p.add_argument("--records", required=True,
                   help="monitor JSONL stream / row JSON / window "
                        "list to fit from")
    p.add_argument("--base-config", default=None,
                   help="config whose analysis block supplies the base "
                        "hw constants (default: the v5e defaults)")
    p.add_argument("--out", default="hw_calibration.json",
                   help="calibration file to write (default: "
                        "hw_calibration.json)")
    p.add_argument("--json", action="store_true",
                   help="emit the calibration payload as JSON on stdout")
    return p


def tune_main(argv) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args = build_tune_parser().parse_args(argv)
    with open(args.config) as f:
        raw = json.load(f)

    from deepspeed_tpu import constants as C
    from deepspeed_tpu.config import (AutotuningConfig,
                                      DeepSpeedConfigError)
    try:
        tune_cfg = AutotuningConfig.from_dict(raw.get(C.AUTOTUNING))
    except DeepSpeedConfigError as e:
        print(f"autotune: invalid autotuning block: {e}",
              file=sys.stderr)
        return 2
    chips = args.chips if args.chips is not None else tune_cfg.chips
    if chips is None:
        print("autotune: the chip count is required — set "
              "autotuning.chips in the config or pass --chips",
              file=sys.stderr)
        return 2
    if chips > 1:
        # must land before jax initializes a backend (same contract as
        # the lint's --devices; run_search fails fast on a mismatch)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{chips}").strip()

    from .autotuner import (AutotuneEmptySearch, AutotuneError,
                            RESULTS_FILENAME, emit_results, run_search)
    model_kw = {"hidden": args.hidden, "layers": args.layers,
                "heads": args.heads, "seq": args.seq,
                "vocab": args.vocab}
    top_k = args.top_k if args.top_k is not None else tune_cfg.top_k
    if args.json:
        # --json promises machine-readable stdout, but the repo logger's
        # engine-build INFO lines (one per traced candidate) stream to
        # stdout — quiet them to warnings for the search
        import logging as _logging
        from deepspeed_tpu.utils.logging import logger as _ds_logger
        prev_level = _ds_logger.level
        _ds_logger.setLevel(_logging.WARNING)
    try:
        outcome = run_search(
            raw, tune_cfg, chips=chips, global_batch=args.global_batch,
            hbm_budget_mb=args.hbm_budget_mb, model_kw=model_kw,
            calibration=args.calibration,
            base_config_path=args.config)
        payload = emit_results(outcome, args.out, top_k)
    except AutotuneEmptySearch as e:
        print(f"autotune: EMPTY SEARCH — {e}", file=sys.stderr)
        return 1
    except AutotuneError as e:
        print(f"autotune: {e}", file=sys.stderr)
        return 2
    finally:
        if args.json:
            _ds_logger.setLevel(prev_level)

    if args.update_golden:
        golden_name = TUNE_GOLDEN_MAP.get(os.path.basename(args.config))
        if golden_name is None:
            print("--update-golden: no golden leaderboard is mapped to "
                  f"{os.path.basename(args.config)!r} (known: "
                  f"{sorted(TUNE_GOLDEN_MAP)})", file=sys.stderr)
            return 2
        path = golden_dir() / golden_name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(
            _tune_golden_payload(outcome, payload), indent=2) + "\n")
        print(f"golden updated: {path}", file=sys.stderr)

    if args.json:
        print(json.dumps(payload, indent=2))
        return 0
    space = outcome.space
    hw = payload["hw"]
    print(f"autotune: {space.n_enumerated} enumerated, "
          f"{len(space.candidates)} candidate(s), "
          f"{len(space.pruned)} pruned, {len(outcome.ranked)} "
          f"survivor(s); hw: "
          + ", ".join(f"{k}={hw[k]:g}" for k in sorted(hw))
          + (f" (calibrated from {outcome.calibration_file})"
             if outcome.calibration_file else " (defaults)"))
    for p in space.pruned:
        print(f"  pruned[{p.stage}] {p.name}: {p.reason}")
    for entry in payload["leaderboard"]:
        lanes = entry["lanes"]
        lane_txt = " | ".join(
            f"{k} {lanes[k] * 1e6:.1f}us"
            for k in ("compute", "memory", "hidden_comm",
                      "exposed_comm", "swap"))
        print(f"  rank {entry['rank']}  {entry['name']}  "
              f"lb {entry['predicted_step_time_lb_s'] * 1e6:.1f}us  "
              f"bound={entry['bound']}  [{lane_txt}]  "
              f"wire {entry['wire_bytes_per_step']} B  "
              f"peak_hbm {entry['peak_hbm_bytes'] / 2 ** 20:.1f} MiB")
    print(f"wrote {os.path.join(args.out, RESULTS_FILENAME)} "
          f"(+{len(payload['leaderboard'])} engine-ready config(s))")
    return 0


def _tune_golden_payload(outcome, payload) -> dict:
    """The pinned form of a search: full ranked ORDERING (exact) plus
    per-candidate lower bounds (band-tolerant in the test)."""
    return {
        "_comment": _TUNE_GOLDEN_COMMENT,
        "chips": payload["chips"],
        "global_batch": payload["global_batch"],
        "n_enumerated": payload["n_enumerated"],
        "n_candidates": payload["n_candidates"],
        "n_survivors": payload["n_survivors"],
        "hw": payload["hw"],
        "ranking": [
            {"rank": i + 1, "name": rc.candidate.name,
             "predicted_step_time_lb_s": round(
                 rc.predicted_step_time_lb_s, 9),
             "bound": rc.report.step_time["bound"],
             "wire_bytes_per_step": int(rc.report.wire_bytes_per_step)}
            for i, rc in enumerate(outcome.ranked)],
    }


def calibrate_main(argv) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args = build_calibrate_parser().parse_args(argv)

    from deepspeed_tpu import constants as C
    from deepspeed_tpu.config import AnalysisConfig
    from .autotuner import extract_reconciliation_windows
    from .cost_model import hw_constants

    analysis_raw = {}
    if args.base_config:
        with open(args.base_config) as f:
            analysis_raw = dict(json.load(f).get(C.ANALYSIS) or {})
    analysis_raw[C.ANALYSIS_MODE] = "off"
    base_hw = hw_constants(AnalysisConfig.from_dict(analysis_raw))

    windows = extract_reconciliation_windows(args.records)
    if not windows:
        print(f"calibrate: no reconciliation windows found in "
              f"{args.records} — need records carrying "
              "measured_step_time_s + lanes (monitor JSONL `reconcile` "
              "records, or a JSON row's embedded `reconciliation`)",
              file=sys.stderr)
        return 1
    from .autotuner import fit_hw_calibration
    payload = fit_hw_calibration(windows, base_hw,
                                 source=os.path.abspath(args.records))
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    if args.json:
        print(json.dumps(payload, indent=2))
        return 0
    print(f"calibrate: {payload['windows_used']} window(s) used, "
          f"{payload['windows_skipped']} skipped")
    for key in C.ANALYSIS_HW_KEYS:
        tag = "fitted" if payload["fitted"][key] else "unfitted (kept)"
        print(f"  {key}: {payload['base_hw'][key]:g} -> "
              f"{payload['hw'][key]:g}  [{tag}]")
    print(f"wrote {args.out} — load it with `tune --calibration "
          f"{args.out}` (or autotuning.calibration_file)")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # subcommand dispatch (docs/autotuner.md); bare flags keep the
    # original lint contract
    if argv and argv[0] == "tune":
        return tune_main(argv[1:])
    if argv and argv[0] == "calibrate":
        return calibrate_main(argv[1:])
    if argv and argv[0] == "lint-source":
        # stdlib-only source lint (docs/source_lint.md) — imported
        # lazily so the jaxpr-lint path never pays for it and the
        # lint-source path never imports jax
        from .source_lint import lint_source_main
        return lint_source_main(argv[1:])
    # lint runs on CPU regardless of what accelerators are attached —
    # must be decided before jax initializes a backend
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args = build_parser().parse_args(argv)
    if args.devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{args.devices}").strip()

    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.config import AnalysisConfig
    from deepspeed_tpu import constants as C
    from .auditor import audit_engine

    if args.devices > 1 and jax.device_count() != args.devices:
        # The device count only takes effect if XLA_FLAGS landed before
        # jax initialized a backend.  Auditing a streamed config on the
        # wrong mesh silently traces a different program (a 1-device
        # run audits the non-streamed fallback with zero collectives)
        # and --update-golden would then write a wrong golden with exit
        # code 0 — fail fast instead.
        print(f"--devices {args.devices} had no effect: jax initialized "
              f"{jax.device_count()} device(s). XLA_FLAGS already pins "
              "xla_force_host_platform_device_count or jax was "
              "initialized before this process set it — unset the "
              "conflicting flag and rerun.", file=sys.stderr)
        return 2

    with open(args.config) as f:
        raw = json.load(f)
    analysis_raw = dict(raw.get(C.ANALYSIS) or {})
    if args.mode is not None:
        analysis_raw[C.ANALYSIS_MODE] = args.mode
    analysis_cfg = AnalysisConfig.from_dict(analysis_raw)

    # The engine is built with analysis off so a mode:"error" config
    # still produces a full printed report here (instead of the
    # constructor raising mid-build); the CLI then applies the mode.
    engine_raw = dict(raw)
    engine_raw[C.ANALYSIS] = dict(raw.get(C.ANALYSIS) or {},
                                  **{C.ANALYSIS_MODE: "off"})

    model, params = _build_trace_model(
        args, raw, bf16=bool(engine_raw.get("bf16", {}).get(
            "enabled", False)))
    engine, _, _, _ = ds.initialize(model=model, config=engine_raw,
                                    model_parameters=params)

    report = audit_engine(engine, cfg=analysis_cfg,
                          hlo=True if args.hlo_audit else None)
    # 1-bit wire tier (docs/onebit.md): the engine runs TWO step programs
    # over its lifetime — warmup (audited above) and the post-freeze
    # compressed program.  Both must pass the gate: a host sync or silent
    # reshard that only exists past freeze_step would otherwise ship.
    onebit_report = None
    if getattr(engine, "_onebit", None) is not None:
        onebit_report = audit_engine(
            engine, cfg=analysis_cfg,
            hlo=True if args.hlo_audit else None, phase="compressed")
    if getattr(engine, "_preemption", None) is not None:
        # the lint builds a REAL engine: a config with
        # resilience.preemption enabled installed signal handlers at
        # init — uninstall them so in-process gate runs (the tier-1
        # test loops this main over every example) stay side-effect-free
        engine._preemption.uninstall()

    if args.update_golden:
        golden_name = GOLDEN_MAP.get(os.path.basename(args.config))
        if golden_name is None:
            print("--update-golden: no golden file is mapped to "
                  f"{os.path.basename(args.config)!r} (known: "
                  f"{sorted(GOLDEN_MAP)})", file=sys.stderr)
            return 2
        if (os.path.basename(args.config) in HLO_GOLDEN_CONFIGS
                and not report.hlo):
            # the golden embeds HLO-cross-check fields: regenerating
            # without the audit on (config lost its analysis.hlo_audit,
            # no --hlo-audit) would silently write nulls
            print("--update-golden: this golden pins HLO cross-check "
                  "fields but the audit did not run — keep analysis."
                  "hlo_audit in the example config or pass --hlo-audit",
                  file=sys.stderr)
            return 2
        path = golden_dir() / golden_name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(_golden_payload(golden_name, report),
                                   indent=2) + "\n")
        print(f"golden updated: {path}")

    if args.json:
        if onebit_report is not None:
            print(json.dumps({
                "phase_warmup": json.loads(report.to_json()),
                "phase_compressed": json.loads(onebit_report.to_json()),
            }, indent=2))
        else:
            print(report.to_json())
    else:
        if onebit_report is not None:
            print("phase warmup: " + report.summary_line())
        else:
            print(report.summary_line())
        if report.hlo:
            print(f"hlo cross-check: "
                  f"{report.hlo_collective_count} collective(s), "
                  f"{report.hlo_wire_bytes_per_step} B/step compiled, "
                  f"divergence_ratio={report.hlo_divergence_ratio:.4f}, "
                  f"{report.hlo['n_silent_reshards']} silent reshard(s)")
        for finding in report.findings:
            print("  " + finding.format())
        if args.dump_sequence:
            for item in report.collective_sequence:
                print("  seq: " + item)
            for rec in report.overlap.get("records", []):
                cls = ("fused" if rec.get("fused") else
                       "carried" if rec["carried"] else
                       "serialized" if rec["serialized"] else
                       "overlapped")
                print(f"  overlap: {rec['prim']}@"
                      f"{rec['scope'] or '<top>'} x{rec['mult']} "
                      f"{rec['wire_bytes']} B -> {cls} "
                      f"(hidden {rec['hidden_fraction'] * 100:.0f}%)")
            for name, nbytes in report.peak_hbm_contributors:
                print(f"  live@peak: {name} = {nbytes} B")
        print(f"lockstep signature: {report.signature}")
        if onebit_report is not None:
            print("phase compressed: " + onebit_report.summary_line())
            if onebit_report.hlo:
                print(f"phase compressed hlo cross-check: "
                      f"{onebit_report.hlo_collective_count} "
                      f"collective(s), "
                      f"{onebit_report.hlo_wire_bytes_per_step} B/step "
                      f"compiled, divergence_ratio="
                      f"{onebit_report.hlo_divergence_ratio:.4f}, "
                      f"{onebit_report.hlo['n_silent_reshards']} silent "
                      f"reshard(s)")
            for finding in onebit_report.findings:
                print("  " + finding.format())
            print(f"phase compressed lockstep signature: "
                  f"{onebit_report.signature}")
    mode = analysis_cfg.mode
    failed = report.has_errors or (onebit_report is not None
                                   and onebit_report.has_errors)
    if mode == "error" and failed:
        print("program audit: FAILED (error-severity findings, "
              "analysis.mode=error)", file=sys.stderr)
        return 1
    return 0
