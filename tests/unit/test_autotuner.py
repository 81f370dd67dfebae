"""Config autotuner (deepspeed_tpu/analysis/autotuner.py,
docs/autotuner.md).

The fast-lane cells the ISSUE pins: a golden leaderboard regression
over the example search space (ordering exact, lower bounds
band-tolerant), monotonicity properties (qwZ never increases wire
bytes; shrinking the HBM budget never adds candidates), the
calibration round-trip (rigged reconciliation windows -> fitted
constants -> the re-ranked search flips the winner as designed), the
bounded smoke search (<= 12 candidates on the simulated 8-device mesh,
nonzero survivors, valid autotune_results.json schema), loud
empty-search failures naming the binding constraint, the NVMe swap
lane (a streamed config must NOT rank like a resident one), and the
row -> calibrate loop.

The module-scoped fixture runs the example search ONCE (ten traced
candidates, ~12 s); every cheap cell reads it instead of re-searching.
"""

import copy
import json
from pathlib import Path

import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu import constants as C
from deepspeed_tpu.analysis.autotuner import (
    AutotuneEmptySearch, AutotuneError, RESULTS_FILENAME,
    emit_results, extract_reconciliation_windows, fit_hw_calibration,
    load_calibration, run_search, static_hbm_floor_bytes,
    validate_results)
from deepspeed_tpu.analysis.cli import (calibrate_main, main as cli_main,
                                        tune_main)
from deepspeed_tpu.analysis.cost_model import (build_step_time_model,
                                               hw_constants, swap_lane)
from deepspeed_tpu.analysis.search_space import (batch_splits,
                                                 enumerate_candidates,
                                                 mesh_factorizations)
from deepspeed_tpu.config import (AnalysisConfig, AutotuningConfig,
                                  DeepSpeedConfigError, ZeroConfig,
                                  validate_hw_constants)

REPO = Path(__file__).resolve().parents[2]
EXAMPLE_TUNE_CFG = REPO / "docs" / "examples" / "gpt2_autotune.json"
GOLDEN_LEADERBOARD = (REPO / "tests" / "unit" / "golden" /
                      "gpt2_autotune_leaderboard.json")

BASE = {
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
    "zero_optimization": {"stage": 2},
    "steps_per_print": 10 ** 9,
}


def _search(axes, **kw):
    raw = copy.deepcopy(BASE)
    raw["autotuning"] = dict({"chips": 8, "global_batch": 16,
                              "max_candidates": 12}, **axes)
    ds.reset_mesh_context()
    try:
        return run_search(raw, chips=8, **kw)
    finally:
        ds.reset_mesh_context()


@pytest.fixture(scope="module")
def example_outcome():
    """The checked-in example search, run once per module (the same
    space the golden pins and the CLI example documents)."""
    raw = json.loads(EXAMPLE_TUNE_CFG.read_text())
    ds.reset_mesh_context()
    try:
        return run_search(raw, base_config_path=str(EXAMPLE_TUNE_CFG))
    finally:
        ds.reset_mesh_context()


@pytest.fixture(scope="module")
def emitted(example_outcome, tmp_path_factory):
    """Top-K emission of the example search (runs the emit gate)."""
    out_dir = tmp_path_factory.mktemp("autotune_out")
    payload = emit_results(example_outcome, str(out_dir), top_k=3)
    return out_dir, payload


# --------------------------------------------------------------------- #
# golden leaderboard regression
# --------------------------------------------------------------------- #
def test_golden_leaderboard_ordering_and_bounds(example_outcome):
    """Candidate ORDERING and names pinned exactly; the static lower
    bounds band-tolerant (25% — the model is deterministic but jaxpr
    byte/flop counts may drift slightly across jax versions).
    Regenerate with: python -m deepspeed_tpu.analysis tune --config
    docs/examples/gpt2_autotune.json --update-golden"""
    golden = json.loads(GOLDEN_LEADERBOARD.read_text())
    assert golden["chips"] == example_outcome.chips == 8
    assert golden["global_batch"] == example_outcome.global_batch == 16
    assert golden["n_candidates"] == len(
        example_outcome.space.candidates)
    assert golden["n_survivors"] == len(example_outcome.ranked)
    got = [(i + 1, rc.candidate.name)
           for i, rc in enumerate(example_outcome.ranked)]
    want = [(e["rank"], e["name"]) for e in golden["ranking"]]
    assert got == want, "ranking ORDER diverged from the golden"
    for entry, rc in zip(golden["ranking"], example_outcome.ranked):
        lb = rc.predicted_step_time_lb_s
        pinned = entry["predicted_step_time_lb_s"]
        assert lb == pytest.approx(pinned, rel=0.25), (
            f"{entry['name']}: lb {lb} left the golden band around "
            f"{pinned}")
        assert rc.report.step_time["bound"] == entry["bound"]
    # default (uncalibrated) search ranks with the canonical constants
    assert golden["hw"] == dict(C.ANALYSIS_HW_DEFAULTS)


def test_golden_search_space_is_bounded(example_outcome):
    """The CI smoke-search bound the ISSUE pins: <= 12 candidates on
    the simulated 8-device mesh, nonzero survivors."""
    assert jax.device_count() == 8
    assert 0 < len(example_outcome.space.candidates) <= 12
    assert len(example_outcome.ranked) > 0


# --------------------------------------------------------------------- #
# emission: schema + auditor-clean engine-ready configs
# --------------------------------------------------------------------- #
def test_emitted_results_schema_and_configs(emitted):
    out_dir, payload = emitted
    on_disk = json.loads((out_dir / RESULTS_FILENAME).read_text())
    validate_results(on_disk)  # the smoke-search schema assert
    assert on_disk["schema"] == C.AUTOTUNE_RESULTS_SCHEMA
    assert on_disk["n_survivors"] > 0
    assert len(on_disk["leaderboard"]) == 3
    for entry in on_disk["leaderboard"]:
        cfg = json.loads((out_dir / entry["config_file"]).read_text())
        # engine-ready: engine knobs only — the search block must not
        # ride along, the provenance block must
        assert C.AUTOTUNING not in cfg
        assert cfg["_autotune"]["name"] == entry["name"]
        assert cfg["_autotune"]["rank"] == entry["rank"]
        mesh = cfg[C.MESH]
        knobs = entry["knobs"]
        assert mesh[C.MESH_DATA_AXIS] == knobs["mesh"]["data"]
        # per-lane attribution present for every winner
        for lane in ("compute", "memory", "hidden_comm",
                     "exposed_comm", "swap"):
            assert lane in entry["lanes"]
    lbs = [e["predicted_step_time_lb_s"] for e in on_disk["leaderboard"]]
    assert lbs == sorted(lbs)


def test_emitted_configs_pass_error_mode_gate(emitted, capsys):
    """Never emit a config the auditor rejects: every written config
    must itself pass the literal CI lint (cli.main --mode error) — the
    emit gate ran in emit_results; re-run it here independently."""
    out_dir, payload = emitted
    entry = payload["leaderboard"][0]
    ds.reset_mesh_context()
    rc = cli_main(["--config", str(out_dir / entry["config_file"]),
                   "--mode", "error"])
    capsys.readouterr()
    ds.reset_mesh_context()
    assert rc == 0


def test_validate_results_rejects_malformed(emitted):
    _, payload = emitted
    bad = copy.deepcopy(payload)
    bad["schema"] = "nope"
    with pytest.raises(AutotuneError, match="schema tag"):
        validate_results(bad)
    bad = copy.deepcopy(payload)
    bad["leaderboard"][0]["rank"] = 7
    with pytest.raises(AutotuneError, match="consecutive"):
        validate_results(bad)
    bad = copy.deepcopy(payload)
    del bad["leaderboard"][0]["lanes"]["swap"]
    with pytest.raises(AutotuneError, match="lanes missing"):
        validate_results(bad)
    bad = copy.deepcopy(payload)
    bad["leaderboard"] = list(reversed(bad["leaderboard"]))
    with pytest.raises(AutotuneError):
        validate_results(bad)


# --------------------------------------------------------------------- #
# monotonicity cells
# --------------------------------------------------------------------- #
def test_qwz_never_increases_wire_bytes(example_outcome):
    """Turning qwZ on (int8 weight gathers) must never INCREASE the
    predicted wire bytes of the otherwise-identical candidate."""
    by_name = {rc.candidate.name: rc for rc in example_outcome.ranked}
    pairs = 0
    for name, rc in by_name.items():
        if "-qwz8" not in name:
            continue
        twin = by_name.get(name.replace("-qwz8", ""))
        assert twin is not None, f"no qwz-off twin for {name}"
        assert (rc.report.wire_bytes_per_step
                <= twin.report.wire_bytes_per_step), (
            f"{name} moved MORE wire than its dense twin")
        pairs += 1
    assert pairs >= 4  # the example space carries 4 qwz pairs


def test_fcm_never_increases_wire_bytes():
    """ISSUE 13 satellite: enabling fused_collective_matmul must never
    INCREASE the predicted wire bytes of the otherwise-identical
    candidate — the per-tile ring moves (W-1)/W of the monolithic
    gather payload (and the fused hops ARE accounted: step_wire_bytes
    counts FCM-scoped ppermutes), while the fused classification moves
    the bytes to the hidden-comm lane."""
    outcome = _search({
        "zero_stages": [3], "stage3_variants": ["streamed"],
        "micro_batches": [2],
        "qwz_bits": [8], "qgz_bits": [8],
        "fused_collective_matmul": [False, True], "top_k": 2})
    by_name = {rc.candidate.name: rc for rc in outcome.ranked}
    pairs = 0
    for name, rc in by_name.items():
        if "-fcm" not in name:
            continue
        twin = by_name.get(name.replace("-fcm", ""))
        assert twin is not None, f"no fcm-off twin for {name}"
        assert rc.candidate.knobs["fused_collective_matmul"] is True
        assert twin.candidate.knobs["fused_collective_matmul"] is False
        assert (rc.report.wire_bytes_per_step
                <= twin.report.wire_bytes_per_step), (
            f"{name} moved MORE wire than its modular twin")
        # the fused candidate's hot wire prices hidden: its exposed-comm
        # lane must not exceed the modular twin's
        assert (rc.report.step_time["t_comm_exposed_s"]
                <= twin.report.step_time["t_comm_exposed_s"] + 1e-12)
        # (the modular twin's skinny leaves, dense under qgZ, leave as
        # the same permutes: a sliver of the fused candidate's wire)
        assert rc.report.step_time["wire_bytes_fused"] > 0
        assert (twin.report.step_time["wire_bytes_fused"]
                < rc.report.step_time["wire_bytes_fused"] / 10)
        pairs += 1
    assert pairs >= 1


def test_onebit_never_increases_wire_bytes():
    """ISSUE 16 satellite: the autotuning.onebit axis swaps the base
    optimizer for its OneBit counterpart and prices the candidate on
    its STEADY-STATE (compressed-phase) program.  The dense twin's grad
    allreduce is GSPMD-inserted (jaxpr-invisible), so monotonicity is
    asserted on the compiled-HLO wire — which the 1-bit candidate's
    explicit packed sync must undercut, never exceed."""
    raw = copy.deepcopy(BASE)
    raw["analysis"] = {"hlo_audit": True}
    raw["autotuning"] = {"chips": 8, "global_batch": 16,
                         "max_candidates": 12, "zero_stages": [2],
                         "micro_batches": [2], "onebit": [False, True]}
    ds.reset_mesh_context()
    try:
        outcome = run_search(raw, chips=8)
    finally:
        ds.reset_mesh_context()
    by_name = {rc.candidate.name: rc for rc in outcome.ranked}
    pairs = 0
    for name, rc in by_name.items():
        if "-1bit" not in name:
            continue
        twin = by_name.get(name.replace("-1bit", ""))
        assert twin is not None, f"no onebit-off twin for {name}"
        assert rc.candidate.knobs["onebit"] is True
        assert twin.candidate.knobs["onebit"] is False
        # the compressed program's wire is explicit -> jaxpr-counted
        assert rc.report.wire_bytes_per_step > 0
        assert (rc.report.hlo["hlo_wire_bytes_per_step"]
                <= twin.report.hlo["hlo_wire_bytes_per_step"]), (
            f"{name} moved MORE compiled wire than its dense twin")
        pairs += 1
    assert pairs >= 1
    # the 1-bit candidate rode in on a OneBit optimizer swap
    onebit_rc = next(rc for rc in outcome.ranked
                     if rc.candidate.knobs["onebit"])
    opt = onebit_rc.candidate.config[C.OPTIMIZER]["type"].lower()
    assert opt.startswith("onebit"), opt


def test_shrinking_hbm_budget_never_adds_candidates(example_outcome):
    """Budget monotonicity, both pruning layers.  Traced layer: a full
    search under a mid budget must survive a strict SUBSET of the
    unrestricted search, with the over-budget candidates pruned by the
    auditor's hbm_budget rule.  Static layer: the pre-trace floor prune
    is monotone in the budget by construction."""
    unrestricted = {rc.candidate.name for rc in example_outcome.ranked}
    peaks = {rc.candidate.name: int(rc.report.peak_hbm_bytes)
             for rc in example_outcome.ranked}
    # halfway between the smallest and largest traced peak: at least
    # one candidate survives, at least one is pruned
    mid = (min(peaks.values()) + max(peaks.values())) / 2 / 2 ** 20
    restricted = _search(
        {"zero_stages": [2, 3], "stage3_variants": ["streamed"],
         "stage3_bucket_sizes": [200000, 0], "micro_batches": [1, 2],
         "qwz_bits": [0, 8], "top_k": 3},
        hbm_budget_mb=mid)
    survivors = {rc.candidate.name for rc in restricted.ranked}
    assert survivors < unrestricted  # strict subset: some were pruned
    assert survivors == {n for n, p in peaks.items()
                         if p <= mid * 2 ** 20}
    for p in restricted.space.pruned:
        assert p.stage in ("auditor", "hbm_floor")
        assert "hbm" in p.reason.lower() or "hbm_budget" in p.reason

    # static floor layer: pure-math monotonicity over the same knobs
    for cand in example_outcome.space.candidates:
        mesh = cand.knobs["mesh"]
        dp = mesh["data"] * mesh["expert"]
        floor = static_hbm_floor_bytes(cand.knobs, 2 ** 21, 2 ** 22, dp)
        assert floor >= 0
        # a bigger budget admits a superset by definition of a single
        # threshold — assert the floor itself is stage-monotone: zero-3
        # sharding can only shrink the resident floor
        if cand.knobs["zero_stage"] == 3:
            z1 = dict(cand.knobs, zero_stage=1)
            assert floor <= static_hbm_floor_bytes(z1, 2 ** 21, 2 ** 22,
                                                   dp)


# --------------------------------------------------------------------- #
# calibration: fit + round-trip through the search
# --------------------------------------------------------------------- #
def _rigged_windows():
    """Two windows designed to fit hbm_gbps and ici_gbps 10x FASTER
    than the v5e defaults: a memory-bound window measured at a tenth of
    its predicted binding lane, and a comm-exposed window whose exposed
    term absorbs a tenth of its predicted time."""
    return [
        {"measured_step_time_s": 0.1,
         "lanes": {"compute": 0.01, "memory": 1.0, "hidden_comm": 0.0,
                   "exposed_comm": 0.0}},
        {"measured_step_time_s": 0.2,
         "lanes": {"compute": 0.1, "memory": 0.05, "hidden_comm": 0.0,
                   "exposed_comm": 1.0}},
    ]


def test_fit_hw_calibration_skips_swap_windows():
    """An NVMe window's disk seconds sit in the measured step but in no
    roofline lane — fitting from it would read 'compute is 6x slower'.
    Swap-tier windows must be skipped, not attributed."""
    base = dict(C.ANALYSIS_HW_DEFAULTS)
    swap_window = {"measured_step_time_s": 6.0,
                   "lanes": {"compute": 1.0, "memory": 0.1,
                             "exposed_comm": 0.0, "swap": 5.0}}
    payload = fit_hw_calibration([swap_window], base)
    assert payload["windows_used"] == 0
    assert payload["windows_skipped"] == 1
    assert payload["hw"] == base  # nothing fitted, nothing corrupted
    mixed = fit_hw_calibration([swap_window] + _rigged_windows(), base)
    assert mixed["windows_used"] == 2 and mixed["windows_skipped"] == 1
    assert mixed["fitted"][C.ANALYSIS_HW_PEAK_TFLOPS] is False


def test_fit_hw_calibration_scales_constants():
    base = dict(C.ANALYSIS_HW_DEFAULTS)
    payload = fit_hw_calibration(_rigged_windows(), base, source="rig")
    assert payload["schema"] == C.HW_CALIBRATION_SCHEMA
    assert payload["windows_used"] == 2
    assert payload["fitted"][C.ANALYSIS_HW_HBM_GBPS] is True
    assert payload["fitted"][C.ANALYSIS_HW_ICI_GBPS] is True
    assert payload["fitted"][C.ANALYSIS_HW_PEAK_TFLOPS] is False
    hw = payload["hw"]
    assert hw[C.ANALYSIS_HW_HBM_GBPS] == pytest.approx(
        base[C.ANALYSIS_HW_HBM_GBPS] * 10, rel=1e-6)
    assert hw[C.ANALYSIS_HW_ICI_GBPS] == pytest.approx(
        base[C.ANALYSIS_HW_ICI_GBPS] * 10, rel=1e-6)
    assert hw[C.ANALYSIS_HW_PEAK_TFLOPS] == base[
        C.ANALYSIS_HW_PEAK_TFLOPS]


def test_calibration_roundtrip_flips_winner(tmp_path, capsys):
    """The designed flip: under the v5e defaults the z2 candidate wins
    (memory-bound roofline); under a calibration fitted from windows
    showing this host's HBM and ICI 10x faster, the wire/io terms
    deflate and the streamed-qwZ candidate overtakes it.  The fit runs
    through the REAL calibrate CLI over monitor-style JSONL records,
    and the re-ranked search loads the written file."""
    records = tmp_path / "monitor.jsonl"
    with records.open("w") as f:
        for w in _rigged_windows():
            f.write(json.dumps(dict(w, kind="reconcile")) + "\n")
    cal_file = tmp_path / "hw_calibration.json"
    rc = calibrate_main(["--records", str(records),
                         "--out", str(cal_file)])
    out = capsys.readouterr()
    assert rc == 0, out.err
    assert "fitted" in out.out
    hw = load_calibration(str(cal_file))
    assert hw[C.ANALYSIS_HW_HBM_GBPS] == pytest.approx(
        C.ANALYSIS_HW_HBM_GBPS_DEFAULT * 10, rel=1e-6)

    axes = {"zero_stages": [2, 3], "stage3_variants": ["streamed"],
            "stage3_bucket_sizes": [0], "micro_batches": [2],
            "qwz_bits": [8]}
    default = _search(axes)
    calibrated = _search(axes, calibration=str(cal_file))
    assert default.ranked[0].candidate.name.startswith("z2")
    assert calibrated.ranked[0].candidate.name.startswith("z3s")
    assert (calibrated.ranked[0].candidate.name
            != default.ranked[0].candidate.name)
    # the calibrated constants ride the outcome's analysis config (and
    # thus the results payload's hw block) under the canonical names
    assert hw_constants(calibrated.analysis_cfg) == hw
    assert calibrated.calibration_file == str(cal_file)


def test_load_calibration_rejects_non_calibration_files(tmp_path):
    p = tmp_path / "x.json"
    p.write_text(json.dumps({"schema": "other"}))
    with pytest.raises(AutotuneError, match="not a calibration file"):
        load_calibration(str(p))
    p.write_text(json.dumps({"schema": C.HW_CALIBRATION_SCHEMA,
                             "hw": {C.ANALYSIS_HW_HBM_GBPS: 100.0}}))
    with pytest.raises(AutotuneError, match="missing"):
        load_calibration(str(p))
    p.write_text(json.dumps({
        "schema": C.HW_CALIBRATION_SCHEMA,
        "hw": {k: -1.0 for k in C.ANALYSIS_HW_KEYS}}))
    with pytest.raises(DeepSpeedConfigError, match="must be > 0"):
        load_calibration(str(p))


def test_calibrate_cli_no_windows_exits_nonzero(tmp_path, capsys):
    records = tmp_path / "empty.jsonl"
    records.write_text(json.dumps({"kind": "step", "loss": 1.0}) + "\n")
    rc = calibrate_main(["--records", str(records),
                         "--out", str(tmp_path / "cal.json")])
    err = capsys.readouterr().err
    assert rc == 1
    assert "no reconciliation windows" in err


def test_bench_row_reconciliation_feeds_calibrate(tmp_path):
    """A JSON row's embedded reconciliation is a calibration source,
    whatever else the row carries."""
    row = {"metric": "x", "value": 1.0, "stale": True,
           "reconciliation": {"measured_step_time_s": 0.5,
                              "lanes": {"compute": 0.2, "memory": 0.1,
                                        "exposed_comm": 0.0}}}
    p = tmp_path / "row.json"
    p.write_text(json.dumps(row))
    windows = extract_reconciliation_windows(str(p))
    assert len(windows) == 1
    assert windows[0]["measured_step_time_s"] == 0.5


# --------------------------------------------------------------------- #
# swap lane: streamed != resident
# --------------------------------------------------------------------- #
def test_swap_lane_prices_nvme_traffic():
    zero = ZeroConfig.from_dict({
        "stage": 3,
        "offload_param": {"device": "nvme", "prefetch_depth": 2},
        "offload_optimizer": {"device": "nvme", "pipeline_depth": 2}})
    swap = swap_lane(zero, None, param_bytes=10 ** 9,
                     opt_state_bytes=2 * 10 ** 9)
    assert swap is not None
    # double-buffered tiers hide under compute like hidden comm
    assert swap["t_hidden_s"] > 0 and swap["t_exposed_s"] == 0
    assert swap["read_bytes"] == 2 * 10 ** 9 + 2 * 10 ** 9
    assert swap["write_bytes"] == 10 ** 9 + 2 * 10 ** 9

    serialized = ZeroConfig.from_dict({
        "stage": 3,
        "offload_param": {"device": "nvme", "prefetch_depth": 1}})
    sswap = swap_lane(serialized, None, param_bytes=10 ** 9,
                      opt_state_bytes=0)
    assert sswap["t_exposed_s"] > 0 and sswap["t_hidden_s"] == 0

    resident = ZeroConfig.from_dict({"stage": 3})
    assert swap_lane(resident, None, param_bytes=10 ** 9,
                     opt_state_bytes=10 ** 9) is None
    cpu = ZeroConfig.from_dict({
        "stage": 2, "offload_optimizer": {"device": "cpu"}})
    assert swap_lane(cpu, None, param_bytes=10 ** 9,
                     opt_state_bytes=10 ** 9) is None


def test_swap_lane_changes_step_time_bound():
    """The satellite regression: with the swap lane folded in, a
    streamed (NVMe) config must rank strictly slower than the identical
    resident one — before this PR they ranked identically."""
    cfg = AnalysisConfig.from_dict({"mode": "off"})
    flops, io = 10 ** 12, 10 ** 9
    without = build_step_time_model(flops, io, [], cfg)
    hidden = {"t_hidden_s": 10.0, "t_exposed_s": 0.0, "read_bytes": 1,
              "write_bytes": 1, "read_gbps": 1.0, "write_gbps": 1.0,
              "source": "test"}
    with_hidden = build_step_time_model(flops, io, [], cfg, swap=hidden)
    assert with_hidden["predicted_step_time_lb_s"] > \
        without["predicted_step_time_lb_s"]
    assert with_hidden["bound"] == "swap"
    assert with_hidden["t_swap_s"] == 10.0
    exposed = dict(hidden, t_hidden_s=0.0, t_exposed_s=3.0)
    with_exposed = build_step_time_model(flops, io, [], cfg,
                                         swap=exposed)
    assert with_exposed["predicted_step_time_lb_s"] == pytest.approx(
        without["predicted_step_time_lb_s"] + 3.0)


def test_nvme_candidate_ranks_slower_than_resident():
    """End-to-end through the search: the NVMe candidate audits its
    resident twin but pays the disk trips via the swap lane."""
    nvme = _search({"zero_stages": [3], "stage3_variants": ["streamed"],
                    "micro_batches": [2],
                    "offload": ["nvme"]})
    resident = _search({"zero_stages": [3],
                        "stage3_variants": ["streamed"],
                        "micro_batches": [2], "offload": ["none"]})
    n, r = nvme.ranked[0], resident.ranked[0]
    assert "off-nvme" in n.candidate.name
    assert n.report.step_time["t_swap_s"] > 0
    assert n.report.step_time["swap"]["source"] in (
        "fallback_default",) or n.report.step_time["swap"][
        "source"].startswith("sweep_ceiling:")
    assert n.predicted_step_time_lb_s > r.predicted_step_time_lb_s


# --------------------------------------------------------------------- #
# loud empty searches
# --------------------------------------------------------------------- #
def test_empty_search_batch_infeasible_names_nearest_worlds():
    with pytest.raises(AutotuneEmptySearch) as ei:
        _search({"zero_stages": [2]}, global_batch=7)
    msg = str(ei.value)
    assert "batch-triple infeasibility" in msg
    assert "Nearest chip counts" in msg
    assert "[7, 1]" in msg


def test_empty_search_hbm_binding_names_budget():
    with pytest.raises(AutotuneEmptySearch) as ei:
        _search({"zero_stages": [2, 3],
                 "stage3_variants": ["streamed"],
                 "micro_batches": [2]}, hbm_budget_mb=0.001)
    msg = str(ei.value)
    assert "HBM budget is the binding constraint" in msg
    assert "smallest feasible estimate" in msg


def test_empty_search_message_not_misattributed_to_hbm():
    """A search where auditor prunes were NOT hbm_budget findings must
    not tell the operator to raise the HBM budget — raising it would
    change nothing."""
    from deepspeed_tpu.analysis.autotuner import (SearchOutcome,
                                                  _empty_search_message)
    from deepspeed_tpu.analysis.search_space import Pruned, SearchSpace
    space = SearchSpace(n_enumerated=2)
    space.pruned = [
        Pruned(name="a", stage="hbm_floor", reason="floor over budget"),
        Pruned(name="b", stage="auditor",
               reason="[overlap] serialized hot-loop gather"),
    ]
    outcome = SearchOutcome(
        space=space, ranked=[], analysis_cfg=None, chips=8,
        global_batch=16, hbm_budget_mb=1.0, model_kw={},
        floor_prunes=[("a", 123)])
    msg = _empty_search_message(outcome)
    assert "HBM budget is the binding constraint" not in msg
    assert "overlap" in msg  # falls through to the per-prune listing


def test_hbm_floor_optimizer_state_is_sound():
    """The floor only assumes state the configured optimizer must
    carry: a hardcoded Adam 2x would over-prune plain-SGD searches."""
    from deepspeed_tpu.analysis.autotuner import _optimizer_moments
    assert _optimizer_moments("AdamW") == 2
    assert _optimizer_moments("adam") == 2
    assert _optimizer_moments("SGDMomentum") == 1
    assert _optimizer_moments("sgd") == 0
    assert _optimizer_moments(None) == 0


def test_tune_cli_empty_search_exits_nonzero(tmp_path, capsys):
    raw = dict(BASE)
    raw["autotuning"] = {"chips": 8, "global_batch": 7,
                         "zero_stages": [2], "max_candidates": 12}
    cfg = tmp_path / "t.json"
    cfg.write_text(json.dumps(raw))
    ds.reset_mesh_context()
    rc = tune_main(["--config", str(cfg), "--out",
                    str(tmp_path / "out")])
    ds.reset_mesh_context()
    err = capsys.readouterr().err
    assert rc == 1
    assert "EMPTY SEARCH" in err
    assert "Nearest chip counts" in err
    assert not (tmp_path / "out" / RESULTS_FILENAME).exists()


def test_tune_cli_requires_chips(tmp_path, capsys):
    cfg = tmp_path / "t.json"
    cfg.write_text(json.dumps(BASE))
    rc = tune_main(["--config", str(cfg)])
    assert rc == 2
    assert "--chips" in capsys.readouterr().err


def test_oversized_space_refuses_silent_truncation():
    with pytest.raises(AutotuneError, match="never truncates silently"):
        _search({"zero_stages": [2, 3], "micro_batches": [1, 2],
                 "qwz_bits": [0, 4, 8], "qgz_bits": [0, 4, 8],
                 "max_candidates": 4})


# --------------------------------------------------------------------- #
# search-space + config validation
# --------------------------------------------------------------------- #
def test_mesh_factorizations_and_batch_splits():
    assert mesh_factorizations(8, (1, 2), (1,)) == [(8, 1, 1), (4, 2, 1)]
    assert mesh_factorizations(8, (3,), (1,)) == []
    assert batch_splits(16, 8) == [(1, 2), (2, 1)]
    assert batch_splits(16, 8, micro_filter=(2,)) == [(2, 1)]
    assert batch_splits(7, 8) == []


def test_autotuning_config_validation():
    with pytest.raises(DeepSpeedConfigError, match="top_k"):
        AutotuningConfig.from_dict({"top_k": 0})
    with pytest.raises(DeepSpeedConfigError, match="zero_stages"):
        AutotuningConfig.from_dict({"zero_stages": [4]})
    with pytest.raises(DeepSpeedConfigError, match="offload"):
        AutotuningConfig.from_dict({"offload": ["gpu"]})
    with pytest.raises(DeepSpeedConfigError, match="hbm_budget_mb"):
        AutotuningConfig.from_dict({"hbm_budget_mb": -1})
    with pytest.raises(DeepSpeedConfigError, match="fixed"):
        AutotuningConfig.from_dict({"fixed": ["not-a-dict"]})
    with pytest.raises(DeepSpeedConfigError, match="stage3_bucket_sizes"):
        AutotuningConfig.from_dict({"stage3_bucket_sizes": [-1]})
    cfg = AutotuningConfig.from_dict({"chips": 8, "qwz_bits": [0, 8]})
    assert cfg.chips == 8 and cfg.qwz_bits == (0, 8)


def test_hw_constants_single_sourced():
    """The canonical names: config block, cost-model payload, and
    calibration override all speak C.ANALYSIS_HW_KEYS."""
    cfg = AnalysisConfig.from_dict({"mode": "off"})
    assert hw_constants(cfg) == dict(C.ANALYSIS_HW_DEFAULTS)
    with pytest.raises(DeepSpeedConfigError, match="must be > 0"):
        validate_hw_constants({C.ANALYSIS_HW_HBM_GBPS: 0.0})
    with pytest.raises(DeepSpeedConfigError, match="must be > 0"):
        AnalysisConfig.from_dict({"mode": "off", "hw_ici_gbps": -5})
    over = cfg.hw_overridden({C.ANALYSIS_HW_ICI_GBPS: 42.0})
    assert over.hw_ici_gbps == 42.0
    assert over.hw_peak_tflops == cfg.hw_peak_tflops


def test_enumeration_is_gated():
    """Stage-1/2 candidates collapse the streamed-only knobs; NVMe
    requires the streamed stage-3 shape; hpZ must divide the dp world."""
    tune = AutotuningConfig.from_dict({
        "chips": 8, "global_batch": 16, "zero_stages": [1, 3],
        "stage3_variants": ["streamed"],
        "micro_batches": [2], "qwz_bits": [0, 8],
        "offload": ["none", "nvme"], "hpz_group_sizes": [0, 3],
        "max_candidates": 64})
    space = enumerate_candidates(dict(BASE), tune, 8, 16)
    names = [c.name for c in space.candidates]
    assert all("qwz" not in n for n in names if n.startswith("z1"))
    assert all("nvme" not in n for n in names if n.startswith("z1"))
    assert not any("hpz3" in n for n in names)  # 3 does not divide 8
    hpz_prunes = [p for p in space.pruned
                  if p.reason.startswith("hpz_group_size 3")]
    # one record per genuinely distinct rejection (per mesh), not one
    # per unrelated knob combination
    assert len(hpz_prunes) == 1
    # NVMe names carry their prefetch depth; cpu-tier names must not
    # grow a bogus 'None' depth suffix
    assert any(n.endswith("off-nvme2") for n in names)
    cpu_space = enumerate_candidates(
        dict(BASE), AutotuningConfig.from_dict({
            "chips": 8, "global_batch": 16, "zero_stages": [2],
            "micro_batches": [2], "offload": ["cpu"],
            "max_candidates": 12}), 8, 16)
    cpu_names = [c.name for c in cpu_space.candidates]
    assert cpu_names and all(n.endswith("off-cpu") for n in cpu_names)


def test_a_bucket_of_zero_is_the_candidate_without_prefetch():
    """The search has no prefetch axis of its own: an entry 0 of
    stage3_bucket_sizes is the streamed candidate that gathers at use,
    and the engine config it emits says so by the bucket alone."""
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.zero.stage3_streaming import (
        plan_layer_streaming)
    tune = AutotuningConfig.from_dict({
        "chips": 8, "global_batch": 16, "zero_stages": [3],
        "stage3_variants": ["streamed"], "micro_batches": [2],
        "stage3_bucket_sizes": [200000, 0]})
    space = enumerate_candidates(dict(BASE), tune, 8, 16)
    by_bucket = {c.knobs["stage3_bucket"]: c for c in space.candidates}
    assert sorted(by_bucket) == [0, 200000]
    assert "-g0-" in by_bucket[0].name and "-g200000-" in by_bucket[200000].name
    assert all("prefetch_mode" not in c.knobs for c in space.candidates)
    for bucket, cand in by_bucket.items():
        zero = DeepSpeedConfig(cand.config, world_size=8).zero_config
        assert zero.prefetch_bucket_size == bucket
        plan = plan_layer_streaming(4, 50_000, zero.max_live_parameters,
                                    zero.prefetch_bucket_size)
        assert plan.prefetch == bool(bucket) and plan.forfeited is None
