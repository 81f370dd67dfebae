"""BENCHMARK.json against the files it names, and the proof that a later
PR adds a configuration, a cell and a per-layer metric as new files
alone."""

import json
import re
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
PERF = REPO / "perf"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _load_reader(name):
    from perf import run
    return run.load_module(str(REPO), "layer_metrics", name)


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["perf", "tests/perf"]
    assert bench["command"][1] == "perf/run.py"
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells still fits the driver's 43,200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lengths(bench):
    entries = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
               + bench["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_entries_have_exactly_the_contract_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_configuration_has_its_file_and_the_reverse(bench):
    named = {c["name"]: c for c in bench["configs"]}
    on_disk = {p.stem for p in (PERF / "configs").glob("*.json")}
    assert set(named) == on_disk
    for name, c in named.items():
        assert c["file"] == f"perf/configs/{name}.json"
        body = json.loads((REPO / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert (PERF / "families" / (body["family"] + ".py")).is_file()
        assert (PERF / "families" / (body["family"] + "_reference.py")
                ).is_file()
    assert {w["config"] for w in bench["workloads"]} == set(named)


def test_every_cell_has_its_file_and_the_reverse(bench):
    from perf import run
    named = {w["name"]: w for w in bench["workloads"]}
    on_disk = {p.stem for p in (PERF / "workloads").glob("*.json")}
    assert set(named) == on_disk
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for name, w in named.items():
        body = json.loads((PERF / "workloads" / f"{name}.json").read_text())
        for key in ("config", "traffic", "chips", "why"):
            assert body[key] == w[key], (name, key)
        traffic = json.loads(
            (PERF / "traffic" / f"{w['traffic']}.json").read_text())
        assert (PERF / "traffic" / (traffic["generator"] + ".py")).is_file()
        check = body["loss_check"]
        assert set(check) == {"steps", "rise"}
        first, last = check["steps"]
        assert run.WARMUP_STEPS < first and first + 4 <= last
        assert 0 < check["rise"]
    used = {w["traffic"] for w in bench["workloads"]}
    assert used == {p.stem for p in (PERF / "traffic").glob("*.json")}


def test_at_most_one_cell_asks_for_four_chips(bench):
    chips = [w["chips"] for w in bench["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 4)


def test_every_per_layer_metric_has_its_reader_and_the_reverse(bench):
    named = {m["name"]: m for m in bench["per_layer"]}
    on_disk = {p.stem for p in (PERF / "layer_metrics").glob("*.py")}
    assert set(named) == on_disk
    for name, m in named.items():
        reader = _load_reader(name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), name
        assert callable(reader.reduce)
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_cells_report_what_the_manifest_says(bench):
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for w in bench["workloads"]:
        body = json.loads(
            (PERF / "workloads" / f"{w['name']}.json").read_text())
        reported = set(body["per_layer"])
        assert reported, w["name"]
        want = {n for n, m in per_layer.items()
                if w["name"] in m.get("workloads", [w["name"]])}
        assert reported == want, w["name"]
    cells = {w["name"] for w in bench["workloads"]}
    for m in per_layer.values():
        # moves names an end-to-end metric, which every cell reports
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", [])) <= cells


@pytest.fixture
def restore_compile_cache():
    """run_cell points JAX's persistent cache at its checkout; the tests
    that share this process get their settings back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_new_cell_config_and_metric_are_new_files_only(
        tmp_path, restore_compile_cache):
    """A throw-away configuration, traffic mix, cell and per-layer metric
    are ADDED to a copy of perf/ (no file of it edited) and run through
    the harness's entry on the CPU, traced."""
    root = tmp_path / "checkout"
    shutil.copytree(PERF, root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "perf").rglob("*")
              if p.is_file()}
    (root / "perf/configs/toy.json").write_text(json.dumps({
        "family": "gpt2", "source": "a test", "reduced": [],
        "activation_function": "gelu_new", "attn_pdrop": 0.1,
        "embd_pdrop": 0.1, "resid_pdrop": 0.1, "initializer_range": 0.02,
        "layer_norm_epsilon": 1e-5, "n_embd": 64, "n_head": 2, "n_layer": 2,
        "n_positions": 64, "vocab_size": 250,
        "assumed": {"vocab_rows_padded": 256}}))
    (root / "perf/traffic/toy.b2.s64.json").write_text(json.dumps({
        "generator": "zipf_tokens", "batch_per_chip": 2, "seq": 64,
        "exponent": 1.0, "pool_steps": 512}))
    (root / "perf/workloads/toy.cell.json").write_text(json.dumps({
        "config": "toy", "traffic": "toy.b2.s64", "chips": 1, "why": "test",
        "job": {"gradient_accumulation_steps": 1,
                "activation_checkpointing": True,
                "ds_config": {
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 2}},
                "parity": {"layers": 2, "rows_per_chip": 2}},
        "loss_check": {"steps": [3, 7], "rise": 1.5},
        "per_layer": ["toy_steps", "compiles_in_window"]}))
    (root / "perf/layer_metrics/toy_steps.py").write_text(
        'LAYER, UNIT, MOVES, SOURCE = "entry", "count", "tokens_per_s", '
        '"program_counter"\n\n\n'
        'def reduce(trace, run):\n'
        '    return run["steps_traced"] + len(trace["devices"])\n')

    from perf import run
    traced = run.run_cell("toy.cell", seed=3, seconds=0.5, trace=True,
                          root=str(root), platform="cpu")
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert traced["metrics"]["toy_steps"] == {
        "value": float(run.TRACED_STEPS), "unit": "count"}
    assert traced["metrics"]["compiles_in_window"]["value"] == 0.0
    assert traced["failed"] == 0 and traced["attempted"] >= run.TRACED_STEPS
    assert traced["device"]["platform"] == "cpu"
    timed = run.run_cell("toy.cell", seed=3, seconds=0.5, trace=False,
                         root=str(root), platform="cpu")
    assert set(timed["metrics"]) == {"tokens_per_s", "step_ms_p50",
                                     "setup_s"}
    assert all(m["value"] > 0 for m in timed["metrics"].values())
    assert "breakdown" not in timed
    # nothing the benchmark already had was touched
    assert all(p.read_bytes() == body for p, body in before.items())
