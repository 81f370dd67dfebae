"""bench.py's contract: the row runs in this process on the device JAX
reports and is stamped with it; any exception exits non-zero; a platform
other than tpu is refused unless JAX_PLATFORMS names cpu explicitly; a
utilization is only computed against published peaks of a known device.
Plus the helpers bench.py shares with chip_smoke.py and benchmarks/
(deepspeed_tpu/utils/chip.py): the peaks table and the compile cache.
"""

import json
import os
import subprocess
import sys

import pytest
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import bench  # noqa: E402
from deepspeed_tpu.utils import chip  # noqa: E402


def _run_main(monkeypatch, row, config="smoke"):
    monkeypatch.setitem(bench.BENCHES, config, row)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--config", config])
    # the cache helper is exercised on its own below; keep this process's
    # jax config as the suite set it
    monkeypatch.setattr(chip, "enable_compile_cache", lambda: None)
    bench.main()


def test_row_is_stamped_with_the_device_it_ran_on(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    _run_main(monkeypatch, lambda: {"metric": "m", "value": 1.0})
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    row = json.loads(lines[0])
    assert row["metric"] == "m" and row["value"] == 1.0
    assert row["platform"] == "cpu"
    assert row["device_kind"] == jax.devices()[0].device_kind
    assert row["device_count"] == len(jax.devices())
    assert "stale" not in row and "degraded" not in row


def test_raising_row_propagates_and_prints_no_row(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")

    def row():
        raise RuntimeError("Mosaic failed to compile the kernel")

    with pytest.raises(RuntimeError, match="Mosaic"):
        _run_main(monkeypatch, row)
    assert capsys.readouterr().out.strip() == ""
    # no retry with the Pallas kernels routed to XLA under the same metric
    from deepspeed_tpu.ops import dispatch
    assert not dispatch._force_xla


def test_raising_row_exits_nonzero():
    """The process-level half of the contract: a non-zero exit code and
    nothing that parses as a result on stdout."""
    code = ("import sys, bench\n"
            "def boom():\n"
            "    raise RuntimeError('row failed')\n"
            "bench.BENCHES['smoke'] = boom\n"
            "sys.argv = ['bench.py', '--config', 'smoke']\n"
            "bench.main()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "row failed" in proc.stderr
    assert "{" not in proc.stdout


def test_non_tpu_platform_is_refused_unless_cpu_is_asked_for(monkeypatch):
    ran = []
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as exc:
        _run_main(monkeypatch, lambda: ran.append(1) or {})
    assert "platform='cpu'" in str(exc.value.code)
    assert not ran
    # an accelerator that is not a TPU is refused too
    monkeypatch.setattr(chip, "device_summary", lambda: {
        "platform": "gpu", "kind": "some gpu", "count": 1})
    with pytest.raises(SystemExit) as exc:
        _run_main(monkeypatch, lambda: ran.append(1) or {})
    assert "platform='gpu'" in str(exc.value.code)
    assert not ran


def test_peak_tflops_raises_on_unknown_device_kind(monkeypatch):
    with pytest.raises(ValueError, match="no published peaks"):
        bench._peak_tflops()  # the CPU sim is not in the table
    monkeypatch.setattr(chip, "device_summary", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert bench._peak_tflops() == 197.0


def test_peaks_table_is_keyed_by_device_kind():
    assert chip.device_peaks("TPU v5 lite") == {"bf16_tflops": 197.0,
                                                "hbm_gbps": 819.0}
    # exact device_kind, not a substring guess that defaults to v5e
    for kind in ("TPU v5", "TPU v6 lite", "cpu", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            chip.device_peaks(kind)


def test_relay_machinery_is_gone():
    for name in ("_probe_tpu", "_reap_probe", "_await_tpu_slot",
                 "_init_backend", "_last_measured", "PEAK_TFLOPS"):
        assert not hasattr(bench, name), name
    source = (REPO / "bench.py").read_text()
    for knob in ("DS_BENCH_WALL_BUDGET", "DS_BENCH_WATCHDOG",
                 "DS_BENCH_SKIP_PROBE", "DS_BENCH_PROBE_PLATFORM",
                 "DS_BENCH_MAX_HUNG_PROBES", "DS_BENCH_INIT_RETRIES",
                 "DS_BENCH_COMPILE_CACHE", "DS_BENCH_LADDER", "os._exit",
                 "sys.exit(0)", "force_xla_kernels"):
        assert knob not in source, knob


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_from_outside_sets_nothing(monkeypatch,
                                                        cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert chip.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert chip.enable_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    # the same path from any process, and git ignores it
    assert chip.enable_compile_cache() == chip.COMPILE_CACHE_DIR
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_harness_and_benchmarks_share_the_cache_helper():
    """No second cache location: nothing under benchmarks/ or in bench.py
    names a cache directory of its own."""
    for path in [REPO / "bench.py", *sorted((REPO / "benchmarks").glob(
            "*.py"))]:
        text = path.read_text()
        assert "jax_compilation_cache_dir" not in text, path.name
        assert "ds_jax_cache" not in text, path.name
    assert "enable_compile_cache()" in (
        REPO / "benchmarks" / "_harness.py").read_text()


def test_time_steps_gas_alignment(monkeypatch):
    """DS_BENCH_ITERS overrides are re-rounded to the accumulation
    boundary (align=gas), keeping whole optimizer steps in the window."""
    calls = {"n": 0}

    def step():
        calls["n"] += 1
        return 0.0

    monkeypatch.setenv("DS_BENCH_ITERS", "12")
    dt, _, n = bench._time_steps(step, warmup=1, iters=10, align=8)
    assert n == 16 and calls["n"] == 17  # 12 rounded up to 2 full cycles
    calls["n"] = 0
    monkeypatch.delenv("DS_BENCH_ITERS")
    dt, _, n = bench._time_steps(step, warmup=1, iters=10, align=3)
    assert n == 12 and calls["n"] == 13


def test_benches_and_metric_names_stay_in_sync():
    """Every --config has a metric entry and vice versa, and the metric a
    parameterized row emits matches it."""
    assert set(bench.BENCHES) == set(bench.METRIC_NAMES)
    # spot-verify the parameterized rows' success metric == error metric
    assert bench.METRIC_NAMES["bert_s512"][0] == \
        "bert_large_z2_s512_samples_per_sec_1chip"
    assert bench.METRIC_NAMES["bert_z2"][0] == \
        "bert_large_z2_samples_per_sec_1chip"
    assert bench.METRIC_NAMES["gpt2_b16"][0] == \
        "gpt2_124m_b16_train_tokens_per_sec_1chip"
    assert bench.METRIC_NAMES["gpt2_b32"][0] == \
        "gpt2_124m_b32_train_tokens_per_sec_1chip"
    assert bench.METRIC_NAMES["gpt2_medium"][0] == \
        "gpt2_355m_train_tokens_per_sec_1chip"
    assert bench.METRIC_NAMES["gpt2_large"][0] == \
        "gpt2_774m_train_tokens_per_sec_1chip"
