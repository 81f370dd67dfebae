"""chip_smoke.py off the chip: a bare run without a TPU fails and prints
no result, the script alone (without the package) fails, the explicit
``--tiny`` CPU lane walks every phase green, and the readers of the
compiled program catch a kernel that is missing or fed the global batch.
Plus the helpers it shares with benchmarks/ (deepspeed_tpu/utils/chip.py,
the peaks table and the compile cache) and the FLOP count behind its MFU
line, which is the benchmark's.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

import jax

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from deepspeed_tpu.utils import chip  # noqa: E402


def _run(args, cwd=REPO, env=None, timeout=600):
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=str(cwd), env=env or dict(os.environ),
                          capture_output=True, text=True, timeout=timeout)


def _results(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


def test_bare_run_without_a_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run([], env=env)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stderr and "need tpu" in proc.stderr
    assert not _results(proc.stdout)
    assert "phase" not in proc.stdout  # nothing ran


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to prove."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run(["--tiny"], cwd=tmp_path, env=env)
    assert proc.returncode != 0
    assert "deepspeed_tpu" in proc.stderr
    assert not _results(proc.stdout)


def test_tiny_lane_walks_every_phase():
    proc = _run(["--tiny"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    assert "platform=cpu" in out
    for phase in ("one-chip ZeRO-2", "four-chip ZeRO-2",
                  "four-chip ZeRO-3 streamed", "loss parity"):
        assert f"phase {phase}" in out, phase
    last = out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 8}}


# One Mosaic custom call as XLA prints it (TPU v5 lite, jax 0.9.0), cut to
# the fields the reader uses.
_CALL = (
    '  %shard_map.{n} = bf16[8,12,1024,64]{{3,2,1,0:T(8,128)(2,1)}} '
    'custom-call(%bitcast.39, %bitcast.300, %bitcast.303, %bitcast.306), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{{s32[1]{{0}}, bf16[{b},12,1024,64]{{3,2,1,0}}, '
    'bf16[{b},12,1024,64]{{3,2,1,0}}, bf16[{b},12,1024,64]{{3,2,1,0}}}}, '
    'frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name='
    '"jit(loss_and_grads)/jvp(layer)/attn/{kernel}/pallas_call" '
    'stack_frame_id=91}}, backend_config={{}}')
_CFG = SimpleNamespace(num_layers=2, num_heads=12, hidden_size=768)
_SIZE = {"batch_per_chip": 8, "seq": 1024}


def _program(batch=8, layers=2, kernels=chip_smoke.FLASH_KERNELS):
    lines = ["HloModule jit_loss_and_grads",
             '  %cc = f32[8] custom-call(%p), custom_call_target="Sharding"']
    for kernel in kernels:
        lines += [_CALL.format(n=i, b=batch, kernel=kernel)
                  for i in range(layers)]
    return "\n".join(lines)


def test_reader_counts_mosaic_calls_by_kernel():
    calls = chip_smoke.mosaic_flash_calls(_program())
    assert {k: len(v) for k, v in calls.items()} == {
        "flash_fwd": 2, "flash_bwd_dkdv": 2}
    assert calls["flash_fwd"][0] == [(1,), (8, 12, 1024, 64),
                                     (8, 12, 1024, 64), (8, 12, 1024, 64)]
    counts = chip_smoke.check_flash_calls(calls, _CFG, _SIZE,
                                          streamed=False)
    assert sum(counts.values()) == 2 * _CFG.num_layers


def test_missing_kernel_fails_the_check():
    """A dispatcher that fell back to XLA attention leaves no custom
    call: the count, not a flag, is what catches it."""
    calls = chip_smoke.mosaic_flash_calls(_program(kernels=()))
    with pytest.raises(AssertionError, match="expected 2 calls"):
        chip_smoke.check_flash_calls(calls, _CFG, _SIZE, streamed=False)
    calls = chip_smoke.mosaic_flash_calls(
        _program(kernels=("flash_fwd",)))
    with pytest.raises(AssertionError, match="flash kernels missing"):
        chip_smoke.check_flash_calls(calls, _CFG, _SIZE, streamed=True)


def test_gathered_operand_fails_the_check():
    """A q/k/v all-gather in front of the kernel shows as the global
    batch in its operands."""
    calls = chip_smoke.mosaic_flash_calls(_program(batch=32))
    with pytest.raises(AssertionError, match="no per-chip operand"):
        chip_smoke.check_flash_calls(calls, _CFG, _SIZE, streamed=False)


def test_mfu_line_divides_by_the_benchmarks_flop_count():
    """GPT-2 124M at S=1,024, the size the smoke test trains: the count
    is perf/flops.py's for the same shapes (causal attention at half the
    square), not one of the program's own."""
    from deepspeed_tpu.models import GPT2Config
    from perf.flops import decoder_train_flops_per_token
    cfg = GPT2Config(**chip_smoke.FULL["model"])
    seq = chip_smoke.FULL["seq"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size, seq) == (
        768, 12, 50304, 1024)
    assert chip_smoke.flops_per_token(cfg, seq) == (
        decoder_train_flops_per_token(768, 12, 1024, 50304))


def test_peaks_table_is_keyed_by_device_kind():
    assert chip.device_peaks("TPU v5 lite") == {"bf16_tflops": 197.0,
                                                "hbm_gbps": 819.0}
    # exact device_kind, not a substring guess that defaults to v5e
    for kind in ("TPU v5", "TPU v6 lite", "cpu", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            chip.device_peaks(kind)


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
    """No second cache location: neither chip_smoke.py nor anything under
    benchmarks/ names a cache directory of its own, and the scripts that
    compile large programs take the package's."""
    for path in [REPO / "chip_smoke.py", *sorted((REPO / "benchmarks").glob(
            "*.py"))]:
        text = path.read_text()
        assert "jax_compilation_cache_dir" not in text, path.name
        assert "ds_jax_cache" not in text, path.name
    for name in ("chip_smoke.py", "benchmarks/convergence_run.py",
                 "benchmarks/grad_diag.py",
                 "benchmarks/infinity_capability.py"):
        assert "enable_compile_cache()" in (REPO / name).read_text(), name
