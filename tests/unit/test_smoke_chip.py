"""chip_smoke.py off the chip: a bare run without a TPU fails and prints
no result, the script alone (without the package) fails, the explicit
``--tiny`` CPU lane walks every phase green, and the readers of the
compiled program catch a kernel that is missing or fed the global batch.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


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
        "flash_fwd": 2, "flash_bwd_dkdv": 2, "flash_bwd_dq": 2}
    assert calls["flash_fwd"][0] == [(1,), (8, 12, 1024, 64),
                                     (8, 12, 1024, 64), (8, 12, 1024, 64)]
    counts = chip_smoke.check_flash_calls(calls, _CFG, _SIZE,
                                          streamed=False)
    assert sum(counts.values()) == 3 * _CFG.num_layers


def test_missing_kernel_fails_the_check():
    """A dispatcher that fell back to XLA attention leaves no custom
    call: the count, not a flag, is what catches it."""
    calls = chip_smoke.mosaic_flash_calls(_program(kernels=()))
    with pytest.raises(AssertionError, match="expected 2 calls"):
        chip_smoke.check_flash_calls(calls, _CFG, _SIZE, streamed=False)
    calls = chip_smoke.mosaic_flash_calls(
        _program(kernels=("flash_fwd", "flash_bwd_dq")))
    with pytest.raises(AssertionError, match="flash kernels missing"):
        chip_smoke.check_flash_calls(calls, _CFG, _SIZE, streamed=True)


def test_gathered_operand_fails_the_check():
    """A q/k/v all-gather in front of the kernel shows as the global
    batch in its operands."""
    calls = chip_smoke.mosaic_flash_calls(_program(batch=32))
    with pytest.raises(AssertionError, match="no per-chip operand"):
        chip_smoke.check_flash_calls(calls, _CFG, _SIZE, streamed=False)
