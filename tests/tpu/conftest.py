"""Real-TPU kernel-parity lane (reference analog: test_cuda_forward.py:333 /
test_cuda_backward.py:335 run fused kernels against reference numerics on
real hardware at fp16/fp32 tolerances).

Unlike tests/unit (which forces the 8-device CPU sim mesh), this lane runs
on the DEFAULT backend.  Run it on the chip:

    python -m pytest tests/tpu -q

When the lane is what was asked for and the backend is not a TPU, every
test FAILS: a skipped kernel-parity run reads as a pass.  Under a mixed
`pytest tests/` run the parent conftest has forced the CPU sim, and the
lane skips itself.
"""

import pytest


@pytest.fixture(autouse=True)
def _require_tpu():
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return
    reason = (f"TPU kernel-parity lane needs a TPU backend "
              f"(default backend: {backend})")
    from tests.conftest import _tpu_lane_only
    if _tpu_lane_only:
        pytest.fail(reason, pytrace=False)
    pytest.skip(reason)
