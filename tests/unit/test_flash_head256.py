"""The flash kernels at a head of 256 for q, k and v (latent attention:
models/glm4_moe_lite.py): forward and the backward kernel against
``mha_reference`` in the Pallas interpreter, causal, at the blocks the
chip plan uses (512 x 1024, the defaults, fitted to the length); and the
two kernels compiled ahead of time for the v5e at the cell's own shape
[2, 20, 8192, 256], where the TPU's compiler can be described (a compile
is not a run).  The topology is described inside a fixture, never while
a module is imported."""

import importlib

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops import dispatch

fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")

DIM = 256
CELL = (2, 20, 8192, DIM)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")
    dispatch.set_pallas_interpret(True)
    yield
    dispatch.set_pallas_interpret(False)


@pytest.mark.parametrize("seq, blocks", [
    (1024, {}),                                  # one 512 x 1024 tile row
    (2048, {}),                                  # two key blocks: the walk
    (512, {"block_q": 256, "block_k": 256}),     # off the diagonal too
])
def test_head_256_matches_the_reference(interpreted, seq, blocks):
    ks = jax.random.split(jax.random.PRNGKey(seq), 4)
    q, k, v, g = (jax.random.normal(key, (1, 2, seq, DIM)) for key in ks)
    scale = 1.0 / 16

    def ours(*a):
        return jnp.sum(fa.flash_attention(
            *a, causal=True, sm_scale=scale, impl="pallas", **blocks) * g)

    def want(*a):
        return jnp.sum(fa.mha_reference(*a, causal=True,
                                        sm_scale=scale) * g)

    text = str(jax.make_jaxpr(jax.grad(ours, (0, 1, 2)))(q, k, v))
    for kernel in ("flash_fwd", "flash_bwd_dkdv"):
        assert kernel in text
    assert "flash_bwd_dq" not in text
    got = jax.value_and_grad(ours, (0, 1, 2))(q, k, v)
    ref = jax.value_and_grad(want, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(
            jnp.max(jnp.abs(b)))


def test_the_default_blocks_hold_at_the_cells_length():
    usable, block_q, block_k = fa._resolve_blocks(
        CELL[2], CELL[2], fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
    assert (usable, block_q, block_k) == (True, 512, 1024)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache and cannot be
    # read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_head_256_kernels_compile_for_v5e_at_the_cells_shape(one_chip):
    """Forward with its residuals and the backward kernel at the
    default blocks: the VMEM plan holds at 256, the head's 8 MB of dq
    resident as float32 with its output beside it, under the limit the
    call reckons for itself (``_bwd_vmem``)."""
    operands = [jax.ShapeDtypeStruct(CELL, jnp.bfloat16, sharding=one_chip)
                for _ in range(5)]
    lse = jax.ShapeDtypeStruct(CELL[:3], jnp.float32, sharding=one_chip)

    def fwd(q, k, v):
        return fa.flash_attention_pallas(q, k, v, causal=True,
                                         sm_scale=1 / 16, return_lse=True)

    def bwd(q, k, v, out, lse, do):
        return fa.flash_attention_bwd_pallas(q, k, v, out, lse, do,
                                             causal=True, sm_scale=1 / 16)

    text = jax.jit(fwd).lower(*operands[:3]).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_fwd" in text
    q, k, v, out, do = operands
    text = jax.jit(bwd).lower(q, k, v, out, lse, do).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_bwd_dkdv" in text and "flash_bwd_dq" not in text
