"""The flash kernels of the main path, compiled ahead of time for the
v5e at the cells' own shapes.  The TPU's compiler is installed where the
chip is not, and refuses what the chip would refuse (tiling, fast
memory), so this guards every later PR at no chip time.  A compile is
not a run: nothing here says anything about time or results.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU's library, and
every xdist worker imports every test file.
"""

import pytest

# [B, H, S, D] of one chip's call in gpt2-large.s1024 and gpt2-xl.z3x4
SHAPES = [(4, 20, 1024, 64), (4, 25, 1024, 64)]
DROPOUT = 0.1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # such a compile is written to the persistent cache and cannot be
    # read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _operands(shape, one_chip, n):
    import jax
    import jax.numpy as jnp
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
            for _ in range(n)]


def _seed(one_chip):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)


@pytest.mark.parametrize("shape", SHAPES, ids=["large", "xl"])
def test_flash_fwd_compiles_for_v5e(shape, one_chip):
    import jax
    from deepspeed_tpu.ops.flash_attention import flash_attention_pallas

    def fwd(q, k, v, seed):
        return flash_attention_pallas(q, k, v, causal=True, return_lse=True,
                                      dropout_rate=DROPOUT, dropout_seed=seed)

    text = jax.jit(fwd).lower(*_operands(shape, one_chip, 3),
                              _seed(one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_fwd" in text


@pytest.mark.parametrize("shape", SHAPES, ids=["large", "xl"])
def test_flash_bwd_kernels_compile_for_v5e(shape, one_chip):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.flash_attention import flash_attention_bwd_pallas

    def bwd(q, k, v, out, lse, do, seed):
        return flash_attention_bwd_pallas(q, k, v, out, lse, do, causal=True,
                                          dropout_rate=DROPOUT,
                                          dropout_seed=seed)

    q, k, v, out, do = _operands(shape, one_chip, 5)
    lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=one_chip)
    text = jax.jit(bwd).lower(q, k, v, out, lse, do,
                              _seed(one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_bwd_dkdv" in text and "flash_bwd_dq" in text
