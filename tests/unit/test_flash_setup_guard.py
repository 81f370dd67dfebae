"""A set-up guard for the flash kernels, at no chip time.  `setup_s` is a
gated metric of the benchmark, and even a warm run traces each
`pallas_call`'s kernel body in Python and lowers it to Mosaic before it
can look its program up in the compile cache: a kernel body unrolled per
sub-tile is paid for at every start, in every program that holds the
kernel.  So each causal kernel, at the cells' shapes, must compile for
the v5e and lower to a module at most twice the size of the same
kernel's non-causal lowering: with the shipped blocks the causal bound
(ops/flash_attention.py _walk_tile) costs a second body, at half the
width, and no more.

The topology is described inside a module-scoped fixture, never while a
module is imported (see tests/perf/test_aot_kernels.py).
"""

import re

import pytest

# [B, H, S, D] of one chip's call in gpt2-large.s1024 and gpt2-xl.z3x4
SHAPES = [(4, 20, 1024, 64), (4, 25, 1024, 64)]
DROPOUT = 0.1
KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")


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


def _lowerings(shape, one_chip, causal):
    """The forward call's and the backward pair's lowerings for the v5e."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.flash_attention import (
        flash_attention_bwd_pallas, flash_attention_pallas)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def fwd(q, k, v, s):
        return flash_attention_pallas(q, k, v, causal=causal,
                                      return_lse=True, dropout_rate=DROPOUT,
                                      dropout_seed=s)

    def bwd(q, k, v, out, lse, do, s):
        return flash_attention_bwd_pallas(q, k, v, out, lse, do,
                                          causal=causal,
                                          dropout_rate=DROPOUT,
                                          dropout_seed=s)

    return (jax.jit(fwd).lower(x, x, x, seed),
            jax.jit(bwd).lower(x, x, x, x, lse, x, seed))


def _module_sizes(lowerings):
    """Bytes of each kernel's serialized Mosaic module, by kernel name."""
    sizes = {}
    for lowered in lowerings:
        for line in lowered.as_text().splitlines():
            if "@tpu_custom_call" in line:
                name = re.search(r'kernel_name = "(\w+)"', line).group(1)
                sizes[name] = len(
                    re.search(r'body\\22: \\22([^\\]*)', line).group(1))
    return sizes


@pytest.mark.parametrize("shape", SHAPES, ids=["large", "xl"])
def test_causal_kernels_compile_and_stay_small(shape, one_chip):
    causal = _lowerings(shape, one_chip, True)
    for lowered in causal:
        lowered.compile()  # raises what the chip's compiler would raise
    size = _module_sizes(causal)
    whole = _module_sizes(_lowerings(shape, one_chip, False))
    assert sorted(size) == sorted(whole) == sorted(KERNELS)
    for kernel in KERNELS:
        assert size[kernel] <= 2 * whole[kernel], (
            f"{kernel} at {shape}: the causal module is {size[kernel]} "
            f"bytes against {whole[kernel]} non-causal: a kernel body "
            "unrolled per sub-tile is traced and lowered at every start")
