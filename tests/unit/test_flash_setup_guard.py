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

The window and the grouped key/value heads (PR 34) are held to the same
measure: each banded kernel, at the shapes of `phi4-mini-flash.s8k`,
compiles and lowers to a module at most twice its plain causal one's;
and a GPT-2 call (no window, equal head counts) still traces the
kernels it traced before them, body for body, so that they cost the
GPT-2 cells no set-up.  (PR 35 replaced the forward kernel's body by a
smaller one; the pin says which.  PR 49 deleted the older body: every q
block, under a lane tile too, compiles through the one that is left.
PR 52 made the backward ONE kernel: the pin says what its body grew by
and what went.)

The topology is described inside a module-scoped fixture, never while a
module is imported (see tests/perf/test_aot_kernels.py).
"""

import re

import pytest

# [B, H, S, D] of one chip's call in gpt2-large.s1024 and gpt2-xl.z3x4
SHAPES = [(4, 20, 1024, 64), (4, 25, 1024, 64)]
DROPOUT = 0.1
KERNELS = ("flash_fwd", "flash_bwd_dkdv")


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


def _lowerings(shape, one_chip, causal, kv_heads=None, dropout=DROPOUT,
               **call):
    """The forward call's and the backward call's lowerings for the v5e;
    `kv_heads` fewer key/value heads than `shape` has query heads, `call`
    a window and its blocks."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.flash_attention import (
        flash_attention_bwd_pallas, flash_attention_pallas)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((shape[0], kv_heads or shape[1], *shape[2:]),
                              jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def fwd(q, k, v, s):
        return flash_attention_pallas(q, k, v, causal=causal,
                                      return_lse=True, dropout_rate=dropout,
                                      dropout_seed=s, **call)

    def bwd(q, k, v, out, lse, do, s):
        return flash_attention_bwd_pallas(q, k, v, out, lse, do,
                                          causal=causal,
                                          dropout_rate=dropout,
                                          dropout_seed=s, **call)

    return (jax.jit(fwd).lower(x, kv, kv, seed),
            jax.jit(bwd).lower(x, kv, kv, x, lse, x, seed))


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


# [B, H, S, D] of the query operand of one call in phi4-mini-flash.s8k:
# 20 query heads on 10 key/value heads, 8,192 positions
PHI4 = (1, 20, 8192, 64)
BANDED = dict(window=512, block_q=512, block_k=512)


def test_banded_kernels_compile_and_stay_small(one_chip):
    banded = _lowerings(PHI4, one_chip, True, kv_heads=10, dropout=0.0,
                        **BANDED)
    for lowered in banded:
        lowered.compile()
    size = _module_sizes(banded)
    plain = _module_sizes(_lowerings(PHI4, one_chip, True, kv_heads=10,
                                     dropout=0.0, block_q=512, block_k=512))
    assert sorted(size) == sorted(k + "_band" for k in KERNELS)
    for kernel in KERNELS:
        assert size[kernel + "_band"] <= 2 * plain[kernel], (
            kernel, size[kernel + "_band"], plain[kernel])


def test_grouped_causal_kernels_compile_at_the_cells_shape(one_chip):
    for lowered in _lowerings(PHI4, one_chip, True, kv_heads=10,
                              dropout=0.0):
        lowered.compile()


@pytest.mark.parametrize("dropout", [0.0, DROPOUT])
@pytest.mark.parametrize("block_q", [64, 8])
def test_kernels_compile_below_a_lane_tile_of_q_rows(block_q, dropout,
                                                     one_chip):
    """There is one forward body, q rows along the lanes, and it takes
    the q block it is given: half a lane tile and a single sublane tile
    compile for the v5e, with dropout and without (PR 35's check, kept;
    tests/tpu runs them)."""
    lowerings = _lowerings((2, 4, 512, 64), one_chip, True, dropout=dropout,
                           block_q=block_q, block_k=512)
    for lowered in lowerings:
        lowered.compile()
    assert sorted(_module_sizes(lowerings)) == sorted(KERNELS)


# The two kernels of a GPT-2 large / xl call: equations of each
# kernel's body, sub-jaxprs in, and the grid's two sequence dimensions.
# (A lowered module's bytes carry its source locations and the caller's
# stack, so they move with any edit of the file; the traced body does
# not.)  The forward's count is PR 35's (q rows along the lanes: 207
# equations for the 246 of the body before it).  The backward's is PR
# 52's: one body of 378 equations where the pair traced 342
# (flash_bwd_dkdv, the count of the parent of PR 34, commit 0275698) and
# 297 (flash_bwd_dq): dq's product and its slice of the resident scratch
# a row group and body, its zeroing and its store.  What it guards now:
# that a GPT-2 call takes these bodies, under these names, the forward
# in one step and two bodies, and that nothing grows them unseen.
PARENT_KERNELS = {"flash_fwd": (207, (2, 1)), "flash_bwd_dkdv": (378, (1, 2))}


def _equations(jaxpr):
    from deepspeed_tpu.analysis.jaxpr_walk import sub_jaxprs
    return sum(1 + sum(_equations(sub.jaxpr) for sub in sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def _traced_kernels(jaxpr, found):
    """{kernel name: (equations of its body, grid)} of every pallas_call."""
    from deepspeed_tpu.analysis.jaxpr_walk import sub_jaxprs
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            assert name not in found
            found[name] = (_equations(eqn.params["jaxpr"]),
                           tuple(eqn.params["grid_mapping"].grid))
        else:
            for sub in sub_jaxprs(eqn):
                _traced_kernels(sub.jaxpr, found)
    return found


@pytest.mark.parametrize("shape", SHAPES, ids=["large", "xl"])
def test_a_gpt2_call_traces_the_parents_kernels(shape):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.flash_attention import (
        flash_attention_bwd_pallas, flash_attention_pallas)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32)
    seed = jax.ShapeDtypeStruct((), jnp.int32)
    found = _traced_kernels(jax.make_jaxpr(
        lambda q, k, v, s: flash_attention_pallas(
            q, k, v, causal=True, return_lse=True, dropout_rate=DROPOUT,
            dropout_seed=s))(x, x, x, seed).jaxpr, {})
    _traced_kernels(jax.make_jaxpr(
        lambda q, k, v, o, lse_, do, s: flash_attention_bwd_pallas(
            q, k, v, o, lse_, do, causal=True, dropout_rate=DROPOUT,
            dropout_seed=s))(x, x, x, x, lse, x, seed).jaxpr, found)
    assert found == {name: (count, shape[:2] + grid)
                     for name, (count, grid) in PARENT_KERNELS.items()}
