"""A set-up guard for the rotary kernels (ops/rotary.py), at no chip
time, in tests/unit/test_flash_setup_guard.py's manner: `setup_s` is a
gated metric of the benchmark, and a `pallas_call`'s body is traced in
Python and lowered to Mosaic at every start, in every program that
holds it (`laguna-xs2.s8k`'s grad program holds nine, the parity's
programs theirs).  So the forward and the backward pass, at the cell's
two shapes, must compile for the v5e, each as ONE kernel, and the traced
body must be the loop it is written as: as many equations whatever the
heads of the layer and the positions of a block, and no more than
twice as many for twice the heads a block holds.

The topology is described inside a module-scoped fixture, never while a
module is imported (see tests/perf/test_aot_kernels.py).
"""

import re

import pytest

KV, DIM = 8, 128
# the QKV products of laguna-xs2.s8k: (batch, positions, query heads,
# half the lanes of a head that turn), a sliding and a full layer
CELL = {"sliding": (2, 8192, 64, 64), "full": (2, 8192, 48, 32)}


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


@pytest.fixture
def kernels(monkeypatch):
    """ops/rotary.py with the dispatcher saying yes: it asks the default
    backend, which is the CPU here."""
    from deepspeed_tpu.ops import rotary
    monkeypatch.setattr(rotary, "pallas_available", lambda: True)
    return rotary


def _passes(rotary, shape, sharding=None):
    """(the forward pass, the backward pass, their arguments' shapes)."""
    import jax
    import jax.numpy as jnp
    batch, seq, heads, half = shape

    def array(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    qkv = array(batch, seq, (heads + 2 * KV) * DIM)
    tables = [array(seq, DIM, dtype=jnp.float32)] * 2
    by_head = tuple(array(batch, n, seq, DIM) for n in (heads, KV, KV))

    def forward(qkv, cos, sin):
        return rotary.rotate_qkv(qkv, cos, sin, half, heads, KV)

    def backward(qkv, cos, sin, cotangents):
        return jax.vjp(lambda x: forward(x, cos, sin), qkv)[1](cotangents)

    return ((forward, (qkv, *tables)),
            (backward, (qkv, *tables, by_head)))


@pytest.mark.parametrize("kind", CELL)
def test_both_passes_compile_as_one_kernel_each(kind, kernels, one_chip):
    import jax
    for (fn, args), name in zip(_passes(kernels, CELL[kind], one_chip),
                                ("rotary_fwd", "rotary_bwd")):
        lowered = jax.jit(fn).lower(*args)
        calls = re.findall(r'kernel_name = "(\w+)"', lowered.as_text())
        assert calls == [name]
        text = lowered.compile().as_text()  # raises what the chip would
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        # nothing of q's or k's size beside the kernel: no copy, no
        # float32 tensor
        assert not re.search(r"= f32\[2,\d+,8192,|= f32\[2,8192,\d{3,}", text)
        assert " copy(" not in text and " transpose(" not in text


def _equations(jaxpr):
    from deepspeed_tpu.analysis.jaxpr_walk import sub_jaxprs
    return sum(1 + sum(_equations(sub.jaxpr) for sub in sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def _bodies(rotary, shape):
    """{kernel name: (equations of its body, grid)} of both passes."""
    import jax
    from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
    found = {}
    for fn, args in _passes(rotary, shape):
        for eqn in (c.eqn for c in iter_eqns(
                jax.make_jaxpr(fn)(*args).jaxpr)):
            if eqn.primitive.name == "pallas_call":
                body = (_equations(eqn.params["jaxpr"]),
                        tuple(eqn.params["grid_mapping"].grid))
                # (jax.vjp traces the forward pass again)
                assert found.setdefault(eqn.params["name"], body) == body
    return found


def test_a_body_is_a_loop_over_positions_and_a_blocks_heads(kernels,
                                                            monkeypatch):
    sliding, full = _bodies(kernels, CELL["sliding"]), _bodies(
        kernels, CELL["full"])
    assert sorted(sliding) == sorted(full) == ["rotary_bwd", "rotary_fwd"]
    # q, k and v in blocks of 8 heads and 512 positions
    assert {grid for _, grid in sliding.values()} == {(2, 16, 10)}
    assert {grid for _, grid in full.values()} == {(2, 16, 8)}
    # the same body a pass but for a full layer's second roll and select
    for name in sliding:
        assert sliding[name][0] < full[name][0] <= 1.3 * sliding[name][0]
    # more heads a layer or positions a sequence: the same body
    more = _bodies(kernels, (2, 16384, 128, 64))
    assert {n: e for n, (e, _) in more.items()} == {
        n: e for n, (e, _) in sliding.items()}
    # fewer positions a block: the same body; half the heads a block:
    # no more than the whole
    monkeypatch.setattr(kernels, "BLOCK_ROWS", 256)
    assert {n: e for n, (e, _) in _bodies(kernels, CELL["sliding"]).items()
            } == {n: e for n, (e, _) in sliding.items()}
    monkeypatch.setattr(kernels, "BLOCK_HEADS", 4)
    for name, (count, _) in _bodies(kernels, CELL["sliding"]).items():
        assert count < sliding[name][0] <= 2 * count
    # and small: three walks (q, k rotated, v copied) of 8 heads
    assert max(e for e, _ in full.values()) <= 600
