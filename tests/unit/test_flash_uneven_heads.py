"""The flash kernels with query/key heads of one size and value heads of
another (latent attention at 128 + 64 rotated on values of 128:
models/xing4.py): forward, ``dq``, ``dk`` and ``dv`` against
``mha_reference`` in the Pallas interpreter, causal, at the default
blocks and off them; the equal-size case beside it, which must lower to
the kernels it lowered to before (names, block shapes); and the two
kernels compiled ahead of time for the v5e at the cell's own shape
[1, 32, 4096, 192 | 128], where the TPU's compiler can be described (a
compile is not a run)."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops import dispatch

fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")

CELL_QK, CELL_V = (1, 32, 4096, 192), (1, 32, 4096, 128)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")
    dispatch.set_pallas_interpret(True)
    yield
    dispatch.set_pallas_interpret(False)


def operands(seq, d_qk, d_v, kv_heads=2):
    ks = jax.random.split(jax.random.PRNGKey(seq + d_qk), 4)
    return (jax.random.normal(ks[0], (1, 2, seq, d_qk)),
            jax.random.normal(ks[1], (1, kv_heads, seq, d_qk)),
            jax.random.normal(ks[2], (1, kv_heads, seq, d_v)),
            jax.random.normal(ks[3], (1, 2, seq, d_v)))


@pytest.mark.parametrize("d_qk, d_v", [(192, 128), (128, 128)])
@pytest.mark.parametrize("seq, blocks, kv_heads", [
    (1024, {}, 2),                                 # one 512 x 1024 tile row
    (2048, {}, 2),                                 # two key blocks: the walk
    (512, {"block_q": 256, "block_k": 256}, 2),    # off the diagonal too
    (512, {"block_q": 256, "block_k": 256}, 1),    # one key head for two
])
def test_uneven_heads_match_the_reference(interpreted, d_qk, d_v, seq,
                                          blocks, kv_heads):
    q, k, v, g = operands(seq, d_qk, d_v, kv_heads)
    scale = 2.0 / d_qk ** 0.5

    def ours(*a):
        return jnp.sum(fa.flash_attention(
            *a, causal=True, sm_scale=scale, impl="pallas", **blocks) * g)

    def want(*a):
        return jnp.sum(fa.mha_reference(*a, causal=True,
                                        sm_scale=scale) * g)

    got = jax.value_and_grad(ours, (0, 1, 2))(q, k, v)
    ref = jax.value_and_grad(want, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(
            jnp.max(jnp.abs(b)))


def test_the_output_has_the_values_head_size(interpreted):
    q, k, v, _ = operands(512, 192, 128)
    out, lse = fa.flash_attention_pallas(q, k, v, causal=True,
                                         interpret=True, return_lse=True)
    assert out.shape == (1, 2, 512, 128) and lse.shape == (1, 2, 512)
    windowed = fa.flash_attention_pallas(q, k, v, causal=True, window=128,
                                         block_q=128, block_k=128,
                                         interpret=True)
    want = fa.mha_reference(q, k, v, causal=True, window=128)
    assert float(jnp.max(jnp.abs(windowed - want))) <= 1e-4


def kernels(d_qk, d_v):
    """[(kernel name, the shapes of its blocks)] of one grad program."""
    q, k, v, g = operands(1024, d_qk, d_v)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        fa.flash_attention(*a, causal=True, impl="pallas") * g),
        (0, 1, 2)))(q, k, v))
    names = re.findall(r"name=(flash_(?:fwd|bwd)\w*)", text)
    # the last dimension of every block of every operand
    widths = re.findall(r"Blocked\(block_size=(\d+)\)\)\)", text)
    return names, set(widths)


def test_equal_sizes_lower_to_the_kernels_of_before(interpreted):
    """Same names and the same block shapes as a call that knows of one
    head size only: every block's last dimension is the one size."""
    names, widths = kernels(128, 128)
    assert sorted(set(names)) == ["flash_bwd_dkdv", "flash_fwd"]
    assert "flash_bwd_dq" not in names
    assert widths == {"128", str(fa._STATS_LANES)}
    uneven_names, uneven = kernels(192, 128)
    assert sorted(set(uneven_names)) == sorted(set(names))
    assert uneven == {"192", "128", str(fa._STATS_LANES)}


def test_the_backward_kernels_memory_counts_both_sizes():
    """At one size the sum is what it was (PR 52's figures: 22.5 MiB at
    8,192 x 128 in blocks of 512 x 1024); values narrower than the keys
    ask for less than keys' size all round, and more than the values'."""
    even = fa._bwd_vmem(8192, 128, 512, 1024, 2)
    assert even == fa._bwd_vmem(8192, 128, 512, 1024, 2, 128)
    assert round(even / 2 ** 20, 1) == 22.5
    assert (fa._bwd_vmem(4096, 128, 512, 1024, 2)
            < fa._bwd_vmem(4096, 192, 512, 1024, 2, 128)
            < fa._bwd_vmem(4096, 192, 512, 1024, 2))
    assert fa._bwd_spans(4096, 192, 512, 1024, 2, 128) == 1


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


def test_uneven_kernels_compile_for_v5e_at_the_cells_shape(one_chip):
    """Heads of 192 are one and a half lane tiles: Mosaic takes the
    blocks whole (the last dimension is the array's), pads the
    contraction, and the VMEM plan holds."""
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, k, v = spec(CELL_QK), spec(CELL_QK), spec(CELL_V)
    out, do, lse = spec(CELL_V), spec(CELL_V), spec(CELL_V[:3], jnp.float32)
    scale = 2.0048 / 192 ** 0.5

    def fwd(q, k, v):
        return fa.flash_attention_pallas(q, k, v, causal=True,
                                         sm_scale=scale, return_lse=True)

    def bwd(q, k, v, out, lse, do):
        return fa.flash_attention_bwd_pallas(q, k, v, out, lse, do,
                                             causal=True, sm_scale=scale)

    text = jax.jit(fwd).lower(q, k, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_fwd" in text
    text = jax.jit(bwd).lower(q, k, v, out, lse, do).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_bwd_dkdv" in text and "flash_bwd_dq" not in text
