"""ops/indexed_attention.py's backward, ONE kernel (``dsa_attn_bwd_dkdv``)
that builds each tile once and makes dq, dk and dv from it: in interpret
mode against ``jax.grad`` of the blocked XLA form over groups, tile
shapes, sequence lengths and dtypes; what the traced program holds; the
VMEM it asks for at the cell's shape, and the bound ``kernels_take`` puts
on the sequence for it."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops import indexed_attention as ia

PACK_BLOCK, DIM, TOPK = 64, 16, 24
# the cell's call: keye-vl2-30b-a3b.s16k, one layer
CELL = {"q": (1, 32, 16384, 128), "kv": (1, 4, 16384, 128)}


def _case(heads, kv_heads, seq, dtype):
    keys = jax.random.split(jax.random.PRNGKey(11), 7)
    q = jax.random.normal(keys[0], (1, heads, seq, DIM))
    k, v = (jax.random.normal(key, (1, kv_heads, seq, DIM))
            for key in keys[1:3])
    packed, _ = ia.index_select_xla(
        jax.random.normal(keys[3], (1, 2, seq, 8)),
        jax.random.normal(keys[4], (1, seq, 8)),
        0.1 * jax.random.normal(keys[5], (1, 2, seq)), TOPK,
        block_q=PACK_BLOCK)
    do = jax.random.normal(keys[6], q.shape)
    return tuple(x.astype(dtype) for x in (q, k, v, do)) + (packed,)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("tiles", [1, 4], ids=["one tile", "four tiles"])
@pytest.mark.parametrize("block_q,block_k", [(128, 64), (64, 128)],
                         ids=["taller than wide", "wider than tall"])
@pytest.mark.parametrize("group", [8, 1], ids=["group of 8", "group of 1"])
def test_fused_backward_equals_the_grad_of_the_xla_form(group, block_q,
                                                        block_k, tiles,
                                                        dtype):
    seq = tiles * max(block_q, block_k)
    scale = 1.0 / math.sqrt(DIM)
    q, k, v, do, packed = _case(group, 1, seq, dtype)
    out, lse = ia.indexed_attention_xla(q, k, v, packed, scale,
                                        block_q=PACK_BLOCK)
    got = ia.indexed_attention_bwd_pallas(
        q, k, v, packed, out, lse, do, sm_scale=scale, block_q=block_q,
        block_k=block_k, pack=PACK_BLOCK, interpret=True)
    # the XLA form in float32 on the operands as the kernel read them
    wide = [x.astype(jnp.float32) for x in (q, k, v, do)]
    want = jax.grad(lambda q_, k_, v_: jnp.sum(
        wide[3] * ia.indexed_attention_xla(
            q_, k_, v_, packed, scale, block_q=PACK_BLOCK)[0]),
        (0, 1, 2))(*wide[:3])
    # bf16: p, ds and the results are rounded to 8 bits, out to the same
    rel = 1e-5 if dtype == jnp.float32 else 1.5e-2
    for a, b in zip(got, want):
        assert a.dtype == dtype and a.shape == b.shape
        np.testing.assert_allclose(a.astype(jnp.float32), b,
                                   atol=rel * float(jnp.abs(b).max()))


@pytest.fixture(scope="module")
def cell_calls():
    """The backward traced (nothing compiled, nothing run) at the cell's
    shape and the file's own tiles: its ``pallas_call`` equations."""
    shape = jax.ShapeDtypeStruct
    q = shape(CELL["q"], jnp.bfloat16)
    k = shape(CELL["kv"], jnp.bfloat16)
    seq = CELL["q"][2]
    traced = jax.make_jaxpr(functools.partial(
        ia.indexed_attention_bwd_pallas, sm_scale=0.1,
        block_q=ia.ATTN_BLOCK, block_k=ia.ATTN_BLOCK, pack=ia.BLOCK_Q))(
        q, k, k, shape((1, seq // ia.PACK, seq), jnp.int32), q,
        shape(CELL["q"][:3], jnp.float32), q)
    return [ctx.eqn for ctx in iter_eqns(traced.jaxpr)
            if ctx.eqn.primitive.name == "pallas_call"]


def test_the_backward_is_one_pallas_call_named_dkdv(cell_calls):
    assert [call.params["name"] for call in cell_calls] == [
        "dsa_attn_bwd_dkdv"]
    assert not hasattr(ia, "_attn_dq_kernel")


def test_vmem_at_the_cells_shape_is_under_the_limit(cell_calls):
    """``_attn_bwd_vmem`` is what the call declares (every block twice,
    its last axis a whole lane tile, and the scratch) and four float32
    tiles of temporaries, and that is under the file's limit."""
    (call,) = cell_calls
    grid = call.params["grid_mapping"]

    def nbytes(shape, dtype):
        *lead, lanes = shape
        return (math.prod(lead) * -(-lanes // 128) * 128
                * jnp.dtype(dtype).itemsize)

    declared = sum(
        2 * nbytes([getattr(b, "block_size", b) for b in m.block_shape],
                   m.array_aval.dtype) for m in grid.block_mappings)
    declared += sum(nbytes(s.shape, s.dtype) for s in grid.scratch_avals)
    reckoned = ia._attn_bwd_vmem(CELL["q"][2], CELL["q"][3])
    assert reckoned == declared + 4 * 4 * ia.ATTN_BLOCK ** 2
    assert reckoned < ia._VMEM_LIMIT
    assert call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes \
        == ia._VMEM_LIMIT


@pytest.mark.parametrize("seq,takes", [(16384, True), (24576, True),
                                       (32768, False)])
def test_kernels_take_refuses_a_sequence_whose_dq_would_not_fit(seq, takes):
    dispatch.set_pallas_interpret(True)
    try:
        assert ia.kernels_take(seq, 128, 64) is takes
    finally:
        dispatch.set_pallas_interpret(False)
    assert not ia.kernels_take(seq, 128, 64)


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip (never while a module is imported: one
    process loads the TPU's library, every xdist worker imports this
    file), the persistent compile cache kept out of it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_backward_compiles_for_v5e_at_the_cells_shape(one_chip):
    """The chip's compiler takes the kernel with its resident gradients
    under ``_VMEM_LIMIT``.  A compile is not a run."""
    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    seq = CELL["q"][2]
    q, k = shape(CELL["q"]), shape(CELL["kv"])
    text = jax.jit(functools.partial(
        ia.indexed_attention_bwd_pallas, sm_scale=0.1,
        block_q=ia.ATTN_BLOCK, block_k=ia.ATTN_BLOCK, pack=ia.BLOCK_Q)).lower(
        q, k, k, shape((1, seq // ia.PACK, seq), jnp.int32), q,
        shape(CELL["q"][:3], jnp.float32), q).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "dsa_attn_bwd_dkdv" in text and "dsa_attn_bwd_dq" not in text
