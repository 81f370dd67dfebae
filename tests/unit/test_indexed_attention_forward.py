"""ops/indexed_attention.py's forward (``dsa_attn_fwd``), a grid step a
tile of a KEY/VALUE head that serves the head's group of query heads: in
interpret mode against the blocked XLA form over groups, tile shapes,
sequence lengths and dtypes; rows that meet no kept key in their first
tiles, or in their last (the mask is applied once, and ``alpha`` wipes
what such a row gathered); a group split because it would not fit; what
the traced program holds at the cell's shape, the VMEM it asks for there,
and that the v5e's compiler takes it."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.ops import indexed_attention as ia

PACK_BLOCK, DIM, TOPK = 64, 16, 24
SCALE = 1.0 / math.sqrt(DIM)
# the cell's call: keye-vl2-30b-a3b.s16k, one layer
CELL = {"q": (1, 32, 16384, 128), "kv": (1, 4, 16384, 128)}


def _operands(heads, kv_heads, seq, dtype):
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(keys[0], (1, heads, seq, DIM))
    k, v = (jax.random.normal(key, (1, kv_heads, seq, DIM))
            for key in keys[1:])
    return tuple(x.astype(dtype) for x in (q, k, v))


def _selected(seq):
    keys = jax.random.split(jax.random.PRNGKey(17), 3)
    return ia.index_select_xla(
        jax.random.normal(keys[0], (1, 2, seq, 8)),
        jax.random.normal(keys[1], (1, seq, 8)),
        0.1 * jax.random.normal(keys[2], (1, 2, seq)), TOPK,
        block_q=PACK_BLOCK)[0]


def _check(q, k, v, packed, block_q, block_k):
    got, got_lse = ia.indexed_attention_fwd_pallas(
        q, k, v, packed, sm_scale=SCALE, block_q=block_q, block_k=block_k,
        pack=PACK_BLOCK, interpret=True)
    # the XLA form in float32 on the operands as the kernel read them
    want, want_lse = ia.indexed_attention_xla(
        *(x.astype(jnp.float32) for x in (q, k, v)), packed, SCALE,
        block_q=PACK_BLOCK)
    assert got.dtype == q.dtype and got.shape == q.shape
    assert got_lse.dtype == jnp.float32 and got_lse.shape == q.shape[:3]
    # bf16: p and the result are rounded to 8 bits
    rel = 2e-6 if q.dtype == jnp.float32 else 1.5e-2
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=rel * float(jnp.abs(want).max()))
    np.testing.assert_allclose(got_lse, want_lse, atol=1e-5)
    return got, got_lse


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("tiles", [1, 4], ids=["one tile", "four tiles"])
@pytest.mark.parametrize("block_q,block_k", [(128, 64), (64, 128)],
                         ids=["taller than wide", "wider than tall"])
@pytest.mark.parametrize("heads,kv_heads", [(8, 1), (8, 2), (2, 2)],
                         ids=["group of 8", "group of 4", "group of 1"])
def test_forward_equals_the_xla_form(heads, kv_heads, block_q, block_k,
                                     tiles, dtype):
    seq = tiles * max(block_q, block_k)
    _check(*_operands(heads, kv_heads, seq, dtype), _selected(seq),
           block_q, block_k)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("kept", ["the newest", "the oldest"])
def test_rows_that_meet_no_kept_key_in_some_of_their_tiles(kept, dtype):
    """Eight keys a row over four key blocks of 64.  The newest: a late
    row meets none in its first tiles, where it gathers ones under a
    maximum of ``_MASKED``, and ``alpha`` = 0.0 wipes them at the tile
    where it meets one.  The oldest: every row's keys lie in the first key
    block, and each later tile adds exact zeros."""
    seq, topk = 256, 8
    t = jnp.arange(seq)[:, None]
    s = jnp.arange(seq)[None, :]
    keep = (s <= t) & ((s > t - topk) if kept == "the newest" else (s < topk))
    assert int(keep.sum(axis=1).min()) >= 1
    packed = ia.pack_keep(keep[None], PACK_BLOCK)
    got, got_lse = _check(*_operands(8, 2, seq, dtype), packed, 64, 64)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(got_lse).all())


def _forward_calls(q, k, packed, block_q, block_k, pack):
    """The forward traced (nothing compiled, nothing run): its
    ``pallas_call`` equations.  Past the jit, whose cache knows nothing of
    a patched ``_VMEM_LIMIT``."""
    traced = jax.make_jaxpr(functools.partial(
        ia.indexed_attention_fwd_pallas.__wrapped__, sm_scale=SCALE,
        block_q=block_q, block_k=block_k, pack=pack))(q, k, k, packed)
    return [ctx.eqn for ctx in iter_eqns(traced.jaxpr)
            if ctx.eqn.primitive.name == "pallas_call"]


def _q_block(call):
    return tuple(getattr(b, "block_size", b) for b in
                 call.params["grid_mapping"].block_mappings[0].block_shape)


def test_heads_a_step_are_the_group_or_its_largest_divisor_that_fits():
    tile = (ia.ATTN_BLOCK, ia.ATTN_BLOCK, 128)
    assert ia._attn_fwd_heads(8, *tile) == 8          # the cell
    assert ia._attn_fwd_heads(1, *tile) == 1
    assert ia._attn_fwd_heads(32, *tile) == 16        # one key/value head
    assert ia._attn_fwd_vmem(32, *tile) > ia._VMEM_LIMIT
    assert ia._attn_fwd_vmem(16, *tile) <= ia._VMEM_LIMIT
    assert ia._attn_fwd_heads(24, *tile) == 12
    assert ia._attn_fwd_heads(40, *tile) == 20
    assert ia._attn_fwd_heads(34, *tile) == 17
    assert ia._attn_fwd_heads(31, *tile) == 1         # a prime, too many


@pytest.mark.parametrize("step", [4, 2, 1])
def test_a_group_that_does_not_fit_is_split(step, monkeypatch):
    """Eight query heads on one key/value head under a limit that holds
    ``step`` of them: the grid walks the group in parts, every part on the
    same key/value head, and the result is the whole group's bit for bit."""
    q, k, v = _operands(8, 1, 256, jnp.float32)
    packed = _selected(256)
    whole = ia.indexed_attention_fwd_pallas(
        q, k, v, packed, sm_scale=SCALE, block_q=64, block_k=64,
        pack=PACK_BLOCK, interpret=True)
    monkeypatch.setattr(ia, "_VMEM_LIMIT",
                        ia._attn_fwd_vmem(step, 64, 64, DIM))
    assert ia._attn_fwd_heads(8, 64, 64, DIM) == step
    (call,) = _forward_calls(q, k, packed, 64, 64, PACK_BLOCK)
    assert call.params["grid_mapping"].grid == (1, 8 // step, 4, 4)
    assert _q_block(call) == (1, step, 64, DIM)
    parts = ia.indexed_attention_fwd_pallas.__wrapped__(
        q, k, v, packed, sm_scale=SCALE, block_q=64, block_k=64,
        pack=PACK_BLOCK, interpret=True)
    for a, b in zip(parts, whole):
        np.testing.assert_array_equal(a, b)


@pytest.fixture(scope="module")
def cell_call():
    """The forward at the cell's shape and the file's own tiles: its one
    ``pallas_call`` equation."""
    shape = jax.ShapeDtypeStruct
    seq = CELL["q"][2]
    (call,) = _forward_calls(
        shape(CELL["q"], jnp.bfloat16), shape(CELL["kv"], jnp.bfloat16),
        shape((1, seq // ia.PACK, seq), jnp.int32), ia.ATTN_BLOCK,
        ia.ATTN_BLOCK, ia.BLOCK_Q)
    return call


def test_the_forward_is_one_call_a_step_a_key_value_heads_group(cell_call):
    assert cell_call.params["name"] == "dsa_attn_fwd"
    assert cell_call.params["grid_mapping"].grid == (1, 4, 16, 16)
    assert _q_block(cell_call) == (1, 8, 1024, 128)


def test_vmem_at_the_cells_shape_is_under_the_limit(cell_call):
    """``_attn_fwd_vmem`` is what the call declares (every block twice,
    its last axis a whole lane tile, and the scratch, the mask's float32
    tile among it) and a float32 tile of temporaries for each head
    abreast and one more, and that is under the file's limit."""
    grid = cell_call.params["grid_mapping"]

    def nbytes(shape, dtype):
        *lead, lanes = shape
        return (math.prod(lead) * -(-lanes // 128) * 128
                * jnp.dtype(dtype).itemsize)

    declared = sum(
        2 * nbytes([getattr(b, "block_size", b) for b in m.block_shape],
                   m.array_aval.dtype) for m in grid.block_mappings)
    declared += sum(nbytes(s.shape, s.dtype) for s in grid.scratch_avals)
    reckoned = ia._attn_fwd_vmem(8, ia.ATTN_BLOCK, ia.ATTN_BLOCK, 128)
    assert reckoned == declared + (1 + ia._abreast(8)) * 4 * ia.ATTN_BLOCK ** 2
    assert reckoned < ia._VMEM_LIMIT
    assert cell_call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes \
        == ia._VMEM_LIMIT


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


@pytest.mark.parametrize("kv_heads", [4, 1],
                         ids=["the cell: 8 heads a step",
                              "one key/value head: 16 a step"])
def test_the_forward_compiles_for_v5e_at_the_cells_shape(kv_heads, one_chip):
    """The chip's compiler takes the kernel with a whole group's blocks
    and state under ``_VMEM_LIMIT``, and with a group of 32 split in two.
    A compile is not a run."""
    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    seq = CELL["q"][2]
    kv = shape((1, kv_heads, seq, 128))
    text = jax.jit(functools.partial(
        ia.indexed_attention_fwd_pallas, sm_scale=0.1,
        block_q=ia.ATTN_BLOCK, block_k=ia.ATTN_BLOCK, pack=ia.BLOCK_Q)).lower(
        shape(CELL["q"]), kv, kv, shape((1, seq // ia.PACK, seq), jnp.int32)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "dsa_attn_fwd" in text
