"""ops/indexed_attention.py's alignment pass (``dsa_align``), a tile's
step through its stages with the heads abreast and the indexer's ReLU
products built once: in interpret mode against ``jax.value_and_grad`` of
the blocked XLA form, the value and the three gradients, over head counts
that put 8, 4, 2 and 1 heads abreast (main and indexer), groups, tile
shapes, sequence lengths and dtypes; rows that meet no kept key in some
tile; the same numbers bit for bit whatever goes abreast; what the traced
program holds at the cell's shape, the VMEM it asks for there, and that
the v5e's compiler takes it."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.ops import indexed_attention as ia

PACK_BLOCK, DIM, IDX_DIM, TOPK = 64, 16, 8, 24
SCALE = 1.0 / math.sqrt(DIM)
# the cell's call: keye-vl2-30b-a3b.s16k, one layer
CELL = {"q": (1, 32, 16384, 128), "k": (1, 4, 16384, 128),
        "q_idx": (1, 16, 16384, 64), "k_idx": (1, 16384, 64)}


def _operands(heads, kv_heads, idx_heads, seq, dtype, keep=None):
    """What the pass reads: the indexer's operands, the main attention's
    q and k with its log-sum-exp over the keep-set (the indexer's own
    choice, or ``keep`` bool [seq, seq] forced on it), the packed
    keep-set and the kept scores' log-sum-exp."""
    keys = jax.random.split(jax.random.PRNGKey(19), 6)
    q, k, v, q_idx, k_idx = (
        jax.random.normal(key, shape).astype(dtype) for key, shape in zip(
            keys, ((1, heads, seq, DIM), (1, kv_heads, seq, DIM),
                   (1, kv_heads, seq, DIM), (1, idx_heads, seq, IDX_DIM),
                   (1, seq, IDX_DIM))))
    w = 0.1 * jax.random.normal(keys[5], (1, idx_heads, seq))
    # the XLA forms in float32 on the operands as the kernel reads them
    wide = [x.astype(jnp.float32) for x in (q, k, v, q_idx, k_idx)]
    if keep is None:
        packed, lse_idx = ia.index_select_xla(wide[3], wide[4], w, TOPK,
                                              block_q=PACK_BLOCK)
    else:
        packed = ia.pack_keep(keep[None], PACK_BLOCK)
        lse_idx = ia.kept_lse(wide[3], wide[4], w, packed, PACK_BLOCK)
    lse = ia.indexed_attention_xla(*wide[:3], packed, SCALE,
                                   block_q=PACK_BLOCK)[1]
    return q_idx, k_idx, w, q, k, lse, packed, lse_idx


def _kernel(operands, block_q, block_k, jitted=True):
    call = ia.index_alignment_pallas if jitted else (
        ia.index_alignment_pallas.__wrapped__)
    loss, grads = call(*operands, sm_scale=SCALE, block_q=block_q,
                       block_k=block_k, pack=PACK_BLOCK, interpret=True)
    return (loss, *grads)


def _check(operands, block_q, block_k):
    q_idx, k_idx, w, q, k, *rest = operands
    got = _kernel(operands, block_q, block_k)
    want, want_grads = jax.value_and_grad(
        lambda a, b, c: ia.index_alignment_xla(
            a, b, c, q.astype(jnp.float32), k.astype(jnp.float32), *rest,
            SCALE, block_q=PACK_BLOCK), (0, 1, 2))(
        q_idx.astype(jnp.float32), k_idx.astype(jnp.float32), w)
    assert float(got[0]) == pytest.approx(float(want), rel=2e-5)
    # bf16: dI / dz is rounded to 8 bits before its two products
    rel = 1e-5 if q.dtype == jnp.float32 else 1e-2
    for a, b, like in zip(got[1:], want_grads, (q_idx, k_idx, w)):
        assert a.dtype == jnp.float32 and a.shape == like.shape
        np.testing.assert_allclose(a, b, atol=rel * float(jnp.abs(b).max()))
    return got


# main heads / key-value heads / indexer heads: abreast, group
HEADS = {"8 and 8 abreast, group 8": (8, 1, 8),
         "8 and 4 abreast, group 4": (8, 2, 4),
         "4 and 4 abreast, group 2": (4, 2, 4),
         "2 and 2 abreast, group 3": (6, 2, 2),
         "1 and 1 abreast, group 3": (3, 1, 1),
         "2 and 2 abreast, group 1": (2, 2, 2)}


def test_the_head_counts_cover_every_width():
    widths = {(ia._abreast(h, ia._ALIGN_ABREAST),
               ia._abreast(n, ia._ALIGN_ABREAST), h // kv)
              for h, kv, n in HEADS.values()}
    assert widths == {(8, 8, 8), (8, 4, 4), (4, 4, 2), (2, 2, 3), (1, 1, 3),
                      (2, 2, 1)}
    # the forward's call is what it was: four at most
    assert [ia._abreast(h) for h in (32, 16, 8, 4, 6, 3)] == [4, 4, 4, 4, 2, 1]


# every pair of tile shape, sequence length and dtype once
TILES = {"taller than wide, one tile, float32": (128, 64, 1, jnp.float32),
         "wider than tall, one tile, bf16": (64, 128, 1, jnp.bfloat16),
         "taller than wide, four tiles, bf16": (128, 64, 4, jnp.bfloat16),
         "wider than tall, four tiles, float32": (64, 128, 4, jnp.float32)}


@pytest.mark.parametrize("block_q,block_k,tiles,dtype", list(TILES.values()),
                         ids=list(TILES))
@pytest.mark.parametrize("heads", list(HEADS.values()), ids=list(HEADS))
def test_alignment_equals_the_xla_forms_value_and_gradients(
        heads, block_q, block_k, tiles, dtype):
    seq = tiles * max(block_q, block_k)
    _check(_operands(*heads, seq, dtype), block_q, block_k)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("kept", ["the newest", "the oldest"])
def test_rows_that_meet_no_kept_key_in_some_of_their_tiles(kept, dtype):
    """Eight keys a row over four key blocks of 64: most tiles up to the
    diagonal hold rows with no kept key, and some no kept pair at all.
    There ``pbar`` is 0.0, the term takes nothing and dL / dI is 0.0, so
    the tile adds exact zeros to every sum."""
    seq, topk = 256, 8
    t = jnp.arange(seq)[:, None]
    s = jnp.arange(seq)[None, :]
    keep = (s <= t) & ((s > t - topk) if kept == "the newest" else (s < topk))
    assert int(keep.sum(axis=1).min()) >= 1
    got = _check(_operands(8, 2, 4, seq, dtype, keep=keep), 64, 64)
    assert all(bool(jnp.isfinite(x).all()) for x in got)


@pytest.mark.parametrize("heads", [(8, 1, 8), (8, 2, 4), (2, 2, 2)],
                         ids=["8 and 8", "8 and 4", "2 and 2"])
def test_the_sums_keep_the_head_order_whatever_goes_abreast(heads,
                                                            monkeypatch):
    """Head by head (``_abreast`` patched to 1) the term and its three
    gradients are the unpatched call's bit for bit: every sum over heads
    is taken in head order.  (Head counts that are powers of two: with 6
    or 10 heads the INTERPRETER's two programs, a loop of 3 trips and
    one of 6 under XLA's CPU compiler, differ in a last bit of the
    gradients; on the chip the cell's 32 and 16 heads give the same bits
    at every width: PERF.md section 6, PR 57.)"""
    operands = _operands(*heads, 256, jnp.bfloat16)
    abreast = _kernel(operands, 64, 128, jitted=False)
    monkeypatch.setattr(ia, "_abreast", lambda heads, most=4: 1)
    one_by_one = _kernel(operands, 64, 128, jitted=False)
    for a, b in zip(abreast, one_by_one):
        np.testing.assert_array_equal(a, b)


def _cell_operands(shape):
    seq = CELL["q"][2]
    return (shape(CELL["q_idx"]), shape(CELL["k_idx"]),
            shape(CELL["q_idx"][:3], jnp.float32), shape(CELL["q"]),
            shape(CELL["k"]), shape(CELL["q"][:3], jnp.float32),
            shape((1, seq // ia.PACK, seq), jnp.int32),
            shape((1, seq), jnp.float32))


@pytest.fixture(scope="module")
def cell_call():
    """The pass at the cell's shape and the file's own tiles, traced
    (nothing compiled, nothing run): its ``pallas_call`` equations."""
    traced = jax.make_jaxpr(functools.partial(
        ia.index_alignment_pallas.__wrapped__, sm_scale=SCALE))(
        *_cell_operands(lambda dims, dtype=jnp.bfloat16:
                        jax.ShapeDtypeStruct(dims, dtype)))
    return [ctx.eqn for ctx in iter_eqns(traced.jaxpr)
            if ctx.eqn.primitive.name == "pallas_call"]


def test_the_pass_is_one_call_a_step_a_tile(cell_call):
    (call,) = cell_call
    assert call.params["name"] == "dsa_align"
    assert call.params["grid_mapping"].grid == (1, 64, 32)
    assert (ia.BLOCK_Q, ia.BLOCK_K) == (256, 512)


def test_vmem_at_the_cells_shape_is_under_the_limit(cell_call):
    """``_align_vmem`` is what the call declares (every block twice, its
    last axis whole lane tiles, and the scratch, ``pbar`` and the ReLU
    tiles among it) and its stated temporaries: four float32 tiles and
    one and a half for each of the indexer's 8 heads abreast (more than
    the main heads' 8), and that is under the file's limit."""
    (call,) = cell_call
    grid = call.params["grid_mapping"]

    def nbytes(shape, dtype):
        *lead, lanes = shape
        return (math.prod(lead) * -(-lanes // 128) * 128
                * jnp.dtype(dtype).itemsize)

    declared = sum(
        2 * nbytes([getattr(b, "block_size", b) for b in m.block_shape],
                   m.array_aval.dtype) for m in grid.block_mappings)
    declared += sum(nbytes(s.shape, s.dtype) for s in grid.scratch_avals)
    reckoned = ia._align_vmem(16384, 32, 4, 128, 16, 64, ia.BLOCK_Q,
                              ia.BLOCK_K)
    tile = 4 * ia.BLOCK_Q * ia.BLOCK_K
    assert ia._ALIGN_ABREAST == 8
    assert reckoned == declared + (4 + 12) * tile
    assert reckoned < ia._VMEM_LIMIT
    assert call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes \
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


def test_the_pass_compiles_for_v5e_at_the_cells_shape(one_chip):
    """The chip's compiler takes the kernel with its blocks, the 8 MiB of
    ReLU tiles and eight heads abreast under ``_VMEM_LIMIT``.  A compile
    is not a run."""
    text = jax.jit(functools.partial(
        ia.index_alignment_pallas, sm_scale=0.1)).lower(*_cell_operands(
            lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
                dims, dtype, sharding=one_chip))).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "dsa_align" in text
