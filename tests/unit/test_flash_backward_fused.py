"""The flash backward as ONE kernel (ops/flash_attention.py _fa_bwd_kernel,
`flash_bwd_dkdv` in a trace; PR 52): each tile is built once and gives dv,
dk and dq; key blocks outermost, q blocks inner, the head's whole dq (or
a span of q rows of it) resident in VMEM as float32.

Here: the gradients against `jax.grad` of `mha_reference` in the Pallas
interpreter, plain / grouped / banded / with dropout at heads of 64, 128
and 256, on several key blocks and several q blocks so that dq is carried
across both; one backward `pallas_call` a flash call in the traced
program; the VMEM the call declares against the reckoning; two and four
spans against one; and the kernel compiled ahead of time for the v5e at
the eight flash cells' own call shapes, where the TPU's compiler can be
described (a compile is not a run).  The topology is described inside a
fixture, never while a module is imported."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from tests.unit.test_flash_causal_bound import (SEED, _kernel_keep_mask,
                                                _reference)

fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")

SEQ = 512
MIB = 1024 * 1024


def _operands(heads, kv_heads, d, seq=SEQ, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(heads * d + seq), 4)
    return tuple(jax.random.normal(key, (1, n, seq, d), dtype)
                 for key, n in zip(ks, (heads, kv_heads, kv_heads, heads)))


def _fused(q, k, v, do, **call):
    """(dq, dk, dv) of the kernels, interpreted: the forward for its
    residuals, then the backward call."""
    call = dict(causal=True, interpret=True, **call)
    out, lse = fa.flash_attention_pallas(q, k, v, return_lse=True, **call)
    return fa.flash_attention_bwd_pallas(q, k, v, out, lse, do, **call)


def _close(got, want, names=("dq", "dk", "dv"), tol=2e-4):
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape, name
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                                   atol=tol * float(jnp.max(jnp.abs(b))),
                                   err_msg=name)


# (kv heads of 4 query heads, window, blocks): 4 x 4 tiles and 4 x 2, so
# dq is carried over key blocks and dk, dv over q blocks
KINDS = {
    "plain": (4, None, dict(block_q=128, block_k=128)),
    "grouped": (2, None, dict(block_q=128, block_k=256)),
    "banded": (2, 200, dict(block_q=128, block_k=128)),
}


@pytest.mark.parametrize("d", [64, 128, 256])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_gradients_match_the_reference(kind, d):
    kv_heads, window, blocks = KINDS[kind]
    q, k, v, do = _operands(4, kv_heads, d)
    got = _fused(q, k, v, do, window=window, **blocks)
    want = jax.grad(lambda *a: jnp.vdot(fa.mha_reference(
        *a, causal=True, window=window), do), argnums=(0, 1, 2))(q, k, v)
    _close(got, want)


@pytest.mark.parametrize("d, seq, blocks", [
    (64, SEQ, dict(block_q=128, block_k=128)),
    (128, SEQ, dict(block_q=64, block_k=256)),
    (256, SEQ, dict(block_q=128, block_k=128)),
    # the shipped blocks: sub-tiles in row groups over two key blocks
    (128, 2048, dict(block_q=512, block_k=1024)),
], ids=["d64", "d128", "d256", "d128_shipped_blocks"])
def test_gradients_under_dropout_match_the_reference(d, seq, blocks):
    """The reference applies the keep mask the forward kernel drew, read
    back position for position: a backward that drew other bits for any
    position, or dropped dq's share of them, fails."""
    rate, heads = 0.1, 2
    q, k, v, do = _operands(heads, heads, d, seq)
    keep = _kernel_keep_mask(heads, seq, rate=rate, **blocks)
    ref = _reference(keep, fa._keep_scale(rate))
    got = _fused(q, k, v, do, dropout_rate=rate, dropout_seed=SEED, **blocks)
    want = jax.grad(lambda *a: jnp.vdot(ref(*a), do),
                    argnums=(0, 1, 2))(q, k, v)
    _close(got, want)


def test_a_non_causal_call_and_the_other_layout():
    q, k, v, do = _operands(2, 2, 64)
    blocks = dict(block_q=128, block_k=256)
    out, lse = fa.flash_attention_pallas(q, k, v, return_lse=True,
                                         interpret=True, **blocks)
    got = fa.flash_attention_bwd_pallas(q, k, v, out, lse, do,
                                        interpret=True, **blocks)
    want = jax.grad(lambda *a: jnp.vdot(fa.mha_reference(*a), do),
                    argnums=(0, 1, 2))(q, k, v)
    _close(got, want)
    t = fa._t_bhsd
    flat = fa.flash_attention_bwd_pallas(
        t(q), t(k), t(v), t(out), lse, t(do), interpret=True, layout="bshd",
        **blocks)
    for a, b in zip(flat, got):
        np.testing.assert_array_equal(np.asarray(t(a)), np.asarray(b))


# --------------------------------------------------------------------------- #
# one backward pallas_call a flash call
# --------------------------------------------------------------------------- #
def _kernels(jaxpr):
    return [ctx.eqn for ctx in iter_eqns(jaxpr)
            if ctx.eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("window, kv_heads, rate", [
    (None, 4, 0.1), (None, 2, 0.0), (256, 2, 0.0)],
    ids=["plain_dropout", "grouped", "banded"])
def test_a_flash_call_traces_one_backward_kernel(monkeypatch, window,
                                                 kv_heads, rate):
    from deepspeed_tpu.ops import dispatch
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")
    dispatch.set_pallas_interpret(True)
    try:
        q, k, v, do = _operands(4, kv_heads, 64, 1024)
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.vdot(
            fa.flash_attention(*a, causal=True, window=window,
                               dropout_rate=rate, dropout_seed=3), do),
            argnums=(0, 1, 2)))(q, k, v).jaxpr
    finally:
        dispatch.set_pallas_interpret(False)
    names = sorted(eqn.params["name"] for eqn in _kernels(jaxpr))
    band = "_band" if window else ""
    assert names == ["flash_bwd_dkdv" + band, "flash_fwd" + band]


# --------------------------------------------------------------------------- #
# VMEM: the reckoning, what the call declares, the spans
# --------------------------------------------------------------------------- #
# [B, H, S, D] of q, key/value heads, window, dropout: the backward call
# of each flash cell (gpt2-large.s1024 and .gas4, gpt2-xl.z3x4,
# gpt2-large.s512, phi4-mini-flash.s8k full and banded, laguna-xs2.s8k
# full and banded, ouro-2.6b.s4k, glm47-flash.s8k)
CELLS = {
    "gpt2-large.s1024": ((4, 20, 1024, 64), 20, None, 0.1),
    "gpt2-xl.z3x4": ((8, 25, 1024, 64), 25, None, 0.1),
    "gpt2-large.s512": ((8, 20, 512, 64), 20, None, 0.1),
    "phi4-mini-flash.s8k": ((1, 20, 8192, 64), 10, None, 0.0),
    "phi4-mini-flash.s8k_band": ((1, 20, 8192, 64), 10, 512, 0.0),
    "laguna-xs2.s8k": ((2, 48, 8192, 128), 8, None, 0.0),
    "laguna-xs2.s8k_band": ((2, 64, 8192, 128), 8, 512, 0.0),
    "ouro-2.6b.s4k": ((1, 16, 4096, 128), 16, None, 0.0),
    "glm47-flash.s8k": ((2, 20, 8192, 256), 20, None, 0.0),
}


def _cell_call(name, sharding=None):
    """(function, operands as shapes) of a cell's backward call."""
    shape, kv_heads, window, rate = CELLS[name]
    kv = (shape[0], kv_heads, *shape[2:])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def bwd(q, k, v, out, lse, do, seed):
        return fa.flash_attention_bwd_pallas(
            q, k, v, out, lse, do, causal=True, window=window,
            dropout_rate=rate, dropout_seed=seed)

    return bwd, (spec(shape), spec(kv), spec(kv), spec(shape),
                 spec(shape[:3], jnp.float32), spec(shape),
                 spec((), jnp.int32))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_call_declares_the_reckoned_vmem(name):
    """One span at every cell's shape, the key-block axis in order (dq is
    carried across it), and the limit the reckoning gives: never under
    the default a call gets that declares nothing."""
    (batch, heads, seq, d), _, window, _ = CELLS[name]
    bwd, operands = _cell_call(name)
    call, = _kernels(jax.make_jaxpr(bwd)(*operands).jaxpr)
    _, block_q, block_k = fa._resolve_blocks(seq, seq, 512, 1024)
    assert fa._bwd_spans(seq, d, block_q, block_k, 2) == 1
    steps = 3 if window else seq // block_q
    assert tuple(call.params["grid_mapping"].grid) == (
        batch, heads, seq // block_k, steps)
    params = call.params["compiler_params"]["mosaic_tpu"]
    assert tuple(str(s).split(".")[-1].lower()
                 for s in params.dimension_semantics) == (
        "parallel", "parallel", "arbitrary", "arbitrary")
    reckoned = fa._bwd_vmem(seq, d, block_q, block_k, 2)
    assert params.vmem_limit_bytes == max(reckoned, fa._VMEM_DEFAULT)
    assert reckoned <= fa._VMEM_LIMIT
    # the head's dq, float32 and its two-byte output twice, is inside it
    assert reckoned > seq * max(d, 128) * 8


def test_the_reckoning_and_where_the_second_span_starts():
    """In blocks of 512 x 1,024 on two-byte operands: what the cells'
    calls ask for, and the first power-of-two lengths that take two
    spans (a head narrower than a lane tile takes one all the same)."""
    def vmem(seq, d):
        return fa._bwd_vmem(seq, d, 512, 1024, 2) / MIB

    assert [vmem(1024, 64), vmem(8192, 64), vmem(8192, 128),
            vmem(4096, 128), vmem(8192, 256)] == [15.5, 22.5, 22.5, 18.5, 34]
    spans = {(seq, d): fa._bwd_spans(seq, d, 512, 1024, 2)
             for d in (64, 128, 256)
             for seq in (32768, 65536, 131072, 262144)}
    assert spans == {
        (32768, 64): 1, (65536, 64): 1, (131072, 64): 2, (262144, 64): 4,
        (32768, 128): 1, (65536, 128): 1, (131072, 128): 2, (262144, 128): 4,
        (32768, 256): 1, (65536, 256): 2, (131072, 256): 4, (262144, 256): 8}
    # float32 operands: dq's output is twice the bytes
    assert fa._bwd_spans(65536, 128, 512, 1024, 4) == 2


SPANNED = {
    "plain_dropout": dict(kv_heads=2, dropout_rate=0.1, dropout_seed=SEED),
    "grouped": dict(kv_heads=1),
    "banded": dict(kv_heads=1, window=200),
    "non_causal": dict(kv_heads=2, causal=False),
}


@pytest.mark.parametrize("kind", sorted(SPANNED))
def test_two_and_four_spans_equal_one(monkeypatch, kind):
    """A sequence whose dq would not fit is walked in spans of q rows:
    forced here by a limit lowered under the shape's need (no argument
    chooses it).  dq leaves span by span from the same sums in the same
    order: equal to the last bit.  dk and dv leave as a partial a span:
    equal to float32 rounding."""
    call = dict(dict(causal=True, dropout_rate=0.0, window=None),
                **SPANNED[kind], block_q=64, block_k=128, interpret=True)
    kv_heads, seed = call.pop("kv_heads"), call.pop("dropout_seed", None)
    q, k, v, do = _operands(2, kv_heads, 64)
    out, lse = fa.flash_attention_pallas(q, k, v, return_lse=True,
                                         dropout_seed=seed, **call)
    run = fa._flash_bwd_call.__wrapped__    # past jit's cache of one span
    call.update(sm_scale=None, layout="bhsd")

    def grid_and_grads():
        args = (q, k, v, out, lse, do, seed)
        kernel, = _kernels(jax.make_jaxpr(
            lambda *a: run(*a, **call))(*args).jaxpr)
        return tuple(kernel.params["grid_mapping"].grid), run(*args, **call)

    nk, nq = SEQ // 128, SEQ // 64
    need = fa._bwd_vmem(SEQ, 64, 64, 128, 4)
    grid, one = grid_and_grads()
    assert grid[2] == nk and fa._bwd_spans(SEQ, 64, 64, 128, 4) == 1
    for spans in (2, 4):
        # room for a span's rows and not for twice as many
        monkeypatch.setattr(fa, "_VMEM_LIMIT", need - (
            SEQ - SEQ // spans) * 128 * 12)
        assert fa._bwd_spans(SEQ, 64, 64, 128, 4) == spans
        grid, parts = grid_and_grads()
        assert grid == (1, 2, spans * nk, nq // spans)
        np.testing.assert_array_equal(np.asarray(parts[0]),
                                      np.asarray(one[0]))
        _close(parts[1:], one[1:], names=("dk", "dv"), tol=1e-5)


# --------------------------------------------------------------------------- #
# ahead of time, for the v5e, at the cells' shapes
# --------------------------------------------------------------------------- #
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


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_fused_backward_compiles_for_v5e_at_the_cells_shape(name,
                                                                one_chip):
    """The chip's compiler takes the call under the limit it declares
    (the reckoning is from above), and the program holds ONE Mosaic call,
    the fused one.  (tests/perf/test_aot_kernels.py's two backward cases
    still ask for two and for `flash_bwd_dq`: the benchmark's file, a
    `benchmark` PR's to mend; these cases carry what they guarded.)"""
    bwd, operands = _cell_call(name, one_chip)
    text = jax.jit(bwd).lower(*operands).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    band = "_band" if CELLS[name][2] else ""
    assert f"flash_bwd_dkdv{band}/pallas_call" in text
    assert "flash_bwd_dq" not in text


def test_two_spans_compile_for_v5e(one_chip):
    """131,072 positions at a head of 128: two spans of 64 MB of dq, the
    key blocks walked twice, under the file's limit."""
    shape = (1, 2, 131072, 128)
    assert fa._bwd_spans(shape[2], shape[3], 512, 1024, 2) == 2

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def bwd(q, k, v, out, lse, do):
        return fa.flash_attention_bwd_pallas(q, k, v, out, lse, do,
                                             causal=True)

    x = spec(shape)
    text = jax.jit(bwd).lower(
        x, x, x, x, spec(shape[:3], jnp.float32), x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
