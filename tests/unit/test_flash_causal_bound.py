"""The causal bound inside a flash tile (ops/flash_attention.py
_walk_tile): a causal call bounds each [block_q, block_k] tile's work
by the diagonal in sub-tiles of _CAUSAL_SUB_K key columns and, in the
backward kernel where the blocks are aligned, in groups of
_CAUSAL_SUB_Q rows: what lies wholly above the diagonal is not computed,
and of the rest only what it can cross is masked, from the same dropout
bits in both kernels.  Everything here runs
through the Pallas interpreter on the CPU;
tests/unit/test_flash_setup_guard.py compiles the same kernels for the
v5e.

With dropout on, the reference needs the kernel's own keep mask (the
dropout stream is seeded by sub-tile coordinates): it is read back from
the forward kernel position for position, and the forward and the three
gradients are then held to a reference that applies exactly that mask —
so a backward kernel that drew other bits for any position fails."""

import base64
import hashlib
import importlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "flash_whole_tile_modules.json")
SEED = 11


def _inputs(heads, seq, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (1, heads, seq, d), jnp.float32)
                 for k in ks)


def _kernel_keep_mask(heads, seq, block_q, block_k, rate, interpret=True,
                      chunk=256, dtype=jnp.float32):
    """[heads, seq, seq] bool: the keep mask the forward kernel draws
    (meaningful under the diagonal).  With q = k = 0 a causal row i puts
    1/(i+1) on each of its columns 0..i, so against one-hot values a kept
    position reads back positive and a dropped one reads 0.  The mask is
    a function of the seed and the coordinates alone, not of q, k, v or
    the head size."""
    zeros = jnp.zeros((1, heads, seq, chunk), dtype)
    keep = np.zeros((heads, seq, seq), bool)
    for c in range(seq // chunk):
        v = np.zeros((1, heads, seq, chunk), np.float32)
        v[:, :, c * chunk + np.arange(chunk), np.arange(chunk)] = 1.0
        out = fa.flash_attention_pallas(
            zeros, zeros, jnp.asarray(v, dtype), causal=True,
            block_q=block_q, block_k=block_k, interpret=interpret,
            dropout_rate=rate, dropout_seed=SEED)
        keep[:, :, c * chunk:(c + 1) * chunk] = np.asarray(
            out[0], np.float32) > 0
    return keep


def _backward_keep_masks(heads, seq, block_q, block_k, rate, interpret=True,
                         chunk=256, dtype=jnp.float32):
    """([heads, seq, seq] bool) x 2: the keep mask the backward kernel
    regenerates, as its dv sees it and as its dq does, read back like
    the forward's.  With q = 0 the
    scores are 0 whatever k is, and with lse = log(i + 1) row i's
    probabilities are 1/(i+1) under the diagonal.  dv = P_dropped^T dO:
    against one-hot dO rows, dv[c, x] is positive where (r0 + x, c) was
    kept.  dq = dS k with dS = P (keep / (1 - rate) dP - delta): with
    out = 0 (delta 0), dO . v^T = 1 everywhere and one-hot k rows,
    dq[r, x] is positive where (r, c0 + x) was kept."""
    shape = (1, heads, seq, chunk)
    zeros = jnp.zeros(shape, dtype)
    lse = jnp.broadcast_to(jnp.log(jnp.arange(1, seq + 1, dtype=jnp.float32)),
                           shape[:3])
    first = np.zeros(shape, np.float32)
    first[..., 0] = 1.0
    first = jnp.asarray(first, dtype)
    kw = dict(causal=True, block_q=block_q, block_k=block_k,
              interpret=interpret, dropout_rate=rate, dropout_seed=SEED)
    in_dkdv = np.zeros((heads, seq, seq), bool)
    in_dq = np.zeros((heads, seq, seq), bool)
    for c in range(seq // chunk):
        hot = np.zeros(shape, np.float32)
        hot[:, :, c * chunk + np.arange(chunk), np.arange(chunk)] = 1.0
        hot = jnp.asarray(hot, dtype)
        _, _, dv = fa.flash_attention_bwd_pallas(
            zeros, zeros, zeros, zeros, lse, hot, **kw)
        in_dkdv[:, c * chunk:(c + 1) * chunk, :] = np.asarray(
            dv[0], np.float32).transpose(0, 2, 1) > 0
        dq, _, _ = fa.flash_attention_bwd_pallas(
            zeros, hot, first, zeros, lse, first, **kw)
        in_dq[:, :, c * chunk:(c + 1) * chunk] = np.asarray(
            dq[0], np.float32) > 0
    return in_dkdv, in_dq


def _reference(keep, inv):
    """Causal attention with the given keep mask applied to the
    normalized probabilities (None: no dropout), in plain XLA."""
    def ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        n = s.shape[-1]
        s = jnp.where(np.tril(np.ones((n, n), bool)), s,
                      fa.DEFAULT_MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1)
        if keep is not None:
            p = jnp.where(jnp.asarray(keep)[None], p * inv, 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return ref


# (seq, block_q, block_k, dropout, heads, bound engages)
CASES = [
    # the shipped blocks: one 512 x 512 tile at 512 (walked whole), the
    # bound alone at 1024, whole-block skip and the bound together at 2048
    (512, 512, 1024, 0.0, 2, False),
    (512, 512, 1024, 0.1, 2, False),
    (1024, 512, 1024, 0.0, 2, True),
    (1024, 512, 1024, 0.1, 2, True),
    (2048, 512, 1024, 0.0, 2, True),
    (2048, 512, 1024, 0.1, 2, True),
    # the cells' head counts (gpt2-large 20, gpt2-xl 25)
    (1024, 512, 1024, 0.1, 20, True),
    (1024, 512, 1024, 0.0, 25, True),
    # small explicit blocks: q blocks the sub-tile is not aligned with
    (1024, 128, 1024, 0.1, 2, True),
    (2048, 256, 1024, 0.0, 2, True),
    # ... and blocks the sub-tile does not divide: walked whole, as before
    (1024, 128, 128, 0.1, 2, False),
    (1024, 256, 256, 0.0, 2, False),
    # a q block taller than the sub-tile: rows 0..511 of sub-tile 1 have
    # every column masked, and must add zeros, not NaN
    (2048, 1024, 1024, 0.0, 2, True),
    (2048, 1024, 1024, 0.1, 2, True),
]


@pytest.mark.parametrize("seq,block_q,block_k,rate,heads,engages", CASES)
def test_causal_forward_and_gradients_match_reference(
        seq, block_q, block_k, rate, heads, engages):
    _, bq, bk = fa._resolve_blocks(seq, seq, block_q, block_k)
    assert bool(fa._causal_sub_tile(bq, bk, True)) == engages
    d = 16 if heads > 2 else 32
    q, k, v, do = _inputs(heads, seq, d)
    keep, inv = None, 1.0
    if rate:
        keep = _kernel_keep_mask(heads, seq, block_q, block_k, rate)
        inv = fa._keep_scale(rate)
        under = np.tril(np.ones((seq, seq), bool))
        share = keep[:, under].mean()
        assert abs(share - 1.0 / inv) < 0.01, share
    ref = _reference(keep, inv)

    out, lse = fa.flash_attention_pallas(
        q, k, v, causal=True, block_q=block_q, block_k=block_k,
        interpret=True, return_lse=True, dropout_rate=rate,
        dropout_seed=SEED)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)

    grads = fa.flash_attention_bwd_pallas(
        q, k, v, out, lse, do, causal=True, block_q=block_q,
        block_k=block_k, interpret=True, dropout_rate=rate,
        dropout_seed=SEED)
    want = jax.grad(lambda q_, k_, v_: jnp.vdot(ref(q_, k_, v_), do),
                    argnums=(0, 1, 2))(q, k, v)
    for name, got, exp in zip(("dq", "dk", "dv"), grads, want):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# --------------------------------------------------------------------------- #
# the forward kernel with q rows along the lanes
# --------------------------------------------------------------------------- #
def _forward_kernel(q, kv=None, **call):
    """The name of the one kernel a forward call traces, on q and on
    keys and values shaped like `kv` (None: like q)."""
    from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
    kv = q if kv is None else kv
    jaxpr = jax.make_jaxpr(lambda x, y: fa.flash_attention_pallas(
        x, y, y, **call))(q, kv)
    name, = [ctx.eqn.params["name"] for ctx in iter_eqns(jaxpr.jaxpr)
             if ctx.eqn.primitive.name == "pallas_call"]
    return name


def _stream_keep_mask(heads, seq, block_q, block_k, rate):
    """[heads, seq, seq] bool: the keep mask as the dropout stream
    DEFINES it (_dropout_keep's docstring; the interpreter's stand-in for
    the chip's PRNG), computed here with no kernel: tile (qi, unit kj)
    of head h draws [block_q, unit / 4] words from (seed + h, qi * units
    + kj + seed * 2654435761), and byte j of word w decides column
    j * unit / 4 + w.  Whatever a kernel's orientation, it must apply
    exactly this."""
    _, bq, bk = fa._resolve_blocks(seq, seq, block_q, block_k)
    unit = fa._causal_sub_tile(bq, bk, True) or bk
    units = seq // unit
    t8 = fa._quantized_threshold(rate)
    keep = np.zeros((heads, seq, seq), bool)
    seed = jnp.int32(SEED)
    for h in range(heads):
        for qi in range(seq // bq):
            for kj in range(units):
                words = np.asarray(fa._interpret_random_bits(
                    seed + h, qi * units + kj + seed * np.int32(-1640531527),
                    (bq, unit // 4)))
                planes = [(words >> np.uint32(8 * j)) & np.uint32(0xFF)
                          for j in range(4)]
                keep[h, qi * bq:(qi + 1) * bq, kj * unit:(kj + 1) * unit] = (
                    np.concatenate(planes, axis=1) < t8)
    return keep


def _reference_lse(q, k, causal=True, window=None):
    """[B, H, S] log-sum-exp of the scaled scores a query sees: all keys,
    or `causal` those up to its own, the last `window` of them; k may
    have fewer heads than q."""
    k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(q.shape[-1])
    row = jnp.arange(s.shape[-2])[:, None]
    col = jnp.arange(s.shape[-1])[None, :]
    if causal:
        seen = (col <= row) & (col > row - (window or s.shape[-1]))
        s = jnp.where(seen, s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1)


# one inner step (512 and 1024: a key block spans the row) and several
# (2048), at q blocks of one to eight lane tiles; and below a lane tile,
# half of one (64 rows) and a single sublane tile (8)
ON_LANES = [(seq, block_q, rate) for seq in (512, 1024, 2048)
            for block_q in (128, 256, 512, 1024) for rate in (0.0, 0.1)]
ON_LANES += [(512, block_q, rate) for block_q in (64, 8)
             for rate in (0.0, 0.1)]


@pytest.mark.parametrize("seq,block_q,rate", ON_LANES)
def test_forward_with_rows_on_the_lanes_position_for_position(
        seq, block_q, rate):
    """out, the log-sum-exp and the applied keep mask of the forward
    kernel that carries q rows along the lanes, against the reference
    under the mask the dropout stream defines: the transposed kernel
    drops exactly the positions the stream names, so the backward kernel,
    which regenerates the stream untransposed, sees the same mask."""
    heads, d, block_k = 1, 32, 1024
    q, k, v, _ = _inputs(heads, seq, d, seed=seq + block_q)
    kw = dict(causal=True, block_q=block_q, block_k=block_k,
              dropout_rate=rate, dropout_seed=SEED)
    assert _forward_kernel(q, **kw) == "flash_fwd"
    keep, inv = None, 1.0
    if rate:
        keep = _stream_keep_mask(heads, seq, block_q, block_k, rate)
        inv = fa._keep_scale(rate)
        applied = _kernel_keep_mask(heads, seq, block_q, block_k, rate)
        under = np.tril(np.ones((seq, seq), bool))
        np.testing.assert_array_equal(applied[:, under], keep[:, under])
        assert not applied[:, ~under].any()
    out, lse = fa.flash_attention_pallas(q, k, v, interpret=True,
                                         return_lse=True, **kw)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference(keep, inv)(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_reference_lse(q, k)),
                               rtol=2e-5, atol=2e-5)


def test_forward_and_backward_see_one_mask():
    """One call's two kernels, each read back position for position:
    the forward (q rows on the lanes, the words transposed) and the
    backward (rows on the sublanes, as drawn; through dv and through dq)
    hold the identical keep mask under the diagonal, which is the
    stream's."""
    heads, seq, block_q, block_k, rate = 2, 1024, 512, 1024, 0.1
    under = np.tril(np.ones((seq, seq), bool))
    forward = _kernel_keep_mask(heads, seq, block_q, block_k, rate)
    in_dkdv, in_dq = _backward_keep_masks(heads, seq, block_q, block_k, rate)
    stream = _stream_keep_mask(heads, seq, block_q, block_k, rate)
    for name, mask in (("flash_fwd", forward),
                       ("flash_bwd_dkdv's dv", in_dkdv),
                       ("flash_bwd_dkdv's dq", in_dq)):
        np.testing.assert_array_equal(mask[:, under], stream[:, under],
                                      err_msg=name)
        assert not mask[:, ~under].any(), name


@pytest.mark.parametrize("call", [
    dict(block_q=64),                           # half a lane tile of rows
    dict(block_q=8),                            # one sublane tile
    dict(block_q=64, dropout_rate=0.1, dropout_seed=SEED),
], ids=["block_q_64", "block_q_8", "block_q_64_dropout"])
def test_every_forward_call_traces_the_one_body(call):
    """There is one forward body: a q block under a lane tile, with
    dropout or without, traces the kernel the cells run, under the name
    the benchmark's flash_ms searches for."""
    q = jnp.zeros((1, 2, 1024, 32), jnp.float32)
    assert _forward_kernel(q, causal=True, **call) == "flash_fwd"


# --------------------------------------------------------------------------- #
# the counter
# --------------------------------------------------------------------------- #
def _painted(seq, block_q, block_k, backward):
    """(computed, unmasked) [seq, seq] bool, painted position by position
    from causal_pieces; no position is painted twice."""
    computed = np.zeros((seq, seq), bool)
    unmasked = np.zeros((seq, seq), bool)
    for row0, row1, col0, col1, mask0 in fa.causal_pieces(
            seq, seq, block_q, block_k, backward=backward):
        assert not computed[row0:row1, col0:col1].any()
        computed[row0:row1, col0:col1] = True
        unmasked[row0:row1, col0:mask0] = True
    return computed, unmasked


# (seq, block_q, block_k, forward's shares, the backward kernel's)
SHARES = [
    # the shipped blocks: one tile on the diagonal at 512, walked whole
    (512, 512, 1024, (1.0, 1.0), (1.0, 1.0)),
    (1024, 512, 1024, (3 / 4, 2 / 4), (10 / 16, 4 / 16)),
    (2048, 512, 1024, (10 / 16, 4 / 16), (36 / 64, 8 / 64)),
    (4096, 512, 1024, (36 / 64, 8 / 64), (136 / 256, 16 / 256)),
    # q blocks the sub-tile is not aligned with: one piece a tile
    (1024, 128, 1024, (3 / 4, 2 / 4), (3 / 4, 2 / 4)),
    (2048, 256, 1024, (5 / 8, 2 / 8), (5 / 8, 2 / 8)),
    # taller q blocks, wider key blocks, blocks walked whole
    (2048, 1024, 1024, (3 / 4, 2 / 4), (36 / 64, 8 / 64)),
    (4096, 512, 2048, (36 / 64, 8 / 64), (136 / 256, 16 / 256)),
    (1536, 512, 1024, (5 / 6, 5 / 6), (5 / 6, 5 / 6)),
    (1024, 128, 128, (36 / 64, 36 / 64), (36 / 64, 36 / 64)),
]


@pytest.mark.parametrize("seq,block_q,block_k,forward,backward", SHARES)
def test_computed_and_masked_shares_against_a_count_over_positions(
        seq, block_q, block_k, forward, backward):
    """In each kernel every position under the diagonal is computed,
    every computed position above it is masked, and the counter's shares
    are the painted areas."""
    got = fa.causal_sub_tile_shares(seq, seq, block_q, block_k, True)
    assert got["flash_fwd"] == pytest.approx(forward)
    assert sorted(got) == ["flash_bwd_dkdv", "flash_fwd"]
    assert got["flash_bwd_dkdv"] == pytest.approx(backward)
    under = np.tril(np.ones((seq, seq), bool))
    for kernel, is_backward in (("flash_fwd", False),
                                ("flash_bwd_dkdv", True)):
        computed, unmasked = _painted(seq, block_q, block_k, is_backward)
        assert computed[under].all()
        assert not unmasked[~under].any()
        assert got[kernel] == pytest.approx(
            (computed.mean(), (computed & ~unmasked).mean()))
    assert set(fa.causal_sub_tile_shares(
        seq, seq, block_q, block_k, False).values()) == {(1.0, 0.0)}


# --------------------------------------------------------------------------- #
# calls the bound does not engage on lower as they did before it existed
# --------------------------------------------------------------------------- #
def _mosaic_modules(lowered_text):
    """The Mosaic module of every tpu_custom_call in a lowering, as text
    without locations (source lines move with every edit)."""
    from jax.extend.mlir import ir
    modules = []
    for m in re.finditer(r'backend_config = "((?:[^"\\]|\\.)*)"',
                         lowered_text):
        config = m.group(1).replace("\\22", '"').replace("\\\\", "\\")
        body = json.loads(config)["custom_call_config"]["body"]
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            modules.append(module.operation.get_asm(enable_debug_info=False))
    return modules


WHOLE_TILE_CALLS = {
    "noncausal_dropout": dict(causal=False, dropout_rate=0.1),
    "noncausal": dict(causal=False, dropout_rate=0.0),
    "causal_256x256": dict(causal=True, dropout_rate=0.1, block_q=256,
                           block_k=256),
    "causal_512x768": dict(causal=True, dropout_rate=0.1, seq=1536),
}


def whole_tile_digests(name):
    """sha256 of the two kernels' Mosaic modules for one call of
    WHOLE_TILE_CALLS at [4, 20, S, 64], lowered for the TPU from here."""
    kw = dict(WHOLE_TILE_CALLS[name])
    shape = (4, 20, kw.pop("seq", 1024), 64)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32)
    seed = jax.ShapeDtypeStruct((), jnp.int32)

    def fwd(q, k, v, s):
        return fa.flash_attention_pallas(q, k, v, return_lse=True,
                                         dropout_seed=s, **kw)

    def bwd(q, k, v, out, lse_, do, s):
        return fa.flash_attention_bwd_pallas(q, k, v, out, lse_, do,
                                             dropout_seed=s, **kw)

    modules = []
    for f, args in ((fwd, (x, x, x, seed)), (bwd, (x, x, x, x, lse, x, seed))):
        text = jax.jit(f).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        modules += _mosaic_modules(text)
    assert len(modules) == 2
    return [hashlib.sha256(m.encode()).hexdigest() for m in modules]


@pytest.mark.parametrize("name", sorted(WHOLE_TILE_CALLS))
def test_whole_tile_calls_lower_as_before_the_bound(name):
    """A non-causal call, and a causal call whose key block the sub-tile
    does not divide, give the Mosaic modules the kernels gave before a
    change that was not meant for them:
    golden/flash_whole_tile_modules.json is recorded by this same
    function, first from the commit before the bound existed (9885b32).

    The forward kernel (entry 0) was rewritten by PR 35, q rows along the
    lanes, for these calls as for every other, and its entries were
    recorded again from that PR's tree; PR 52 made the backward ONE
    kernel for every call (entry 1, recorded from that PR's tree, where
    the pair's two entries were) and left entry 0 as it found it.  What
    they guard now is that a later change to the causal bound leaves a
    whole-tile call alone."""
    with open(GOLDEN) as f:
        recorded = json.load(f)
    assert whole_tile_digests(name) == recorded[name]
