"""Pallas-kernel numerics parity on REAL TPU at bf16 tolerances.

Reference analog: tests/unit/test_cuda_forward.py:333 and
test_cuda_backward.py:335 — fused-kernel outputs and gradients vs a
reference implementation at half-precision tolerances on real hardware.
The CPU sim mesh can only exercise these kernels in interpret mode, which
does not cover lane masking, MXU accumulation order, or real bf16
rounding; this lane does.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.flash_attention import (flash_attention,
                                               flash_attention_pallas,
                                               mha_reference)
from deepspeed_tpu.runtime.quantize import quantize_dequantize

# bf16 has ~3 decimal digits; sums over S=1024 add noise
BF16_RTOL = 2e-2
BF16_ATOL = 2e-2


def _qkv(b, h, s, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d), dtype) for k in ks)


@pytest.mark.parametrize("s,causal", [(256, False), (1024, True),
                                      (1536, True)])
def test_flash_forward_parity_bf16(s, causal):
    q, k, v = _qkv(2, 4, s, 64, jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=BF16_RTOL, atol=BF16_ATOL)


def test_flash_backward_parity_bf16():
    q, k, v = _qkv(2, 4, 512, 64, jnp.bfloat16, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       impl="pallas").astype(jnp.float32)
                       ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v,
                                     causal=True).astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2)


def test_flash_pallas_non_lane_multiple_lengths():
    """Lengths the block-fit logic ACCEPTS onto the Pallas path without
    being 128-multiples (the advisor-r3 gap): a q length of 328 tiles as
    one 41-sublane block (8-aligned, not lane-aligned) against k=1024,
    and S=1152 self-attention tiles as 384x384 (non-power-of-2 blocks).
    Interpret mode cannot validate these tilings under Mosaic."""
    q, _, _ = _qkv(1, 2, 328, 64, jnp.bfloat16, seed=8)
    _, k, v = _qkv(1, 2, 1024, 64, jnp.bfloat16, seed=9)
    out = flash_attention(q, k, v, causal=False, impl="pallas")
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=BF16_RTOL, atol=BF16_ATOL)

    q, k, v = _qkv(1, 2, 1152, 64, jnp.bfloat16, seed=10)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       impl="pallas").astype(jnp.float32)
                       ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v,
                                     causal=True).astype(jnp.float32) ** 2)

    out = flash_attention(q, k, v, causal=True, impl="pallas")
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=BF16_RTOL, atol=BF16_ATOL)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2)


def test_flash_dispatcher_unaligned_length_falls_back():
    """Non-lane-aligned lengths must take the XLA path (the advisor-r2
    alignment gate) and still be numerically right on TPU."""
    q, k, v = _qkv(1, 2, 1000, 64, jnp.bfloat16, seed=2)
    out = flash_attention(q, k, v, causal=True)  # auto -> XLA fallback
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=BF16_RTOL, atol=BF16_ATOL)


def test_group_quantizer_roundtrip_tpu():
    x = jax.random.normal(jax.random.PRNGKey(4), (4096, 256), jnp.float32)
    dq = quantize_dequantize(x, bits=8, groups=64)
    err = float(jnp.abs(dq - x).max() / jnp.abs(x).max())
    assert err < 0.02, err


def test_engine_smoke_one_step_tpu():
    """One real engine train step on the chip — the package boundary works
    end-to-end on TPU, not just through the CPU sim mesh."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    ds.reset_mesh_context()
    cfg = GPT2Config(vocab_size=512, n_positions=128, hidden_size=128,
                     num_layers=2, num_heads=2, bf16=True)
    model = GPT2Model(cfg)
    engine, _, _, _ = ds.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2},
                "steps_per_print": 10 ** 9})
    ids = np.random.RandomState(0).randint(0, 512, (2, 128)).astype(np.int32)
    loss = engine.forward(ids)
    engine.backward(loss)
    engine.step()
    assert np.isfinite(float(loss))
    ds.reset_mesh_context()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bsh_layout_parity_bf16_tpu(causal):
    """The transpose-free [B, S, heads, d] layout — now the training
    layer's default attention path — compiled by REAL Mosaic (interpret
    mode cannot validate the (1, rows, 1, d) block tiling)."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_bsh

    q, k, v = _qkv(2, 4, 1024, 64, jnp.bfloat16, seed=5)

    def to_bsh(t):
        return t.transpose(0, 2, 1, 3)

    out = flash_attention_bsh(to_bsh(q), to_bsh(k), to_bsh(v), causal=causal,
                              impl="pallas")
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3), np.float32),
        np.asarray(ref, np.float32), rtol=BF16_RTOL, atol=BF16_ATOL)

    def loss_bsh(q_, k_, v_):
        o = flash_attention_bsh(to_bsh(q_), to_bsh(k_), to_bsh(v_),
                                causal=causal, impl="pallas")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(mha_reference(q_, k_, v_,
                                     causal=causal).astype(jnp.float32) ** 2)

    gb = jax.grad(loss_bsh, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_block_sparse_flash_parity_bf16_tpu(causal):
    """Compiled-Mosaic parity of the block-sparse flash kernel (fwd+bwd)
    vs the dense-masked XLA reference at a lane-aligned block (128)."""
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
    from deepspeed_tpu.ops.sparse_attention.block_sparse_flash import (
        block_sparse_flash_attention, layout_gather)

    h, block, s, d = 4, 128, 1024, 64
    cfg = FixedSparsityConfig(num_heads=h, block=block, num_local_blocks=2,
                              num_global_blocks=1)
    layout = cfg.make_layout(s)
    fidx, fvalid = layout_gather(layout)
    tidx, tvalid = layout_gather(layout, transpose=True)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (2, h, s, d), jnp.bfloat16) for kk in ks)

    mask = np.kron(layout, np.ones((block, block)))
    bias = jnp.asarray(np.where(mask > 0, 0.0, -1e30)
                       .astype(np.float32))[None]

    def loss_sparse(q, k, v):
        o = block_sparse_flash_attention(q, k, v, fidx, fvalid, tidx, tvalid,
                                         block, causal=causal)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def loss_ref(q, k, v):
        # fp32 reference: at degenerate causal rows (q-position 0 of a
        # block attending one key) the true dq is EXACTLY 0 via
        # dp - delta cancellation; a bf16 reference on the MXU leaves
        # ~0.1-magnitude cancellation noise there while the kernel's
        # in-kernel fp32 math gives the exact 0 (measured round 4 —
        # 39/524288 "mismatches" were the reference's noise, not kernel
        # error; CPU interpret hid it by emulating bf16 in fp32)
        o = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=causal, bias=bias)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    (_, out), gs = jax.jit(jax.value_and_grad(
        loss_sparse, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, ref), gr = jax.jit(jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_flash_inkernel_dropout_tpu():
    """In-kernel probability dropout on the compiled Mosaic path:
    determinism per seed, drop-rate statistics via a ones-valued v, exact
    rate-0 equality, and a directional finite-difference check of the
    custom VJP (valid because a fixed seed makes the function
    deterministic).  The draw packs four mask bytes per random word."""
    from deepspeed_tpu.ops.flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    shape = (2, 4, 1024, 64)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks[:3])
    ones_v = jnp.ones_like(v)
    rate = 0.2

    def attn(q_, k_, v_, seed):
        return flash_attention(q_, k_, v_, causal=True, impl="pallas",
                               dropout_rate=rate, dropout_seed=seed)

    o1 = jax.jit(attn)(q, k, ones_v, 11)
    o2 = jax.jit(attn)(q, k, ones_v, 11)
    o3 = jax.jit(attn)(q, k, ones_v, 12)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert float(jnp.max(jnp.abs(o1 - o3))) > 0.0
    # each out row = sum of dropped-normalized P against ones: mean 1
    assert abs(float(jnp.mean(o1)) - 1.0) < 0.05

    o0 = flash_attention(q, k, v, causal=True, impl="pallas",
                         dropout_rate=0.0)
    onodrop = flash_attention(q, k, v, causal=True, impl="pallas")
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(onodrop))

    # directional finite differences through the full custom VJP
    def loss(q_, k_, v_):
        return jnp.sum(attn(q_, k_, v_, 11).astype(jnp.float32) ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    rng = np.random.RandomState(0)
    # eps must be large enough that the fp32 loss difference (magnitude
    # ~1e4, so ~1e-1 evaluation noise after cancellation) doesn't dominate
    # the quotient: at 1e-2 even an exact-gradient XLA reference fails its
    # own finite-difference check here.
    eps = 1e-1
    for i, (x, g) in enumerate(zip((q, k, v), grads)):
        u = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        args_p = [q, k, v]; args_m = [q, k, v]
        args_p[i] = x + eps * u
        args_m[i] = x - eps * u
        fd = (float(loss(*args_p)) - float(loss(*args_m))) / (2 * eps)
        an = float(jnp.sum(g * u))
        assert abs(fd - an) / (abs(fd) + abs(an) + 1e-6) < 5e-2, \
            (i, fd, an)


def test_fused_dequant_matmul_parity_tpu():
    """Compiled-Mosaic parity of the fused int8 dequant-matmul at the
    decode shapes (M=8 GEMV-ish) and a prefill shape."""
    from deepspeed_tpu.ops.quant import (QuantizedWeight,
                                         fused_dequant_matmul, dequant)
    rng = np.random.RandomState(2)
    for (m, k, n, groups) in [(8, 768, 2304, 8), (256, 768, 3072, 8)]:
        x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32),
                        jnp.bfloat16)
        qw = jnp.asarray(rng.randint(-127, 128, (k, n)).astype(np.int8))
        scale = jnp.asarray(
            np.abs(rng.standard_normal((groups, 1))).astype(np.float32))
        w = QuantizedWeight(qw, scale)
        out = jax.jit(lambda a: fused_dequant_matmul(a, w))(x)
        ref = x.astype(jnp.float32) @ dequant(w, jnp.float32)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2.0)


@pytest.mark.parametrize("block_q", [64, 8])
@pytest.mark.parametrize("rate", [0.0, 0.1])
def test_flash_forward_below_a_lane_tile_tpu(block_q, rate):
    """The one forward body (q rows along the lanes) compiled by Mosaic
    at q blocks under a lane tile, half of one and a single sublane
    tile: out and the log-sum-exp against the reference without dropout;
    with it, the mask read back position for position keeps the share
    the threshold names and nothing above the diagonal."""
    from tests.unit.test_flash_causal_bound import (_kernel_keep_mask,
                                                    _reference_lse)
    seq, heads = 512, 2
    call = dict(causal=True, block_q=block_q, block_k=512)
    if not rate:
        q, k, v = _qkv(1, heads, seq, 64, jnp.bfloat16, seed=block_q)
        out, lse = flash_attention_pallas(q, k, v, return_lse=True, **call)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(mha_reference(q, k, v, causal=True), np.float32),
            rtol=BF16_RTOL, atol=BF16_ATOL)
        np.testing.assert_allclose(np.asarray(lse),
                                   np.asarray(_reference_lse(q, k)),
                                   rtol=1e-4, atol=1e-4)
        return
    under = np.tril(np.ones((seq, seq), bool))
    keep = _kernel_keep_mask(heads, seq, block_q, 512, rate,
                             interpret=False, chunk=128, dtype=jnp.bfloat16)
    assert abs(keep[:, under].mean() - 230 / 256) < 0.005
    assert not keep[:, ~under].any()


@pytest.mark.parametrize("seq,block_q,block_k", [
    (1024, 512, 1024),     # the cells' call: one inner step, the sub-tiles
    (2048, 512, 1024),     # two inner steps
    (1024, 256, 256),      # words of half a lane tile, transposed
])
def test_flash_forward_and_backward_see_one_mask_tpu(seq, block_q,
                                                     block_k):
    """The chip's PRNG, position for position: the forward kernel (q
    rows along the lanes: it transposes the drawn words) and the
    backward kernel (rows on the sublanes, as drawn; through its dv and
    through its dq) of one call hold
    the identical keep mask under the diagonal, each read back through
    one-hot operands (tests/unit/test_flash_causal_bound.py), and keep
    the share the threshold names."""
    from tests.unit.test_flash_causal_bound import (_backward_keep_masks,
                                                    _kernel_keep_mask)
    heads, rate = 2, 0.1
    read = dict(interpret=False, chunk=128, dtype=jnp.bfloat16)
    under = np.tril(np.ones((seq, seq), bool))
    forward = _kernel_keep_mask(heads, seq, block_q, block_k, rate, **read)
    in_dkdv, in_dq = _backward_keep_masks(heads, seq, block_q, block_k,
                                          rate, **read)
    assert abs(forward[:, under].mean() - 230 / 256) < 0.005
    for name, mask in (("flash_bwd_dkdv's dv", in_dkdv),
                       ("flash_bwd_dkdv's dq", in_dq)):
        np.testing.assert_array_equal(mask[:, under], forward[:, under],
                                      err_msg=name)
    assert not forward[:, ~under].any()


@pytest.mark.parametrize("seq,heads,kv_heads,window,causal", [
    (1024, 4, 4, None, False),    # one step, no mask
    (2048, 4, 2, None, True),     # two steps, grouped heads
    (2048, 4, 2, 512, True),      # the band
])
def test_flash_forward_lse_tpu(seq, heads, kv_heads, window, causal):
    """The log-sum-exp the forward stores (parallel/sequence.py combines
    shards by it; the backward kernel exponentiates against it) is
    m + log l to float32 rounding, through the [1, block_q] statistics
    and their one transpose."""
    from tests.unit.test_flash_causal_bound import _reference_lse
    ks = jax.random.split(jax.random.PRNGKey(seq), 3)
    q = jax.random.normal(ks[0], (1, heads, seq, 64), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, kv_heads, seq, 64), jnp.bfloat16)
            for kk in ks[1:])
    call = dict(window=window, block_q=512, block_k=512) if window else {}
    _, lse = flash_attention_pallas(q, k, v, causal=causal, return_lse=True,
                                    **call)
    want = _reference_lse(q, k, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("heads", [2, 20])
def test_latent_layout_kernels_equal_the_xla_lines_tpu(heads, monkeypatch):
    """ops/latent_layout.py on the chip, two blocks of positions: q, k
    and v bit for bit what ``by_head`` + ``apply_rotary`` + the joins
    give (the TPU contracts no multiply-add), d(q product) too, the one
    key's cotangent to the plain path's two roundings, the way back and
    its VJP copies."""
    from tests.unit import test_latent_layout as unit
    monkeypatch.setattr(unit, "SEQ", 1024)
    args = unit._inputs(heads, jnp.bfloat16, seed=heads)
    cts = unit._cotangents(heads, jnp.bfloat16)
    ours = jax.jit(unit._kernels, static_argnums=4)(*args, heads)
    want = jax.jit(unit._plain, static_argnums=4)(*args, heads)
    for a, b in zip(ours, want):
        unit._same(a, b)
    ours_b = unit._pulled(unit._kernels, args, cts, heads)
    want_b = unit._pulled(unit._plain, args, cts, heads)
    unit._same(ours_b[0], want_b[0])
    size = float(jnp.max(jnp.abs(want_b[3])))
    np.testing.assert_allclose(
        np.asarray(ours_b[3], np.float32), np.asarray(want_b[3], np.float32),
        rtol=2 ** -6, atol=size * 2 ** -7)
    from deepspeed_tpu.ops.latent_layout import heads_to_flat
    flat, pull = jax.vjp(heads_to_flat, cts[2])
    unit._same(flat, cts[2].transpose(0, 2, 1, 3).reshape(
        unit.BATCH, 1024, heads * unit.VDIM))
    unit._same(pull(flat)[0], cts[2])


@pytest.mark.parametrize("cell", ["granite", "nemotron", "phi4"])
def test_causal_conv_kernels_equal_the_xla_form_tpu(cell):
    """ops/causal_conv.py on the chip at a cell's channel widths, two
    blocks of 4,096 positions: x, B and C bit for bit the XLA form's (the
    taps in its order, one rounding; the TPU contracts no multiply-add);
    d xBC, rounded once where XLA's derivative rounds each tap's share,
    to a bf16 value of it; the taps' and the bias' gradients, float32
    sums in another order, to 1e-5."""
    from tests.unit import test_causal_conv as unit
    _, _, widths = unit.CELLS[cell]
    args = unit.operands(1, 8192, widths, seed=3)
    got = jax.jit(lambda *a: unit.through("kernel", a, widths))(*args)
    want = jax.jit(lambda *a: unit.through("xla", a, widths))(*args)
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    dx, dx_xla = (np.asarray(t[1], np.float32) for t in (got, want))
    np.testing.assert_allclose(dx, dx_xla, rtol=2 ** -6,
                               atol=float(np.abs(dx_xla).max()) * 2 ** -8)
    for g, w in zip(got[2:], want[2:]):
        assert unit.rel(g, w) < 1e-5
