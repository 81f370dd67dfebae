"""``ops/latent_layout.py`` (the Pallas interpreter on the CPU) against
the lines it replaces in ``models/glm4_moe_lite.py``, which stay the
plain definition: ``by_head`` + ``apply_rotary`` on the 64-wide slices +
the ``concatenate``s with the one rotated key broadcast to the heads on
the way in, the transpose and reshape on the way back.  Heads of 192 +
64 and values of 256 as published, few positions.  Then which shapes
``latent_block`` takes, and the model through the kernels against the
model through XLA."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                Glm4MoeLiteModel)
from deepspeed_tpu.models.laguna import apply_rotary, rotary_table
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops import latent_layout as ll

BATCH, SEQ, RANK = 2, 128, 32
NOPE, ROPE, VDIM = 192, 64, 256


@pytest.fixture(autouse=True)
def interpreter(monkeypatch):
    dispatch.set_pallas_interpret(True)
    # two blocks of positions a row, so that the position index maps work
    monkeypatch.setattr(ll, "BLOCK_ROWS", 64)
    yield
    dispatch.set_pallas_interpret(False)


def _table():
    return rotary_table(SEQ, 1000000.0 ** (
        -2.0 * jnp.arange(ROPE // 2, dtype=jnp.float32) / ROPE))


def _inputs(heads, dtype, seed=0, nope=NOPE, vdim=VDIM):
    """(the query product, the key/value latent, the up-projection as
    the model holds it, the one key a position)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    return (normal(keys[0], BATCH, SEQ, heads * (nope + ROPE)),
            normal(keys[1], BATCH, SEQ, RANK),
            normal(keys[2], RANK, heads * (nope + vdim)) / 4,
            normal(keys[3], BATCH, SEQ, ROPE))


def _plain(q, ckv, kv_b, k_rope, heads, nope=NOPE, vdim=VDIM):
    """What ``_heads`` runs on a shape the kernels do not take."""
    batch, seq = q.shape[:2]
    table = _table()

    def by_head(t, dim):
        return t.reshape(batch, seq, heads, dim).transpose(0, 2, 1, 3)

    q = by_head(q, nope + ROPE)
    kv = by_head(ckv @ kv_b, nope + vdim)
    q_rope = apply_rotary(q[..., nope:], table)
    k_rope = apply_rotary(k_rope[:, None], table)
    return (jnp.concatenate([q[..., :nope], q_rope], axis=-1),
            jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_rope, (batch, heads, seq, ROPE))], axis=-1),
            kv[..., nope:])


def _kernels(q, ckv, kv_b, k_rope, heads, nope=NOPE, vdim=VDIM):
    k_lo, k_hi, v_w = ll.split_kv_columns(kv_b, heads, nope, vdim)
    return ll.latent_heads(q, ckv @ k_lo, ckv @ k_hi, ckv @ v_w, k_rope,
                           *ll.latent_tables(*_table()), heads)


def _ulps(a, b):
    """Distance in representable bf16 values, elementwise."""
    def ordinal(x):
        bits = np.asarray(x).view(np.uint16).astype(np.int32)
        return np.where(bits & 0x8000, -(bits & 0x7FFF), bits)
    return np.abs(ordinal(a) - ordinal(b))


def _close(ours, want, scale):
    """bf16: bit for bit on 99% and nowhere further than one value apart
    but for what float32 loses on products of size ``scale`` (the CPU
    contracts a multiply and an add, tests/unit/test_rotary.py
    ``_one_ulp``); float32: to float32's rounding of such products."""
    assert ours.shape == want.shape and ours.dtype == want.dtype
    apart = np.abs(np.asarray(ours, np.float32) - np.asarray(want,
                                                             np.float32))
    if ours.dtype == jnp.float32:
        assert apart.max() <= 2 ** -21 * scale
        return
    distance = _ulps(ours, want)
    assert np.all((distance <= 1) | (apart <= 2 ** -22 * scale))
    assert (distance == 0).mean() >= 0.99


def _same(ours, want):
    """Bit for bit (signed zeros and infinities too)."""
    assert ours.shape == want.shape and ours.dtype == want.dtype
    bits = np.uint16 if ours.dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(np.asarray(ours).view(bits),
                                  np.asarray(want).view(bits))


DTYPES = [jnp.bfloat16, jnp.float32]
# heads: a block and a half-filled second position block's worth; fewer
# than a block; several blocks
HEADS = [2, 4, 12]


# ---------------------------------------------------------------------- #
# the way in
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
def test_the_way_in_equals_by_head_apply_rotary_and_the_joins(heads, dtype):
    assert ll.latent_block(SEQ, NOPE, ROPE, VDIM, heads) == (
        64, 2 if heads == 2 else 4)
    args = _inputs(heads, dtype)
    ours = jax.jit(_kernels, static_argnums=4)(*args, heads)
    want = jax.jit(_plain, static_argnums=4)(*args, heads)
    scale = float(jnp.max(jnp.abs(args[0])))
    for a, b in zip(ours[:2], want[:2]):
        # the unrotated lanes are the product's own values
        _same(a[..., :NOPE], b[..., :NOPE])
        _close(a[..., NOPE:], b[..., NOPE:], scale)
    _same(ours[2], want[2])                                   # v: a copy
    # every head's rotated key is the one key
    _same(ours[1][:, 1:, :, NOPE:], ours[1][:, :-1, :, NOPE:])


def test_signed_zeros_and_infinities_come_through_the_unrotated_lanes():
    heads = 2
    q, ckv, kv_b, k_rope = _inputs(heads, jnp.bfloat16, seed=5)
    q = q.at[:, ::3, ::5].set(-0.0).at[:, 1::7, 3::11].set(jnp.inf)
    ours = _kernels(q, ckv, kv_b, k_rope, heads)[0]
    want = q.reshape(BATCH, SEQ, heads, NOPE + ROPE).transpose(0, 2, 1, 3)
    _same(ours[..., :NOPE], want[..., :NOPE])


@pytest.mark.parametrize("nope,vdim", [(320, 128), (192, 384)],
                         ids=["three-tile-keys", "three-tile-values"])
def test_heads_of_other_whole_tiles(nope, vdim):
    heads = 2
    assert ll.latent_block(SEQ, nope, ROPE, vdim, heads) == (64, 2)
    args = _inputs(heads, jnp.bfloat16, seed=6, nope=nope, vdim=vdim)
    ours = _kernels(*args, heads, nope=nope, vdim=vdim)
    want = _plain(*args, heads, nope=nope, vdim=vdim)
    for a, b in zip(ours[:2], want[:2]):
        _same(a[..., :nope], b[..., :nope])
        _close(a[..., nope:], b[..., nope:], float(jnp.max(jnp.abs(args[0]))))
    _same(ours[2], want[2])


# ---------------------------------------------------------------------- #
# the way in, backward
# ---------------------------------------------------------------------- #
def _cotangents(heads, dtype, seed=10):
    return tuple(
        jax.random.normal(jax.random.PRNGKey(seed + i),
                          (BATCH, heads, SEQ, dim), jnp.float32).astype(dtype)
        for i, dim in enumerate((NOPE + ROPE, NOPE + ROPE, VDIM)))


def _pulled(fn, args, cotangents, heads):
    return jax.jit(lambda args, ct: jax.vjp(
        lambda *a: fn(*a, heads), *args)[1](ct))(args, cotangents)


@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
def test_the_backward_pass_equals_the_plain_paths_vjp(heads, dtype):
    args, cts = _inputs(heads, dtype, seed=2), _cotangents(heads, dtype)
    ours = _pulled(_kernels, args, cts, heads)
    want = _pulled(_plain, args, cts, heads)
    scale = float(jnp.max(jnp.abs(cts[0])))
    # d(q product): a copy and a rotation back
    dq, dq_want = (t.reshape(BATCH, SEQ, heads, NOPE + ROPE)
                   for t in (ours[0], want[0]))
    _same(dq[..., :NOPE], dq_want[..., :NOPE])
    _close(dq[..., NOPE:], dq_want[..., NOPE:], scale)
    # d(latent) and d(up-projection): products of the copied cotangents,
    # summed in another order (three products where the plain path has
    # one)
    for a, b in zip(ours[1:3], want[1:3]):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2 ** -6 if dtype == jnp.bfloat16 else 1e-5,
            atol=float(jnp.max(jnp.abs(b))) * (
                2 ** -7 if dtype == jnp.bfloat16 else 1e-5))


@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
def test_the_one_keys_cotangent_is_the_heads_sum_rotated_back(heads, dtype):
    """Against the plain path's VJP on the same values in float32: the
    kernel sums the heads in float32 and rounds once, the plain path in
    the cotangents' dtype rounds the sum and then the rotation."""
    args, cts = _inputs(heads, dtype, seed=3), _cotangents(heads, dtype, 20)
    ours = _pulled(_kernels, args, cts, heads)[3]
    exact = _pulled(_plain, tuple(a.astype(jnp.float32) for a in args),
                    tuple(c.astype(jnp.float32) for c in cts), heads)[3]
    assert ours.shape == (BATCH, SEQ, ROPE) and ours.dtype == dtype
    size = float(jnp.max(jnp.abs(exact)))
    np.testing.assert_allclose(
        np.asarray(ours, np.float32), exact,
        rtol=2 ** -8 if dtype == jnp.bfloat16 else 2e-6, atol=size * 2e-6)
    # and no further from the plain path in this dtype than its two
    # roundings
    plain = _pulled(_plain, args, cts, heads)[3]
    np.testing.assert_allclose(
        np.asarray(ours, np.float32), np.asarray(plain, np.float32),
        rtol=2 ** -6 if dtype == jnp.bfloat16 else 1e-5, atol=size * (
            2 ** -7 if dtype == jnp.bfloat16 else 1e-5))


# ---------------------------------------------------------------------- #
# the way back
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
def test_the_way_back_and_its_vjp_are_copies(heads, dtype):
    a = _cotangents(heads, dtype, seed=30)[2]
    d_flat = jax.random.normal(jax.random.PRNGKey(31),
                               (BATCH, SEQ, heads * VDIM)).astype(dtype)

    def plain(a):
        return a.transpose(0, 2, 1, 3).reshape(BATCH, SEQ, heads * VDIM)

    for fn in (ll.heads_to_flat, plain):
        out, pull = jax.vjp(fn, a)
        _same(out, plain(a))
        _same(pull(d_flat)[0], d_flat.reshape(
            BATCH, SEQ, heads, VDIM).transpose(0, 2, 1, 3))


# ---------------------------------------------------------------------- #
# the shape decides
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shape,why", [
    ((SEQ, 160, 64, 256, 4), "the rotated slice crosses a tile"),
    ((SEQ, 192, 32, 256, 4), "a head that is not whole tiles"),
    ((SEQ, 224, 32, 256, 4), "a rotated slice that is no half tile"),
    ((SEQ, 192, 64, 192, 4), "a value head that is not whole tiles"),
    ((SEQ, 64, 64, 128, 4), "no whole tile of unrotated lanes"),
    ((SEQ + 32, 192, 64, 256, 4), "a ragged sequence"),
    ((SEQ, 192, 64, 256, 5), "heads that do not pair up"),
])
def test_latent_block_refuses(shape, why):
    assert ll.latent_block(SEQ, NOPE, ROPE, VDIM, 4) == (64, 4)
    assert ll.latent_block(*shape) is None, why


def test_latent_block_needs_a_tpu_or_the_interpreter(monkeypatch):
    monkeypatch.setattr(ll, "BLOCK_ROWS", 512)
    assert ll.latent_block(8192, NOPE, ROPE, VDIM, 20) == (512, 4)
    assert ll.latent_block(32, NOPE, ROPE, VDIM, 20) is None
    dispatch.set_pallas_interpret(False)
    assert ll.latent_block(8192, NOPE, ROPE, VDIM, 20) is None
    with pytest.raises(ValueError, match="no shape of the kernels"):
        _kernels(*_inputs(2, jnp.bfloat16), 2)
    with pytest.raises(ValueError, match="no shape of the kernels"):
        ll.heads_to_flat(_cotangents(2, jnp.bfloat16)[2])
    monkeypatch.setattr(ll, "pallas_available", lambda: True)
    assert ll.latent_block(8192, NOPE, ROPE, VDIM, 20) == (512, 4)


# ---------------------------------------------------------------------- #
# the model
# ---------------------------------------------------------------------- #
def _model(dtype_bf16):
    return Glm4MoeLiteModel(Glm4MoeLiteConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
        v_head_dim=VDIM, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, experts_held=(2, 4), bf16=dtype_bf16))


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
def test_the_model_through_the_kernels_equals_the_model_through_xla(bf16):
    model = _model(bf16)
    assert model.rotary_plan(SEQ) == (("latent", "kernel", 64, 2),)
    params = model.init_params(jax.random.PRNGKey(0))
    if bf16:
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 128)
    # the gates' picks from one pass, so that a rounding moves no choice
    picks = model.routing(params, ids)[1]

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, None, ids, picks=picks)))(params)

    ours = loss_and_grads()
    dispatch.set_pallas_interpret(False)
    assert model.rotary_plan(SEQ) == (("latent", "xla"),)
    want = loss_and_grads()
    rtol = 2e-2 if bf16 else 2e-5
    np.testing.assert_allclose(ours[0], want[0], rtol=rtol)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours[1]),
                            jax.tree.leaves(want[1])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= rtol * max(np.abs(b).max(), 1e-6), path
