"""ops/cca.py on the CPU in float32: each conv against
``jax.lax.conv_general_dilated`` (the depthwise one on zeros before the
sequence, the one within a head, head by head, against ONE grouped conv
over all of them on conv0's bias there), the q-k mean and the unit norm
against their formulas, the value shift at position 0, and causality:
position t unmoved by any change after t."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import cca

HEADS, KV, DIM = 4, 2, 16
CHANNELS = (HEADS + KV) * DIM
BATCH, SEQ = 2, 12


def _params(taps0=2, taps1=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"conv0_w": jax.random.normal(k[0], (CHANNELS, taps0)),
            "conv0_b": jax.random.normal(k[1], (CHANNELS,)),
            "conv1_w": jax.random.normal(
                k[2], (HEADS + KV, taps1, DIM, DIM)) / math.sqrt(DIM),
            "conv1_b": jax.random.normal(k[3], (CHANNELS,))}


def _inputs(seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (BATCH, SEQ, HEADS * DIM)),
            jax.random.normal(k[1], (BATCH, SEQ, KV * DIM)),
            jax.random.normal(k[2], (BATCH, SEQ, KV * DIM)))


def _mix(q, k, v, p):
    """``mix_heads`` with the heads side by side again."""
    q_heads, k_heads, shifted = cca.mix_heads(q, k, v, p, HEADS, KV)
    return (jnp.concatenate(q_heads, axis=-1),
            jnp.concatenate(k_heads, axis=-1), shifted)


def _lax_conv(u, kernel, groups, front):
    """``conv_general_dilated`` over the sequence of u [B, S, C] with
    ``front`` ([taps - 1, C]) laid before position 0; kernel [taps, C /
    groups, C] (width, in a group, out)."""
    padded = jnp.concatenate(
        [jnp.broadcast_to(front, (u.shape[0], *front.shape)), u], axis=1)
    return jax.lax.conv_general_dilated(
        padded, kernel, window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=groups,
        precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("taps", [2, 3])
def test_the_depthwise_conv_is_lax_conv_on_zeros_before_the_sequence(taps):
    p = _params(taps0=taps)
    u = jnp.concatenate(_inputs()[:2], axis=-1)
    got = cca.depthwise_conv(u, p["conv0_w"], p["conv0_b"])
    kernel = p["conv0_w"].T[:, None, :]               # [taps, 1, C]
    want = _lax_conv(u, kernel, CHANNELS,
                     jnp.zeros((taps - 1, CHANNELS))) + p["conv0_b"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("taps", [2, 3])
def test_the_conv_within_a_head_is_a_grouped_lax_conv_on_the_bias(taps):
    """One group a head; before position 0 it reads ``before`` (conv0's
    bias: the released module pads its zeros in front of conv0)."""
    p = _params(taps1=taps)
    u = jnp.concatenate(_inputs(2)[:2], axis=-1)
    before = p["conv0_b"]

    def every_head(fill):
        return jnp.concatenate([
            cca.head_conv(u[..., g * DIM:(g + 1) * DIM], p["conv1_w"][g],
                          p["conv1_b"][g * DIM:(g + 1) * DIM],
                          fill[g * DIM:(g + 1) * DIM])
            for g in range(HEADS + KV)], axis=-1)

    got = every_head(before)
    # [G, taps, in, out] -> [taps, in, G x out]
    kernel = p["conv1_w"].transpose(1, 2, 0, 3).reshape(taps, DIM, CHANNELS)
    want = _lax_conv(u, kernel, HEADS + KV, jnp.broadcast_to(
        before, (taps - 1, CHANNELS))) + p["conv1_b"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and not on zeros
    zeros = every_head(jnp.zeros_like(before))
    assert float(jnp.max(jnp.abs(zeros[:, 0] - got[:, 0]))) > 1e-2
    np.testing.assert_allclose(zeros[:, taps - 1:], got[:, taps - 1:],
                               rtol=1e-5, atol=1e-5)


def test_the_mean_the_sum_and_the_shift_position_by_position():
    p = _params()
    q, k, v = _inputs(3)
    mq, mk, mv = _mix(q, k, v, p)
    group = HEADS // KV
    q4 = np.asarray(q).reshape(BATCH, SEQ, HEADS, DIM)
    k4 = np.asarray(k).reshape(BATCH, SEQ, KV, DIM)
    m_q = np.stack([(q4[:, :, i] + k4[:, :, i // group]) / 2
                    for i in range(HEADS)], axis=2)
    m_k = np.stack([m_q[:, :, j * group:(j + 1) * group].mean(axis=2)
                    for j in range(KV)], axis=2)
    u = np.concatenate([q, k], axis=-1)
    w0, b0 = np.asarray(p["conv0_w"]), np.asarray(p["conv0_b"])
    w1, b1 = np.asarray(p["conv1_w"]), np.asarray(p["conv1_b"])
    first = np.zeros((BATCH, SEQ, CHANNELS))
    for t in range(SEQ):
        first[:, t] = (u[:, t - 1] * w0[:, 0] if t else 0) + (
            u[:, t] * w0[:, 1]) + b0
    second = np.zeros((BATCH, SEQ, HEADS + KV, DIM))
    first4 = first.reshape(BATCH, SEQ, HEADS + KV, DIM)
    for t in range(SEQ):
        prev = first4[:, t - 1] if t else np.broadcast_to(
            b0.reshape(HEADS + KV, DIM), first4[:, 0].shape)
        second[:, t] = (np.einsum("bgi,gio->bgo", prev, w1[:, 0])
                        + np.einsum("bgi,gio->bgo", first4[:, t], w1[:, 1])
                        + b1.reshape(HEADS + KV, DIM))
    np.testing.assert_allclose(
        mq, (second[:, :, :HEADS] + m_q).reshape(BATCH, SEQ, -1),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        mk, (second[:, :, HEADS:] + m_k).reshape(BATCH, SEQ, -1),
        rtol=2e-5, atol=2e-5)
    # the value shift: head 0 this position's, head 1 the one before,
    # zeros at position 0
    v = np.asarray(v)
    np.testing.assert_array_equal(mv[..., :DIM], v[..., :DIM])
    np.testing.assert_array_equal(mv[:, 1:, DIM:], v[:, :-1, DIM:])
    np.testing.assert_array_equal(mv[:, 0, DIM:], 0.0)


@pytest.mark.parametrize("at", [0, 5, SEQ - 1])
def test_no_position_is_moved_by_a_change_after_it(at):
    p = _params()
    q, k, v = _inputs(4)
    base = _mix(q, k, v, p)
    bump = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ - at - 1, 1))
    moved = _mix(*(t.at[:, at + 1:].add(bump) for t in (q, k, v)), p)
    for a, b in zip(base, moved):
        np.testing.assert_array_equal(a[:, :at + 1], b[:, :at + 1])
        if at < SEQ - 1:
            assert float(jnp.max(jnp.abs(a[:, at + 1:] - b[:, at + 1:]))) > 0
    # and its gradient reaches no earlier position's input from later ones
    grad = jax.grad(lambda q: jnp.sum(_mix(q, k, v, p)[0]
                                      [:, at]))(q)
    assert float(jnp.sum(jnp.abs(grad[:, at + 1:]))) == 0.0
    assert float(jnp.max(jnp.abs(grad[:, :at + 1]))) > 0.0


def test_every_head_has_norm_sqrt_d_and_a_key_head_its_temperature():
    q, k, _ = _inputs(5)
    tau = jnp.array([0.5, 1.75])
    qn, kn = cca.unit_norm_heads(cca._heads(q, HEADS), cca._heads(k, KV),
                                 tau, jnp.float32)
    norms = jnp.linalg.norm(qn.reshape(BATCH, SEQ, HEADS, DIM), axis=-1)
    np.testing.assert_allclose(norms, math.sqrt(DIM), rtol=1e-5)
    norms = jnp.linalg.norm(kn.reshape(BATCH, SEQ, KV, DIM), axis=-1)
    np.testing.assert_allclose(
        norms, math.sqrt(DIM) * np.broadcast_to(tau, norms.shape), rtol=1e-5)
    # the direction is the input's
    cos = jnp.sum(qn * q, axis=-1) / (
        jnp.linalg.norm(qn, axis=-1) * jnp.linalg.norm(q, axis=-1))
    assert float(jnp.min(cos)) > 0.3


def test_bf16_arrays_are_mixed_in_float32_and_rounded_once():
    """On bf16 arrays the result is the float32 computation on the same
    (already rounded) inputs, rounded once after the norm: the mixed q
    and k leave ``mix_heads`` in float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _params())
    q, k, v = (t.astype(jnp.bfloat16) for t in _inputs(6))
    tau = jnp.array([0.5, 1.75], jnp.bfloat16)
    mq, mk, mv = _mix(q, k, v, p)
    assert (mq.dtype, mk.dtype, mv.dtype) == (
        jnp.float32, jnp.float32, jnp.bfloat16)
    q_heads, k_heads, _ = cca.mix_heads(q, k, v, p, HEADS, KV)
    assert all(t.dtype == jnp.float32 for t in q_heads + k_heads)
    got = cca.unit_norm_heads(q_heads, k_heads, tau, jnp.bfloat16)
    assert all(t.dtype == jnp.bfloat16 for t in got)
    plain = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    wq, wk, _ = cca.mix_heads(*(t.astype(jnp.float32) for t in (q, k, v)),
                              plain, HEADS, KV)
    want = cca.unit_norm_heads(wq, wk, tau.astype(jnp.float32),
                               jnp.float32)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b.astype(jnp.bfloat16))
