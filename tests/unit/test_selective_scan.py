"""The chunked selective scan (ops/selective_scan.py), both forms, against
the recurrence written position by position: forward and every gradient,
at lengths that are and are not multiples of the chunk."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.selective_scan import (entry_state_bytes,
                                              selective_scan)


def _sequential(x, dt, a_mat, b_mat, c_mat, d_vec):
    def row(x, dt, b_mat, c_mat):
        def step(s, inputs):
            x_t, dt_t, b_t, c_t = inputs
            s = (jnp.exp(dt_t[:, None] * a_mat) * s
                 + (dt_t * x_t)[:, None] * b_t[None])
            return s, s @ c_t + d_vec * x_t
        return jax.lax.scan(step, jnp.zeros(a_mat.shape),
                            (x, dt, b_mat, c_mat))[1]
    return jax.vmap(row)(x, dt, b_mat, c_mat)


def _operands(batch, seq, channels, states):
    ks = jax.random.split(jax.random.PRNGKey(seq + channels), 7)
    return (jax.random.normal(ks[0], (batch, seq, channels)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, channels))
                            - 2.0),
            -jnp.exp(jax.random.normal(ks[2], (channels, states))),
            jax.random.normal(ks[3], (batch, seq, states)),
            jax.random.normal(ks[4], (batch, seq, states)),
            jax.random.normal(ks[5], (channels,))), jax.random.normal(
                ks[6], (batch, seq, channels))


@pytest.fixture
def interpreted():
    dispatch.set_pallas_interpret(True)
    yield
    dispatch.set_pallas_interpret(False)


def _check(seq, channels, states, chunk, batch=2):
    ops, g = _operands(batch, seq, channels, states)
    with jax.default_matmul_precision("highest"):
        ours = jax.value_and_grad(
            lambda *a: jnp.sum(selective_scan(*a, chunk=chunk) * g),
            range(6))(*ops)
        want = jax.value_and_grad(
            lambda *a: jnp.sum(_sequential(*a) * g), range(6))(*ops)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(
            jnp.max(jnp.abs(b)) + 1e-6)


@pytest.mark.parametrize("seq, chunk", [(64, 16), (50, 16), (7, 16),
                                        (33, 8)])
def test_xla_form_matches_the_sequential_scan(seq, chunk):
    _check(seq, 8, 4, chunk)


@pytest.mark.parametrize("seq, channels, chunk", [
    (64, 256, 16), (40, 128, 16), (256, 1024, 128)])
def test_kernels_match_the_sequential_scan(interpreted, seq, channels,
                                           chunk):
    _check(seq, channels, 16, chunk, batch=1 if channels > 256 else 2)


def test_large_steps_do_not_overflow():
    # exp(-A cumsum(dt)) would: every factor the op forms is at most 1
    ops, _ = _operands(1, 64, 8, 4)
    ops = (ops[0], 40.0 * ops[1], 16.0 * ops[2], *ops[3:])
    y = selective_scan(*ops, chunk=32)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(jnp.max(jnp.abs(y - _sequential(*ops)))) < 1e-3


def test_entry_states_of_the_cell():
    # 8,192 positions in chunks of 128: 64 states of 5120 x 16 float32
    assert entry_state_bytes(1, 8192, 5120, 16) == 64 * 5120 * 16 * 4
