"""The flash kernels with a window and with grouped key/value heads
(interpret mode) against ``mha_reference`` with the band as a mask:
forward and the three gradients, at 20 query heads on 10."""

import importlib

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops import dispatch
from tests.unit.test_flash_causal_bound import (_forward_kernel,
                                                _reference_lse)

fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")
    dispatch.set_pallas_interpret(True)
    yield
    dispatch.set_pallas_interpret(False)


def _compare(seq, heads, kv_heads, window, block_q, block_k, dim=64):
    ks = jax.random.split(jax.random.PRNGKey(seq + heads), 4)
    q, g = (jax.random.normal(k, (1, heads, seq, dim)) for k in ks[:2])
    k, v = (jax.random.normal(k, (1, kv_heads, seq, dim)) for k in ks[2:])
    ours = jax.value_and_grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=True, window=window, block_q=block_q, block_k=block_k,
        impl="pallas") * g), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(fa.mha_reference(
        *a, causal=True, window=window) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(
            jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("seq, heads, kv_heads, window, block_q, block_k", [
    (512, 20, 10, 128, 128, 128),     # the cell's form: window = blocks
    (512, 4, 2, 128, 64, 256),        # key blocks wider than q blocks
    (512, 4, 4, 100, 128, 128),       # a window no block divides
    (512, 2, 2, 200, 256, 128),       # q blocks wider than key blocks
    (512, 4, 2, None, 128, 128),      # grouped heads, no window
    (1024, 2, 1, None, 512, 1024),    # grouped heads, the sub-tile walk
])
def test_band_and_groups_match_the_masked_reference(
        interpreted, seq, heads, kv_heads, window, block_q, block_k):
    _compare(seq, heads, kv_heads, window, block_q, block_k)


def _forward_kernel_name(seq, heads, kv_heads, **call):
    return _forward_kernel(jnp.zeros((1, heads, seq, 64)),
                           jnp.zeros((1, kv_heads, seq, 64)), causal=True,
                           **call)


@pytest.mark.parametrize("seq, heads, kv_heads, window, block_q, block_k", [
    (512, 4, 2, 128, 128, 128),       # two inner steps of the band
    (1024, 2, 1, 300, 256, 128),      # five: a window no block divides
    (1024, 2, 2, 512, 512, 512),      # the cell's blocks and window
    (2048, 4, 2, None, 512, 1024),    # grouped heads, two key blocks
])
def test_forward_with_rows_on_the_lanes_under_a_band_and_groups(
        seq, heads, kv_heads, window, block_q, block_k):
    """The banded and the grouped forward take the kernel that carries q
    rows along the lanes, several inner steps with the running max and
    sum in its [1, block_q] scratch: out and the log-sum-exp, position
    for position, against the masked reference."""
    call = dict(window=window, block_q=block_q, block_k=block_k)
    assert _forward_kernel_name(seq=seq, heads=heads, kv_heads=kv_heads,
                                **call) == (
        "flash_fwd_band" if window else "flash_fwd")
    ks = jax.random.split(jax.random.PRNGKey(seq + block_q), 3)
    q = jax.random.normal(ks[0], (1, heads, seq, 64))
    k, v = (jax.random.normal(key, (1, kv_heads, seq, 64)) for key in ks[1:])
    out, lse = fa.flash_attention_pallas(q, k, v, causal=True,
                                         interpret=True, return_lse=True,
                                         **call)
    want = fa.mha_reference(q, k, v, causal=True, window=window)
    want_lse = _reference_lse(q, k, window=window)
    assert float(jnp.max(jnp.abs(out - want))) <= 2e-5
    assert float(jnp.max(jnp.abs(lse - want_lse))) <= 2e-5


def test_a_band_on_half_a_lane_tile_of_rows_takes_the_one_forward():
    call = dict(window=128, block_q=64, block_k=256)
    assert _forward_kernel_name(seq=512, heads=4, kv_heads=2,
                                **call) == "flash_fwd_band"
    ks = jax.random.split(jax.random.PRNGKey(64), 3)
    q = jax.random.normal(ks[0], (1, 4, 512, 64))
    k, v = (jax.random.normal(key, (1, 2, 512, 64)) for key in ks[1:])
    out, lse = fa.flash_attention_pallas(q, k, v, causal=True,
                                         interpret=True, return_lse=True,
                                         **call)
    want = fa.mha_reference(q, k, v, causal=True, window=128)
    assert float(jnp.max(jnp.abs(out - want))) <= 2e-5
    assert float(jnp.max(jnp.abs(lse - _reference_lse(q, k, window=128)))
                 ) <= 2e-5


def test_the_band_visits_its_tiles_only():
    band = fa._Band(512, 512, 512, 16, 16)
    assert (band.steps_k, band.steps_q) == (2, 2)       # of 16
    assert [band.first_k(qi) for qi in (0, 1, 5)] == [0, 0, 4]
    assert [band.first_q(ki) for ki in (0, 15)] == [0, 15]
    band = fa._Band(100, 128, 128, 4, 4)
    assert (band.steps_k, band.steps_q) == (2, 2)


def test_a_window_needs_a_causal_call_and_takes_no_dropout(interpreted):
    x = jnp.zeros((1, 2, 256, 64))
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention_pallas(x, x, x, causal=False, window=64,
                                  interpret=True)
    with pytest.raises(ValueError, match="dropout"):
        fa.flash_attention_pallas(x, x, x, causal=True, window=64,
                                  dropout_rate=0.1, dropout_seed=1,
                                  interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        three = jnp.zeros((1, 3, 256, 64))
        fa.mha_reference(x, three, three)
