"""The plain Phi-4-mini-flash reference against the engine at a tiny size
on the CPU (bf16 engine, float32 reference, the family's own parity and
tolerances), the faults the parity must catch, and the family's counts."""

import json
from pathlib import Path

import numpy as np
import pytest

from perf.families import phi4flash, phi4flash_reference
from perf.traffic import zipf_tokens

REPO = Path(__file__).resolve().parents[2]
TOY = {"family": "phi4flash", "hidden_act": "silu", "hidden_size": 64,
       "intermediate_size": 128, "layer_norm_eps": 1e-5, "mb_per_layer": 2,
       "num_attention_heads": 8, "num_hidden_layers": 6,
       "num_key_value_heads": 4, "sliding_window": 8,
       "tie_word_embeddings": True, "vocab_size": 250,
       "published": {"num_hidden_layers": 32},
       "kept": {"self_pairs": 1, "cross_pairs": 1},
       "assumed": {"vocab_rows_padded": 256, "ssm_state": 16, "ssm_conv": 4,
                   "ssm_expand": 2, "dt_rank": 4}}
JOB = {"gradient_accumulation_steps": 1, "activation_checkpointing": True,
       "batch_per_chip": 2, "seq": 64,
       "ds_config": {
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "bf16": {"enabled": True, "grads_in_compute_dtype": True},
           "zero_optimization": {"stage": 2}}}


def _ids(seq=64):
    return zipf_tokens.make({"exponent": 1.0, "pool_steps": 1, "seq": seq},
                            2, TOY["vocab_size"], seed=11)[0]


def _parity(seq=64):
    import jax
    return phi4flash.parity(TOY, JOB, jax.devices()[:1], 5, _ids(seq))


def test_engine_agrees_with_the_reference():
    got = _parity()
    assert got["ok"], got
    assert got["loss_rel"] <= phi4flash.LOSS_RTOL
    assert got["grad_norm_rel"] <= phi4flash.GRAD_NORM_RTOL
    assert got["grad_err_rel"] <= phi4flash.GRAD_ERR_RTOL
    # an untrained model on 256 rows sits near ln 256
    assert abs(got["ref_loss"] - np.log(256)) < 0.5


def _no_damping(index):
    return 0.0


def _no_skip(xs, dt, a_mat, b_mat, c_mat, d_skip):
    return _RECURRENCE(xs, dt, a_mat, b_mat, c_mat, 0.0 * d_skip)


def _no_band(q, k, v1, v2, window):
    return _ATT(q, k, v1, v2, 0)


def _fp8_products(a, b):
    import jax.numpy as jnp
    return (a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            @ b.astype(jnp.float8_e4m3fn).astype(jnp.float32))


_RECURRENCE = phi4flash_reference.recurrence
_ATT = phi4flash_reference.att


# each at a shape no other test uses, so that the reference is traced anew
@pytest.mark.parametrize("name, fault, seq", [
    ("lam0", _no_damping, 48),       # drops (1 - lam0), and lam's lam0
    ("recurrence", _no_skip, 40),    # drops the Dskip term
    ("att", _no_band, 56),           # drops the band of the window layer
    ("mm", _fp8_products, 24),       # the precision below the engine's
])
def test_a_dropped_term_fails_the_parity(monkeypatch, name, fault, seq):
    monkeypatch.setattr(phi4flash_reference, name, fault)
    got = _parity(seq)
    assert not got["ok"], got


def test_the_counts_of_the_configuration():
    config = json.loads(
        (REPO / "perf" / "configs" / "phi4-mini-flash.json").read_text())
    job = {"seq": 8192, "batch_per_chip": 1}
    per_kind = phi4flash.layer_parameters(config)
    # ISSUE 34's table: 119.9M a Mamba layer, 98.3M an attention layer,
    # 104.9M a gated-memory layer, 91.7M a cross-attention layer
    for kind, millions in (("mamba", 119.9), ("attn", 98.3), ("gmu", 104.9),
                           ("cross", 91.7)):
        assert abs(per_kind[kind] / 1e6 - millions) < 0.1, kind
    assert phi4flash.kept_kinds(config) == [
        "mamba", "attn", "mamba", "attn", "gmu", "cross"]
    need = phi4flash.flops_per_token(config, job)
    assert 4.4e9 < need < 4.8e9
    # a causal call counts half the square, a banded one its band
    assert phi4flash.band_keys(8192, None) == 8192 * 8193 / 2
    assert phi4flash.band_keys(8192, 512) == 512 * 513 / 2 + 7680 * 512
    full, _ = phi4flash.flash_call_cost("flash_fwd", config, job)
    band, moved = phi4flash.flash_call_cost("flash_fwd_band", config, job)
    assert full == 2 * 2 * 20 * 64 * 8192 * 8193 / 2
    assert band / full == pytest.approx(
        phi4flash.band_keys(8192, 512) / phi4flash.band_keys(8192, None))
    assert moved == (2 * 20 + 2 * 10) * 8192 * 64 * 2
    ops, nbytes = phi4flash.sscan_call_cost("sscan_fwd", config, job)
    assert ops == 9 * 5120 * 16 * 8192
    assert nbytes == (3 * 5120 + 2 * 16) * 8192 * 4
    assert phi4flash.vocab_rows(config) == 25008
