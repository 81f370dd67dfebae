"""The Laguna family's comparison with its plain reference (perf/families/
laguna.py, laguna_reference.py) on the CPU at a small size: the engine
(bf16 compute, float32 router) passes its three parts, and each of six
faults put into the REFERENCE'S side makes at least one limit fail, so
the comparison can tell the architecture's terms apart."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.families import laguna as family
from perf.families import laguna_reference as reference

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _config():
    config = json.loads((ROOT / "perf/configs/laguna-xs2.json").read_text())
    config.update(hidden_size=128, intermediate_size=256,
                  num_key_value_heads=2, head_dim=128, sliding_window=8,
                  num_experts=4, num_experts_per_tok=4,
                  moe_intermediate_size=128,
                  shared_expert_intermediate_size=128, vocab_size=256,
                  num_attention_heads_per_layer=[2, 4, 4, 4, 2])
    config["published"] = {**config["published"], "num_experts": 16}
    config["kept"] = {**config["kept"], "experts_first": 4}
    # at width 128 the published 0.02 leaves every attention nearly
    # uniform, and no fault of the positions could show
    config["assumed"] = {**config["assumed"], "initializer_range": 0.05}
    config["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, factor=4, rope_theta=100)
    return config


JOB = {"gradient_accumulation_steps": 1, "activation_checkpointing": True,
       "batch_per_chip": 2, "seq": 192,
       "ds_config": {
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
           "bf16": {"enabled": True, "grads_in_compute_dtype": True},
           "zero_optimization": {"stage": 2}}}


@pytest.fixture(scope="module")
def program():
    """The engine's side of the comparison on a batch of two rows, once
    for every case."""
    import deepspeed_tpu as ds
    config = _config()
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(11), (2, JOB["seq"]), 0, config["vocab_size"]),
        np.int32)
    out = family.program_side(config, JOB, jax.devices()[:1], 5, ids)
    ds.reset_mesh_context()
    return config, ids, out


def _fp8(a, b):
    def cast(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return cast(a) @ cast(b)


def _softmax_scores(u, w_router):
    return jax.nn.softmax(reference.mm(u, w_router), axis=-1)


def _no_gate(u, w_gate):
    return jnp.ones((u.shape[0], w_gate.shape[1]), u.dtype)


def _spec_with(**fields):
    sound = family.reference_spec

    def faulty(config):
        return sound(config)._replace(**fields)
    return faulty


FAULTS = {
    "sound": None,
    "no 2.5": (family, "reference_spec", _spec_with(scale=1.0)),
    "softmax for sigmoid": (reference, "router_scores", _softmax_scores),
    "no gate": (reference, "head_gate", _no_gate),
    "full rotary on a full layer": (
        family, "reference_spec", _spec_with(full_rotated=128)),
    "the window off by one": (
        family, "reference_spec", _spec_with(window=9)),
    "fp8 products": (reference, "mm", _fp8),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_engine_parity_and_the_faults_that_must_each_fail(
        fault, program, monkeypatch):
    config, ids, out = program
    if FAULTS[fault] is not None:
        monkeypatch.setattr(*FAULTS[fault])
    got = family.judge(config, out, ids, jax.devices()[0])
    print(fault, json.dumps(got))
    if fault == "sound":
        assert got["ok"], got
        # the program the window times chose the picks it was then handed
        assert got["timed_loss"] == pytest.approx(got["loss"], rel=1e-4)
        # the reference on its OWN picks is the same model: close, and
        # not compared (the chip's set-up does not pay for this pass)
        free, _ = jax.jit(lambda w, i: reference.forward(
            w, i, family.reference_spec(config)))(out["weights"], ids)
        assert abs(float(free) - got["ref_loss"]) < 0.05
    else:
        assert not got["ok"] and got["failed"], got


def test_routing_agreement_explains_near_ties_only():
    ref = jnp.asarray([[[0.9, 0.8, 0.5, 0.4999, 0.1],
                        [0.9, 0.8, 0.5, 0.3, 0.1],
                        [0.9, 0.8, 0.5, 0.3, 0.1]]])
    ours = ref + 1e-4
    picks = jnp.asarray([[[0, 1, 3], [0, 1, 2], [0, 1, 4]]], jnp.int32)
    err, differ, unexplained = family.routing_agreement(
        ours, picks, ref, delta=1e-3)
    assert float(err) < 3e-4
    # token 0 flipped a near tie, token 2 a pick 0.4 away
    assert float(differ) == pytest.approx(2 / 3)
    assert float(unexplained) == pytest.approx(1 / 3)


def test_counts_follow_the_rows_the_routing_sends_here(monkeypatch):
    config = json.loads((ROOT / "perf/configs/laguna-xs2.json").read_text())
    job = {"batch_per_chip": 2, "seq": 8192}
    # no engine has run: what a router that favours nobody would send
    assert family.routing_counters() is None
    assert family.held_share(config) == 1 / 8
    per_token = family.flops_per_token(config, job)
    # ISSUE 36's reckoning: about 0.8 GFLOP forward, 2.4 trained
    assert 2.3e9 < per_token < 2.6e9
    # the run's counter says half of that landed here: the routed
    # experts' term halves, and nothing else moves
    routed = 6 * 3 * 8 * 2048 * 512 * 4 / 8      # four sparse layers
    monkeypatch.setattr(family, "routing_counters",
                        lambda: {"held_pick_share": 1 / 16})
    assert family.held_share(config) == 1 / 16
    assert per_token - family.flops_per_token(config, job) == pytest.approx(
        routed / 2)
    monkeypatch.undo()
    ops, moved = family.gmm_call_cost("gmm_rows", config, job, 16384)
    assert ops == 1.5 * 2 * 16384 * 2048 * 512
    assert moved == 16384 * (2048 + 1024 + 512 + 2048) + 32 * 3 * 2048 * 512
    assert family.gmm_call_cost("gmm_weights", config, job, 16384)[1] > moved
    work, _ = family.flash_call_cost("flash_fwd_band", config, job)
    assert work == 2 * 2 * 2 * 64 * 128 * family.band_keys(8192, 512)
    work, _ = family.flash_call_cost("flash_fwd", config, job)
    assert work == 2 * 2 * 2 * 48 * 128 * family.band_keys(8192, None)
