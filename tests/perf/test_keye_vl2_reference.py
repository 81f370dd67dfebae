"""The Keye-VL-2.0 family on the CPU at a small size (S = 256, topk 32,
2 layers, 8 experts of which 4 are held): the model against its plain
reference leaf by leaf in float32, the engine (bf16) through the family's
three-part comparison with faults that must each fail, and what the two
loss terms promise.  The kernels, the select, the expert shares and the
family's counts are in test_keye_vl2_kernels.py."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.keye_vl2 import KeyeVL2Model
from perf.families import keye_vl2 as family
from perf.families import keye_vl2_reference as reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ, TOPK = 256, 32


def _published():
    return json.loads(
        (ROOT / "perf/configs/keye-vl2-30b-a3b.json").read_text())


def _config():
    config = _published()
    config.update(hidden_size=64, head_dim=16, num_attention_heads=4,
                  num_key_value_heads=2, moe_intermediate_size=32,
                  num_experts=4, num_local_experts=4, num_experts_per_tok=2,
                  num_hidden_layers=2, vocab_size=256)
    config["rope_scaling"] = {**config["rope_scaling"],
                              "mrope_section": [2, 3, 3]}
    config["sa_config"] = {**config["sa_config"], "indexer_num_heads": 4,
                           "indexer_head_dim": 8, "topk": TOPK}
    config["published"] = {**config["published"], "num_experts": 8}
    config["kept"] = {**config["kept"], "experts_first": 2}
    # at width 64 the published 0.02 leaves every score nearly flat
    config["assumed"] = {**config["assumed"], "initializer_range": 0.1}
    return config


JOB = {"gradient_accumulation_steps": 1, "activation_checkpointing": True,
       "batch_per_chip": 1, "seq": SEQ,
       "ds_config": {
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
           "bf16": {"enabled": True, "grads_in_compute_dtype": True},
           "zero_optimization": {"stage": 2}}}


def _ids(rows=1, seed=11):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (rows, SEQ), 0, 256), np.int32)


def _model():
    job = {**JOB, "activation_checkpointing": False}
    cfg = family.model_config(_config(), job)
    cfg.bf16 = False
    model = KeyeVL2Model(cfg)
    return model, model.init_params(jax.random.PRNGKey(3))


# ---------------------------------------------------------------------- #
# the model against the reference, float32, leaf by leaf
# ---------------------------------------------------------------------- #
def test_model_equals_the_reference_in_every_leaf():
    model, params = _model()
    ids = _ids()
    spec = family.reference_spec(_config(), block=64)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: model(p, None, ids), has_aux=True))(params)
    (ref_loss, (ref_main, ref_index, _, _, counts)), ref_grads = jax.jit(
        lambda w: reference.loss_and_grads(w, ids, spec))(
        family.reference_params(params, spec))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert float(counters["main_loss"]) == pytest.approx(float(ref_main),
                                                         rel=1e-5)
    assert float(counters["index_loss"]) == pytest.approx(float(ref_index),
                                                          rel=1e-4)
    share = family.kept_share(_config(), JOB)
    assert float(counters["kept_share"]) == pytest.approx(share, abs=1e-6)
    assert float(counts[:, 0].sum()) == 2 * family.selected_pairs(
        SEQ, TOPK)
    ours = family.reference_params(grads, spec)
    flat, _ = jax.tree_util.tree_flatten_with_path(ref_grads)
    for (path, theirs), mine in zip(flat, jax.tree.leaves(ours)):
        scale = float(jnp.max(jnp.abs(theirs)))
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(mine - theirs))) < 2e-4 * scale, path


def test_the_two_terms_reach_separate_weights():
    """An indexer leaf's gradient is unchanged when L_lm is scaled and is
    zero without L_I; a main leaf's is unchanged when L_I is scaled."""
    model, params = _model()
    ids = _ids()

    @jax.jit
    def weighted(a, b):
        def objective(p):
            _, main, index = model.loss_terms(p, ids)
            return a * main + b * index
        return jax.grad(objective)(params)

    def grads(a, b):
        return jax.device_get(weighted(a, b))

    def split(tree):
        indexer = tree["layers"].pop("indexer")
        return indexer, tree

    base_idx, base_main = split(grads(1.0, 1.0))
    lm_idx, _ = split(grads(2.0, 1.0))
    _, index_main = split(grads(1.0, 3.0))
    only_idx, only_main = split(grads(0.0, 1.0))
    for a, b in zip(jax.tree.leaves(base_idx), jax.tree.leaves(lm_idx)):
        assert float(jnp.max(jnp.abs(a))) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(base_main), jax.tree.leaves(index_main)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(base_idx), jax.tree.leaves(only_idx)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(not np.any(np.asarray(g)) for g in jax.tree.leaves(only_main))


# ---------------------------------------------------------------------- #
# the engine through the family's comparison
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def program():
    import deepspeed_tpu as ds
    config = _config()
    ids = _ids()
    out = family.program_side(config, JOB, jax.devices()[:1], 5, ids)
    ds.reset_mesh_context()
    return config, ids, out


def _fp8(spec, a, b):
    def cast(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(spec, cast(a), cast(b))


def _spec_with(**fields):
    sound = family.reference_spec

    def faulty(config, block=64):
        return sound(config, block)._replace(**fields)
    return faulty


FAULTS = {
    "sound": None,
    "the alignment term twice": (
        family, "reference_spec", _spec_with(index_weight=2.0)),
    "fp8 products": (reference, "contract", _fp8),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_engine_parity_and_the_faults_that_must_each_fail(
        fault, program, monkeypatch):
    config, ids, out = program
    if FAULTS[fault] is not None:
        monkeypatch.setattr(*FAULTS[fault])
    got = family.judge(config, JOB, out, ids, jax.devices()[0])
    print(fault, json.dumps(got))
    if fault == "sound":
        assert got["ok"], got
        assert got["kept_share"] == pytest.approx(
            family.kept_share(config, JOB), abs=1e-6)
    else:
        assert not got["ok"] and got["failed"], got
