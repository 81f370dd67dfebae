"""models/granite_hybrid.py at a small size on the CPU: the model against
the plain reference of perf/families/granite_hybrid_reference.py on
seeded weights (loss, logits, every leaf's gradient), each of the four
multipliers shown to matter, the attention scale (1/64, not 1/sqrt(64)),
no positional operation anywhere, scanned against unrolled groups, the
kernels' path through the interpreter, the stack plan's fields and line,
and the engine's normal path (``deepspeed_tpu.initialize``) with what the
model refuses."""

import dataclasses
import importlib
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.models import GraniteHybridConfig, GraniteHybridModel
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.ops.dispatch import set_pallas_interpret
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    stack_plan_line)
from perf.families import granite_hybrid as family
from perf.families import granite_hybrid_reference as reference

gh = importlib.import_module("deepspeed_tpu.models.granite_hybrid")
ROOT = pathlib.Path(__file__).resolve().parents[2]
VOCAB = 256


def _file_config(**changes):
    """The benchmark's configuration file with toy sizes."""
    config = json.loads(
        (ROOT / "perf/configs/granite-4.0-h-micro.json").read_text())
    config.update(
        hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
        vocab_size=VOCAB, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"])
    # weights large enough that the logits differ by token: at the
    # published 0.02 a toy's loss is ln(vocab) whatever the model does
    config["assumed"] = {**config["assumed"], "initializer_range": 0.3}
    config.update(changes)
    return config


def _model(config=None, **model_changes):
    config = _file_config() if config is None else config
    cfg = family.model_config(config, {"activation_checkpointing": False})
    return GraniteHybridModel(dataclasses.replace(
        cfg, bf16=False, **model_changes))


def _ids(rows=2, seq=40, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, VOCAB)


def _spec(config):
    return family.reference_spec(config)._replace(row_block=16, pos_block=8)


@pytest.fixture(scope="module")
def sides():
    """The toy model's and the reference's loss, gradients and logits on
    the same seeded weights and ids."""
    config = _file_config()
    model = _model(config)
    params = model.init_params(jax.random.PRNGKey(1))
    ids, spec = _ids(), _spec(config)
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(p, None, ids))(params)
    weights = family.reference_params(params, spec)
    ref_loss, ref_grads = reference.loss_and_grads(weights, ids, spec)
    return {"config": config, "model": model, "params": params, "ids": ids,
            "spec": spec, "loss": loss, "weights": weights,
            "grads": family.reference_params(grads, spec),
            "ref_loss": ref_loss, "ref_grads": ref_grads}


def test_the_model_is_the_reference_on_seeded_weights(sides):
    assert float(sides["loss"]) == pytest.approx(float(sides["ref_loss"]),
                                                 rel=1e-5)
    ours = jax.tree_util.tree_leaves_with_path(sides["grads"])
    theirs = jax.tree.leaves(sides["ref_grads"])
    assert len(ours) == len(theirs) == 34
    for (path, a), b in zip(ours, theirs):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))
    logits = sides["model"].logits(sides["params"], sides["ids"])
    assert logits.shape == sides["ids"].shape + (VOCAB,)
    np.testing.assert_allclose(
        logits, reference.logits(sides["weights"], sides["ids"],
                                 sides["spec"]), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name, other", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attention_multiplier", 0.25)])
def test_each_multiplier_matters(sides, name, other):
    """A model built with one multiplier at another value (``other``:
    none at all, or 1/sqrt(16) for the scale) is no longer the
    reference, by far more than rounding."""
    model = _model(sides["config"], **{name: other})
    loss = float(model.loss(sides["params"], None, sides["ids"]))
    assert abs(loss - float(sides["ref_loss"])) > 1e-4 * float(
        sides["ref_loss"])


def test_the_scale_is_the_multiplier_and_nothing_turns(sides, monkeypatch):
    """``flash_attention`` is handed ``attention_multiplier`` (1/64 in the
    published file, where 1/sqrt(64) is 1/8), and the program holds no
    sine, cosine or position table."""
    published = json.loads(
        (ROOT / "perf/configs/granite-4.0-h-micro.json").read_text())
    assert published["attention_multiplier"] == 1 / 64 != 64 ** -0.5
    assert published["position_embedding_type"] == "nope"
    seen = []
    real = gh.flash_attention

    def spy(q, k, v, **kw):
        seen.append((q.shape, k.shape, kw))
        return real(q, k, v, **kw)

    monkeypatch.setattr(gh, "flash_attention", spy)
    model = _model(sides["config"])
    jaxpr = jax.make_jaxpr(lambda p: model.loss(p, None, sides["ids"]))(
        sides["params"])
    assert len(seen) == 1
    q_shape, k_shape, kw = seen[0]
    assert kw == {"causal": True,
                  "sm_scale": sides["config"]["attention_multiplier"]}
    assert q_shape[1] == 4 and k_shape[1] == 2      # grouped heads
    names = {ctx.eqn.primitive.name for ctx in iter_eqns(jaxpr)}
    assert not names & {"sin", "cos"}, names


def test_scanned_groups_are_the_unrolled_ones(sides):
    scanned = _model(sides["config"], scan_layers=True)
    loss, grads = jax.value_and_grad(
        lambda p: scanned.loss(p, None, sides["ids"]))(sides["params"])
    assert float(loss) == pytest.approx(float(sides["loss"]), rel=1e-6)
    for a, b in zip(jax.tree.leaves(family.reference_params(
            grads, sides["spec"])), jax.tree.leaves(sides["grads"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_the_kernels_path_is_the_same_model():
    """Heads of 64, eight of them, 128 states, chunks of 128: the shapes
    the Pallas kernels take, through the interpreter, against the same
    model on the XLA form."""
    config = _file_config(
        hidden_size=256, intermediate_size=128, shared_intermediate_size=128,
        mamba_n_heads=8, mamba_d_head=64, mamba_d_state=128,
        mamba_chunk_size=128, num_hidden_layers=2,
        layer_types=["mamba", "attention"])
    model = _model(config)
    params = model.init_params(jax.random.PRNGKey(4))
    ids = _ids(rows=1, seq=256, seed=5)
    assert model.scan_form() == "xla"
    want, want_grads = jax.value_and_grad(
        lambda p: model.loss(p, None, ids))(params)
    set_pallas_interpret(True)
    try:
        assert model.scan_form() == "kernel"
        got, grads = jax.value_and_grad(
            lambda p: model.loss(p, None, ids))(params)
    finally:
        set_pallas_interpret(False)
    assert float(got) == pytest.approx(float(want), rel=2e-4)
    mixer = grads["runs"][0]["mixer"]
    for name in ("A_log", "dt_bias", "D", "conv_w", "norm_w", "in_w"):
        a, b = mixer[name], want_grads["runs"][0]["mixer"][name]
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 3e-2, \
            name


@pytest.mark.parametrize("seq,form", [(64, "kernel"), (40, "xla")])
def test_the_conv_kernels_are_the_same_model(seq, form, monkeypatch):
    """ops/causal_conv.py's kernels through the interpreter (the scan
    left on its XLA form): an inner width and B and C of one lane tile
    each, a whole block of positions; 40 positions are none, and the
    same switch then leaves the model on the XLA form.  The plan's line
    names the form; loss and every gradient leaf against the model
    without the switch."""
    from deepspeed_tpu.ops import causal_conv
    config = _file_config(mamba_d_state=128, num_hidden_layers=2,
                          layer_types=["mamba", "attention"])
    model = _model(config)
    params = model.init_params(jax.random.PRNGKey(4))
    ids = _ids(rows=2, seq=seq, seed=5)
    value_and_grad = jax.value_and_grad(lambda p: model.loss(p, None, ids))
    assert stack_plan_line(model.stack_plan(2, seq)).endswith("conv: xla")
    monkeypatch.setattr(causal_conv, "pallas_interpret", lambda: True)
    assert model.scan_form() == "xla"
    assert stack_plan_line(model.stack_plan(2, seq)).endswith(
        f"conv: {form}")
    kernels = sorted({c.eqn.params["name"] for c in iter_eqns(
        jax.make_jaxpr(value_and_grad)(params).jaxpr)
        if c.eqn.primitive.name == "pallas_call"})
    if form == "xla":
        assert kernels == []        # the program without the switch
        return
    assert kernels == ["causal_conv_bwd", "causal_conv_fwd"]
    got, grads = value_and_grad(params)
    monkeypatch.undo()
    want, want_grads = value_and_grad(params)
    assert float(got) == pytest.approx(float(want), rel=2e-4)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b))


def test_the_stack_plan_and_its_line(sides):
    model = sides["model"]
    plan = model.stack_plan(2, 40)
    assert plan[R.M_STACK_LAYERS] == (
        (0, "mamba", 0), (1, "mamba", 0), (2, "attention", 0),
        (3, "mamba", 0))
    form, chunk, entry_bytes, runs, mode, groups, conv = plan[R.M_STACK_SSD]
    assert (form, chunk, runs, mode, groups, conv) == (
        "xla", 16, "mamba x2, attention, mamba", "unrolled", 1, "xla")
    # 2 rows x 3 chunks x 8 heads x 16 x 16 states, float32
    assert entry_bytes == 2 * 3 * 8 * 16 * 16 * 4
    line = stack_plan_line(plan)
    assert line.startswith("layer stack: 0:mamba, 1:mamba, 2:attention")
    assert line.endswith(
        "runs of like layers: mamba x2, attention, mamba, unrolled; "
        "state-space duality scan: xla in chunks of 16, 49,152 B of "
        "chunk-entry states a layer; conv: xla")
    cut = GraniteHybridConfig(num_hidden_layers=10, vocab_size=12544)
    assert cut.runs() == [("mamba", 0, 5), ("attention", 5, 1),
                          ("mamba", 6, 4)]
    # several groups of B and C are the shared mixer's to run
    # (models/mamba2.py); heads that do not divide into them are refused
    assert GraniteHybridConfig(mamba_n_groups=8).mixer.conv_dim == (
        4096 + 2 * 8 * 128)
    with pytest.raises(ValueError, match="mamba_n_groups 3"):
        GraniteHybridConfig(mamba_n_groups=3)
    with pytest.raises(ValueError, match="inner width"):
        GraniteHybridConfig(mamba_n_heads=32)
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybridConfig(num_hidden_layers=2,
                            layer_types=("mamba", "conv"))


def _engine_config(**extra):
    return {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
            **extra}


def test_the_engine_trains_it_on_the_normal_path():
    cfg = dataclasses.replace(family.model_config(
        _file_config(), {"activation_checkpointing": True}), bf16=False)
    model = GraniteHybridModel(cfg)
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=8)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh, model_parameters=params,
        config=_engine_config(zero_optimization={"stage": 2}))
    ids = np.asarray(_ids(rows=8, seq=32, seed=7))
    losses = []
    for _ in range(6):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] - 0.05, losses
    ds.reset_mesh_context()


@pytest.mark.parametrize("path, extra, axes", [
    ("zero3_streaming", {"zero_optimization": {"stage": 3}}, {"data": 8}),
    ("pipeline", {"train_batch_size": 4}, {"data": 4, "pipe": 2})])
def test_the_model_refuses_what_it_has_not_been_run_on(path, extra, axes):
    model = _model()
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(**axes)
    with pytest.raises(NotImplementedError, match=path):
        ds.initialize(model=model, mesh=mesh,
                      model_parameters=model.init_params(
                          jax.random.PRNGKey(0)),
                      config=_engine_config(**extra))
    ds.reset_mesh_context()
