"""models/nemotron_h.py on the CPU at toy widths, pattern ``MEMEM*EME``:
the model against the plain reference (perf/families/
nemotron_h_reference.py) in float32 (loss, logits, every gradient, the
picks), four faults of the reference's side that the comparison must see
(a dropped group index, a gate after the norm, a gated expert, a
rotation), the stack's plan and its line, what the engine refuses, and
three steps through ``deepspeed_tpu.initialize``.  The experts without a
gate and the share test are tests/unit/test_relu2_experts.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.models import NemotronHConfig, NemotronHModel
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    stack_plan_line)
from perf.families import nemotron_h as family
from perf.families import nemotron_h_reference as reference

PATTERN = "MEMEM*EME"


def _config(**over):
    fields = dict(
        vocab_size=128, hidden_size=64, num_hidden_layers=9,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=4,
        chunk_size=16, n_routed_experts=16, num_experts_per_tok=3,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
        experts_held=(4, 4), bf16=False, initializer_range=0.3)
    return NemotronHConfig(**{**fields, **over})


def _spec(cfg):
    return reference.Spec(
        pattern=tuple(cfg.hybrid_override_pattern),
        heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, ssm_heads=cfg.mamba_num_heads,
        ssm_dim=cfg.mamba_head_dim, states=cfg.ssm_state_size,
        groups=cfg.n_groups, eps=cfg.layer_norm_epsilon,
        picked=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        held_first=cfg.experts_held[0], pos_block=8)


@pytest.fixture(scope="module")
def sides():
    """The model's loss, gradients, logits and routing on a row of 40
    tokens, and the same weights under the reference's names; the
    selection biases off zero, so that the choice by score + bias and the
    weights without it are both in play."""
    cfg = _config()
    model = NemotronHModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    for i, (name, kind) in enumerate(cfg.layers()):
        if kind == "E":
            params[name]["moe"]["bias"] = 0.05 * jax.random.normal(
                jax.random.PRNGKey(100 + i), (1, cfg.n_routed_experts))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0, 128)
    spec = _spec(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, None, ids)))(params)
    scores, picks = jax.jit(model.routing)(params, ids)
    return {"cfg": cfg, "model": model, "params": params, "ids": ids,
            "spec": spec, "loss": float(loss), "picks": picks,
            "scores": scores, "logits": jax.jit(model.logits)(params, ids),
            "grads": family.reference_params(grads, spec),
            "weights": family.reference_params(params, spec)}


def _reference(sides, picks="program"):
    forced = sides["picks"] if picks == "program" else None
    # traced anew each call: a test replaces one of the reference's small
    # functions to see the comparison fail
    return jax.jit(lambda w, i, p: reference.loss_and_grads(
        w, i, sides["spec"], p))(sides["weights"], sides["ids"], forced)


def test_loss_logits_gradients_and_picks_are_the_references(sides):
    (loss, (scores, own_picks)), grads = _reference(sides, picks=None)
    # the reference's OWN choice is the program's
    np.testing.assert_array_equal(np.sort(own_picks, -1),
                                  np.sort(sides["picks"], -1))
    assert scores.shape == (4, 40, 16)
    np.testing.assert_allclose(scores, sides["scores"], rtol=2e-4, atol=1e-6)
    assert sides["loss"] == pytest.approx(float(loss), rel=2e-5)
    np.testing.assert_allclose(
        sides["logits"], reference.logits(sides["weights"], sides["ids"],
                                          sides["spec"]),
        rtol=2e-3, atol=2e-4)
    for (path, g), r in zip(
            jax.tree_util.tree_leaves_with_path(sides["grads"]),
            jax.tree.leaves(grads)):
        size = float(jnp.linalg.norm(r))
        err = float(jnp.linalg.norm(g - r))
        assert err <= 2e-3 * size + 1e-9, (jax.tree_util.keystr(path), err,
                                           size)
    biases = family.gate_biases(sides["grads"])
    assert float(jnp.abs(biases).max()) == 0.0


def _ungrouped(t, heads):
    return jnp.repeat(t[:1], heads, axis=0)


def _gate_after(y, z, gain, spec):
    seq = y.shape[0]
    g = y.reshape(seq, spec.groups, -1)
    g = g / jnp.sqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + spec.eps)
    return g.reshape(seq, -1) * gain * reference.silu(z)


def _gated(p, u):
    h = reference.mm(u, p["Wup"])
    return reference.mm(reference.silu(h) * h, p["Wdown"])


def _rotated(t):
    """Rotary positions on q or k [S, heads, D] (rotate-half, theta
    10,000): the config's ``rope_theta``, which this family does not
    apply."""
    seq, dim = t.shape[0], t.shape[-1]
    inv = 10000.0 ** (-2.0 * jnp.arange(dim // 2) / dim)
    angle = (jnp.arange(seq)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = t[..., :dim // 2], t[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


FAULTS = {
    "a dropped group index": ("group_of_head", _ungrouped),
    "a gate after the norm": ("grouped_gated_norm", _gate_after),
    "a gated expert": ("relu2_mlp", _gated),
    "a rotation": ("placed", _rotated),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_sees_a_wrong_term(sides, fault, monkeypatch):
    """Each fault, put into the REFERENCE's side, moves the loss past the
    family's limit on it and the gradients far past theirs."""
    monkeypatch.setattr(reference, *FAULTS[fault])
    (loss, _), grads = _reference(sides)
    loss_rel = abs(sides["loss"] - float(loss)) / float(loss)
    err = float(reference.global_norm(jax.tree.map(
        lambda a, b: a - b, sides["grads"], grads))
        / reference.global_norm(grads))
    print(fault, loss_rel, err)
    assert loss_rel > family.LOSS_RTOL, (fault, loss_rel)
    assert err > 3 * family.GRAD_ERR_RTOL, (fault, err)


# ---------------------------------------------------------------------- #
# the stack, the engine
# ---------------------------------------------------------------------- #
def test_the_stack_plan_and_its_line(sides):
    model = sides["model"]
    plan = model.stack_plan(2, 40)
    assert [kind for _, kind, _ in plan[R.M_STACK_LAYERS]] == [
        "mamba", "experts", "mamba", "experts", "mamba", "attention",
        "experts", "mamba", "experts"]
    form, chunk, entry_bytes, runs, mode, groups, conv = plan[R.M_STACK_SSD]
    assert (form, chunk, runs, mode, groups, conv) == (
        "xla", 16, "M, E, M, E, M, *, E, M, E", "unrolled", 4, "xla")
    assert entry_bytes == 2 * 3 * 8 * 8 * 16 * 4      # 2 rows x 3 chunks
    assert plan[R.M_STACK_EXPERTS_HELD] == (4, 4, 16)
    line = stack_plan_line(plan)
    assert "0:mamba, 1:experts, 2:mamba" in line
    assert "routed experts 4 to 7 of 16 held here" in line
    assert "on 4 groups of B and C" in line
    assert line.endswith("chunk-entry states a layer; conv: xla")
    assert model.gates() == [((f"layers_{i:02d}", "moe"), 1)
                             for i in (1, 3, 6, 8)]
    published = NemotronHConfig()
    assert published.hybrid_override_pattern[:9] == PATTERN
    assert (published.hybrid_override_pattern.count("M"),
            published.hybrid_override_pattern.count("E"),
            published.hybrid_override_pattern.count("*")) == (23, 23, 6)
    assert published.mixer.d_inner == 4096 != 2 * published.hidden_size
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        _config(hybrid_override_pattern="MEX")


@pytest.mark.parametrize("seq,form", [(64, "kernel"), (40, "xla")])
def test_the_conv_kernels_are_the_same_model(seq, form, monkeypatch):
    """ops/causal_conv.py's kernels through the interpreter (the scan
    and the experts left on their XLA forms), the first three layers
    (mixer, experts, mixer): an inner width of one lane tile, four groups
    of 32 states another; a whole block of positions, or 40, which the
    kernels refuse and the same switch leaves on the XLA form.  The
    plan's line names the form; loss and every gradient leaf against the
    model without the switch."""
    from deepspeed_tpu.ops import causal_conv
    model = NemotronHModel(_config(
        num_hidden_layers=3, mamba_head_dim=16, ssm_state_size=32))
    params = model.init_params(jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, seq), 0, 128)
    value_and_grad = jax.value_and_grad(lambda p: model.loss(p, None, ids))
    assert stack_plan_line(model.stack_plan(2, seq)).endswith("conv: xla")
    monkeypatch.setattr(causal_conv, "pallas_interpret", lambda: True)
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


def _engine_config(**extra):
    return {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
            **extra}


def _initialize(config, **mesh):
    model = NemotronHModel(_config(activation_checkpointing=True))
    ds.reset_mesh_context()
    made = ds.initialize_mesh(devices=jax.devices()[:max(
        mesh.values(), default=1)], **(mesh or {"data": 1}))
    return ds.initialize(model=model, mesh=made,
                         model_parameters=model.init_params(
                             jax.random.PRNGKey(0)), config=config)[0]


def test_three_steps_through_initialize_move_the_biases_and_the_loss(sides):
    engine = _initialize(_engine_config(
        zero_optimization={"stage": 2},
        bf16={"enabled": False}))
    losses = []
    for _ in range(3):
        loss = engine.forward(sides["ids"])
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[2] < losses[1] < losses[0]
    bias = np.asarray(engine.params["layers_01"]["moe"]["bias"])
    # three updates of gamma up or down, by the sign of mean(c) - c
    assert set(np.round(np.abs(bias) / 0.001).astype(int).ravel()) <= {
        0, 1, 2, 3}
    assert np.abs(bias).max() > 0
    assert set(engine.model_counters()) == {"load_max_over_mean"}
    ds.reset_mesh_context()


@pytest.mark.parametrize("path,config,mesh", [
    ("zero3_streaming", {"zero_optimization": {"stage": 3}}, {}),
    ("pipeline", {}, {"pipe": 2}),
])
def test_the_engine_refuses_what_the_model_has_not_run(path, config, mesh):
    with pytest.raises(NotImplementedError, match=path):
        _initialize(_engine_config(**config), **mesh)
    ds.reset_mesh_context()


def test_an_expert_axis_is_refused_by_the_layer():
    model = NemotronHModel(_config())
    ds.reset_mesh_context()
    ds.initialize_mesh(devices=jax.devices()[:2], expert=2, data=1)
    try:
        with pytest.raises(NotImplementedError, match="expert axis is 2"):
            model.loss(model.init_params(jax.random.PRNGKey(0)), None,
                       jnp.zeros((2, 16), jnp.int32))
    finally:
        ds.reset_mesh_context()
