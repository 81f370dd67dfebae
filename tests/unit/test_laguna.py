"""``models/laguna.py`` against the plain reference of the benchmark
(``perf/families/laguna_reference.py``) at a small size on the CPU,
float32: each layer kind alone, then the stack whole, loss and every
gradient leaf, with and without checkpointing; YaRN and partial rotary
against closed-form values; the published depth's parameter count; the
stack's plan; a train loop through ``ds.initialize`` with the routing
counters on."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.laguna import (FULL, SLIDING, LagunaConfig,
                                         LagunaModel, apply_rotary,
                                         rotary_table, yarn_inv_freq)
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    RematBudget)
from perf.families import laguna as family
from perf.families import laguna_reference as reference

WINDOW = 8


def _config(**over):
    kw = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
              num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
              sliding_window=WINDOW,
              num_attention_heads_per_layer=(4, 6, 6, 6, 4),
              num_experts=16, num_experts_per_tok=4,
              moe_intermediate_size=32, shared_expert_intermediate_size=32,
              experts_held=(4, 8), yarn_factor=4.0,
              yarn_original_max_position_embeddings=16, bf16=False)
    kw.update(over)
    return LagunaConfig(**kw)


def _spec(cfg):
    return reference.Spec(
        layers=tuple((family.KINDS[kind], heads, sparse)
                     for _, kind, heads, sparse in cfg.layer_plan()),
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        window=cfg.sliding_window, eps=cfg.rms_norm_eps,
        picked=cfg.num_experts_per_tok, scale=cfg.moe_routed_scaling_factor,
        held_first=cfg.experts_held[0],
        full_rotated=int(cfg.head_dim * cfg.full_partial_rotary_factor),
        yarn_factor=cfg.yarn_factor,
        yarn_original=cfg.yarn_original_max_position_embeddings)


def _params(model, seed=0):
    """Seeded weights with every norm weight off its initial 1, so that
    no term is silent."""
    params = model.init_params(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return tree.unflatten([x + 0.05 * jax.random.normal(k, x.shape)
                           for x, k in zip(leaves, keys)])


def _close(ours, want, rtol=2e-4):
    ours, want = jax.tree.leaves(ours), jax.tree.leaves(want)
    assert len(ours) == len(want)
    for a, b in zip(ours, want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= rtol * float(
            jnp.max(jnp.abs(b)) + 1e-9)


_reference_results = {}


def _reference(params, ids, spec):
    """The reference's loss, routing and gradients, once per spec (the
    variants of one stack share weights, ids and reference)."""
    if spec not in _reference_results:
        _reference_results[spec] = jax.jit(
            reference.loss_and_grads, static_argnums=(2,))(
            family.reference_params(params, spec), ids, spec)
    return _reference_results[spec]


def _against_reference(cfg, seq=24):
    model = LagunaModel(cfg)
    params = _params(model)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, seq), 0,
                             cfg.vocab_size)
    spec = _spec(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, None,
                                                              ids)
        routed = jax.jit(model.routing)(params, ids)
        (want, (scores, picks)), want_grads = _reference(params, ids, spec)
    assert abs(float(loss) - float(want)) <= 2e-5 * float(want)
    _close(family.reference_params(grads, spec), want_grads)
    if routed is not None:
        np.testing.assert_allclose(routed[0], scores, rtol=2e-5)
        assert (np.sort(routed[1], -1) == np.sort(picks, -1)).all()


@pytest.mark.parametrize("kind, heads, dense", [
    (FULL, 4, True), (SLIDING, 6, False), (FULL, 4, False)])
def test_each_layer_kind_alone(kind, heads, dense):
    _against_reference(_config(
        num_hidden_layers=1, layer_types=(kind,),
        num_attention_heads_per_layer=(heads,),
        mlp_only_layers=(0,) if dense else ()))


@pytest.mark.parametrize("checkpointing", [False, True])
def test_the_stack_whole_every_gradient_leaf(checkpointing):
    _against_reference(_config(activation_checkpointing=checkpointing))


def test_forced_picks_are_taken_by_program_and_reference_alike():
    cfg = _config()
    model, spec = LagunaModel(cfg), _spec(cfg)
    params = _params(model)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0, 128)
    # every sparse layer is made to pick experts 3, 4, 5 and 9: one
    # outside the held range, one never scored highly
    picks = jnp.broadcast_to(jnp.asarray([3, 4, 5, 9], jnp.int32),
                             (4, 2 * 24, 4))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, None, ids, picks=picks)))(params)
        free = jax.jit(model.loss)(params, None, ids)
        (want, _), want_grads = jax.jit(
            reference.loss_and_grads, static_argnums=(2,))(
            family.reference_params(params, spec), ids, spec, picks)
    assert abs(float(loss) - float(free)) > 1e-4
    assert abs(float(loss) - float(want)) <= 2e-5 * float(want)
    _close(family.reference_params(grads, spec), want_grads)


def test_yarn_frequencies_against_closed_form():
    """The published numbers: 64 rotated dimensions, theta 500,000,
    factor 64 over 4,096 original positions, beta 64 and 1: dimensions 0
    to 5 keep their frequency, 16 to 31 have it divided by 64, a linear
    ramp between."""
    got = np.asarray(yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0))
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    low = math.floor(64 * math.log(4096 / (2 * math.pi * 64))
                     / (2 * math.log(500000.0)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(500000.0)))
    assert (low, high) == (5, 16)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(
        got[10], plain[10] * (1 - 5 / 11) + plain[10] / 64 * 5 / 11,
        rtol=1e-6)
    assert abs(1.4158883083359672 - (0.1 * math.log(64) + 1)) < 1e-12
    # the reference computes the same table from its own formula
    cos, sin, r = reference.rotary_angles(
        24, "full", reference.Spec(layers=()))
    ours = rotary_table(24, jnp.asarray(got), 1.4158883083359672)
    assert r == 64
    np.testing.assert_allclose(ours[0], cos, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ours[1], sin, rtol=1e-5, atol=1e-6)


def test_partial_rotary_turns_the_first_dimensions_only():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 6, 16))
    inv_freq = jnp.asarray([1.0, 0.5, 0.25, 0.125])      # r = 8 of 16
    out = np.asarray(apply_rotary(x, rotary_table(6, inv_freq, 2.0)))
    x = np.asarray(x)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    for t in range(6):
        for i in range(4):
            c, s = 2 * math.cos(t * inv_freq[i]), 2 * math.sin(
                t * inv_freq[i])
            a, b = x[0, :, t, i], x[0, :, t, i + 4]       # pair (i, i + r/2)
            np.testing.assert_allclose(out[0, :, t, i], a * c - b * s,
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(out[0, :, t, i + 4], b * c + a * s,
                                       rtol=1e-5, atol=1e-6)


def test_published_depth_counts_33_44_billion_parameters():
    model = LagunaModel(LagunaConfig())
    assert round(model.num_params() / 1e9, 2) == 33.44
    plan = model.config.layer_plan()
    assert len(plan) == 40
    assert [p[1:] for p in plan[:5]] == [
        (FULL, 48, False), (SLIDING, 64, True), (SLIDING, 64, True),
        (SLIDING, 64, True), (FULL, 48, True)]
    assert sum(kind == FULL for _, kind, _, _ in plan) == 10


def test_the_cut_is_a_depth_a_held_range_and_a_row_count():
    cut = LagunaConfig(num_hidden_layers=5, experts_held=(0, 32),
                       vocab_size=12544)
    model = LagunaModel(cut)
    assert model.num_params() == 691_623_936
    assert [(g[0], g[4], g[5]) for g in cut.groups()] == [
        ("layers_00", 0, 1), ("layers_01", 1, 3), ("layers_04", 4, 1)]
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert shapes["layers_01"]["moe"]["router"].shape == (3, 2048, 256)
    assert shapes["layers_01"]["moe"]["experts"]["w1"].shape == (
        3, 32, 2048, 1024)
    assert shapes["layers_04"]["attn"]["qkv_w"].shape == (1, 2048, 64 * 128)
    assert shapes["head"].shape == (2048, 12544)


def test_the_plan_names_the_layers_and_the_held_experts():
    model = LagunaModel(_config(activation_checkpointing=True))
    budget = RematBudget(10 ** 12, working_set=0)
    model.install_remat_budget(budget)
    params = model.init_params(jax.random.PRNGKey(0))
    jax.eval_shape(model.loss, params, None, jnp.zeros((2, 40), jnp.int32))
    plan = budget.take_plan()
    assert [(i, k) for i, k, _ in plan[R.M_STACK_LAYERS]] == [
        (0, "full_attention+dense"), (1, "sliding_attention+experts"),
        (2, "sliding_attention+experts"), (3, "sliding_attention+experts"),
        (4, "full_attention+experts")]
    assert plan[R.M_STACK_LAYERS][1][2] == WINDOW
    assert tuple(plan[R.M_STACK_EXPERTS_HELD]) == (4, 8, 16)
    # 80 tokens x 4 picks, half the experts held: 160 rows, a whole tile
    assert plan[R.M_STACK_DISPATCH_ROWS] == 256
    assert plan[R.M_REMAT_LAYERS] == 5


@pytest.mark.parametrize("budget", [
    None, RematBudget(None), RematBudget(1, working_set=0),
    RematBudget(10 ** 12, working_set=0)],
    ids=["no budget", "no limit", "nothing fits", "everything fits"])
def test_a_recomputing_layer_keeps_its_picks(budget, capsys):
    """The picks are a saved residual of the checkpointed body whatever
    the budget admits, and where there is none (the CPU, a streamed
    ZeRO-3, a device that reports no limit): the backward pass reads
    them, it does not choose again."""
    model = LagunaModel(_config(activation_checkpointing=True))
    if budget is not None:
        model.install_remat_budget(budget)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 40), jnp.int32)
    jax.ad_checkpoint.print_saved_residuals(model.loss, params, None, ids)
    saved = [line for line in capsys.readouterr().out.splitlines()
             if "routing_picks" in line]
    assert saved and all(line.startswith("i32[80,4]") for line in saved)


def test_trains_through_initialize_with_the_routing_counters(tmp_path):
    import deepspeed_tpu as ds
    model = LagunaModel(_config(bf16=True, activation_checkpointing=True))
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
                "steps_per_print": 10 ** 9,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                "bf16": {"enabled": True, "grads_in_compute_dtype": True},
                "zero_optimization": {"stage": 2},
                "monitor": {"enabled": True, "moe": True,
                            "reconcile": False,
                            "output_path": str(tmp_path)}})
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 128)
    losses = []
    for _ in range(6):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses
    from deepspeed_tpu.monitor.moe import summarize_window
    summary = summarize_window(engine._monitor_moe_stats())
    assert summary[R.M_DROP_FRAC] == 0.0
    assert summary[R.M_LAYERS_PER_STEP] == 4.0
    # picks a step, summed over the four sparse layers
    assert summary[R.M_TOKENS_PER_STEP] == 4 * 2 * 40 * 4
    assert summary[R.M_HELD_RANGE] == [4, 12]
    assert summary[R.M_HELD_ROWS_MAX] >= summary[R.M_HELD_ROWS_MEAN] > 0
    assert 0 < summary[R.M_HELD_PICK_SHARE] < 1
    # 256 rows a chunk for at most 320 picks: one trip or two a layer
    assert 1.0 <= summary[R.M_DISPATCH_CHUNKS] <= 2.0
    engine.monitor.close()
    ds.reset_mesh_context()
