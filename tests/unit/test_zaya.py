"""models/zaya.py on the CPU at toy widths (3 layers of 64, 4 query heads
on 2 key/value heads of 16, 4 experts of which 2 are held, a router state
of 16): what the family refuses, the router's carry from layer to layer,
the balancing bias, the door in ``DroplessMoE`` for a caller's logits,
the second carry in the byte budget's plan, the tied table under the
fused cross-entropy's padding, and three steps through
``deepspeed_tpu.initialize``.  The model against its plain reference is
tests/perf/test_zaya_reference.py's."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import ZayaConfig, ZayaModel
from deepspeed_tpu.models.zaya import head_chunk, residual_merge
from deepspeed_tpu.moe.dropless import DroplessMoE
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.ops.fused_cross_entropy import fused_linear_cross_entropy
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing

LAYERS, HIDDEN, WIDE, EXPERTS, VOCAB = 3, 64, 16, 4, 250


def _config(**over):
    fields = dict(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_hidden_layers=LAYERS,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=EXPERTS, moe_intermediate_size=32,
        router_hidden_size=WIDE, experts_held=(1, 2), bf16=False,
        initializer_range=0.3)
    return ZayaConfig(**{**fields, **over})


@pytest.fixture(scope="module")
def toy():
    model = ZayaModel(_config())
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, VOCAB)
    return model, params, ids


# ---------------------------------------------------------------------- #
# what the family refuses, in words
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("over,said", [
    (dict(renormalize=True), "cut off from the loss"),
    (dict(layer_types=("hybrid", "hybrid_sliding", "hybrid")), "layer_types"),
    (dict(layer_types=("hybrid",)), "layer_types"),
    (dict(num_attention_heads=3), "multiple of the key/value heads"),
    (dict(experts_held=(3, 2)), "no range of the 4 experts"),
])
def test_the_configuration_refuses_what_the_family_cannot_do(over, said):
    with pytest.raises(ValueError, match=said):
        ZayaModel(_config(**over))


def test_two_picks_a_token_may_be_renormalised():
    assert _config(num_experts_per_tok=2, renormalize=True).renormalize


def _engine_config(**extra):
    return {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
            **extra}


def _initialize(config, **mesh):
    model = ZayaModel(_config(activation_checkpointing=True))
    ds.reset_mesh_context()
    made = ds.initialize_mesh(devices=jax.devices()[:max(
        mesh.values(), default=1)], **(mesh or {"data": 1}))
    return ds.initialize(model=model, mesh=made,
                         model_parameters=jax.jit(model.init_params)(
                             jax.random.PRNGKey(0)), config=config)[0]


@pytest.mark.parametrize("path,config,mesh", [
    ("zero3_streaming", {"zero_optimization": {"stage": 3}}, {}),
    ("pipeline", {}, {"pipe": 2}),
])
def test_the_engine_refuses_what_the_model_has_not_run(path, config, mesh):
    with pytest.raises(NotImplementedError, match=path):
        _initialize(_engine_config(**config), **mesh)
    ds.reset_mesh_context()


def test_an_expert_axis_is_refused_by_the_layer(toy):
    model, params, ids = toy
    ds.reset_mesh_context()
    ds.initialize_mesh(devices=jax.devices()[:2], expert=2, data=1)
    try:
        with pytest.raises(NotImplementedError, match="expert axis is 2"):
            model.loss(params, None, ids)
    finally:
        ds.reset_mesh_context()


# ---------------------------------------------------------------------- #
# the door for a caller's logits
# ---------------------------------------------------------------------- #
def test_a_layer_without_its_own_router_has_no_matrix_and_needs_logits():
    layer = DroplessMoE(HIDDEN, EXPERTS, 1, 32, None, score="softmax",
                        renormalize=False, own_router=False,
                        selection_bias=True)
    params = layer.init_params(jax.random.PRNGKey(0))
    assert sorted(params) == ["bias", "experts"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, HIDDEN))
    logits = jax.random.normal(jax.random.PRNGKey(2), (2, 6, EXPERTS))
    with pytest.raises(ValueError, match="needs its caller's"):
        layer.apply(params, x)
    y, routing = layer.apply(params, x, logits=logits)
    assert y.shape == x.shape
    np.testing.assert_allclose(
        routing.scores, jax.nn.softmax(logits.reshape(-1, EXPERTS), -1),
        rtol=1e-6)
    # top-1 by softmax, the weight NOT renormalised: the probability
    np.testing.assert_array_equal(
        routing.picks[:, 0], jnp.argmax(logits.reshape(-1, EXPERTS), -1))
    np.testing.assert_allclose(routing.weights[:, 0],
                               jnp.max(routing.scores, -1), rtol=1e-6)
    assert float(jnp.max(routing.weights)) < 1.0
    # and the gradient reaches the logits through that weight
    grad = jax.grad(lambda z: jnp.sum(layer.apply(params, x, logits=z)[0]
                                      ** 2))(logits)
    assert float(jnp.linalg.norm(grad)) > 0


def test_a_layer_with_its_own_router_is_what_it_was_and_takes_no_logits():
    layer = DroplessMoE(HIDDEN, EXPERTS, 2, 32, 32)
    params = layer.init_params(jax.random.PRNGKey(0))
    assert sorted(params) == ["experts", "router", "shared"]
    x = jax.random.normal(jax.random.PRNGKey(1), (5, HIDDEN))
    with pytest.raises(ValueError, match="takes no logits"):
        layer.apply(params, x, logits=jnp.zeros((5, EXPERTS)))
    assert layer.apply(params, x)[0].shape == x.shape


# ---------------------------------------------------------------------- #
# the router: its carry, its bias
# ---------------------------------------------------------------------- #
def _set(params, path, value):
    """A copy of ``params`` with the leaf at ``path`` replaced."""
    params = jax.tree.map(lambda a: a, params)
    node = params
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = value
    return params


def test_a_layers_scores_move_with_the_state_of_the_layer_before(toy):
    """With every pick forced onto expert 0, which is not held (the
    stream is then the same whatever the routers say), layer 0's
    down-projection bias moves layer 0's scores, and layer 1's and 2's
    through the carried state alone: with layer 1's gamma at zero they
    stand still from layer 1 on."""
    model, params, ids = toy
    picks = jnp.zeros((LAYERS, ids.size, 1), jnp.int32)

    @jax.jit
    def scores(p):
        return model._run(p, ids, picks, lambda r: r.scores)[2]

    bumped = _set(params, ("layers", "router", "down_b"),
                  params["layers"]["router"]["down_b"].at[0].add(0.5))
    base, moved = scores(params), scores(bumped)
    assert all(float(jnp.max(jnp.abs(base[i] - moved[i]))) > 1e-4
               for i in range(LAYERS))
    cut = ("entry", "gamma")
    no_carry = params["entry"]["gamma"].at[0].set(0.0)
    base = scores(_set(params, cut, no_carry))
    moved = scores(_set(bumped, cut, no_carry))
    assert float(jnp.max(jnp.abs(base[0] - moved[0]))) > 1e-4
    np.testing.assert_array_equal(base[1:], moved[1:])


def test_a_layers_picks_move_with_the_carried_state(toy):
    model, params, ids = toy
    routing = jax.jit(model.routing)
    base = routing(params, ids)[1]
    moved = routing(_set(
        params, ("entry", "gamma"), -4.0 * params["entry"]["gamma"]), ids)[1]
    np.testing.assert_array_equal(base[0], moved[0])     # reads no state
    assert int(jnp.sum(base[1] != moved[1])) > 0


def test_layer_zero_has_no_entry_scales_and_no_gamma(toy):
    model, params, _ = toy
    assert {k: v.shape for k, v in params["entry"].items()} == {
        "a": (LAYERS - 1, HIDDEN), "c": (LAYERS - 1, HIDDEN),
        "gamma": (LAYERS - 1, WIDE)}
    assert sorted(params["layers"]["attn_res"]) == ["d", "g"]
    assert sorted(params["layers"]["moe_res"]) == ["a", "c", "d", "g"]
    rows = model.entries(params)
    assert all(v.shape[0] == LAYERS for v in rows.values())
    assert (float(rows["a"][0, 0]), float(rows["c"][0, 0]),
            float(rows["gamma"][0, 0])) == (1.0, 0.0, 0.0)
    # one layer: nothing in ``entry``, and the model still runs
    single = ZayaModel(_config(num_hidden_layers=1))
    p = jax.jit(single.init_params)(jax.random.PRNGKey(0))
    assert p["entry"]["a"].shape == (0, HIDDEN)
    assert math.isfinite(float(jax.jit(single.loss)(
        p, None, jnp.zeros((1, 8), jnp.int32))))


def test_the_bias_moves_the_pick_and_takes_no_gradient(toy):
    model, params, ids = toy
    routing = jax.jit(model.routing)
    scores, picks = routing(params, ids)
    lifted = _set(params, ("layers", "moe", "bias"),
                  jnp.zeros((LAYERS, EXPERTS)).at[:, 2].set(1.0))
    # with the picks forced to the lifted model's the scores are the same
    # function of the same stream: layer 0's are equal whatever follows
    new_scores, new_picks = routing(lifted, ids)
    assert set(np.asarray(new_picks).ravel()) == {2}
    assert int(jnp.sum(picks != 2)) > 0
    np.testing.assert_allclose(new_scores[0], scores[0], rtol=1e-6)
    grads = jax.jit(jax.grad(lambda p: model.loss(p, None, ids)))(lifted)
    assert float(jnp.max(jnp.abs(grads["layers"]["moe"]["bias"]))) == 0.0
    # the router still learns: expert 2 is held (1 and 2 are)
    assert float(jnp.linalg.norm(grads["layers"]["router"]["w3"])) > 0


def test_a_pick_on_an_absent_expert_adds_nothing_but_d(toy):
    """Every pick forced onto expert 0, which is not held: the expert
    sublayer's output is zero and the merge is ``a (x + c) + g d``."""
    model, params, ids = toy
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, HIDDEN))
    p = jax.tree.map(lambda a: a[0], params["layers"])
    logits = jnp.zeros((2, 24, EXPERTS)).at[..., 0].set(5.0)
    y, routing = model.moe.apply(p["moe"], x, logits=logits)
    assert set(np.asarray(routing.picks).ravel()) == {0}
    assert float(jnp.max(jnp.abs(y))) == 0.0
    res = {"a": jnp.full((HIDDEN,), 1.5), "c": jnp.full((HIDDEN,), 0.25),
           "g": jnp.full((HIDDEN,), 2.0), "d": jnp.full((HIDDEN,), -0.5)}
    np.testing.assert_allclose(residual_merge(x, y, res),
                               1.5 * (x + 0.25) + 2.0 * -0.5, rtol=1e-6)
    # the first sublayer of the model: no a, c
    first = {"g": res["g"], "d": res["d"]}
    np.testing.assert_allclose(residual_merge(x, y, first), x - 1.0,
                               rtol=1e-6)


# ---------------------------------------------------------------------- #
# the second carry in the byte budget, the plan's line
# ---------------------------------------------------------------------- #
def test_the_router_state_is_charged_to_the_byte_budget_a_layer():
    model = ZayaModel(_config(activation_checkpointing=True))
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 24), jnp.int32)
    budget = checkpointing.RematBudget(10 ** 9, working_set=0)
    model.install_remat_budget(budget)
    jax.make_jaxpr(lambda p: model.loss(p, None, ids))(params)
    plan = budget.plan
    # float32 [2, 24, 16], the minor dimension padded to 128 lanes
    assert plan[R.M_REMAT_SIDE_CARRY_BYTES] == 2 * 24 * 128 * 4
    assert plan[R.M_REMAT_LAYERS] == LAYERS
    rows = model.moe.working_set_bytes(2 * 24, 4)
    assert plan[R.M_REMAT_WORKING_SET_BYTES] == rows + LAYERS * (
        2 * 24 * 128 * 4)
    assert plan[R.M_STACK_CCA] == (4, 2, 16, 2, 2, WIDE)
    line = checkpointing.stack_plan_line(plan)
    assert "compressed convolutional attention: 4 query heads on 2" in line
    assert "carries a state of 16 from layer to layer" in line
    assert "routed experts 1 to 2 of 4 held here" in line
    assert line.endswith("rotary: hybrid xla")


def test_one_array_as_carry_plans_what_it_planned():
    """A stack without a side carry: no field, the working set as it
    was."""
    def body(carry, xs):
        return carry + xs, None

    carry, xs = jnp.zeros((2, 8, HIDDEN)), jnp.zeros((3, HIDDEN))
    plans = []
    for handed in (carry, (carry,)):
        budget = checkpointing.RematBudget(10 ** 9, working_set=0)
        checkpointing.checkpoint_layers(
            [((lambda c, x: (c[0] + x,) if isinstance(c, tuple)
               else c + x), xs)] if isinstance(handed, tuple)
            else [(body, xs)], budget, handed, 100)
        plans.append(budget.plan)
    assert plans[0] == plans[1]
    assert R.M_REMAT_SIDE_CARRY_BYTES not in plans[0]


# ---------------------------------------------------------------------- #
# the tied table at a vocabulary that is no whole number of chunks
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("vocab,tokens,want", [
    (32784, 16382, 16392),      # the cell: two equal parts, nothing padded
    (12544, 4095, 12544),       # whole lane tiles: even_chunk's
    (250, 46, 250),             # one chunk
    (65551, 16382, None),       # a prime: the auto plan pads
])
def test_the_head_chunk_pads_nothing_where_equal_parts_exist(
        vocab, tokens, want):
    assert head_chunk(vocab, tokens) == want


@pytest.mark.parametrize("chunk", [64, 125, None])
def test_a_tied_table_under_the_padded_cross_entropy(chunk):
    """250 rows in chunks of 64 (the last padded and masked), of 125
    (equal parts) and in one: the loss and the ONE leaf's gradient, the
    head's with its pad columns sliced off plus the embedding's, against
    the plain form."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    table = 0.3 * jax.random.normal(k[0], (VOCAB, HIDDEN))
    ids = jax.random.randint(k[1], (40,), 0, VOCAB)
    mix = jax.random.normal(k[2], (HIDDEN, HIDDEN)) / 8.0

    def fused(table):
        h = jnp.tanh(table[ids[:-1]] @ mix)
        return fused_linear_cross_entropy(h, table.T, ids[1:], chunk)

    def plain(table):
        h = jnp.tanh(table[ids[:-1]] @ mix)
        logp = jax.nn.log_softmax(h @ table.T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))

    got, grad = jax.value_and_grad(fused)(table)
    want, want_grad = jax.value_and_grad(plain)(table)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert grad.shape == (VOCAB, HIDDEN)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------- #
# through deepspeed_tpu.initialize
# ---------------------------------------------------------------------- #
def test_three_steps_move_the_biases_the_loss_and_the_counters(toy):
    _, _, ids = toy
    engine = _initialize(_engine_config(
        zero_optimization={"stage": 2}, bf16={"enabled": False}))
    losses = []
    for _ in range(3):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[2] < losses[1] < losses[0]
    bias = np.asarray(engine.params["layers"]["moe"]["bias"])
    assert bias.shape == (LAYERS, EXPERTS)
    # three updates of gamma up or down, by the sign of mean(c) - c
    assert set(np.round(np.abs(bias) / 0.001).astype(int).ravel()) <= {
        0, 1, 2, 3}
    assert np.abs(bias).max() > 0
    counters = engine.model_counters()
    assert set(counters) == {
        R.M_LOAD_MAX_OVER_MEAN, R.M_ROUTER_STATE_RMS, R.M_CCA_TAU_MEAN,
        R.M_RESIDUAL_SCALE_MEAN}
    assert counters[R.M_LOAD_MAX_OVER_MEAN] >= 1.0
    assert counters[R.M_ROUTER_STATE_RMS] > 0
    # 1 at the start, moved by at most three steps of the optimizer
    assert counters[R.M_CCA_TAU_MEAN] == pytest.approx(1.0, abs=0.02)
    assert counters[R.M_RESIDUAL_SCALE_MEAN] == pytest.approx(1.0, abs=0.02)
    # the scales and the temperature are the optimizer's, and moved
    fresh = jax.jit(ZayaModel(_config()).init_params)(jax.random.PRNGKey(0))
    for path in (("entry", "a"), ("entry", "gamma"),
                 ("layers", "attn", "tau"), ("layers", "moe_res", "d")):
        a, b = engine.params, fresh
        for key in path:
            a, b = a[key], b[key]
        assert float(jnp.max(jnp.abs(a - b))) > 0, path
    ds.reset_mesh_context()
