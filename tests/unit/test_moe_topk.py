"""k of E routing without a capacity and the layer that holds a range of
the experts (moe/dropless.py): the picks and their weights, nothing
dropped under a skewed router, the layer against a token-by-token loop
(both forms of the grouped product), and the shares test: the parts the
eight ranks of an expert-parallel layout compute add up to the uncut
layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import DroplessMoE, GatedExpertMLP, route_topk
from deepspeed_tpu.ops import dispatch

HIDDEN, EXPERTS, K, FF = 128, 16, 4, 128


@pytest.fixture(params=["xla", "pallas"])
def gmm_form(request):
    """Both forms of the grouped product: the interpreter makes the
    layer's ``gmm`` take the kernels."""
    dispatch.set_pallas_interpret(request.param == "pallas")
    yield request.param
    dispatch.set_pallas_interpret(False)


def _logits(tokens=64, skew=0.0):
    logits = jax.random.normal(jax.random.PRNGKey(3), (tokens, EXPERTS))
    # a router that has all but collapsed onto experts 5 and 6
    return logits.at[:, 5:7].add(skew)


@pytest.mark.parametrize("score, renormalize, total", [
    ("sigmoid", True, 2.5), ("softmax", True, 2.5),
    ("sigmoid", False, None), ("softmax", False, None)])
def test_picks_are_the_k_largest_and_weights_sum_to_the_scale(
        score, renormalize, total):
    logits = _logits()
    routing = route_topk(logits, K, score, renormalize, 2.5)
    scores = np.asarray(jax.nn.sigmoid(logits) if score == "sigmoid"
                        else jax.nn.softmax(logits, -1))
    want = np.argsort(-scores, axis=-1, kind="stable")[:, :K]
    assert (np.sort(np.asarray(routing.picks), -1) == np.sort(want, -1)).all()
    np.testing.assert_allclose(routing.scores, scores, rtol=1e-6)
    picked = np.take_along_axis(scores, np.asarray(routing.picks), -1)
    if renormalize:
        np.testing.assert_allclose(routing.weights.sum(-1), total, rtol=1e-5)
        np.testing.assert_allclose(
            routing.weights, 2.5 * picked / picked.sum(-1, keepdims=True),
            rtol=1e-5)
    else:
        np.testing.assert_allclose(routing.weights, 2.5 * picked, rtol=1e-5)
    assert int(routing.counts.sum()) == logits.shape[0] * K
    assert (np.asarray(routing.counts) == np.bincount(
        np.asarray(routing.picks).ravel(), minlength=EXPERTS)).all()


def test_forced_picks_keep_their_own_scores():
    logits = _logits()
    picks = jnp.tile(jnp.arange(K, dtype=jnp.int32), (logits.shape[0], 1))
    routing = route_topk(logits, K, picks=picks)
    assert (routing.picks == picks).all()
    scores = jax.nn.sigmoid(logits)[:, :K]
    np.testing.assert_allclose(
        routing.weights, scores / scores.sum(-1, keepdims=True), rtol=1e-5)
    with pytest.raises(ValueError):
        route_topk(logits, K, score="tanh")


def _layer(held=None):
    return DroplessMoE(HIDDEN, EXPERTS, K, FF, FF, scale=2.5,
                       experts_held=held)


def _by_token(layer, params, x, picks=None):
    """The layer token by token: no sort, no grouped product."""
    first, count = layer.experts_held
    routing = layer.route(params, x, picks)
    out = layer.shared.apply(params["shared"], x)
    for j in range(layer.k):
        local = routing.picks[:, j] - first
        held = (local >= 0) & (local < count)
        one = jax.tree.map(lambda w: w[jnp.clip(local, 0, count - 1)],
                           params["experts"])
        y = jax.vmap(lambda p, row: layer.expert.apply(p, row[None])[0])(
            one, x)
        out = out + jnp.where(held, routing.weights[:, j], 0.0)[:, None] * y
    return out


@pytest.mark.parametrize("held, skew", [(None, 0.0), ((4, 8), 0.0),
                                        ((4, 4), 6.0), ((8, 8), 6.0)])
def test_layer_matches_a_loop_over_tokens_and_drops_nothing(
        held, skew, gmm_form):
    layer = _layer(held)
    params = layer.init_params(jax.random.PRNGKey(0))
    # a router whose scores follow the skewed logits
    params["router"] = params["router"].at[:, 5:7].add(skew / HIDDEN)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, HIDDEN)) + 1.0
    g = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        (_, routing), _ = (layer.apply(params, x), None)
        picks = routing.picks

        def ours(params, x):
            return jnp.sum(layer.apply(params, x, picks=picks)[0] * g)

        def want(params, x):
            flat = x.reshape(-1, HIDDEN)
            return jnp.sum(_by_token(layer, params, flat, picks).reshape(
                x.shape) * g)

        a = jax.value_and_grad(ours, (0, 1))(params, x)
        b = jax.value_and_grad(want, (0, 1))(params, x)
    for got, ref in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert float(jnp.max(jnp.abs(got - ref))) <= 2e-5 * float(
            jnp.max(jnp.abs(ref)) + 1.0)
    stats = layer.stats(routing)
    tokens = x.shape[0] * x.shape[1]
    assert float(stats.dropped) == 0.0
    assert float(stats.tokens) == tokens * K
    assert float(stats.expert_counts.sum()) == tokens * K
    first, count = layer.experts_held
    assert float(stats.held_rows_max) == float(
        routing.counts[first:first + count].max())
    if skew:
        # nearly every token picked the two favoured experts: far more
        # rows than any capacity factor would have let through
        assert int(routing.counts[5]) > 0.9 * tokens


def test_the_shares_of_eight_ranks_add_up_to_the_uncut_layer():
    whole = _layer()
    params = whole.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (48, HIDDEN))
    with jax.default_matmul_precision("highest"):
        want, routing = whole.apply(params, x)
        shared = whole.shared.apply(params["shared"], x)
        routed = jnp.zeros_like(want)
        per = EXPERTS // 8
        for rank in range(8):
            share = _layer((rank * per, per))
            mine = {**params, "experts": jax.tree.map(
                lambda w: w[rank * per:(rank + 1) * per], params["experts"])}
            # a rank's own initialisation gives its experts the weights
            # the uncut layer gives them
            own = share.init_params(jax.random.PRNGKey(0))
            for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(mine)):
                np.testing.assert_array_equal(a, b)
            part, its = share.apply(mine, x)
            assert (its.picks == routing.picks).all()
            routed = routed + (part - shared)
    # the eight routed parts, and the shared expert once
    np.testing.assert_allclose(routed + shared, want, rtol=2e-5, atol=2e-6)


def test_gated_expert_is_silu_gate_times_up_then_down():
    expert = GatedExpertMLP(HIDDEN, FF)
    p = expert.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, HIDDEN))
    gate, up = x @ p["w1"][:, :FF], x @ p["w1"][:, FF:]
    np.testing.assert_allclose(expert.apply(p, x),
                               (up * gate / (1 + jnp.exp(-gate))) @ p["w2"],
                               rtol=1e-5, atol=1e-7)


def test_a_range_outside_the_scored_experts_is_refused():
    with pytest.raises(ValueError):
        _layer((12, 8))
