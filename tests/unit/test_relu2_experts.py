"""The experts WITHOUT a gate (moe/experts.py ``ReluSquaredExpertMLP``)
in ``moe.DroplessMoE``: value and every gradient against a dense loop, on
``ragged_dot`` and on the grouped product's kernels at a width of half a
lane tile; what ``working_set_bytes`` counts for them; and THE SHARE TEST
of the model-configs guide: the sixteen ranks' parts of one expert layer,
the shared expert counted once, add up to the uncut plain reference's
layer (perf/families/nemotron_h_reference.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import DroplessMoE, ReluSquaredExpertMLP
from deepspeed_tpu.ops import dispatch
from perf.families import nemotron_h_reference as reference

HIDDEN, EXPERTS, K, FF, SHARED = 128, 16, 3, 64, 192


def _layer(held=None):
    return DroplessMoE(HIDDEN, EXPERTS, K, FF, SHARED, scale=2.5,
                       experts_held=held, init_std=0.1,
                       selection_bias=True, first_chunk_always=True,
                       expert=ReluSquaredExpertMLP)


def _dense(layer, params, x):
    """The layer by a dense loop over the held experts, every expert on
    every token: relu squared, NO gate, two matrices."""
    scores = jax.nn.sigmoid(x @ params["router"])
    _, picks = jax.lax.top_k(scores + params["bias"], layer.k)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = layer.scale * picked / jnp.sum(picked, -1, keepdims=True)
    first, count = layer.experts_held
    y = jnp.square(jnp.maximum(x @ params["shared"]["w1"], 0.0)) \
        @ params["shared"]["w2"]
    for e in range(count):
        mine = jnp.sum(weights * (picks == first + e), axis=-1)
        w1, w2 = (params["experts"][n][e] for n in ("w1", "w2"))
        y = y + mine[:, None] * (jnp.square(jnp.maximum(x @ w1, 0.0)) @ w2)
    return y


@pytest.mark.parametrize("form", ["xla", "pallas"])
@pytest.mark.parametrize("held", [None, (4, 4)])
def test_ungated_experts_in_the_dropless_layer_are_a_dense_loop(held, form):
    """Value and every gradient; the experts' width 64 is half a lane
    tile, which the grouped product's kernels take as one block (the
    interpreter) and ``ragged_dot`` as it is."""
    layer = _layer(held)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    params = layer.init_params(keys[0])
    params["bias"] = 0.05 * jax.random.normal(keys[3], (EXPERTS,))
    assert params["experts"]["w1"].shape[1:] == (HIDDEN, FF)
    assert params["shared"]["w1"].shape == (HIDDEN, SHARED)
    x = jax.random.normal(keys[1], (96, HIDDEN))
    cot = jax.random.normal(keys[2], (96, HIDDEN))
    dispatch.set_pallas_interpret(form == "pallas")
    try:
        with jax.default_matmul_precision("highest"):
            got = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
                layer.apply(p, x)[0] * cot), argnums=(0, 1)))(params, x)
            want = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
                _dense(layer, p, x) * cot), argnums=(0, 1)))(params, x)
    finally:
        dispatch.set_pallas_interpret(False)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-4)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1]),
                    strict=True):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * float(
            jnp.max(jnp.abs(b)) + 1e-9)
    # the working set counts the first product at its real width
    gated = DroplessMoE(HIDDEN, EXPERTS, K, FF, SHARED, experts_held=held)
    rows = layer.capacity(96)
    assert gated.working_set_bytes(96, 2) - layer.working_set_bytes(96, 2) \
        == rows * 2 * FF


def test_the_shares_of_sixteen_ranks_add_up_to_the_uncut_references_layer():
    """THE SHARE TEST: one expert layer cut sixteen ways (an expert a
    rank here), each rank's part from ``DroplessMoE`` on its own experts,
    the shared expert counted once, against the plain reference's layer
    with all sixteen experts."""
    whole = _layer()
    params = whole.init_params(jax.random.PRNGKey(0))
    params["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(5),
                                              (EXPERTS,))
    x = jax.random.normal(jax.random.PRNGKey(1), (48, HIDDEN))
    spec = reference.Spec(picked=K, scale=2.5, held_first=0)
    uncut = {"Wr": params["router"], "bias": params["bias"],
             "shared": {"Wup": params["shared"]["w1"],
                        "Wdown": params["shared"]["w2"]},
             "experts": {"Wup": params["experts"]["w1"],
                         "Wdown": params["experts"]["w2"]}}
    with jax.default_matmul_precision("highest"):
        want, (_, picks) = reference.experts(uncut, x, spec)
        shared = reference.relu2_mlp(uncut["shared"], x)
        routed = jnp.zeros_like(want)
        for rank in range(16):
            share = _layer((rank, 1))
            mine = {**params, "experts": jax.tree.map(
                lambda w: w[rank:rank + 1], params["experts"])}
            # a rank's own initialisation gives its expert the weights
            # the uncut layer gives it
            own = share.init_params(jax.random.PRNGKey(0))
            for a, b in zip(jax.tree.leaves(own["experts"]),
                            jax.tree.leaves(mine["experts"])):
                np.testing.assert_array_equal(a, b)
            part, its = share.apply(mine, x)
            np.testing.assert_array_equal(np.sort(its.picks, -1),
                                          np.sort(picks, -1))
            routed = routed + (part - shared)
    np.testing.assert_allclose(routed + shared, want, rtol=2e-5, atol=2e-6)
