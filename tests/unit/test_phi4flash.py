"""``models/phi4flash.py`` against the plain reference of the benchmark
(``perf/families/phi4flash_reference.py``) at a small size on the CPU,
float32: every mixer kind alone, then the stack whole with two readers of
the kept memory and of the kept keys and values, loss and every gradient
leaf; the published depth's parameter count; the stack's plan."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashModel
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    RematBudget)
from perf.families import phi4flash as family
from perf.families import phi4flash_reference as reference

WINDOW = 8


def _config(**over):
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
              num_attention_heads=8, num_key_value_heads=4,
              sliding_window=WINDOW, self_pairs=1, cross_pairs=2,
              bf16=False)
    kw.update(over)
    return Phi4FlashConfig(**kw)


def _params(model, seed=0):
    """Seeded weights with every bias, norm weight and lambda vector off
    its initial 0 or 1, so that no term is silent."""
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


def _mixer_of(model, params, kind):
    plan = family.reference_plan(model.config)
    at = [k for _, k in plan].index(kind)
    return (family.reference_params(params, plan)["layers"][at]["mixer"],
            plan[at][0])


@pytest.fixture(scope="module")
def setup():
    model = Phi4FlashModel(_config())
    params = _params(model)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (2, 40, 64))
    memory = jax.random.normal(ks[1], (2, 40, 128))
    kv = tuple(jax.random.normal(k, (2, 2, 40, 8))
               for k in jax.random.split(ks[2], 4))
    g = jax.random.normal(ks[3], (2, 40, 64))
    return model, params, x, memory, kv, g


def _grads(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(fn, range(len(args)))(*args)


def test_mamba_mixer_alone(setup):
    model, params, x, _, _, g = setup
    ours_p = params["mid_mamba"]["mixer"]
    ref_p, _ = _mixer_of(model, params, "mamba_mem")

    def ours(p, x):
        out, y = model._mamba(p, x)
        return jnp.sum(out * g) + jnp.sum(jnp.sin(y))   # y is an output too

    def want(p, x):
        out, y = reference.mamba(p, x)
        return jnp.sum(out * g) + jnp.sum(jnp.sin(y))

    (a, (ga, gx)), (b, (gb, gy)) = _grads(ours, ours_p, x), _grads(
        want, ref_p, x)
    _close((a, gx), (b, gy))
    _close(family.reference_params(
        {**params, "mid_mamba": {**params["mid_mamba"], "mixer": ga}},
        family.reference_plan(model.config))["layers"][2]["mixer"], gb)


@pytest.mark.parametrize("seq,kernels", [
    (64, ["causal_conv_bwd", "causal_conv_fwd"]), (40, [])])
def test_mamba_mixer_through_the_conv_kernels(setup, seq, kernels,
                                              monkeypatch):
    """The mixer's conv is ops/causal_conv.py's, over the first half of
    the projection: through the kernels (the interpreter; 128 channels,
    a whole block of positions) the mixer's outputs and gradients are
    the XLA form's; 40 positions are no block, and the same switch leaves
    the XLA form."""
    from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
    from deepspeed_tpu.ops import causal_conv
    model, params, _, _, _, _ = setup
    p = params["mid_mamba"]["mixer"]
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    x = jax.random.normal(ks[0], (2, seq, 64))
    g = jax.random.normal(ks[1], (2, seq, 64))

    def ours(p, x):
        out, y = model._mamba(p, x)
        return jnp.sum(out * g) + jnp.sum(jnp.sin(y))

    want = _grads(ours, p, x)
    monkeypatch.setattr(causal_conv, "pallas_interpret", lambda: True)
    assert sorted({c.eqn.params["name"] for c in iter_eqns(
        jax.make_jaxpr(jax.grad(ours, (0, 1)))(p, x).jaxpr)
        if c.eqn.primitive.name == "pallas_call"}) == kernels
    _close(_grads(ours, p, x), want)


def test_gated_memory_unit_alone(setup):
    model, params, x, memory, _, g = setup
    ours_p = jax.tree.map(lambda a: a[0], params["cross"])["gmu"]["mixer"]
    ref_p = {"Win": ours_p["in_w"], "Wout": ours_p["out_w"]}
    ours = _grads(lambda p, x, m: jnp.sum(model._gmu(p, x, m) * g),
                  ours_p, x, memory)
    want = _grads(lambda p, x, m: jnp.sum(
        ((m * reference.silu(x @ p["Win"])) @ p["Wout"]) * g),
        ref_p, x, memory)
    _close(ours[0], want[0])
    _close((ours[1][0]["in_w"], ours[1][0]["out_w"], ours[1][1:]),
           (want[1][0]["Win"], want[1][0]["Wout"], want[1][1:]))


@pytest.mark.parametrize("kind, window", [("window", WINDOW), ("full", None)])
def test_attention_mixer_alone(setup, kind, window):
    model, params, x, _, _, g = setup
    layer = (params["mid_attn"] if kind == "full" else jax.tree.map(
        lambda a: a[0], params["self"])["attn"])
    ref_p, index = _mixer_of(model, params, kind)
    lam0 = model.config.lambda_init(index)

    def loss(out, kv):     # the kept keys and values are outputs too
        return jnp.sum(out * g) + sum(jnp.sum(jnp.cos(t)) for t in kv)

    ours = _grads(lambda p, x: loss(*model._attn(p, x, lam0, window)),
                  layer["mixer"], x)
    want = _grads(lambda p, x: loss(*reference.attention(
        p, x, index, window or 0, 1e-5, 8)), ref_p, x)
    _close((ours[0], ours[1][1]), (want[0], want[1][1]))
    names = {"qkv_w": "Wqkv", "qkv_b": "bqkv", "out_w": "Wo", "out_b": "bo",
             "subln_w": "g", "lq1": "lq1", "lk1": "lk1", "lq2": "lq2",
             "lk2": "lk2"}
    for mine, theirs in names.items():
        _close(ours[1][0][mine], want[1][0][theirs])


def test_cross_attention_alone(setup):
    model, params, x, _, kv, g = setup
    ours_p = jax.tree.map(lambda a: a[1], params["cross"])["cross"]["mixer"]
    plan = family.reference_plan(model.config)
    ref_p = family.reference_params(params, plan)["layers"][7]["mixer"]
    index = plan[7][0]
    assert plan[7] == (21, "cross")
    ours = _grads(lambda p, x, kv: jnp.sum(model._cross(
        p, x, kv, model.config.lambda_init(index)) * g), ours_p, x, kv)
    want = _grads(lambda p, x, kv: jnp.sum(reference.differential(
        p, x @ p["Wq"] + p["bq"], kv, index, 0, 1e-5, 8) * g), ref_p, x, kv)
    # the loss, and the cotangents of x and of the kept keys and values
    _close((ours[0], ours[1][1:]), (want[0], want[1][1:]))
    _close([ours[1][0][k] for k in ("q_w", "q_b", "out_w", "out_b")],
           [want[1][0][k] for k in ("Wq", "bq", "Wo", "bo")])


@pytest.mark.parametrize("checkpointing, scan", [
    (False, None), (True, None), (True, True)])
def test_the_stack_whole_every_gradient_leaf(checkpointing, scan):
    """Two cross pairs: the cotangents of m and of layer 17's keys and
    values are sums over two readers each; every leaf of the middle
    layers' gradients carries them.  `scan`: the pairs as `lax.scan`
    bodies (the published depth's form), m and the keys and values closed
    over by the cross-decoder's."""
    model = Phi4FlashModel(_config(activation_checkpointing=checkpointing,
                                   scan_layers=scan, self_pairs=2))
    params = _params(model, seed=3)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    plan = family.reference_plan(model.config)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(
            params, None, ids)
        want, want_grads = jax.jit(
            reference.loss_and_grads, static_argnums=(2, 3, 4, 5))(
            family.reference_params(params, plan), ids, plan, 1e-5, 8, WINDOW)
    _close(loss, want, rtol=1e-5)
    _close(family.reference_params(grads, plan), want_grads)
    assert float(reference.global_norm(
        want_grads["layers"][2]["mixer"]["A_log"])) > 0


def test_published_depth_counts_3_85_billion_parameters():
    count = Phi4FlashModel(Phi4FlashConfig()).num_params()
    assert abs(count - 3.85e9) <= 0.01 * 3.85e9, count
    plan = Phi4FlashConfig().layer_plan()
    assert [i for i, _, _ in plan] == list(range(32))
    kinds = [k for _, k, _ in plan]
    assert (kinds.count("mamba"), kinds.count("attn"), kinds.count("gmu"),
            kinds.count("cross")) == (8, 8, 7, 7)
    assert plan[16][1:] == ("mamba+memory", 0) and plan[1][2] == 512


def test_the_cut_is_two_counts_and_a_row_count():
    cut = Phi4FlashConfig(vocab_size=25088, self_pairs=1, cross_pairs=1)
    assert [i for i, _, _ in cut.layer_plan()] == [0, 1, 16, 17, 18, 19]
    assert abs(Phi4FlashModel(cut).num_params() - 697.3e6) < 0.5e6
    # the family's own count of the same shapes, from the equations
    import json
    from pathlib import Path
    config = json.loads((Path(__file__).resolve().parents[2] / "perf"
                         / "configs" / "phi4-mini-flash.json").read_text())
    per_kind = family.layer_parameters(config)
    counted = (sum(per_kind[k] for k in family.kept_kinds(config))
               + 2 * 2560 + 25088 * 2560)
    assert counted == Phi4FlashModel(cut).num_params()


def test_the_plan_rides_on_the_recomputation_plan():
    model = Phi4FlashModel(_config(activation_checkpointing=True))
    budget = RematBudget(10 ** 12, working_set=0)
    model.install_remat_budget(budget)
    params = model.init_params(jax.random.PRNGKey(0))
    jax.eval_shape(model.loss, params, None, jnp.zeros((2, 40), jnp.int32))
    plan = budget.take_plan()
    assert [(i, k) for i, k, _ in plan[R.M_STACK_LAYERS]] == [
        (0, "mamba"), (1, "attn"), (16, "mamba+memory"), (17, "attn+kv"),
        (18, "gmu"), (19, "cross"), (20, "gmu"), (21, "cross")]
    assert plan[R.M_STACK_LAYERS][1][2] == WINDOW
    assert plan[R.M_STACK_SCAN_CHUNK] == 128
    assert plan[R.M_STACK_SCAN_ENTRY_BYTES] == 2 * 1 * 128 * 16 * 4
    kept = dict(plan[R.M_STACK_CROSS_LAYER_KEPT])
    assert kept["layer 16 scan output m"] == 2 * 40 * 128 * 4
    assert kept["layer 17 keys"] == kept["layer 17 values"] == 2 * 4 * 40 * 8 * 4
    assert plan[R.M_REMAT_LAYERS] == 5      # 1 + 1 + 1 + 2 scanned steps


def test_trains_through_initialize():
    import deepspeed_tpu as ds
    model = Phi4FlashModel(_config(bf16=True, activation_checkpointing=True))
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
                "steps_per_print": 10 ** 9,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                "bf16": {"enabled": True, "grads_in_compute_dtype": True},
                "zero_optimization": {"stage": 2}})
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    losses = []
    for _ in range(6):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses
    ds.reset_mesh_context()
