"""``models/glm4_moe_lite.py`` against the plain reference of the
benchmark (``perf/families/glm4_moe_lite_reference.py``) at a small size
with real ratios on the CPU, float32, through ``ds.initialize``: the
objective, its two terms and every gradient leaf; the shares of the
experts adding up to the uncut layer; the selection bias (no gradient,
no decay, the sign update over summed micro-batches, bit-equal to the
reference over three steps, kept by a checkpoint); the prediction
module's inputs and targets; the shared leaves' gradients; which path
the latent heads' rotation and layout take; the engine paths that refuse
a model with leaves the optimizer does not own."""


import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import glm4_moe_lite as glm
from deepspeed_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                Glm4MoeLiteModel)
from deepspeed_tpu.moe.dropless import DroplessMoE, route_topk
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.rotary import rotary_block
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    RematBudget, stack_plan_line)
from perf.families import glm4_moe_lite as family
from perf.families import glm4_moe_lite_reference as reference

VOCAB, SEQ, EXPERTS = 128, 16, 16


def _config(**over):
    """A query latent narrower than the hidden size, nope 3 x rope, v =
    nope + rope, 16 experts of which 8 are held from the fourth on."""
    kw = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=4, q_lora_rank=24,
              kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
              v_head_dim=16, rope_theta=100.0, n_routed_experts=EXPERTS,
              num_experts_per_tok=4, moe_intermediate_size=32,
              experts_held=(4, 8), bf16=False)
    kw.update(over)
    return Glm4MoeLiteConfig(**kw)


def _spec(cfg):
    return reference.Spec(
        sparse=tuple(i >= cfg.first_k_dense_replace
                     for i in range(cfg.num_hidden_layers)),
        heads=cfg.num_attention_heads, kv_rank=cfg.kv_lora_rank,
        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
        theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
        picked=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        held_first=cfg.experts_held[0], mtp_weight=cfg.mtp_loss_weight,
        gamma=cfg.bias_update_rate)


def _params(model, seed=0):
    """Seeded weights with every norm weight off its initial 1 and every
    bias off 0 (whole multiples of gamma), so that no term is silent."""
    params = model.init_params(jax.random.PRNGKey(seed))
    gamma = model.config.bias_update_rate

    def moved(path, leaf):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1),
                                 zlib.crc32(jax.tree_util.keystr(path).encode())
                                 % 2 ** 31)
        if family._is_bias(path):
            return gamma * jax.random.randint(key, leaf.shape, -30, 31
                                              ).astype(jnp.float32)
        return leaf + 0.05 * jax.random.normal(key, leaf.shape)

    return jax.tree_util.tree_map_with_path(moved, params)


def _ids(seed, rows=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0,
                              VOCAB)


def _engine(model, params, gas=1, **config):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh, model_parameters=params, config={
            "train_batch_size": 2 * gas,
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": gas, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-3, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 2}, **config})
    return engine


def _close(ours, want, rtol=2e-4):
    ours = jax.tree_util.tree_leaves_with_path(ours)
    want = jax.tree.leaves(want)
    assert len(ours) == len(want)
    for (path, a), b in zip(ours, want):
        assert a.shape == b.shape, path
        assert float(jnp.max(jnp.abs(a - b))) <= rtol * float(
            jnp.max(jnp.abs(b)) + 1e-9), jax.tree_util.keystr(path)


_reference_jit = jax.jit(reference.loss_and_grads, static_argnums=(2,))
_reference_forward = jax.jit(reference.forward, static_argnums=(2,))


def _reference_of(params, ids, spec):
    with jax.default_matmul_precision("highest"):
        return _reference_jit(family.reference_params(params, spec), ids,
                              spec)


# ---------------------------------------------------------------------- #
# (a) the objective, its terms and every gradient leaf, through the engine
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("held, checkpointing", [
    ((4, 8), False), ((4, 8), True), ((0, 2), False)])
def test_engine_loss_terms_and_every_gradient_leaf(held, checkpointing):
    cfg = _config(experts_held=held,
                  activation_checkpointing=checkpointing)
    model, spec = Glm4MoeLiteModel(cfg), _spec(cfg)
    params, ids = _params(model), _ids(5)
    with jax.default_matmul_precision("highest"):
        engine = _engine(model, params)
        loss = float(engine.forward(ids))
        counters = engine.model_counters()
        terms = counters[R.M_MAIN_LOSS], counters[R.M_MTP_LOSS]
        grads = family.reference_params(engine._cached_grads, spec)
    (want, (main, mtp, scores, picks)), want_grads = _reference_of(
        params, ids, spec)
    assert loss == pytest.approx(float(want), rel=2e-5)
    assert terms[0] == pytest.approx(float(main), rel=2e-5)
    assert terms[1] == pytest.approx(float(mtp), rel=2e-5)
    assert loss == pytest.approx(terms[0] + 0.3 * terms[1], rel=1e-6)
    _close(grads, want_grads)
    routed = jax.jit(model.routing)(params, ids)
    np.testing.assert_allclose(routed[0], scores, rtol=2e-5)
    assert (np.sort(routed[1], -1) == np.sort(picks, -1)).all()
    ds.reset_mesh_context()


def test_the_published_depth_counts_its_parameters():
    # every layer and expert, the whole vocabulary: the source's 30B-A3B
    model = Glm4MoeLiteModel(Glm4MoeLiteConfig())
    assert 30.0e9 < model.num_params() < 31.5e9
    plan = model.stack_plan()
    assert plan[R.M_STACK_LATENT] == (768, 512, 192, 64, 256, 20)
    assert plan[R.M_STACK_MTP] == (1, 0.3)
    assert plan[R.M_STACK_EXPERTS_HELD] == (0, 64, 64)
    assert plan[R.M_STACK_LAYERS][-1] == (47, "mtp:latent+experts", 0)


# ---------------------------------------------------------------------- #
# (c) the shares add up to the uncut layer
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("held", [2, 4, 8])
def test_all_shares_add_up_to_the_uncut_sparse_layer(held):
    hid, k = 64, 4
    whole = DroplessMoE(hid, EXPERTS, k, 32, 32, scale=1.8,
                        selection_bias=True)
    params = whole.init_params(jax.random.PRNGKey(2))
    params["bias"] = 0.001 * jax.random.randint(
        jax.random.PRNGKey(3), (EXPERTS,), -30, 31).astype(jnp.float32)
    params = jax.tree.map(lambda a: a * 6 if a.ndim > 1 else a, params)
    u = jax.random.normal(jax.random.PRNGKey(4), (40, hid))

    def gated(p):
        gate, up = jnp.split(p["w1"], 2, axis=-1)
        return {"Wgate": gate, "Wup": up, "Wdown": p["w2"]}

    spec = reference.Spec(sparse=(True,), held_first=0)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.sparse_ffn(
            {"Wr": params["router"], "bias": params["bias"],
             "shared": gated(params["shared"]),
             "experts": gated(params["experts"])}, u, spec)
        shared = whole.shared.apply(params["shared"], u)
        total = shared
        for first in range(0, EXPERTS, held):
            share = DroplessMoE(hid, EXPERTS, k, 32, 32, scale=1.8,
                                experts_held=(first, held),
                                selection_bias=True)
            mine = {**params, "experts": jax.tree.map(
                lambda a: a[first:first + held], params["experts"])}
            y, _ = share.apply(mine, u)
            # what every chip computes alike is counted once
            total = total + (y - shared)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------- #
# (d) the selection bias
# ---------------------------------------------------------------------- #
def test_the_bias_chooses_and_nothing_else():
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.4, -1.0]])
    plain = route_topk(logits, 2)
    assert sorted(np.asarray(plain.picks[0])) == [0, 1]
    # too small to change the order: nothing changes
    small = route_topk(logits, 2, bias=jnp.asarray([0, 0, .01, 0, 0.]))
    assert sorted(np.asarray(small.picks[0])) == [0, 1]
    # lifts expert 2 over expert 1: the pick changes, the scores do not,
    # and the weights are the scores at the new picks
    lifted = route_topk(logits, 2, bias=jnp.asarray([0, 0, .2, 0, 0.]))
    assert sorted(np.asarray(lifted.picks[0])) == [0, 2]
    np.testing.assert_array_equal(lifted.scores, plain.scores)
    s = np.asarray(plain.scores[0])
    order = np.asarray(lifted.picks[0])
    np.testing.assert_allclose(lifted.weights[0],
                               s[order] / s[order].sum(), rtol=1e-6)
    np.testing.assert_array_equal(lifted.counts, [1, 0, 1, 0, 0])
    # on forced picks the bias is inert
    forced = route_topk(logits, 2, picks=plain.picks,
                        bias=jnp.asarray([0, 0, 5., 0, 0]))
    np.testing.assert_array_equal(forced.weights, plain.weights)


def test_the_bias_has_exactly_no_gradient():
    cfg = _config()
    model = Glm4MoeLiteModel(cfg)
    params, ids = _params(model), _ids(6)
    grads = jax.jit(jax.grad(model.loss))(params, None, ids)
    seen = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        if family._is_bias(path):
            seen += leaf.shape[0]
            assert not np.asarray(leaf).any()
    assert seen == 2    # the sparse layer and the module's block


def test_router_without_a_bias_lowers_to_what_it_lowered_to():
    """(h) ``route_topk`` as it stood before it took a bias, against the
    call the other models make: the same jaxpr."""
    from jax.ad_checkpoint import checkpoint_name

    def before(logits, k, scale):
        logits = logits.astype(jnp.float32)
        scores = jax.nn.sigmoid(logits)
        _, picks = jax.lax.top_k(scores, k)
        picks = checkpoint_name(picks.astype(jnp.int32), "routing_picks")
        picked = jnp.take_along_axis(scores, picks, axis=-1)
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
        counts = jnp.sum(picks[..., None] == jnp.arange(logits.shape[-1]),
                         axis=(0, 1), dtype=jnp.int32)
        return picks, scale * weights, scores, counts

    logits = jnp.zeros((48, 32))
    was = jax.make_jaxpr(lambda x: before(x, 8, 2.5))(logits)
    now = jax.make_jaxpr(
        lambda x: tuple(route_topk(x, 8, scale=2.5))[:4])(logits)
    assert str(now) == str(was)
    layer = DroplessMoE(64, 32, 8, 32, 32)
    assert "bias" not in layer.init_params(jax.random.PRNGKey(0))


def test_three_steps_of_engine_and_reference_agree_on_the_bias(tmp_path):
    """After ``engine.step()`` every bias is ``old + gamma sign(mean(c) -
    c)`` with c summed over the step's two micro-batches, bit for bit
    over three steps; AdamW's decay (0.1 here) has not touched it; the
    monitor's flag decides nothing; a save and a load keep it."""
    cfg = _config()
    model, spec = Glm4MoeLiteModel(cfg), _spec(cfg)
    engine = _engine(model, _params(model), gas=2)
    assert not engine._moe_stats_enabled
    for step in range(3):
        weights = family.reference_params(jax.device_get(engine.params),
                                          spec)
        before, counts = family.gate_biases(weights), 0
        for micro in range(2):
            ids = _ids(10 * step + micro)
            with jax.default_matmul_precision("highest"):
                _, (_, _, _, picks) = _reference_forward(weights, ids, spec)
            counts = counts + reference.pick_counts(picks, EXPERTS)
            engine.backward(engine.forward(ids))
            engine.step()
        want = jnp.stack([reference.bias_update(b, c, spec.gamma)
                          for b, c in zip(before, counts)])
        got = family.gate_biases(family.reference_params(
            jax.device_get(engine.params), spec))
        assert (np.asarray(got) == np.asarray(want)).all(), step
        assert (np.asarray(got) != np.asarray(before)).any()
    engine.save_checkpoint(str(tmp_path), tag="t")
    again = _engine(model, model.init_params(jax.random.PRNGKey(9)), gas=2)
    again.load_checkpoint(str(tmp_path), tag="t")
    kept = family.gate_biases(family.reference_params(
        jax.device_get(again.params), spec))
    assert (np.asarray(kept) == np.asarray(got)).all()
    ds.reset_mesh_context()


def test_the_monitor_reads_the_new_counters(tmp_path):
    from deepspeed_tpu.monitor import moe
    cfg = _config()
    model = Glm4MoeLiteModel(cfg)
    engine = _engine(model, _params(model), monitor={
        "enabled": True, "moe": True, "reconcile": False,
        "output_path": str(tmp_path)})
    assert engine._moe_stats_enabled
    for step in range(2):
        engine.backward(engine.forward(_ids(step)))
        engine.step()
    summary = moe.summarize_window(engine._monitor_moe_stats())
    assert summary[R.M_LAYERS_PER_STEP] == 2
    assert summary[R.M_LOAD_MAX_OVER_MEAN] >= 1.0
    assert 4.0 < summary[R.M_MAIN_LOSS] < 6.5
    assert 4.0 < summary[R.M_MTP_LOSS] < 6.5
    assert summary[R.M_HELD_RANGE] == [4, 12]
    engine.monitor.close()
    ds.reset_mesh_context()


# ---------------------------------------------------------------------- #
# (e), (f) the prediction module's inputs, targets and shared leaves
# ---------------------------------------------------------------------- #
def _targets(model, params, ids):
    """The label arrays the model hands its two cross-entropies."""
    seen = []
    sound = glm.fused_linear_cross_entropy

    def spy(h, w, targets, ignore_index=None):
        seen.append(targets)
        return sound(h, w, targets, ignore_index=ignore_index)

    glm.fused_linear_cross_entropy = spy
    try:
        model.loss_terms(params, ids)
    finally:
        glm.fused_linear_cross_entropy = sound
    return tuple(seen)


def test_the_module_reads_the_next_token_and_is_scored_two_ahead(
        monkeypatch):
    cfg = _config()
    model = Glm4MoeLiteModel(cfg)
    params = _params(model)
    # a hand-made row in which every token occurs once
    ids = jnp.asarray([[7 + 3 * i for i in range(SEQ)]])
    labels = []
    sound = glm.fused_linear_cross_entropy

    def spy(h, w, targets, ignore_index=None):
        labels.append((targets, ignore_index))
        return sound(h, w, targets, ignore_index=ignore_index)

    monkeypatch.setattr(glm, "fused_linear_cross_entropy", spy)
    jax.eval_shape(model.loss_terms, params, ids)
    monkeypatch.undo()
    row = np.asarray(ids[0])
    assert [ignore for _, ignore in labels] == [glm.IGNORE] * 2
    # the targets are traced values: evaluate what was traced for them
    main, mtp = jax.jit(lambda i: _targets(model, params, i))(ids)
    np.testing.assert_array_equal(main, [*row[1:], glm.IGNORE])
    np.testing.assert_array_equal(mtp, [*row[2:], glm.IGNORE, glm.IGNORE])
    # with the hidden state's half of the projection at zero the module
    # reads the embedding alone: position i moves row t_{i+1}.  The last
    # two positions carry no loss and a causal mask lets nothing of them
    # reach a scored one, so rows t_0 (read where there is no next token)
    # and t_{S-1} (read at S-2) get exactly nothing.
    params[glm.MTP]["proj"] = params[glm.MTP]["proj"].at[
        cfg.hidden_size:].set(0.0)
    moved = jax.jit(jax.grad(lambda wte: model.loss_terms(
        {**params, "wte": wte}, ids)[2]))(params["wte"])
    touched = np.asarray(jnp.any(moved != 0, axis=-1))
    assert not touched[row[0]] and not touched[row[-1]]
    assert touched[row[1:-1]].all()
    assert touched.sum() == SEQ - 2


def test_the_shared_leaves_get_both_uses_gradients():
    cfg = _config()
    model = Glm4MoeLiteModel(cfg)
    params, ids = _params(model), _ids(8)

    whole, main, mtp = jax.jit(lambda p: tuple(
        jax.grad(lambda q, i=i: model.loss_terms(q, ids)[i])(p)
        for i in range(3)))(params)
    for name in ("wte", "head"):
        assert float(jnp.max(jnp.abs(main[name]))) > 0
        assert float(jnp.max(jnp.abs(mtp[name]))) > 0
        np.testing.assert_allclose(
            whole[name], main[name] + cfg.mtp_loss_weight * mtp[name],
            rtol=1e-4, atol=1e-7)
    # the module's own leaves hear nothing of the main head
    assert not np.asarray(main[glm.MTP]["proj"]).any()


# ---------------------------------------------------------------------- #
# which path the latent heads' rotation and layout take
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("interpret, over, seq, path", [
    (True, {}, 128, ("kernel", 128, 2)),
    (True, {}, 96, ("xla",)),                      # no whole loop trips
    (True, {"num_attention_heads": 3}, 128, ("xla",)),
    (True, {"qk_nope_head_dim": 96, "qk_rope_head_dim": 32,
            "v_head_dim": 128}, 128, ("xla",)),
    (False, {}, 128, ("xla",))],
    ids=["kernels", "S=96", "three heads", "a 32-wide slice",
         "no interpreter"])
def test_the_shape_decides_the_latent_path_and_the_plan_says_which(
        interpret, over, seq, path):
    """ops/rotary.py's kernels take whole heads of 128 of a fused QKV
    product and never this model's; ops/latent_layout.py's take heads of
    whole tiles whose last 64 lanes turn, in pairs, over whole blocks of
    positions, with a TPU or the interpreter.  The stack's plan, its log
    line and the kernels in the program say the same."""
    dispatch.set_pallas_interpret(interpret)
    try:
        assert rotary_block(8192, 128, 20, 20) is not None or not interpret
        assert rotary_block(8192, 64, 20, 1) is None
        assert rotary_block(8192, 256, 20, 20) is None
        cfg = _config(**{**dict(
            qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
            num_attention_heads=2, activation_checkpointing=True), **over})
        model = Glm4MoeLiteModel(cfg)
        assert model.rotary_plan(seq) == (("latent", *path),)
        assert (model.latent_block(seq) is None) == (path == ("xla",))
        budget = RematBudget(10 ** 12, working_set=0)
        model.install_remat_budget(budget)
        text = str(jax.make_jaxpr(jax.grad(model.loss))(
            model.init_params(jax.random.PRNGKey(0)), None,
            jnp.zeros((1, seq), jnp.int32)))
        plan = budget.take_plan()
        assert plan[R.M_STACK_ROTARY] == (("latent", *path),)
        line = stack_plan_line(plan)
        assert "rotary_fwd" not in text and "rotary_bwd" not in text
        kernels = ("latent_heads_fwd", "latent_heads_bwd",
                   "latent_flat_fwd", "latent_flat_bwd")
        if path == ("xla",):
            assert line.endswith("rotary: latent xla")
            assert "latent_" not in text
        else:
            assert line.endswith("rotary: latent kernel (blocks of 128 "
                                 "positions x 2 heads)")
            assert all(name in text for name in kernels)
    finally:
        dispatch.set_pallas_interpret(False)


# ---------------------------------------------------------------------- #
# the engine paths that cannot thread the stats refuse the model
# ---------------------------------------------------------------------- #
def test_a_custom_grad_program_refuses_a_model_with_exempt_leaves():
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    class Scheduled(DeepSpeedEngine):
        # what the pipeline engine installs before the programs are built
        _custom_grad_program = staticmethod(lambda *a, **k: None)

    model = Glm4MoeLiteModel(_config())
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    with pytest.raises(NotImplementedError, match="pipeline"):
        Scheduled(model=model, mesh=mesh, model_parameters=_params(model),
                  config={"train_batch_size": 2, "steps_per_print": 10 ** 9,
                          "optimizer": {"type": "AdamW",
                                        "params": {"lr": 1e-3}}})
    ds.reset_mesh_context()


def test_the_streamed_zero3_scan_refuses_a_model_with_exempt_leaves():
    class Streamed(Glm4MoeLiteModel):
        def install_zero3_streaming(self, context):
            self.stream = context

    model = Streamed(_config())
    with pytest.raises(NotImplementedError, match="ZeRO-3"):
        _engine(model, _params(model), zero_optimization={"stage": 3})
    ds.reset_mesh_context()


def test_a_model_without_exempt_leaves_is_what_it_was():
    """The doors are the model's: an engine of a model that declares
    neither exempt leaves nor counters hands its apply program no stats
    and its grad program returns two values."""
    from deepspeed_tpu.models.laguna import LagunaConfig, LagunaModel
    cfg = LagunaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_key_value_heads=2,
                       head_dim=16, sliding_window=8,
                       num_attention_heads_per_layer=(4, 6), num_experts=16,
                       num_experts_per_tok=4, moe_intermediate_size=32,
                       shared_expert_intermediate_size=32, bf16=False)
    model = LagunaModel(cfg)
    engine = _engine(model, model.init_params(jax.random.PRNGKey(0)))
    assert engine._exempt is None and engine.model_counters() is None
    out = engine._grad_fn(engine.params, engine.scaler_state, engine._rng,
                          _ids(1))
    assert len(out) == 2
    ds.reset_mesh_context()
