"""``models/xing4.py`` against the plain reference of the benchmark
(``perf/families/xing4_reference.py``) at a small size with real ratios
on the CPU, float32: the objective, its terms, every gradient leaf and
every sublayer's mixes, with and without the prediction module, directly
and through ``ds.initialize``; the shares of the experts adding up to
the uncut layer with the shared expert and the streams counted once; the
byte budget's account of a carry of four streams, and of the one-stream
families' as it was; the counters the monitor writes; the stack's log
line."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import Xing4Config, Xing4Model
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.ops import hyper_connection as H
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ck
from perf.families import glm4_moe_lite as glm_family
from perf.families import xing4 as family
from perf.families import xing4_reference as reference

VOCAB, SEQ, EXPERTS = 128, 16, 16
YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 8,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def _config(**over):
    """A query latent narrower than the hidden size, query/key heads of
    12 + 4 on value heads of 12, 16 experts of which 8 are held from the
    fourth on, 4 streams, YaRN by 8 from 8 positions."""
    kw = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
              num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24,
              kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
              v_head_dim=12, rope_theta=100.0, rope_scaling=YARN,
              first_k_dense_replace=1, n_routed_experts=EXPERTS,
              num_experts_per_tok=4, moe_intermediate_size=32,
              experts_held=(4, 8), num_nextn_predict_layers=1, bf16=False)
    kw.update(over)
    return Xing4Config(**kw)


def _spec(cfg, **over):
    scaling = cfg.rope_scaling
    return reference.Spec(
        sparse=tuple(i >= cfg.first_k_dense_replace
                     for i in range(cfg.num_hidden_layers)),
        heads=cfg.num_attention_heads, kv_rank=cfg.kv_lora_rank,
        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
        theta=cfg.rope_theta,
        yarn=(scaling["factor"], scaling["original_max_position_embeddings"],
              scaling["beta_fast"], scaling["beta_slow"]),
        softmax_factor=(0.1 * np.log(scaling["factor"]) + 1.0) ** 2,
        eps=cfg.rms_norm_eps, picked=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, held_first=cfg.experts_held[0],
        mtp_weight=cfg.mtp_loss_weight, gamma=cfg.bias_update_rate,
        streams=cfg.hc_mult, rounds=cfg.hc_sinkhorn_iters,
        hc_eps=cfg.hc_eps, clamp=(cfg.mhc_h_res_clamp_min,
                                  cfg.mhc_h_res_clamp_max))._replace(**over)


def _params(model, seed=0):
    """Seeded weights with every norm weight off its initial 1, every
    selection bias off 0 and every hyper-connection off its start (alpha
    near 0.5, ``b`` spread by 1), so that no term is silent."""
    params = model.init_params(jax.random.PRNGKey(seed))
    gamma = model.config.bias_update_rate

    def moved(path, leaf):
        key = jax.random.fold_in(
            jax.random.PRNGKey(seed + 1),
            zlib.crc32(jax.tree_util.keystr(path).encode()) % 2 ** 31)
        if glm_family._is_bias(path):
            return gamma * jax.random.randint(key, leaf.shape, -30, 31
                                              ).astype(jnp.float32)
        noise = jax.random.normal(key, leaf.shape)
        hc = family._hc_leaf(path)
        if hc is not None and hc[1] == "alpha":
            return 0.5 + 0.1 * noise
        if hc is not None and hc[1] == "b":
            return leaf + noise
        return leaf + 0.05 * noise

    return jax.tree_util.tree_map_with_path(moved, params)


def _ids(seed, rows=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0,
                              VOCAB)


def _close(ours, want, rtol=3e-4):
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
# (a) the objective, its terms, every gradient leaf and the mixes
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("modules, checkpointing", [
    (1, False), (0, False), (1, True)],
    ids=["module", "no module", "module, checkpointed"])
def test_loss_terms_every_gradient_leaf_and_the_mixes(modules,
                                                      checkpointing):
    cfg = _config(num_nextn_predict_layers=modules,
                  activation_checkpointing=checkpointing)
    model, spec = Xing4Model(cfg), _spec(cfg)
    params, ids = _params(model), _ids(5)
    with jax.default_matmul_precision("highest"):
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            lambda p: model(p, None, ids), has_aux=True))(params)
        (scores, picks), mixed = jax.jit(model.routing_and_mixes)(params,
                                                                  ids)
    (want, (main, mtp, ref_scores, ref_picks, ref_mixes)), want_grads = \
        _reference_of(params, ids, spec)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    assert float(counters[R.M_MAIN_LOSS]) == pytest.approx(float(main),
                                                           rel=2e-5)
    assert float(counters[R.M_MTP_LOSS]) == pytest.approx(float(mtp),
                                                          rel=2e-5)
    assert (float(mtp) > 0) == bool(modules)
    _close(family.reference_params(grads, spec), want_grads)
    np.testing.assert_allclose(scores, ref_scores, rtol=2e-5)
    assert (np.sort(picks, -1) == np.sort(ref_picks, -1)).all()
    blocks = cfg.num_hidden_layers + modules
    for ours, theirs in zip(family.stacked_mixes(mixed), ref_mixes):
        assert ours.shape == theirs.shape and ours.shape[:2] == (blocks, 2)
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=1e-6)
    # the counters are the mixes' own
    res = np.asarray(mixed.res)
    assert float(counters[R.M_HC_ROW_ERR]) == pytest.approx(
        np.abs(res.sum(3) - 1).max(), rel=1e-4, abs=1e-7)
    assert float(counters[R.M_HC_COL_ERR]) == pytest.approx(
        np.abs(res.sum(2) - 1).max(), rel=1e-4, abs=1e-7)
    assert float(counters[R.M_HC_PRE_MEAN]) == pytest.approx(
        np.asarray(mixed.pre).mean(), rel=1e-5)
    assert float(counters[R.M_HC_POST_MEAN]) == pytest.approx(
        np.asarray(mixed.post).mean(), rel=1e-5)


@pytest.mark.parametrize("fault", [
    {"rounds": 1}, {"post_scale": 1.0}, {"softmax_factor": 1.0},
    {"yarn": None}, {"dynamic": False}],
    ids=lambda f: next(iter(f)))
def test_a_reference_with_a_term_changed_is_another_model(fault):
    """What the comparison above would not see it could not pin: each
    changed term moves the loss, or a mix (the rounds' own effect on the
    loss is in the sixth digit at this size)."""
    cfg = _config()
    model = Xing4Model(cfg)
    params, ids = _params(model), _ids(5)
    named = family.reference_params(params, _spec(cfg))
    with jax.default_matmul_precision("highest"):
        sound, aux = _reference_forward(named, ids, _spec(cfg))
        other, other_aux = _reference_forward(named, ids,
                                              _spec(cfg, **fault))
    mixes_apart = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(aux[4], other_aux[4]))
    assert (abs(float(other) - float(sound)) > 2e-4 * float(sound)
            or mixes_apart > 1e-3)


def test_equal_streams_at_the_start_are_the_plain_network():
    """At the initial hyper-connections (alpha 0, so exactly) four equal
    streams stay equal and the model is GLM-4 MoE Lite's on one stream
    with the same weights: same loss."""
    from deepspeed_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                    Glm4MoeLiteModel)
    cfg = _config(hc_alpha_init=0.0, hc_res_off_diagonal=-40.0)
    model = Xing4Model(cfg)
    params, ids = model.init_params(jax.random.PRNGKey(3)), _ids(2)
    plain = Glm4MoeLiteModel(Glm4MoeLiteConfig(**{
        k: getattr(cfg, k) for k in Glm4MoeLiteConfig.__dataclass_fields__}))
    with jax.default_matmul_precision("highest"):
        ours = float(jax.jit(model.loss)(params, None, ids))
        want = float(jax.jit(plain.loss)(params, None, ids))
    # sum-out hands the head 4 x the one stream; RMSNorm takes it back
    assert ours == pytest.approx(want, rel=1e-4)


# ---------------------------------------------------------------------- #
# (b) through the engine, and what the monitor writes
# ---------------------------------------------------------------------- #
def _engine(model, params, **config):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh, model_parameters=params, config={
            "train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-3, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 2}, **config})
    return engine


def test_the_engine_trains_it_and_the_monitor_reads_the_counters(tmp_path):
    from deepspeed_tpu.monitor import moe
    cfg = _config(activation_checkpointing=True)
    model, spec = Xing4Model(cfg), _spec(cfg)
    params, ids = _params(model), _ids(5)
    with jax.default_matmul_precision("highest"):
        engine = _engine(model, params, monitor={
            "enabled": True, "moe": True, "reconcile": False,
            "output_path": str(tmp_path)})
        loss = float(engine.forward(ids))
        grads = family.reference_params(engine._cached_grads, spec)
    (want, _), want_grads = _reference_of(params, ids, spec)
    assert loss == pytest.approx(float(want), rel=2e-5)
    _close(grads, want_grads)
    losses = []
    for step in range(3):
        losses.append(float(engine.forward(_ids(step))))
        engine.backward(losses[-1])
        engine.step()
    assert all(np.isfinite(losses))
    # the hyper-connections' leaves are the optimizer's, and moved
    before = jax.tree.leaves(params["layers_01"][family.HC_NAMES[0]])
    after = jax.tree.leaves(engine.params["layers_01"][family.HC_NAMES[0]])
    assert all(float(jnp.max(jnp.abs(a - b))) > 0
               for a, b in zip(after, before))
    summary = moe.summarize_window(engine._monitor_moe_stats())
    # two sparse layers and the module, four forwards over three steps
    assert summary[R.M_LAYERS_PER_STEP] == 4
    assert summary[R.M_HC_COL_ERR] < 1e-5
    assert 0.0 <= summary[R.M_HC_ROW_ERR] < 1.0
    assert 0.0 < summary[R.M_HC_PRE_MEAN] < 1.0
    assert 0.0 < summary[R.M_HC_POST_MEAN] < 2.0
    assert 4.0 < summary[R.M_MAIN_LOSS] < 6.5
    engine.monitor.close()
    ds.reset_mesh_context()


# ---------------------------------------------------------------------- #
# (c) the share test
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("held", [2, 8])
def test_all_shares_add_up_to_the_uncut_sparse_layer(held):
    """A sparse layer on four streams, cut ``EXPERTS / held`` ways: what
    every share computes alike (attention, the hyper-connections' mixes
    and their carry ``H_res X``, the shared expert) counted once, the
    shares' routed parts, written back through the same ``H_post``, add
    up to the uncut reference's layer."""
    cfg = _config(num_hidden_layers=2, num_nextn_predict_layers=0,
                  experts_held=(0, EXPERTS))
    whole = Xing4Model(cfg)
    params, ids = _params(whole, seed=2), _ids(9, rows=1)
    spec = _spec(cfg)
    named = family.reference_params(params, spec)
    layer = named["layers"][1]
    with jax.default_matmul_precision("highest"):
        x = jnp.broadcast_to(named["embed"][ids[0]],
                             (cfg.hc_mult, SEQ, cfg.hidden_size))
        x, _, _ = reference.layer(named["layers"][0], x, False, spec)
        want, _, _ = reference.layer(layer, x, True, spec)

        p = jax.tree.map(lambda a: a[0], params["layers_01"])

        def share_of(first, experts):
            """The layer's streams from the share that holds ``held``
            experts from ``first`` on, with ``experts`` as their weights."""
            share = Xing4Model(_config(
                num_hidden_layers=2, num_nextn_predict_layers=0,
                experts_held=(first, held)))
            return _one_layer(share, {**p, "moe": {
                **p["moe"], "experts": experts}}, x[None])

        # experts that give nothing: what every share computes alike
        common = share_of(0, jax.tree.map(
            lambda a: jnp.zeros_like(a[:held]), p["moe"]["experts"]))
        total = common
        for first in range(0, EXPERTS, held):
            total = total + (share_of(first, jax.tree.map(
                lambda a: a[first:first + held], p["moe"]["experts"]))
                - common)
    assert float(jnp.max(jnp.abs(total[0] - common[0]))) > 1e-3
    np.testing.assert_allclose(total[0], want, rtol=3e-4, atol=3e-5)


def _one_layer(model, p, x):
    """The streams after one sparse layer of ``model`` on ``x``
    [1, n, S, C], by the model's own body."""
    stacked = jax.tree.map(lambda a: a[None], p)
    params = {"layers_01": stacked}
    body, xs, _, _ = [
        b for b in model._bodies(
            {**params, "layers_00": None}, x.shape[2], None,
            (lambda r: None, lambda m: None)) if b[2]][0]
    out, _ = body(x, jax.tree.map(lambda a: a[0], xs))
    return out


# ---------------------------------------------------------------------- #
# (d) the byte budget
# ---------------------------------------------------------------------- #
V5E_LIMIT = 16_909_336_064


def test_the_budget_charges_four_streams_to_the_carries_alone():
    """``working_set_bytes``: the carries cost n widths a token and
    layer, the layer's own pass LAYER_WIDTHS of ONE width; charged four
    times over it would ask 2.8 GB more at the cell's shape and refuse
    every residual.  One stream: the bytes of before, to the byte, for
    GPT-2 large (PR 33's figure) and GLM-4.7-Flash's cell."""
    cast = 2 * 759_346_446
    four = ck.working_set_bytes(4096, 3584, 5, 16384, 2, cast, streams=4)
    assert four == cast + 420_000_000 + 4096 * (
        (5 * 4 + 32) * 3584 * 2 + 4 * 16384)
    as_width = ck.working_set_bytes(4096, 4 * 3584, 5, 16384, 2, cast)
    assert as_width - four == 4096 * 32 * 3 * 3584 * 2 == 2_818_572_288
    large_cast = 1_548_317_696
    assert ck.working_set_bytes(4096, 1280, 36, 50304, 2,
                                large_cast) == 3_505_530_112
    assert ck.working_set_bytes(4096, 1280, 36, 50304, 2, large_cast,
                                streams=1) == 3_505_530_112
    glm_cast = 2 * 706_912_064
    assert ck.working_set_bytes(16384, 2048, 6, 19456, 2, glm_cast) == (
        glm_cast + 420_000_000 + 16384 * (38 * 2048 * 2 + 4 * 19456)
    ) == 5_659_029_376


@pytest.mark.parametrize("streams, shape, layers, head, cast", [
    (4, (1, 4, 4096, 3584), 5, 16384, 2 * 759_346_446),
    (1, (2, 8192, 2048), 6, 19456, 2 * 706_912_064),
    (1, (4, 1024, 1280), 36, 50304, 1_548_317_696)],
    ids=["xing4.0-29b-a4b.s4k", "glm47-flash.s8k", "gpt2-large.s1024"])
def test_the_plan_of_a_stack_reads_its_carry(streams, shape, layers, head,
                                             cast):
    budget = ck.RematBudget(V5E_LIMIT, state_bytes=7 * cast, cast_bytes=cast)
    tokens, width = shape[0] * shape[-2], shape[-1]
    ck.checkpoint_layers(
        [(lambda carry, xs: (carry, None),
          jax.ShapeDtypeStruct((layers, 1), jnp.float32))], budget,
        jax.ShapeDtypeStruct(shape, jnp.bfloat16), head,
        **({"streams": streams} if streams != 1 else {}))
    assert budget.plan[R.M_REMAT_WORKING_SET_BYTES] == (
        cast + 420_000_000 + tokens * (
            (layers * streams + 32) * width * 2 + 4 * head))
    assert budget.plan[R.M_REMAT_BUDGET_BYTES] > 0


def test_the_model_offers_its_residuals_and_the_plan_names_the_streams():
    cfg = _config(activation_checkpointing=True)
    model = Xing4Model(cfg)
    assert model.carry_streams == 4
    budget = ck.RematBudget(10 ** 12, working_set=0)
    model.install_remat_budget(budget)
    jax.make_jaxpr(jax.grad(model.loss))(
        model.init_params(jax.random.PRNGKey(0)), None,
        jnp.zeros((1, SEQ), jnp.int32))
    plan = budget.take_plan()
    # the mixes' sums are kept always, like the picks; the sublayer's
    # input is the last name the budget admits
    assert H.MIX_NAME in ck.ALWAYS_KEPT
    assert H.MIX_NAME not in plan[R.M_REMAT_OFFERED]
    assert plan[R.M_REMAT_OFFERED][-1] == H.INPUT_NAME == ck.RESIDUAL_ORDER[-1]
    assert H.INPUT_NAME in plan[R.M_REMAT_KEPT]
    assert plan[R.M_STACK_STREAMS] == (4, 20, -30.0, 30.0)
    line = ck.stack_plan_line(plan)
    assert ("4 residual streams mixed by hyper-connections (20 Sinkhorn "
            "rounds from logits clamped to [-30, 30])") in line
    assert "values of 12" in line and "latent xla" in line
    # a budget one byte short of the names before it keeps no ``u``
    by_name = dict(plan[R.M_REMAT_KEPT_BYTES_BY_NAME])
    assert H.INPUT_NAME not in ck.saved_residual_names(
        by_name, 1, sum(by_name.values()) - 1)
