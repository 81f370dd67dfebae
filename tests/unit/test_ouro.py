"""The looped decoder (models/ouro.py): a stack run four times on the same
weights, the exit gate and the exit distribution's mix of four head
passes, against the plain reference of perf/families/ouro_reference.py
at a small size; the recurrence's executor (models/layer_stack.py), the
per-token fused cross-entropy (ops/fused_cross_entropy.py), the byte
budget's ``passes`` (checkpointing.checkpoint_layers) and the paths the
model refuses."""

import json
import logging
import math
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models.layer_stack import (run_layer_recurrence,
                                              run_layer_stack)
from deepspeed_tpu.models.ouro import (IGNORE, OuroConfig, OuroModel,
                                       exit_distribution, kl_to_uniform)
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.fused_cross_entropy import (
    even_chunk, fused_linear_cross_entropy,
    fused_linear_cross_entropy_per_token)
from deepspeed_tpu.profiling import scope_map
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ck
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    RematBudget, stack_plan_line)
from perf.families import ouro as family
from perf.families import ouro_reference as reference

VOCAB, SEQ = 250, 24
GOLDEN = pathlib.Path(__file__).parent / "golden" / "remat_plans_one_pass.json"


def _config(**over):
    kw = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=4, head_dim=16, rope_theta=100.0,
              bf16=False)
    kw.update(over)
    return OuroConfig(**kw)


def _spec(cfg):
    return reference.Spec(
        heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
        theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
        passes=cfg.total_ut_steps, beta=cfg.exit_kl_weight, row_block=16)


def _params(model, seed=0):
    """Seeded weights with every norm gain and the gate off its initial
    1 or 0, so that no term is silent."""
    params = model.init_params(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return tree.unflatten([x + 0.05 * jax.random.normal(k, x.shape)
                           for x, k in zip(leaves, keys)])


def _ids(seed, rows=2, seq=SEQ):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0,
                              VOCAB)


def _close(ours, want, rtol=2e-4):
    ours = jax.tree_util.tree_leaves_with_path(ours)
    want = jax.tree.leaves(want)
    assert len(ours) == len(want)
    for (path, a), b in zip(ours, want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=rtol * scale, rtol=rtol,
                                   err_msg=jax.tree_util.keystr(path))


# -- (a) the program against the reference -------------------------------- #

@pytest.mark.parametrize("over", [
    {}, {"activation_checkpointing": True},
    {"total_ut_steps": 3, "num_hidden_layers": 1}],
    ids=["four passes of two layers", "checkpointed",
         "three passes of one layer"])
def test_loss_terms_and_every_gradient_match_the_reference(over):
    cfg = _config(**over)
    model = OuroModel(cfg)
    params, ids, spec = _params(model, 3), _ids(4), _spec(cfg)
    (loss, counters), grads = jax.value_and_grad(
        lambda p: model(p, None, ids), has_aux=True)(params)
    (ref_loss, ref), ref_grads = reference.loss_and_grads(
        family.reference_params(params), ids, spec)
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-5)
    np.testing.assert_allclose(counters[R.M_TASK_LOSS], ref["task_loss"],
                               rtol=2e-5)
    np.testing.assert_allclose(counters[R.M_EXIT_KL], ref["exit_kl"],
                               rtol=2e-4)
    masses = [counters[R.M_EXIT_MASS + str(t + 1)]
              for t in range(cfg.total_ut_steps)]
    np.testing.assert_allclose(masses, ref["exit_mass"], rtol=2e-5)
    assert sum(masses) == pytest.approx(1.0, abs=1e-6)
    np.testing.assert_allclose(
        counters[R.M_EXIT_STEP_MEAN],
        sum((t + 1) * m for t, m in enumerate(ref["exit_mass"])), rtol=2e-5)
    # the four exits' own losses and distribution, token by token
    losses, p, valid = model.exit_terms(params, ids)
    n = float(valid.sum())
    assert n == ids.shape[0] * (SEQ - 1)
    np.testing.assert_allclose(jnp.sum(losses * valid, axis=1) / n,
                               ref["exit_losses"], rtol=2e-5)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    # every gradient, and the gate's 65 numbers apart
    ours = family.reference_params(grads)
    _close(family.gate_of(ours), family.gate_of(ref_grads))
    _close(ours, ref_grads)
    # the objective is its counters' sum and never under the mix
    assert loss == pytest.approx(
        counters[R.M_TASK_LOSS] + cfg.exit_kl_weight * counters[R.M_EXIT_KL],
        rel=1e-6)
    assert loss >= counters[R.M_TASK_LOSS]


def test_labels_given_are_the_labels_scored():
    model = OuroModel(_config())
    params, ids = _params(model, 1), _ids(2)
    shifted = jnp.where(jnp.arange(SEQ) < SEQ - 1, jnp.roll(ids, -1, axis=1),
                        IGNORE)
    assert model.loss(params, None, ids) == pytest.approx(
        model.loss(params, None, ids, labels=shifted), rel=1e-6)
    # a position without a target adds nothing: its gate has no gradient
    half = jnp.where(jnp.arange(SEQ) < SEQ // 2, shifted, IGNORE)
    _, _, valid = model.exit_terms(params, ids, half)
    assert float(valid.sum()) == 2 * (SEQ // 2)


def test_logits_are_the_last_pass():
    model = OuroModel(_config())
    params, ids = _params(model, 5), _ids(6)
    logits = model.logits(params, ids)
    assert logits.shape == (2, SEQ, VOCAB)
    losses, _, valid = model.exit_terms(params, ids)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    last = losses[-1].reshape(2, SEQ)[:, :-1]
    np.testing.assert_allclose(last, picked, rtol=1e-4, atol=1e-5)


def test_parameter_counts_of_the_model_and_of_the_cut():
    assert OuroModel(OuroConfig()).num_params() == 2_667_974_657
    cut = OuroModel(OuroConfig(num_hidden_layers=8))
    assert cut.num_params() == 612_438_017 == (
        8 * 51_388_416 + 201_326_592 + 2_048 + 2_049)
    shapes = jax.eval_shape(cut.init_params, jax.random.PRNGKey(0))
    assert shapes["layers"]["attn"]["qkv_w"].shape == (8, 2048, 3 * 2048)
    assert shapes["layers"]["ffn"]["w1"].shape == (8, 2048, 2 * 5632)
    assert shapes["gate"]["w"].shape == (2048,)
    config = json.loads((pathlib.Path(__file__).resolve().parents[2] / "perf"
                         / "configs" / "ouro-2.6b.json").read_text())
    assert 8 * family.layer_matrices(config) + 8 * 4 * 2048 + 2 * 49152 * (
        2048) + 2048 + 2049 == cut.num_params()


def test_the_gate_starts_at_one_half():
    model = OuroModel(_config())
    params = model.init_params(jax.random.PRNGKey(0))
    _, counters = model(params, None, _ids(0))
    masses = [float(counters[R.M_EXIT_MASS + str(t)]) for t in (1, 2, 3, 4)]
    assert masses == pytest.approx([0.5, 0.25, 0.125, 0.125])
    assert float(counters[R.M_EXIT_KL]) == pytest.approx(
        math.log(4) - 1.75 * math.log(2), rel=1e-5)


# -- (b) the recurrence's executor ------------------------------------------ #

@pytest.mark.parametrize("use_scan", [False, True])
def test_tied_gradient_is_the_sum_of_an_untied_copys(use_scan):
    """Four passes over the same stacked weights: the weights' gradient
    is the sum of the four gradients of a stack whose passes each have a
    copy of their own."""
    passes, layers, width = 4, 3, 8
    k_w, k_x, k_g = jax.random.split(jax.random.PRNGKey(0), 3)
    ws = 0.5 * jax.random.normal(k_w, (layers, width, width))
    x = jax.random.normal(k_x, (5, width))
    gain = 1.0 + 0.1 * jax.random.normal(k_g, (width,))

    def body(carry, w):
        return jnp.tanh(carry @ w), None

    def after_pass(carry):
        carry = carry * gain
        return carry, carry

    def tied(ws):
        _, ys = run_layer_recurrence(body, x, ws, passes, use_scan,
                                     after_pass)
        return jnp.sum(ys ** 2), ys

    def untied(copies):
        carry, ys = x, []
        for w in copies:
            carry, y = after_pass(run_layer_stack(body, carry, w, use_scan))
            ys.append(y)
        return jnp.sum(jnp.stack(ys) ** 2)

    (_, ys), grad = jax.value_and_grad(tied, has_aux=True)(ws)
    assert ys.shape == (passes, 5, width)
    per_pass = jax.grad(untied)([ws] * passes)
    assert all(float(jnp.abs(g).max()) > 0 for g in per_pass)
    np.testing.assert_allclose(grad, sum(per_pass), rtol=1e-5, atol=1e-6)


# -- (c) the per-token fused cross-entropy ---------------------------------- #

@pytest.mark.parametrize("chunk", [None, 64, 250])
def test_per_token_cross_entropy_values_and_vjp(chunk):
    """Against full logits: the losses and the VJP under a random
    cotangent, a vocabulary of 250 padded to whole chunks of 64, and an
    ignored label."""
    n, hid = 40, 32
    k_h, k_w, k_l, k_g = jax.random.split(jax.random.PRNGKey(1), 4)
    h = jax.random.normal(k_h, (n, hid))
    w = 0.3 * jax.random.normal(k_w, (hid, VOCAB))
    labels = jax.random.randint(k_l, (n,), 0, VOCAB).at[::7].set(IGNORE)
    cotangent = jax.random.normal(k_g, (n,))

    def plain(h, w):
        logp = jax.nn.log_softmax(h @ w, axis=-1)
        picked = -jnp.take_along_axis(
            logp, jnp.clip(labels, 0)[:, None], axis=-1)[:, 0]
        return jnp.where(labels == IGNORE, 0.0, picked)

    def fused(h, w):
        return fused_linear_cross_entropy_per_token(h, w, labels, chunk,
                                                    IGNORE)

    want, pull_plain = jax.vjp(plain, h, w)
    got, pull_fused = jax.vjp(fused, h, w)
    assert got.dtype == jnp.float32 and got.shape == (n,)
    assert float(jnp.abs(got[::7]).max()) == 0.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(pull_fused(cotangent), pull_plain(cotangent)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # the mean form is this under a mean over the labelled tokens
    mean = fused_linear_cross_entropy(h, w, labels, chunk, IGNORE)
    assert mean == pytest.approx(
        float(got.sum() / (labels != IGNORE).sum()), rel=1e-6)


def test_even_chunks_leave_no_padding():
    assert even_chunk(49152, 16384) == 24576       # the cell's: two halves
    assert even_chunk(49152, 4096) == 49152        # one pass: whole
    assert even_chunk(250, 1 << 20) is None        # no whole lane tiles
    assert even_chunk(50304, 32768) is None        # 3 x 131 tiles: in 4?
    assert even_chunk(50304, 16384) == 50304 // 3  # 131 tiles a part


# -- (d) the byte budget counts applications --------------------------------- #

def _plan(model, rows=2, seq=SEQ):
    budget = RematBudget(16_909_336_064, state_bytes=1_000_000_000,
                         cast_bytes=12_345_678)
    model.install_remat_budget(budget)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    jax.eval_shape(model.loss, params, None,
                   jax.ShapeDtypeStruct((rows, seq), jnp.int32))
    return budget.plan


def test_four_passes_offer_and_keep_four_times_the_bytes(monkeypatch):
    monkeypatch.setattr(dispatch, "_interpret", True)
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")
    seq = 128
    four = _plan(OuroModel(_config(activation_checkpointing=True)), seq=seq)
    model = OuroModel(_config(activation_checkpointing=True))
    # the same stack planned as if it ran once
    monkeypatch.setattr(
        "deepspeed_tpu.models.ouro.checkpoint_layers",
        lambda *a, passes, **kw: ck.checkpoint_layers(*a, **kw))
    one = _plan(model, seq=seq)
    assert four[R.M_REMAT_PASSES] == 4 and R.M_REMAT_PASSES not in one
    assert four[R.M_REMAT_LAYERS] == 8 and one[R.M_REMAT_LAYERS] == 2
    assert one[R.M_REMAT_KEPT] == four[R.M_REMAT_KEPT] == ck.RESIDUAL_ORDER[:2]
    assert one[R.M_REMAT_KEPT_BYTES] > 0
    assert four[R.M_REMAT_KEPT_BYTES] == 4 * one[R.M_REMAT_KEPT_BYTES]
    assert four[R.M_REMAT_KEPT_BYTES_PER_LAYER] == one[
        R.M_REMAT_KEPT_BYTES_PER_LAYER]
    # six more carries of [2, 128, 64] float32
    assert (four[R.M_REMAT_WORKING_SET_BYTES]
            - one[R.M_REMAT_WORKING_SET_BYTES]) == 6 * 2 * seq * 64 * 4
    assert ck._layers_phrase(four) == (
        "8 layer applications (2 layers x 4 passes)")
    assert ck._layers_phrase(one) == "2 layers"


def test_the_cells_plan_keeps_the_flash_residuals(monkeypatch):
    """The benchmark cell's stack (8 layers at the published widths run 4
    times, one row of 4,096 tokens) under the v5e's memory limit and the
    cell's state: 32 applications' carries in the working set, 32 sets of
    flash residuals and 32 first products of the gated FFN kept (the
    second name since PR 46: tests/unit/test_remat_policy.py has the
    bytes by name)."""
    monkeypatch.setattr(dispatch, "_interpret", True)
    model = OuroModel(OuroConfig(num_hidden_layers=8,
                                 activation_checkpointing=True))
    entries = model.num_params()
    budget = RematBudget(16_909_336_064, state_bytes=14 * entries + 4,
                         cast_bytes=2 * entries)
    model.install_remat_budget(budget)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    jax.eval_shape(model.loss, params, None,
                   jax.ShapeDtypeStruct((1, 4096), jnp.int32))
    plan = budget.plan
    assert plan[R.M_REMAT_LAYERS] == 32 and plan[R.M_REMAT_PASSES] == 4
    assert plan[R.M_REMAT_STATE_BYTES] == 8_574_132_242
    assert plan[R.M_REMAT_WORKING_SET_BYTES] == (
        2 * entries + ck.MARGIN_BYTES
        + 4096 * ((32 + ck.LAYER_WIDTHS) * 2048 * 2 + 4 * 49152))
    assert plan[R.M_REMAT_KEPT] == ck.RESIDUAL_ORDER[:2]
    # out bf16 [1, 16, 4096, 128], the row statistics and the gate and up
    # product bf16 [1, 4096, 2 x 5632], 32 times
    assert plan[R.M_REMAT_KEPT_BYTES_PER_LAYER] == (
        16 * 4096 * 128 * 2 + 16 * 4096 * 4 + 4096 * 11264 * 2)
    assert plan[R.M_REMAT_KEPT_BYTES] == 32 * plan[
        R.M_REMAT_KEPT_BYTES_PER_LAYER] <= plan[R.M_REMAT_BUDGET_BYTES]
    assert plan[R.M_STACK_PASSES] == (4, 32)
    assert "; run 4 times on the same weights: 32 layer applications a step" \
        in stack_plan_line(plan)


def _older_family(name):
    """(model, rows, seq) of an older family's toy, as its own tests
    build it, checkpointing on."""
    if name == "gpt2":
        from tests.unit.test_remat_policy import _gpt2
        return _gpt2(128), 2, 128
    if name == "phi4flash":
        from deepspeed_tpu.models.phi4flash import Phi4FlashModel
        from tests.unit.test_phi4flash import _config as toy
        return Phi4FlashModel(toy(activation_checkpointing=True)), 2, 128
    if name == "laguna":
        from deepspeed_tpu.models.laguna import LagunaModel
        from tests.unit.test_laguna import _config as toy
        return LagunaModel(toy(activation_checkpointing=True)), 2, 128
    from deepspeed_tpu.models.glm4_moe_lite import Glm4MoeLiteModel
    from tests.unit.test_glm4_moe_lite import _config as toy
    return Glm4MoeLiteModel(toy(activation_checkpointing=True)), 2, 128


def plan_and_lines(name):
    """An older family's plan as JSON and the lines the budget logs for
    it (the golden file is what this gave on the parent of PR 45, commit
    68bc076, from a scratch script that holds a copy of it; three of its
    entries are PR 46's: the test below says which fields moved)."""
    model, rows, seq = _older_family(name)
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    from deepspeed_tpu.utils.logging import logger
    handler = Keep()
    logger.addHandler(handler)
    try:
        plan = _plan(model, rows, seq)
    finally:
        logger.removeHandler(handler)
    return {"plan": json.loads(json.dumps(plan)),
            "lines": [line for line in lines if "layer s" in line]}


@pytest.mark.parametrize("name", ["gpt2", "phi4flash", "laguna", "glm"])
def test_one_pass_is_the_parents_plan_byte_for_byte(name, monkeypatch):
    """``gpt2`` is still what the parent of PR 45 planned and logged, byte
    for byte.  PR 46 recorded ``phi4flash``, ``laguna`` and ``glm`` anew:
    their gated FFN offers its first product as the second name of
    ``RESIDUAL_ORDER`` and the toys' 15 GB budgets admit it, so
    ``remat_offered`` and ``remat_kept`` gained ``ffn_gate_up``,
    ``remat_kept_bytes`` and ``remat_kept_bytes_per_layer`` its bytes,
    the new ``remat_kept_bytes_by_name`` appeared (it rides on a plan that
    keeps several names) and the first log line says the same; budget,
    state, working set, layers and every ``stack_*`` field and the second
    line are the parent's (GPT-2's MLP offers no such name: PERF.md
    section 6, PR 33 and PR 46)."""
    monkeypatch.setattr(dispatch, "_interpret", True)
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")
    want = json.loads(GOLDEN.read_text())[name]
    got = plan_and_lines(name)
    assert want["lines"] and got["lines"] == want["lines"]
    assert got["plan"] == want["plan"]
    assert R.M_REMAT_PASSES not in got["plan"]


# -- (e) the KL form ----------------------------------------------------------- #

def test_kl_form_is_never_under_the_mix_and_has_the_entropy_forms_gradient():
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(2), (3, 50))
    losses = 5.0 + jax.random.normal(jax.random.PRNGKey(3), (4, 50))
    beta = 0.1

    def plain_p(z):
        lam = jax.nn.sigmoid(z)
        return jnp.stack([lam[0], lam[1] * (1 - lam[0]),
                          lam[2] * (1 - lam[0]) * (1 - lam[1]),
                          (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])

    def kl_form(z):
        p = exit_distribution(z)
        mix = jnp.mean(jnp.sum(p * losses, axis=0))
        kl = jnp.mean(kl_to_uniform(p))
        return mix + beta * kl, (mix, kl)

    def entropy_form(z):
        p = plain_p(z)
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        return jnp.mean(jnp.sum(p * losses, axis=0) - beta * entropy)

    np.testing.assert_allclose(exit_distribution(logits), plain_p(logits),
                               rtol=1e-5)
    (value, (mix, kl)), grad = jax.value_and_grad(kl_form, has_aux=True)(
        logits)
    assert kl >= 0 and value >= mix
    assert value == pytest.approx(
        float(entropy_form(logits)) + beta * math.log(4), rel=1e-6)
    np.testing.assert_allclose(grad, jax.grad(entropy_form)(logits),
                               rtol=1e-4, atol=1e-7)
    # a gate that is sure either way: every factor keeps its precision,
    # the exits still sum to one and 0 ln 0 is 0
    far = jnp.array([[60.0, -60.0, 120.0], [60.0, -60.0, 120.0],
                     [-60.0, 60.0, 0.0]])
    p = exit_distribution(far)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    assert float(p[1, 0]) == pytest.approx(math.exp(-60.0), rel=1e-5)
    assert bool(jnp.all(jnp.isfinite(kl_to_uniform(p))))
    grads = jax.grad(lambda z: jnp.sum(kl_to_uniform(exit_distribution(z))))(
        far)
    assert bool(jnp.all(jnp.isfinite(grads)))


# -- (f) through the engine ------------------------------------------------------ #

def _engine(model, params, **config):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh, model_parameters=params, config={
            "train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
            "steps_per_print": 10 ** 9,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-3, "weight_decay": 0.01}},
            "zero_optimization": {"stage": 2}, **config})
    return engine


def test_trains_through_initialize_and_reports_its_counters():
    model = OuroModel(_config(activation_checkpointing=True))
    params = model.init_params(jax.random.PRNGKey(0))
    engine = _engine(model, params)
    ids = _ids(9)
    losses = []
    for _ in range(6):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert all(b < a for a, b in zip(losses, losses[1:]))
    counters = engine.model_counters()
    assert set(counters) == set(model.aux_counters) == {
        "task_loss", "exit_kl", "exit_step_mean", "exit_mass_1",
        "exit_mass_2", "exit_mass_3", "exit_mass_4"}
    assert sum(counters[f"exit_mass_{t}"] for t in (1, 2, 3, 4)) == (
        pytest.approx(1.0, abs=1e-5))
    assert 1.0 < counters["exit_step_mean"] < 4.0
    # the gate has moved off its zeros
    assert float(jnp.abs(engine.params["gate"]["w"]).max()) > 0
    assert engine.model_counters() is None
    ds.reset_mesh_context()


@pytest.mark.parametrize("path,config,mesh", [
    ("zero3_streaming", {"zero_optimization": {"stage": 3}}, {}),
    ("pipeline", {}, {"pipe": 2}),
])
def test_paths_the_model_refuses_by_name(path, config, mesh):
    model = OuroModel(_config())
    params = model.init_params(jax.random.PRNGKey(0))
    ds.reset_mesh_context()
    devices = jax.devices()[:2 if mesh else 1]
    ctx = ds.initialize_mesh(devices=devices, data=1, **mesh)
    with pytest.raises(NotImplementedError) as refused:
        ds.initialize(model=model, mesh=ctx, model_parameters=params, config={
            "train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2}, **config})
    assert f"OuroModel under {path}" in str(refused.value)
    assert OuroModel.refuses[path] in str(refused.value)
    ds.reset_mesh_context()


# -- (g) names: scopes, parts, the region ----------------------------------------- #

def test_scopes_parts_and_the_exit_region_name_the_grad_program():
    model = OuroModel(_config(activation_checkpointing=True))
    params, ids = model.init_params(jax.random.PRNGKey(0)), _ids(0)
    text = jax.jit(jax.grad(lambda p: model.loss(p, None, ids))).lower(
        params).compile().as_text()
    tags = scope_map.parse(text)
    parts = scope_map.parse_parts(text).values()
    regions = scope_map.parse_regions(text)
    assert {scope for scope, _ in tags.values()} >= {
        "embed", "attn", "mlp", "layer", "head"}
    assert {"forward", "backward"} <= {phase for _, phase in tags.values()}
    assert set(parts) >= {"qkv", "rotary", "layout", "core", "out"}
    assert set(regions.values()) == {None, "exit"}
    # the head's work lies under the region, the layers' does not
    for name, region in regions.items():
        scope = tags[name][0]
        assert region is None or scope in ("head", "other"), (name, scope)
        assert scope != "head" or region == "exit", name


def test_the_stack_plan_names_passes_and_applications():
    model = OuroModel(_config(activation_checkpointing=True))
    plan = model.stack_plan(SEQ)
    assert plan[R.M_STACK_PASSES] == (4, 8)
    assert plan[R.M_STACK_ROTARY] == (("full_attention", "xla"),)
    assert stack_plan_line(plan) == (
        "layer stack: 0:full_attention, 1:full_attention; run 4 times on "
        "the same weights: 8 layer applications a step; rotary: "
        "full_attention xla")


def test_rotary_kernels_where_the_shape_is_theirs(monkeypatch):
    """Heads of 128 and whole blocks of positions: the model takes
    ``rotate_qkv`` (under the interpreter here) and gives what the XLA
    rotation gives."""
    cfg = _config(hidden_size=256, num_attention_heads=2,
                  num_key_value_heads=2, head_dim=128, num_hidden_layers=1,
                  total_ut_steps=2)
    model = OuroModel(cfg)
    params, ids = _params(model, 7), _ids(8, rows=1, seq=128)
    assert model.rotary_plan(128) is None
    want = model.loss(params, None, ids)
    monkeypatch.setattr(dispatch, "_interpret", True)
    assert model.rotary_plan(128) is not None
    assert "kernel" in stack_plan_line(model.stack_plan(128))
    text = str(jax.make_jaxpr(model.loss)(params, None, ids))
    assert "rotary_fwd" in text
    assert model.loss(params, None, ids) == pytest.approx(float(want),
                                                          rel=2e-5)
