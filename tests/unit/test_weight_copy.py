"""The weights as the grad program reads them: the default apply program
writes the master's compute-dtype copy in the pass that writes the
master, and every micro-batch's grad program is launched on that copy
where it used to cast the whole master itself
(``DeepSpeedEngine._plan_weight_copy``).  Bit for bit the trajectory of
the in-program cast; rebuilt wherever the master is written outside the
apply; never saved; counted as state by the layer scan's byte budget;
one ``convert`` a leaf in the apply's text and none in the grad
program's; and the paths that build programs of their own keep their
cast."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models import GPT2Config, GPT2Model
from deepspeed_tpu.profiling import scope_map
from deepspeed_tpu.runtime.activation_checkpointing import (
    checkpointing as ck)
from deepspeed_tpu.runtime.engine import _masked_leaves
from tests.unit import test_glm4_moe_lite as glm_toy
from tests.unit.test_engine import HIDDEN, make_engine

Glm4MoeLiteModel = glm_toy.Glm4MoeLiteModel
VOCAB, SEQ = 64, 16
BF16 = {"enabled": True}
BF16_HALF_GRADS = {"enabled": True, "grads_in_compute_dtype": True}


def _gpt2(**over):
    kw = dict(vocab_size=VOCAB, n_positions=SEQ, hidden_size=32,
              num_layers=2, num_heads=4, embd_dropout=0.1, attn_dropout=0.1,
              hidden_dropout=0.1)
    kw.update(over)
    return GPT2Model(GPT2Config(**kw))


def _engine(model, params, gas=1, **config):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    return ds.initialize(
        model=model, mesh=mesh, model_parameters=params,
        rng=jax.random.PRNGKey(7), config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": gas, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-3, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 2}, **config})[0]


def _batches(steps, gas, seed=1):
    rng = np.random.RandomState(seed)
    return [[rng.randint(0, VOCAB, (2, SEQ)).astype(np.int32)
             for _ in range(gas)] for _ in range(steps)]


def _equal(a, b, atol=None, of_largest=None):
    """Bit for bit; or to ``atol``; or to the share ``of_largest`` of a
    leaf's largest magnitude."""
    a, b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        if of_largest is not None:
            atol = of_largest * np.abs(y).max()
        if atol is None:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(x, y, rtol=0, atol=atol)


def _copy_is_the_cast_of_the_master(engine):
    copies = _masked_leaves(engine._weights, engine._copy_mask)
    masters = _masked_leaves(engine.params, engine._copy_mask)
    assert copies and all(c.dtype == engine.compute_dtype for c in copies)
    _equal(copies, [m.astype(engine.compute_dtype) for m in masters])
    # a leaf without a copy is the stored leaf itself
    for w, p, m in zip(jax.tree.leaves(engine._weights),
                       jax.tree.leaves(engine.params), engine._copy_mask):
        assert m or w is p


class _InProgramCast:
    """The parent's loop on an engine's own bodies: every micro-batch's
    grad program is launched on the master and casts it."""

    def __init__(self, engine):
        self.e = engine
        self.grad = jax.jit(engine._loss_and_grads)
        self.apply = jax.jit(engine._apply_core)
        self.state = (engine.params, engine.opt_state, engine.scaler_state)

    def step(self, micro_batches):
        """(losses, each micro-batch's gradients, the new master)."""
        e, (params, opt_state, scaler) = self.e, self.state
        losses, grads, acc, stats = [], [], None, None
        for ids in micro_batches:
            (args, _) = e._shard_batch(((ids,), {}))
            loss, g, *extras = self.grad(params, scaler, e._next_rng(),
                                         *args)
            losses.append(loss)
            grads.append(g)
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
            if e._exempt is not None:
                stats = extras[0] if stats is None else jax.tree.map(
                    jnp.add, stats, extras[0])
        params, opt_state, scaler, _ = self.apply(
            params, opt_state, scaler, acc, None, stats)
        self.state = (params, opt_state, scaler)
        return losses, grads, params

    def take_state_of(self, engine):
        """Copies: the engine donates its own at its next apply."""
        self.state = jax.tree.map(jnp.array, (
            engine.params, engine.opt_state, engine.scaler_state))


def _gpt2_pair(gas, bf16):
    model = _gpt2()
    params = model.init_params(jax.random.PRNGKey(0))
    return (_engine(model, params, gas=gas, bf16=bf16),
            _engine(_gpt2(), params, gas=gas, bf16=bf16))


def _glm(takes_the_copy):
    """GLM's toy, the one model with leaves the optimizer does not own.
    The class keeps its cast (``ExpertStack.casts_own_weights``: what the
    chip measured); a model that does not say so takes the copy."""
    model = Glm4MoeLiteModel(glm_toy._config())
    if takes_the_copy:
        model.casts_own_weights = None
    return model


def _glm_pair(gas, bf16):
    params = glm_toy._params(_glm(True))
    return (_engine(_glm(True), params, gas=gas, bf16=bf16),
            _engine(_glm(True), params, gas=gas, bf16=bf16))


# One bf16 value of a leaf's largest, for the model whose two programs the
# CPU's compiler does not round alike: of GLM's toy, launched on the copy,
# three gradient elements of thousands come one value off what the SAME
# program gives on the copy cast back to fp32, which in turn equals the
# in-program cast on the master bit for bit.  The difference is the CPU's
# order of summing a product whose operand is a parameter, not the values
# it is given.
ONE_BF16_VALUE = {"of_largest": 2.0 ** -7}


@pytest.mark.parametrize("pair, gas, bf16, exact", [
    (_gpt2_pair, 1, BF16, True), (_gpt2_pair, 4, BF16, True),
    (_gpt2_pair, 1, BF16_HALF_GRADS, True),
    (_gpt2_pair, 4, BF16_HALF_GRADS, True),
    (_glm_pair, 2, BF16_HALF_GRADS, False),
], ids=["gas1", "gas4", "gas1-half-grads", "gas4-half-grads",
        "exempt-leaves-half-grads"])
def test_six_steps_equal_the_in_program_cast(pair, gas, bf16, exact):
    """Losses, every micro-batch's gradients and the master after every
    step, bit for bit (GPT-2); GLM's toy, with leaves the optimizer does
    not own: losses and those leaves bit for bit, gradients to
    ONE_BF16_VALUE, the master after a step FROM THE SAME STATE to a
    twentieth of a step of Adam."""
    engine, twin = pair(gas, bf16)
    assert engine._copy_refused is None
    exempt = engine._exempt is not None
    # the optimizer's leaves in fp32 have a copy, the model's own none
    assert sum(engine._copy_mask) == len(engine._copy_mask) - (
        sum(engine._exempt.mask) if exempt else 0)
    parent = _InProgramCast(twin)
    grad_dtype = jnp.bfloat16 if "grads_in_compute_dtype" in bf16 else (
        jnp.float32)
    start = jax.tree.map(np.asarray, engine.params)
    for micro_batches in _batches(6, gas):
        if not exact:
            parent.take_state_of(engine)
        want_losses, want_grads, want_params = parent.step(micro_batches)
        for ids, want_loss, want in zip(micro_batches, want_losses,
                                        want_grads):
            loss = engine.forward(ids)
            assert float(loss) == float(want_loss)
            assert all(g.dtype == grad_dtype
                       for g in jax.tree.leaves(engine._cached_grads))
            _equal(engine._cached_grads, want,
                   **({} if exact else ONE_BF16_VALUE))
            engine.backward(loss)
            engine.step()
        _equal(engine.params, want_params,
               **({} if exact else {"atol": 1e-3 / 20}))
        _copy_is_the_cast_of_the_master(engine)
    if exempt:
        def own(tree):
            return _masked_leaves(tree, engine._exempt.mask)
        _equal(own(engine.params), own(want_params))
        assert any(not np.array_equal(a, b)
                   for a, b in zip(own(start), own(engine.params)))
    ds.reset_mesh_context()


# ---------------------------------------------------------------------- #
# every other writer of the master rebuilds the copy
# ---------------------------------------------------------------------- #
def _simple(**config):
    """tests/unit/test_engine.py's engine of the simple model, on the
    whole mesh."""
    ds.reset_mesh_context()
    return make_engine(**config)


def _simple_batch(seed):
    rng = np.random.RandomState(seed)
    return (rng.normal(0, 1, (8, HIDDEN)).astype(np.float32),
            rng.normal(0, 1, (8,)).astype(np.float32))


def _next_loss(engine):
    return float(engine.forward(*_simple_batch(99)))


def _fresh_from(tmp_path, tag, **config):
    fresh = _simple(**config)
    fresh.load_checkpoint(str(tmp_path), tag=tag)
    return fresh


def _after_load_checkpoint(tmp_path):
    config = {"bf16": BF16}
    engine = _simple(**config)
    for seed in range(2):
        engine.backward(engine.forward(*_simple_batch(seed)))
        engine.step()
    engine.save_checkpoint(str(tmp_path), tag="t")
    for seed in range(2, 4):
        engine.backward(engine.forward(*_simple_batch(seed)))
        engine.step()
    engine.load_checkpoint(str(tmp_path), tag="t")
    return engine, "t", config


def _after_an_overflow_skipped_step(tmp_path):
    config = {"fp16": {"enabled": True, "initial_scale_power": 4,
                       "loss_scale_window": 2, "hysteresis": 1,
                       "min_loss_scale": 0.25}}
    engine = _simple(**config)
    engine.backward(engine.forward(*_simple_batch(0)))
    engine.step()
    engine.backward(engine.forward(
        np.full((8, HIDDEN), np.nan, np.float32), np.zeros(8, np.float32)))
    engine.step()
    assert engine.overflow and engine.skipped_steps == 1
    engine.save_checkpoint(str(tmp_path), tag="t")
    return engine, "t", config


def _after_a_sentinel_rollback(tmp_path):
    from deepspeed_tpu.runtime.resilience.chaos import poison_batch
    config = {"bf16": BF16, "resilience": {
        "enabled": True, "sentinel": {
            "enabled": True, "policy": "rewind", "anomaly_budget": 5,
            "warmup_steps": 50}}}
    engine = _simple(**config)
    for seed in range(2):
        engine.backward(engine.forward(*_simple_batch(seed)))
        engine.step()
    engine.save_checkpoint(str(tmp_path), tag="good")
    engine.backward(engine.forward(*_simple_batch(2)))
    engine.step()
    engine.backward(engine.forward(*poison_batch(_simple_batch(3))))
    engine.step()
    assert engine.sentinel.rewinds == 1 and engine.global_steps == 2
    return engine, "good", config


def _after_the_setter(tmp_path):
    config = {"bf16": BF16}
    engine = _simple(**config)
    engine.backward(engine.forward(*_simple_batch(0)))
    engine.step()
    engine.params = jax.tree.map(lambda x: x * 0.5, engine.params)
    engine.save_checkpoint(str(tmp_path), tag="t")
    return engine, "t", config


@pytest.mark.parametrize("writer", [
    _after_load_checkpoint, _after_an_overflow_skipped_step,
    _after_a_sentinel_rollback, _after_the_setter],
    ids=lambda f: f.__name__.lstrip("_"))
def test_the_next_loss_is_a_fresh_engines_from_the_same_state(
        writer, tmp_path):
    engine, tag, config = writer(tmp_path)
    _copy_is_the_cast_of_the_master(engine)
    loss = _next_loss(engine)
    fresh = _fresh_from(tmp_path, tag, **config)
    _equal(fresh.params, engine.params)
    assert loss == _next_loss(fresh)


def test_a_checkpoint_holds_the_master_alone(tmp_path):
    engine = _simple(bf16=BF16)
    engine.backward(engine.forward(*_simple_batch(0)))
    engine.step()
    engine.save_checkpoint(str(tmp_path), tag="t")
    masters = len(jax.tree.leaves(engine.params))
    states = 0
    for path in (tmp_path / "t").glob("*.npz"):
        with np.load(path) as arrays:
            for name in arrays.files:
                # bf16 would come back as raw two-byte void
                assert arrays[name].dtype.itemsize != 2, (path.name, name)
            if "model_states" in path.name:
                states = len(arrays.files)
    assert states == masters


# ---------------------------------------------------------------------- #
# the byte budget: the copy is state, the budget what it was
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("config, kept", [
    ({"bf16": BF16_HALF_GRADS}, True),
    ({"bf16": BF16}, True),
    ({"bf16": BF16, "sparse_gradients": True,
      "zero_optimization": {"stage": 1}}, False),
    ({}, False),
], ids=["half-grads", "bf16", "sparse-gradients", "fp32"])
def test_the_budget_is_the_parents_with_the_copy_among_the_state(
        monkeypatch, config, kept):
    limit = 16 * 10 ** 9
    monkeypatch.setattr(ck, "device_bytes_limit", lambda device: limit)
    model = _gpt2(activation_checkpointing=True)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = _engine(model, params, gas=2, **config)
    budget = model._remat_budget
    n = sum(x.size for x in jax.tree.leaves(params))
    half = "grads_in_compute_dtype" in config.get("bf16", {})
    # the parent's arithmetic: master, two moments and a count, two
    # gradient trees (gas 2); the copy in the grad program's working set
    state = 4 * n * 3 + 4 + 2 * n * (2 if half else 4)
    cast = 2 * n if "bf16" in config else 0
    shape = (2 * SEQ, 32, 2, VOCAB, 2)
    parent = limit - state - ck.working_set_bytes(*shape, cast)
    assert (engine._weights is not None) == kept
    assert budget.state_bytes == state + (cast if kept else 0)
    assert budget.cast_bytes == (0 if kept else cast)
    assert budget.bytes(ck.working_set_bytes(
        *shape, budget.cast_bytes)) == parent
    ds.reset_mesh_context()


# ---------------------------------------------------------------------- #
# the programs' texts
# ---------------------------------------------------------------------- #
def _converts_under_the_cast(text):
    """Instructions of ``text`` that convert fp32 to bf16 under the name
    of the weight cast, fused or not."""
    return [line for line in text.splitlines()
            if re.search(r"= bf16\[[\d,]*\]\S* convert\(", line)
            and scope_map.CAST_SCOPE in line]


@pytest.mark.parametrize("bf16", [BF16, BF16_HALF_GRADS],
                         ids=["fp32-grads", "half-grads"])
def test_the_apply_converts_each_leaf_once_and_the_grad_program_none(bf16):
    model = _gpt2(scan_layers=True)
    engine = _engine(model, model.init_params(jax.random.PRNGKey(0)),
                     bf16=bf16)
    engine.backward(engine.forward(np.zeros((2, SEQ), np.int32)))
    engine.step()
    texts = {name: text() for name, text in engine.step_programs()}
    assert _converts_under_the_cast(texts["jit_loss_and_grads"]) == []
    assert "cast" not in set(scope_map.parse_parts(
        texts["jit_loss_and_grads"]).values())
    assert len(_converts_under_the_cast(texts["jit_apply_step"])) == sum(
        engine._copy_mask)
    # the twin on the master: one convert a leaf in the grad program
    twin = jax.jit(engine._loss_and_grads).lower(
        engine.params, engine.scaler_state, engine._rng,
        jnp.zeros((2, SEQ), jnp.int32)).compile().as_text()
    assert len(_converts_under_the_cast(twin)) >= sum(engine._copy_mask)
    ds.reset_mesh_context()


def test_the_new_copy_takes_a_donated_buffer():
    model = _gpt2()
    engine = _engine(model, model.init_params(jax.random.PRNGKey(0)),
                     bf16=BF16)
    engine.backward(engine.forward(np.zeros((2, SEQ), np.int32)))
    old = _masked_leaves(engine._weights, engine._copy_mask)
    engine.step()
    assert all(x.is_deleted() for x in old)
    text = dict(engine.step_programs())["jit_apply_step"]()
    header = text.splitlines()[0]
    aliased = {int(k) for k in re.findall(r"\{(\d+)\}: \(\d+, \{\}",
                                          header)}
    outputs = len(jax.tree.leaves((engine.params, engine.opt_state,
                                   engine.scaler_state))) + 1
    copies = sum(engine._copy_mask)
    # fp32 gradient buffers: only the old copy has the new one's shapes
    assert set(range(outputs, outputs + copies)) <= aliased
    ds.reset_mesh_context()


def test_the_log_says_who_writes_and_who_reads(caplog):
    import logging
    from deepspeed_tpu.utils.logging import logger
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            model = _gpt2()
            params = model.init_params(jax.random.PRNGKey(0))
            engine = _engine(model, params, bf16=BF16)
            _engine(_gpt2(), params)
    finally:
        logger.removeHandler(caplog.handler)
    n = sum(x.size for x in jax.tree.leaves(params))
    lines = [r.getMessage() for r in caplog.records
             if "weight copy:" in r.getMessage()]
    assert len(lines) == 2
    assert (f"jit_apply_step writes {sum(engine._copy_mask)} of "
            f"{len(engine._copy_mask)} leaves, {2 * n:,} B, in bfloat16 "
            "for jit_loss_and_grads to read") in lines[0]
    assert "none, the grad program casts the master itself (no leaf " \
        "differs from the compute dtype)" in lines[1]
    ds.reset_mesh_context()


# ---------------------------------------------------------------------- #
# the paths that keep their own cast
# ---------------------------------------------------------------------- #
def _pipeline():
    from tests.unit import test_pipe
    ds.reset_mesh_context()
    engine = test_pipe._engine(config=dict(test_pipe.CONFIG, bf16=BF16))
    x, y = test_pipe.make_data(64)
    it = test_pipe._batch_iter(x, y, 4)
    return engine, lambda: engine.train_batch(it)


def _gpt2_on_the_mesh(hidden=32, seq=SEQ, bf16=True, tied=True, **config):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=-1)
    model = GPT2Model(GPT2Config(
        vocab_size=128, n_positions=seq, hidden_size=hidden, num_layers=4,
        num_heads=4, bf16=bf16, embd_dropout=0.0, attn_dropout=0.0,
        hidden_dropout=0.0, tie_word_embeddings=tied))
    dp = mesh.data_parallel_world_size
    engine = ds.initialize(
        model=model, mesh=mesh, rng=jax.random.PRNGKey(7),
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9, "bf16": {"enabled": bf16},
                **config})[0]
    ids = np.random.RandomState(1).randint(0, 128, (dp, seq)).astype(
        np.int32)

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss
    return engine, step


def _sparse_gradients():
    # row-sparse gradients want an embedding of their own
    return _gpt2_on_the_mesh(tied=False, sparse_gradients=True)


def _zero3_stream():
    # tests/unit/test_zero3_streaming.py's shapes, and fp32: XLA's CPU
    # partitioner aborts on other streams (.claude/skills/verify, round 11)
    from tests.unit import test_zero3_streaming as z3
    engine, step = _gpt2_on_the_mesh(
        hidden=64, seq=z3.SEQ, bf16=False, zero_optimization={
            "stage": 3, "stage3_param_persistence_threshold": 0,
            "stage3_max_live_parameters": z3.LAYER_PARAMS,
            "stage3_prefetch_bucket_size": 0})
    assert engine._zero3_stream is not None
    return engine, step


def _simple_path(bf16=True, **config):
    engine = _simple(bf16={"enabled": bf16}, **config)
    batch = _simple_batch(0)

    def step():
        loss = engine.forward(*batch)
        engine.backward(loss)
        engine.step()
        return loss
    return engine, step


def _onebit():
    # fp32, as tests/unit/test_onebit.py: XLA's CPU compiler aborts on the
    # bf16 phase programs
    return _simple_path(
        bf16=False,
        optimizer={"type": "OneBitAdam",
                   "params": {"lr": 1e-3, "freeze_step": 2}},
        zero_optimization={"stage": 2, "low_bandwidth": {"onebit": True}})


def _offload():
    return _simple_path(zero_optimization={
        "stage": 2, "offload_optimizer": {"device": "cpu"}})


def _a_model_that_says_so():
    model = _glm(False)
    engine = _engine(model, glm_toy._params(model), bf16=BF16)
    ids = _batches(1, 1)[0][0]

    def step():
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        return loss
    return engine, step


@pytest.mark.parametrize("build, why", [
    (_a_model_that_says_so, "routed experts"),
    (_pipeline, "pipeline engine"), (_sparse_gradients, "sparse_gradients"),
    (_onebit, "1-bit"), (_offload, "offload"), (_zero3_stream, "ZeRO-3")], ids=lambda x: getattr(x, "__name__", None))
def test_a_path_with_programs_of_its_own_keeps_its_cast(build, why):
    engine, step = build()
    assert engine._weights is None and engine._copy_mask is None
    assert why in engine._copy_refused
    losses = [float(step()) for _ in range(3)]
    assert all(np.isfinite(losses)), losses
    assert engine._weights is None
    ds.reset_mesh_context()


def test_the_auditor_sees_the_copy_donated():
    model = _gpt2()
    engine = _engine(model, model.init_params(jax.random.PRNGKey(0)),
                     bf16=BF16_HALF_GRADS,
                     analysis={"mode": "warn", "donation_min_mb": 0.01})
    from deepspeed_tpu.analysis import rules
    from deepspeed_tpu.analysis.auditor import engine_targets
    assert [f for f in engine.program_audit.findings
            if f.rule == rules.RULE_DONATION] == []
    apply = [t for t in engine_targets(engine)
             if t.label == "apply_step"][0]
    copy = [a for a in apply.args if a.label == "weight_copy"][0]
    n = sum(x.size for x in jax.tree.leaves(engine.params))
    assert copy.donated and copy.consumed and copy.nbytes == 2 * n
    # the apply as it is dispatched: the master's leaves and the copy's
    assert len(apply.closed_jaxpr.jaxpr.outvars) == len(jax.tree.leaves(
        (engine.params, engine.opt_state, engine.scaler_state))) + 1 + sum(
        engine._copy_mask)
    ds.reset_mesh_context()
