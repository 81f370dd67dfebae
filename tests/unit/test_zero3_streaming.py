"""Explicit ZeRO-3 streaming (stage3_streaming.py): the
stage3_max_live_parameters / stage3_prefetch_bucket_size consumers.

Reference behavior being mirrored: stage3.py:294
PartitionedParameterCoordinator (gather-at-use, bounded live set, prefetch)
— here asserted as (a) plan math honoring the knobs, (b) trajectory equality
with the non-streamed baseline across group sizes / prefetch / TP.
"""

import functools

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.models import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.zero.stage3_streaming import plan_layer_streaming

GLOBAL_BATCH = 8
SEQ = 32


def test_plan_honors_max_live():
    # 8 layers x 100 params; max_live 250 -> groups of 2, no prefetch room
    plan = plan_layer_streaming(num_layers=8, params_per_layer=100,
                                max_live_parameters=250,
                                prefetch_bucket_size=0)
    assert plan.layers_per_step == 2 and not plan.prefetch
    assert plan.live_parameters <= 250

    # prefetch halves the per-group budget (double buffer)
    plan = plan_layer_streaming(8, 100, 400, prefetch_bucket_size=100)
    assert plan.prefetch and plan.layers_per_step == 2
    assert plan.live_parameters <= 400

    # prefetch bucket smaller than a layer -> no prefetch
    plan = plan_layer_streaming(8, 100, 400, prefetch_bucket_size=50)
    assert not plan.prefetch and plan.layers_per_step == 4

    # budget can't hold two groups: max_live wins over prefetch
    plan = plan_layer_streaming(8, 100, 150, prefetch_bucket_size=100)
    assert not plan.prefetch and plan.layers_per_step == 1
    assert plan.live_parameters <= 150

    # group size always divides the layer count
    plan = plan_layer_streaming(6, 100, 500, 0)
    assert 6 % plan.layers_per_step == 0 and plan.layers_per_step == 3


def test_plan_degenerate():
    # max_live below one layer still streams one layer at a time
    plan = plan_layer_streaming(4, 1000, 10, 0)
    assert plan.layers_per_step == 1
    # unconstrained budget with prefetch: split into two overlapped groups
    # (same live set as one giant group, but the gathers overlap compute)
    plan = plan_layer_streaming(4, 10, 10 ** 9, 10 ** 9)
    assert plan.layers_per_step == 2 and plan.prefetch
    # the carried prefetch has NO even-group-count constraint: 18 layers
    # at a 6-group budget take groups of 6 (3 groups)
    plan = plan_layer_streaming(18, 100, 1300, 100)
    assert plan.prefetch and plan.layers_per_step == 6


def test_plan_prefetch_structures():
    # a bucket of 0 never prefetches, even with room to spare
    plan = plan_layer_streaming(8, 100, 10 ** 9, 0)
    assert not plan.prefetch
    assert plan.forfeited is None  # none was requested, nothing forfeited
    # an odd prime layer count prefetches: groups of 1, 7 carried steps
    plan = plan_layer_streaming(7, 100, 10 ** 9, 10 ** 9)
    assert plan.prefetch and plan.layers_per_step == 1
    # a single layer cannot form 2 groups: forfeits loudly, and says why
    plan = plan_layer_streaming(1, 100, 10 ** 9, 10 ** 9)
    assert not plan.prefetch and plan.layers_per_step == 1
    assert plan.forfeited is not None and ">= 2 groups" in plan.forfeited
    # a bucket that asks for prefetch which max_live cannot double-buffer
    # is a forfeit too (bucket < one layer stays the silent off switch)
    plan = plan_layer_streaming(8, 100, 150, prefetch_bucket_size=100)
    assert not plan.prefetch and plan.forfeited is not None
    assert "double buffer" in plan.forfeited
    plan = plan_layer_streaming(8, 100, 150, prefetch_bucket_size=50)
    assert not plan.prefetch and plan.forfeited is None


def test_body_closing_over_tracers_is_diagnosed(monkeypatch):
    """NO streaming mode can differentiate a body that captures traced
    values (shard_map cannot transpose captured tracers; the carried
    custom_vjp differentiates only explicit inputs) — scan() must log
    the actionable diagnosis up front instead of leaving the user with
    a bare NotImplementedError / UnexpectedTracerError from deep inside
    grad.  A clean body stays carried and silent.  (The repo logger
    sets propagate=False, so capture the log_dist call itself.)"""
    import jax.numpy as jnp
    from deepspeed_tpu.runtime.zero import stage3_streaming as s3
    from deepspeed_tpu.runtime.zero.stage3_streaming import (
        Zero3StreamContext, _body_closes_over_tracers)

    logged = []
    monkeypatch.setattr(
        s3, "log_dist", lambda msg, *a, **k: logged.append(str(msg)))
    ds.reset_mesh_context()
    ds.initialize_mesh(data=-1)
    ctx = ds.get_mesh_context()
    stream = Zero3StreamContext(ctx, 10 ** 9, 10 ** 9)
    stacked = jnp.asarray(np.random.RandomState(0).randn(4, 8, 8),
                          jnp.float32) * 0.1
    x = jnp.ones((8, 8), jnp.float32)

    def loss(params, tied):
        def body(c, xs):
            return jnp.tanh(c @ xs[0]["w"] * tied), None  # tied: captured

        return stream.scan(body, x, {"w": params}, ()).sum()

    with pytest.raises(Exception):  # the pre-existing grad failure
        jax.jit(jax.grad(loss, argnums=(0, 1)))(stacked, jnp.float32(0.7))
    assert any("closes over traced values" in m for m in logged), logged
    logged.clear()

    # a clean body (everything threaded through the scan) stays carried
    # and does not warn
    stream2 = Zero3StreamContext(ctx, 10 ** 9, 10 ** 9)

    def clean_loss(params):
        def body(c, xs):
            return jnp.tanh(c @ xs[0]["w"]), None

        return stream2.scan(body, x, {"w": params}, ()).sum()

    jax.jit(jax.grad(clean_loss))(stacked)
    assert stream2.last_plan.prefetch
    assert not any("closes over traced values" in m for m in logged)
    assert not _body_closes_over_tracers(lambda c, xs: (c, None))
    ds.reset_mesh_context()


def _train(zero_cfg: dict, tp: int = 1, steps: int = 3, num_layers: int = 4):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=-1, model=tp)
    cfg = GPT2Config(vocab_size=128, n_positions=SEQ, hidden_size=64,
                     num_layers=num_layers, num_heads=4, bf16=False,
                     embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    dp = mesh.data_parallel_world_size
    conf = {
        "train_micro_batch_size_per_gpu": GLOBAL_BATCH // dp,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": zero_cfg,
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, rng=jax.random.PRNGKey(7))
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                        (GLOBAL_BATCH, SEQ), 0, 128),
                     np.int32)
    losses = []
    for _ in range(steps):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    final = jax.tree.map(np.asarray, engine.params)
    stream = engine._zero3_stream
    ds.reset_mesh_context()
    return losses, final, stream


@functools.lru_cache(maxsize=None)
def _baseline():
    """``_train`` without ZeRO, once for every test that compares with
    it: (losses, final parameters)."""
    return _train({"stage": 0})[:2]


# one layer of the test model ~ 4*64*64 + 2*64*256 + 9*64 + 256 = 50k params
LAYER_PARAMS = 4 * 64 * 64 + 2 * 64 * 256 + 9 * 64 + 256


@pytest.mark.parametrize("stream_cfg", [
    # one layer per group, no prefetch
    {"stage3_max_live_parameters": LAYER_PARAMS,
     "stage3_prefetch_bucket_size": 0},
    # one layer per group + double-buffer prefetch
    {"stage3_max_live_parameters": 2 * LAYER_PARAMS,
     "stage3_prefetch_bucket_size": 2 * LAYER_PARAMS},
    # two layers per group
    {"stage3_max_live_parameters": 2 * LAYER_PARAMS,
     "stage3_prefetch_bucket_size": 0},
])
def test_streaming_matches_baseline(stream_cfg):
    base_losses, base_params = _baseline()
    cfg = dict(stage=3, stage3_param_persistence_threshold=0, **stream_cfg)
    losses, params, stream = _train(cfg)
    assert stream is not None and stream.active
    plan = stream.plan_for(
        {"dummy": np.zeros((4,) + (LAYER_PARAMS,), np.float32)})
    # max_live honored by construction (one-layer floor: the stream cannot
    # gather less than a whole layer)
    assert plan.live_parameters <= max(stream.max_live_parameters,
                                       plan.params_per_layer)
    np.testing.assert_allclose(losses, base_losses, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(base_params)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_backward_regathers_instead_of_saving():
    """The gathered layer params must NOT be saved as scan residuals (that
    would materialize the full unsharded stack and defeat max_live); the
    backward pass re-gathers (reference: stage3.py:546 PreBackwardFunction
    re-fetch).  Visible in the jaxpr as all_gathers in both the forward
    scan body and the remat backward body."""
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=-1)
    cfg = GPT2Config(vocab_size=128, n_positions=SEQ, hidden_size=64,
                     num_layers=4, num_heads=4, bf16=False, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    conf = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {
            "stage": 3, "stage3_param_persistence_threshold": 0,
            "stage3_max_live_parameters": LAYER_PARAMS,
            "stage3_prefetch_bucket_size": 0},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=conf,
                                    model_parameters=params, mesh=mesh,
                                    rng=jax.random.PRNGKey(7))
    ids = np.zeros((GLOBAL_BATCH, SEQ), np.int32)

    def loss_fn(p):
        return model.loss(p, None, ids)

    jaxpr = str(jax.make_jaxpr(jax.grad(loss_fn))(engine.params))
    assert jaxpr.count("all_gather") >= 2, \
        "expected all_gathers in both the forward scan and the remat backward"
    ds.reset_mesh_context()


def test_streaming_with_tensor_parallel():
    base_losses, base_params = _baseline()
    losses, params, stream = _train(
        {"stage": 3, "stage3_param_persistence_threshold": 0,
         "stage3_max_live_parameters": LAYER_PARAMS,
         "stage3_prefetch_bucket_size": LAYER_PARAMS}, tp=2)
    assert stream is not None and stream.active
    # TP=2 re-partitions the matmuls (and the chunked fused CE reassociates
    # its vocab sums) — the tolerance admits fp32 summation-order noise
    # but nothing structural.
    np.testing.assert_allclose(losses, base_losses, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(base_params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_zero3_bf16_streams_on_cpu():
    """z3 + bf16 must run the EXPLICIT streaming path on every backend
    (regression: XLA CPU's AllReducePromotion used to hard-abort on the
    half-precision reduce-scatter the region's backward emits, forcing a
    GSPMD fallback; _all_gather_f32grad now runs that collective in fp32)."""
    import jax
    import numpy as np
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=-1)
    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=16,
                     num_layers=2, num_heads=2, bf16=True, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    engine, _, _, _ = ds.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": 3, "stage3_param_persistence_threshold": 0},
                "steps_per_print": 10 ** 9},
        mesh=mesh, rng=jax.random.PRNGKey(7))
    stream = engine._zero3_stream
    assert stream is not None and stream.active
    # the streamed region really engages for the bf16 carry (no fallback)
    dummy_carry = jax.numpy.zeros((8, 16, 16), "bfloat16")
    assert stream.usable(dummy_carry, params=engine.params)
    ids = np.random.RandomState(0).randint(0, 64, (8, 16)).astype(np.int32)

    # the compiled grad graph must contain the streaming all_gathers
    def loss_fn(p):
        return model.loss(p, None, ids)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss_fn))(engine.params))
    assert jaxpr.count("all_gather") >= 2, \
        "bf16 ZeRO-3 must take the explicit streaming path, not GSPMD"

    losses = []
    for _ in range(5):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def _tiny_ids(seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (8, 16),
                                         0, 64), np.int32)


def _tiny_engine(zero_cfg, bf16=False, num_layers=5, mesh_axes=None,
                 pld=False, dropout=0.0, checkpointing=False,
                 optimizer="Adam"):
    """Tiny GPT-2 under an engine on a fresh mesh.  Returns (engine,
    model); the caller resets the mesh context."""
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(**(mesh_axes or {"data": -1}))
    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                     num_layers=num_layers, num_heads=4, bf16=bf16,
                     embd_dropout=dropout, attn_dropout=dropout,
                     hidden_dropout=dropout,
                     activation_checkpointing=checkpointing)
    model = GPT2Model(cfg)
    conf = {
        "train_micro_batch_size_per_gpu": 8 // mesh.data_parallel_world_size
        if mesh.data_parallel_world_size <= 8 else 1,
        "optimizer": {"type": optimizer, "params": {"lr": 1e-3}},
        "zero_optimization": zero_cfg,
        "steps_per_print": 10 ** 9,
    }
    if bf16:
        conf["bf16"] = {"enabled": True}
    if pld:
        # theta(t) = 0.5 + 0.5 exp(-gamma t): 1 at step 0, 0.5 from step 1
        conf["progressive_layer_drop"] = {"enabled": True, "theta": 0.5,
                                          "gamma": 5.0}
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, rng=jax.random.PRNGKey(7))
    return engine, model


def _train_tiny(zero_cfg, steps=2, seed_ids=1, **engine_kw):
    """Fast trainer for the prefetch-mode parity matrix: tiny model, two
    steps, losses + final params.  Modes are compared against each other
    (same gather/quantization structure), so tolerances stay tight."""
    engine, _ = _tiny_engine(zero_cfg, **engine_kw)
    ids = _tiny_ids(seed_ids)
    losses = []
    for _ in range(steps):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    final = jax.tree.map(np.asarray, engine.params)
    plan = engine._zero3_stream.last_plan
    ds.reset_mesh_context()
    return losses, final, plan


def _mode_cfg(mode, extra=None, live=2 * 12832):
    """A streamed stage-3 block whose structure follows the bucket:
    "carried" asks for a prefetch bucket of the live budget, "off" for
    none (gathers at use)."""
    cfg = {"stage": 3, "stage3_param_persistence_threshold": 0,
           "stage3_max_live_parameters": live,
           "stage3_prefetch_bucket_size": live if mode == "carried" else 0}
    cfg.update(extra or {})
    return cfg


def _group_cfg(mode, layers_per_step):
    """``_mode_cfg`` whose plan comes out at ``layers_per_step`` (12,832
    parameters a layer; carried halves the live budget for its double
    buffer)."""
    return _mode_cfg(mode, live=layers_per_step * 12832
                     * (2 if mode == "carried" else 1))


# The three parity tests below step with SGD, whose update is linear in
# the gradient, so their tolerances bound the GRADIENTS: under Adam an
# ulp on a gradient that is zero in exact arithmetic (the key bias's)
# becomes 2e-6 of parameter between any two fusings of one program.
@pytest.mark.parametrize("mode", ["carried"])
def test_carried_mode_parity_fp32(mode):
    """Prefetch parity (ISSUE 7): the carried double-buffer program must
    train identically to the at-use gather-per-group program — 5 layers,
    an ODD group count."""
    l_off, p_off, plan_off = _train_tiny(_mode_cfg("off"), optimizer="sgd")
    assert not plan_off.prefetch
    l_m, p_m, plan_m = _train_tiny(_mode_cfg(mode), optimizer="sgd")
    assert plan_m.prefetch
    assert plan_m.num_layers // plan_m.layers_per_step == 5
    np.testing.assert_allclose(l_m, l_off, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_m), jax.tree.leaves(p_off)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_carried_mode_parity_bf16():
    l_off, p_off, _ = _train_tiny(_mode_cfg("off"), bf16=True)
    l_car, p_car, plan = _train_tiny(_mode_cfg("carried"), bf16=True)
    assert plan.prefetch
    # bf16 rounds differently under the two program structures (XLA
    # fuses the carried and at-use bodies differently); the tolerance
    # admits half-precision noise, nothing structural
    np.testing.assert_allclose(l_car, l_off, rtol=2e-4)
    # Adam normalizes bf16-rounded grads into O(lr) updates — a sign
    # flip on a near-zero gradient element diverges by 2 x lr x steps =
    # 4e-3 worst case — so params get an Adam-noise-ceiling atol while
    # the losses above carry the tight parity signal; a structural bug
    # would diff at O(1)
    for a, b in zip(jax.tree.leaves(p_car), jax.tree.leaves(p_off)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-3)
    assert l_car[-1] < l_car[0]  # still actually training


def test_carried_low_bandwidth_parity():
    """Carried prefetch composes with the qwZ quantized wire: both modes
    quantize identically (same blockwise layout, straight-through
    backward), so the trajectories match tightly."""
    lb = {"low_bandwidth": {"enabled": True, "qwz_bits": 8}}
    l_off, p_off, _ = _train_tiny(_mode_cfg("off", lb), optimizer="sgd")
    l_car, p_car, plan = _train_tiny(_mode_cfg("carried", lb),
                                     optimizer="sgd")
    assert plan.prefetch
    np.testing.assert_allclose(l_car, l_off, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_car), jax.tree.leaves(p_off)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_carried_hpz_parity():
    """Carried prefetch composes with the hpZ sub-mesh fast path: the
    hot-loop gathers stay confined to the secondary axes in both modes,
    and the trajectories match."""
    lb = {"low_bandwidth": {"enabled": True, "hpz_group_size": 2}}
    mesh_axes = {"data": 4, "expert": 2}
    l_off, p_off, _ = _train_tiny(_mode_cfg("off", lb),
                                  mesh_axes=mesh_axes, optimizer="sgd")
    l_car, p_car, plan = _train_tiny(_mode_cfg("carried", lb),
                                     mesh_axes=mesh_axes, optimizer="sgd")
    assert plan.prefetch
    np.testing.assert_allclose(l_car, l_off, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_car), jax.tree.leaves(p_off)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# --------------------------------------------------------------------- #
# the backward's wire: shifted permutes in the gradients' own dtype
# --------------------------------------------------------------------- #
def _grads_of(engine, model, ids):
    return jax.tree.map(np.asarray, jax.jit(jax.grad(
        lambda p: model.loss(p, None, ids)))(engine.params))


@pytest.mark.parametrize("mode", ["carried", "off"])
def test_two_group_gradients_match_the_unstreamed_engine(mode):
    """A streamed two-group model's gradients, their reduce-scatters
    gone as permutes, equal the non-streamed engine's: with prefetch
    (``scatter_grads`` one group late, under the next group's backward)
    and without (the gather's own transpose inside the scan)."""
    ids = _tiny_ids()
    engine, model = _tiny_engine({"stage": 0}, num_layers=4)
    want = _grads_of(engine, model, ids)
    engine, model = _tiny_engine(_group_cfg(mode, 2), num_layers=4)
    stream = engine._zero3_stream
    got = _grads_of(engine, model, ids)
    assert stream.last_plan.prefetch == (mode == "carried")
    assert stream.last_plan.num_layers // stream.last_plan.layers_per_step == 2
    # ten of a layer's twelve leaves: the two column-parallel biases'
    # one dimension is the tensor-parallel axis's, so ZeRO leaves them whole
    assert stream.last_grad_wire["permuted_leaves"] == 10
    assert stream.last_grad_wire["native_leaves"] == 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    ds.reset_mesh_context()


@pytest.mark.parametrize("mode,groups", [("carried", 3), ("off", 2)])
def test_streamed_grad_program_permutes_where_it_reduce_scattered(mode,
                                                                  groups):
    """The grad jaxpr of the streamed stack holds no reduce-scatter: each
    gathered leaf's transpose is ``W - 1`` permutes a group, operands in
    the leaf's dtype (issued one group late under the carried stream,
    inside the scan's transposed body where groups are gathered at
    use)."""
    from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
    engine, model = _tiny_engine(_group_cfg(mode, 2), num_layers=2 * groups)
    ids = _tiny_ids()
    jx = jax.make_jaxpr(jax.grad(lambda p: model.loss(p, None, ids)))(
        engine.params)
    permutes = scatters = 0
    for c in iter_eqns(jx):
        name = c.eqn.primitive.name
        if name == "ppermute":
            permutes += c.mult
            assert c.eqn.invars[0].aval.dtype == np.float32
        scatters += name in ("psum_scatter", "reduce_scatter")
    assert scatters == 0
    leaves = engine._zero3_stream.last_grad_wire["permuted_leaves"]
    assert leaves == 10
    assert permutes == (8 - 1) * leaves * groups
    ds.reset_mesh_context()


def test_grad_wire_counter_names_permuted_and_native_leaves(monkeypatch):
    """The plan line and the ``ds.*`` collector say how a group's
    gradients leave: as permutes where the shards divide the gathered
    dimension within ``PERMUTE_SCATTER_MAX_WORLD``, as the native
    collective past it, and the bytes a shard sends a step."""
    import jax.numpy as jnp
    from deepspeed_tpu.monitor import trace as host_trace
    from deepspeed_tpu.runtime.comm import low_bandwidth as lb
    from deepspeed_tpu.runtime.zero import stage3_streaming as s3
    from deepspeed_tpu.runtime.zero.stage3_streaming import Zero3StreamContext
    logged = []
    monkeypatch.setattr(
        s3, "log_dist", lambda msg, *a, **k: logged.append(str(msg)))
    ds.reset_mesh_context()
    ds.initialize_mesh(data=-1)
    ctx = ds.get_mesh_context()
    x = jnp.ones((8, 16), jnp.bfloat16)
    params = {"w": jnp.ones((4, 16, 16), jnp.bfloat16) * 0.1}

    def loss(stream, p):
        def body(c, xs):
            return jnp.tanh(c @ xs[0]["w"]), None
        return stream.scan(body, x, p, ()).astype(jnp.float32).sum()

    def wire_of():
        stream = Zero3StreamContext(ctx, 10 ** 9, 10 ** 9)
        since = host_trace.last_span()[2]
        jax.make_jaxpr(jax.grad(lambda p: loss(stream, p)))(params)
        marks = [m for m in host_trace.spans(since - 1)
                 if m[0] == "ds.zero3.grad_wire"]
        return stream.last_grad_wire, marks[-1][3]

    # [2 layers a group, 16, 16] bf16 over 8 shards, 2 groups a step:
    # seven of eight chunks leave a shard in bf16
    wire, mark = wire_of()
    sent = 2 * 16 * 16 * 2 * 7 // 8 * 2
    assert wire == mark == {
        "permuted_leaves": 1, "native_leaves": 0, "quantized_leaves": 0,
        "permute_bytes_per_step": sent, "native_bytes_per_step": 0}
    assert (f"as permutes for 1 leaves ({sent:,} B sent per shard and "
            "step) and as the native reduce-scatter for 0 (0 B)"
            ) in logged[-1]

    monkeypatch.setattr(lb, "PERMUTE_SCATTER_MAX_WORLD", 2)
    wire, mark = wire_of()
    assert wire == mark == {
        "permuted_leaves": 0, "native_leaves": 1, "quantized_leaves": 0,
        "permute_bytes_per_step": 0, "native_bytes_per_step": 2 * sent}
    assert "as permutes for 0 leaves" in logged[-1]
    ds.reset_mesh_context()


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four described chips of a v5e host (never while a module is
    imported: one process loads the TPU's library, every xdist worker
    imports this file), the persistent compile cache kept out of it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_streamed_grad_program_compiles_to_async_permutes_for_v5e(v5e_2x2):
    """The chip's compiler, given the streamed grad program of a tiny
    two-group GPT-2 over the four chips of ``v5e:2x2``, emits no
    reduce-scatter and ``(W - 1) x leaves x groups`` asynchronous
    collective-permutes whose operands are bf16.  A compile is not a
    run: what the starts and dones have between them on the chip is the
    benchmark's ``collective_exposed_ms``."""
    import re
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import MeshContext, set_mesh_context
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner
    from deepspeed_tpu.runtime.zero.stage3_streaming import Zero3StreamContext

    ds.reset_mesh_context()
    ctx = MeshContext.create(devices=v5e_2x2, data=4)
    set_mesh_context(ctx)
    cfg = GPT2Config(vocab_size=256, n_positions=128, hidden_size=128,
                     num_layers=4, num_heads=2, bf16=True,
                     embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    per_layer = 12 * 128 * 128 + 13 * 128
    stream = Zero3StreamContext(ctx, 4 * per_layer, 4 * per_layer, 0)
    model.install_zero3_streaming(stream)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)
    part = ZeroPartitioner(ctx, 3)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, part.param_shardings(shapes))
    ids = jax.ShapeDtypeStruct((8, 128), jnp.int32,
                               sharding=NamedSharding(ctx.mesh, P("data")))
    try:
        text = jax.jit(jax.grad(
            lambda p, i: model.loss(p, None, i))).lower(
                params, ids).compile().as_text()
    finally:
        ds.reset_mesh_context()
    wire = stream.last_grad_wire
    assert stream.last_plan.prefetch
    assert stream.last_plan.num_layers // stream.last_plan.layers_per_step == 2
    assert wire["native_leaves"] == 0 and wire["permuted_leaves"] == 10
    assert " reduce-scatter(" not in text
    assert "reduce-scatter-start(" not in text
    starts = re.findall(r"= \(?(\w+)\[[^\n]*? collective-permute-start\(",
                        text)
    assert len(starts) == (4 - 1) * wire["permuted_leaves"] * 2
    assert set(starts) == {"bf16"}
    assert " collective-permute(" not in text  # none left synchronous


def test_stream_context_low_bandwidth_wiring():
    """Zero3StreamContext consumes the ZeroLowBandwidthConfig: hpZ
    confines the param manual set (and spec sizes) to the resolved
    sub-mesh; qwZ/qgZ route leaf gathers through the quantized
    collective (jaxpr shows the int8 payload riding the wire)."""
    import jax.numpy as jnp
    from deepspeed_tpu.config import ZeroLowBandwidthConfig
    from deepspeed_tpu.runtime.zero.stage3_streaming import Zero3StreamContext

    ds.reset_mesh_context()
    ds.initialize_mesh(data=4, expert=2)
    ctx = ds.get_mesh_context()

    # hpZ: param gathers confined to the inner axis, grads still span all
    lbc = ZeroLowBandwidthConfig(hpz_group_size=2)
    stream = Zero3StreamContext(ctx, 10 ** 9, 0, low_bandwidth=lbc)
    assert stream.manual == frozenset({"data", "expert"})
    assert stream.param_manual == frozenset({"expert"})
    assert stream.param_axis_sizes["data"] == 1
    assert stream.param_axis_sizes["expert"] == 2

    # qwZ: the quantized gather traces an int8 all_gather + fp32 scales
    lbc = ZeroLowBandwidthConfig(qwz_bits=8)
    stream = Zero3StreamContext(ctx, 10 ** 9, 0, low_bandwidth=lbc)

    def body(shard):
        return stream._gather_leaf(shard, ("data", "expert"), 0)

    from jax.sharding import PartitionSpec as P
    x = jnp.zeros((16, 8), jnp.float32)
    jaxpr = str(jax.make_jaxpr(jax.shard_map(
        body, mesh=ctx.mesh, in_specs=P(("data", "expert")), out_specs=P(),
        check_vma=False))(x))
    assert "i8" in jaxpr and "all_gather" in jaxpr
    # off (or integer leaves) falls back to the fp32-transpose gather
    stream_off = Zero3StreamContext(ctx, 10 ** 9, 0)
    jaxpr_off = str(jax.make_jaxpr(jax.shard_map(
        lambda s: stream_off._gather_leaf(s, ("data", "expert"), 0),
        mesh=ctx.mesh, in_specs=P(("data", "expert")), out_specs=P(),
        check_vma=False))(x))
    assert "i8" not in jaxpr_off
    ds.reset_mesh_context()


def test_stream_context_per_direction_wire_gate():
    """_leaf_wire_bits degrades each direction independently: the
    forward gate compares against the leaf's native width, the backward
    against the fp32 wire the dense fallback actually moves
    (f32_psum_scatter promotes half grads) — so a bf16 leaf too skinny
    for qwZ still gets its qgZ reduce-scatter, and a truly skinny leaf
    (per-element scales) goes fully dense."""
    import jax.numpy as jnp
    from deepspeed_tpu.config import ZeroLowBandwidthConfig
    from deepspeed_tpu.runtime.zero.stage3_streaming import Zero3StreamContext

    ds.reset_mesh_context()
    ds.initialize_mesh(data=-1)
    ctx = ds.get_mesh_context()
    lbc = ZeroLowBandwidthConfig(qwz_bits=8, qgz_bits=8)
    stream = Zero3StreamContext(ctx, 10 ** 9, 0, low_bandwidth=lbc)

    wide = jnp.zeros((1, 64, 256), jnp.float32)
    assert stream._leaf_wire_bits(wide, 1) == (8, 8)
    # (2, 128) bf16 gathered along dim 1: rest=2 → fwd int8+scales (6B)
    # loses to native bf16 (4B) but beats the fp32 backward wire (8B)
    half = jnp.zeros((2, 128), jnp.bfloat16)
    assert stream._leaf_wire_bits(half, 1) == (0, 8)
    # rest=1 (bias, one layer per group): per-element scales lose to
    # both wires — fully dense
    bias = jnp.zeros((1, 128), jnp.float32)
    assert stream._leaf_wire_bits(bias, 1) == (0, 0)
    # integer leaves never quantize
    ints = jnp.zeros((1, 64, 256), jnp.int32)
    assert stream._leaf_wire_bits(ints, 1) == (0, 0)
    # lbc off → always dense
    off = Zero3StreamContext(ctx, 10 ** 9, 0)
    assert off._leaf_wire_bits(wide, 1) == (0, 0)
    ds.reset_mesh_context()


def test_stream_context_rejects_misaligned_hpz():
    """An hpz_group_size that doesn't match a ZeRO-axis suffix fails at
    context build with the valid sizes listed (engine-build-time error,
    not a mid-training trace surprise)."""
    from deepspeed_tpu.config import ZeroLowBandwidthConfig
    from deepspeed_tpu.runtime.zero.stage3_streaming import Zero3StreamContext

    ds.reset_mesh_context()
    ds.initialize_mesh(data=4, expert=2)
    ctx = ds.get_mesh_context()
    with pytest.raises(ValueError, match="hpz_group_size=3.*valid sizes"):
        Zero3StreamContext(ctx, 10 ** 9, 0,
                           low_bandwidth=ZeroLowBandwidthConfig(
                               hpz_group_size=3))
    ds.reset_mesh_context()


# -- the carried VJP saves one carry a LAYER and recomputes each layer once -- #

def _weighted_prim_count(jaxpr, name, weight=1):
    """Occurrences of primitive ``name`` in ``jaxpr`` and every jaxpr
    riding in its equations' params, each weighted by the ``length`` of
    the scans around it — how often the primitive RUNS in one call."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            total += weight
        inner_w = weight * (eqn.params["length"]
                            if eqn.primitive.name == "scan" else 1)
        for v in eqn.params.values():
            for sub in jax.tree.leaves(
                    v, is_leaf=lambda x: hasattr(x, "jaxpr") or
                    hasattr(x, "eqns")):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += _weighted_prim_count(inner, name, inner_w)
    return total


@pytest.mark.parametrize("num_layers,g,checkpointing", [
    (8, 4, True), (8, 2, True), (8, 1, True), (8, 4, False)])
def test_carried_stream_runs_each_layer_forward_twice(num_layers, g,
                                                      checkpointing):
    """A step runs every layer forward TWICE under the carried stream —
    once in the forward scan, once for its own backward — for every
    group size, with or without ``activation_checkpointing``.  With
    group-boundary residuals the backward's ``jax.vjp`` of a whole group
    ran the group forward a THIRD time to rebuild the checkpointed
    layers' input carries: 3L - S forwards (22 and 20 in the first two
    cases).  Counted as the ``tanh`` of gelu (one per layer forward) on
    the DCE'd grad jaxpr, weighted by scan lengths; the compiled CPU
    program cannot show it (the CPU backend drops ``jax.checkpoint``'s
    optimisation barriers and merges the recomputations, the TPU keeps
    them)."""
    from jax.interpreters import partial_eval as pe

    engine, model = _tiny_engine(_group_cfg("carried", g),
                                 num_layers=num_layers,
                                 checkpointing=checkpointing)
    ids = _tiny_ids()
    closed = jax.make_jaxpr(jax.grad(
        lambda p: model.loss(p, jax.random.PRNGKey(3), ids)))(engine.params)
    plan = engine._zero3_stream.last_plan
    assert plan.prefetch and plan.layers_per_step == g
    jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    assert _weighted_prim_count(jaxpr, "tanh") == 2 * num_layers
    ds.reset_mesh_context()


def test_carried_residuals_are_per_layer_carries(monkeypatch):
    """The carried VJP saves every layer's input carry — ``[S-1, g, ...]``
    per carry leaf from the forward scan and ``[g, ...]`` from the
    epilogue group, ``S x g`` carries in all — beside the SHARDED inputs,
    and never a gathered (``zero3_gathered``) buffer, which would stack
    the unsharded model."""
    from deepspeed_tpu.runtime.zero import stage3_streaming as s3

    seen = {}
    build = s3._build_carried_stream

    def recording_build(steps, g, gather_group, *rest):
        def recording_gather(shards):
            full = gather_group(shards)
            seen["gathered"] = [leaf.shape for leaf in full]
            return full

        carried = build(steps, g, recording_gather, *rest)
        fwd = carried.fwd

        def recording_fwd(*args):
            out, res = fwd(*args)
            seen["steps_g"] = (steps, g)
            seen["carry"] = jax.tree.leaves(args[0])[0].shape
            seen["res"] = [leaf.shape for leaf in jax.tree.leaves(res)]
            return out, res

        carried.fwd = recording_fwd
        return carried

    monkeypatch.setattr(s3, "_build_carried_stream", recording_build)
    engine, model = _tiny_engine(_group_cfg("carried", 2), num_layers=8,
                                 checkpointing=True)
    ids = _tiny_ids()
    jax.make_jaxpr(jax.grad(
        lambda p: model.loss(p, jax.random.PRNGKey(3), ids)))(engine.params)
    assert seen["steps_g"] == (4, 2)
    # the saved carries: leading [S-1, g] and [g], then one batch-shard
    # activation: S x g = num_layers of them
    assert seen["res"][:2] == [(3, 2) + seen["carry"], (2,) + seen["carry"]]
    assert not any(shape[-3:] == seen["carry"] for shape in seen["res"][2:])
    # nothing of a gathered group's shape ([g, full dims]) is saved; the
    # sharded inputs keep their [S, g, local dims] form.  Leaves too small
    # to shard gather to their own shape and say nothing either way.
    full = set(seen["gathered"])
    sharded = {shape[1:] for shape in seen["res"][2:]}
    assert full - sharded, (full, sharded)
    assert not any(shape in full - sharded for shape in seen["res"])
    ds.reset_mesh_context()


@pytest.mark.parametrize("pld", [False, True], ids=["dropout", "dropout+pld"])
def test_carried_parity_with_checkpointing_and_dropout(pld):
    """Carried against ``off`` with ``activation_checkpointing`` on, g > 1
    and the three dropouts at 0.1: the per-layer backward must hand every
    layer's recomputation the rng (and the PLD keep-probability and key)
    its forward drew, or the masks differ and so do the trajectories.
    The PLD case also restacks the float extras' cotangents.  Tolerances
    are ``test_carried_mode_parity_fp32``'s; the optimizer is SGD, whose
    step is linear in the gradient, so they bound the GRADIENTS: the two
    programs' differ by an ulp (6e-8 at most, on 0.14), a wrong mask by
    the size of a leaf's gradient (a perturbed key in the backward moves
    parameters by 1e-5, a hundred times ``atol``).  Under Adam the same
    ulp on a gradient that is zero in exact arithmetic (the key bias's)
    becomes 2e-6 of parameter, between ANY two fusings of one program."""
    kw = dict(num_layers=8, checkpointing=True, dropout=0.1, pld=pld,
              optimizer="sgd")
    l_off, p_off, plan = _train_tiny(_group_cfg("off", 4), **kw)
    assert not plan.prefetch
    l_car, p_car, plan = _train_tiny(_group_cfg("carried", 4), **kw)
    assert plan.prefetch and plan.layers_per_step == 4
    np.testing.assert_allclose(l_car, l_off, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_car), jax.tree.leaves(p_off)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
