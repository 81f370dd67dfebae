"""The Pallas flash call under a device mesh (ops/dispatch.py
manual_kernel_region): XLA cannot partition a Mosaic kernel, so the call
must sit in a region manual over every mesh axis — under plain GSPMD jit
and nested inside the streamed ZeRO-3 region.  The kernels run through
the Pallas interpreter on the 8-device CPU mesh; the chip lane repeats
the same programs compiled (chip_smoke.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.flash_attention import flash_attention, mha_reference

B, H, S, D = 8, 4, 128, 32


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(dispatch, "_interpret", True)
    # S=128 sits below the auto crossover; the kernel is the subject here
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")


def _qkv(seed=0, batch=B):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (batch, H, S, D), jnp.float32)
                 for k in ks)


def _loss(attn):
    def f(q, k, v):
        return jnp.sum(attn(q, k, v) ** 2)
    return f


def _pallas_eqn_shapes(jaxpr):
    """Operand shapes of every pallas_call in a jaxpr, nested ones too."""
    return [tuple(v.aval.shape for v in ctx.eqn.invars)
            for ctx in iter_eqns(jaxpr)
            if ctx.eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("axes", [{"data": 4, "model": 1},
                                  {"data": 2, "model": 2},
                                  {"data": 1, "expert": 2, "model": 2}])
def test_sharded_flash_matches_reference(interpret, axes):
    """Value and gradient parity with mha_reference under GSPMD jit, and
    the kernel sees per-shard operands (no gather in front of it)."""
    n = int(np.prod(list(axes.values())))
    ctx = ds.initialize_mesh(devices=jax.devices()[:n], **axes)
    q, k, v = _qkv()
    sh = NamedSharding(ctx.mesh, P(("data", "expert"), "model"))
    q, k, v = (jax.device_put(t, sh) for t in (q, k, v))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=128)

    def ref(q, k, v):
        return mha_reference(q, k, v, causal=True)

    grad = jax.jit(jax.value_and_grad(_loss(flash), argnums=(0, 1, 2)))
    val, gs = grad(q, k, v)
    val_r, gs_r = jax.value_and_grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(val, val_r, rtol=1e-5)
    for a, b in zip(gs, gs_r):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    batch_world = axes["data"] * axes.get("expert", 1)
    local = (B // batch_world, H // axes["model"], S, D)
    shapes = _pallas_eqn_shapes(jax.make_jaxpr(grad)(q, k, v))
    assert len(shapes) == 2  # forward, and the one backward kernel
    for operands in shapes:
        assert local in operands and (B, H, S, D) not in operands


def test_indivisible_dims_stay_replicated(interpret):
    """A batch the data axes do not divide runs whole on every shard
    instead of failing the region's specs (batch-1 decode, odd heads)."""
    ds.initialize_mesh(data=4, model=2)
    q, k, v = _qkv(batch=2)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=64, block_k=128))(q, k, v)
    np.testing.assert_allclose(out, mha_reference(q, k, v, causal=True),
                               rtol=1e-5, atol=1e-5)


def _dropped(out):
    """Dropout fingerprint per batch row: with v = 1 every output element
    is the kept probability mass of its row of P."""
    return np.asarray(out[:, 0, :, 0])


def test_dropout_masks_differ_across_batch_shards(interpret):
    """Every shard numbers its rows and heads from 0, so the region folds
    the shard index into the seed; identical inputs on every batch row
    then still draw different masks on different shards."""
    ds.initialize_mesh(devices=jax.devices()[:4], data=4)
    q, k, _ = _qkv(batch=1)
    q, k = (jnp.tile(t, (4, 1, 1, 1)) for t in (q, k))
    v = jnp.ones_like(q)

    def run(seed):
        return _dropped(jax.jit(lambda q, k, v: flash_attention(
            q, k, v, block_q=64, block_k=128, dropout_rate=0.5,
            dropout_seed=seed))(q, k, v))

    rows = run(7)
    for i in range(4):
        for j in range(i + 1, 4):
            assert not np.allclose(rows[i], rows[j]), (i, j)
    np.testing.assert_array_equal(rows, run(7))       # regenerable
    assert not np.allclose(rows, run(8))              # and seeded
    # unsharded, the rows differ through the kernel's own (seed, b) term
    ds.reset_mesh_context()
    whole = run(7)
    assert not np.allclose(whole[0], whole[1])


def test_dropout_backward_regenerates_forward_mask(interpret):
    """The two backward kernels rebuild the forward's mask from the seed
    and tile coordinates: the gradient w.r.t. v is exactly the dropped
    attention transposed, so it must agree with the forward output."""
    ds.initialize_mesh(devices=jax.devices()[:2], data=2)
    q, k, v = _qkv(batch=2)

    def f(v_):
        return flash_attention(q, k, v_, block_q=64, block_k=128,
                               dropout_rate=0.3, dropout_seed=3)

    out, vjp = jax.vjp(f, v)
    g = jnp.ones_like(out)
    (dv,) = vjp(g)
    # <f(v), g> is linear in v: <dv, v> == <f(v), g>
    np.testing.assert_allclose(jnp.vdot(dv, v), jnp.vdot(out, g),
                               rtol=1e-4)


def test_flash_inside_streamed_zero3_region(interpret):
    """ZeRO-3 streams the layer stack in a shard_map manual over the data
    axis only; the kernel region nests over the axes still automatic.
    The streamed engine's first loss and one step match the GSPMD
    stage-2 engine's on the same batch (dropout off)."""
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    def first_losses(stage, mesh_axes):
        ds.reset_mesh_context()
        ds.initialize_mesh(**mesh_axes)
        cfg = GPT2Config(vocab_size=128, n_positions=S, hidden_size=64,
                         num_layers=2, num_heads=2, bf16=False,
                         embd_dropout=0.0, attn_dropout=0.0,
                         hidden_dropout=0.0)
        model = GPT2Model(cfg)
        # the layer's default 512x1024 blocks fit S=128 as 128x128
        engine, _, _, _ = ds.initialize(
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config={"train_batch_size": 8,
                    "train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                    "zero_optimization": {"stage": stage}})
        ids = np.random.RandomState(0).randint(0, 128, (8, S), np.int32)
        losses = []
        for _ in range(2):
            loss = engine.forward(ids)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        uses_kernel = bool(_pallas_eqn_shapes(jax.make_jaxpr(
            lambda p: model.loss(p, None, ids))(engine.params)))
        return losses, uses_kernel

    z2, k2 = first_losses(2, {"data": 4, "model": 2})
    z3, k3 = first_losses(3, {"data": 4, "model": 2})
    assert k2 and k3
    assert np.isfinite(z2).all() and z2[1] < z2[0]
    np.testing.assert_allclose(z3, z2, rtol=2e-5)


def test_region_is_skipped_without_a_mesh(interpret):
    """No mesh context, a one-device mesh, or a caller already manual
    over every axis: the kernel is called as is."""
    calls = []

    def fn(idx, x):
        calls.append(jax.sharding.get_abstract_mesh().manual_axes)
        return x + idx

    x = jnp.ones((4, 2))
    ds.reset_mesh_context()
    np.testing.assert_array_equal(
        dispatch.manual_kernel_region(fn, (x,), ({0: ("data",)},),
                                      {0: ("data",)}), x)
    ds.initialize_mesh(devices=jax.devices()[:1])
    dispatch.manual_kernel_region(fn, (x,), ({0: ("data",)},),
                                  {0: ("data",)})
    assert calls == [(), ()]

    ctx = ds.initialize_mesh(devices=jax.devices()[:4], data=4)
    out = jax.jit(lambda x: dispatch.manual_kernel_region(
        fn, (x,), ({0: ("data",)},), {0: ("data",)}))(x)
    assert set(calls[-1]) == set(ctx.mesh.axis_names)
    np.testing.assert_array_equal(out[:, 0], 1 + np.arange(4))
