"""End-to-end pipeline-parallel training on the simulated 8-device mesh
(reference: tests/unit/test_pipe.py:268 — tiny-model pipeline convergence)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
from deepspeed_tpu.runtime.pipe.module import (LayerSpec, PipeLayer,
                                               PipelineModule, TiedLayerSpec)

HIDDEN = 16
IN_DIM = 8
OUT_DIM = 8


class EmbedLayer(PipeLayer):
    def __init__(self, in_dim=IN_DIM, hidden=HIDDEN):
        self.in_dim, self.hidden = in_dim, hidden

    def init_params(self, rng, x):
        return {"w": jax.random.normal(rng, (self.in_dim, self.hidden),
                                       jnp.float32) * 0.5}

    def apply(self, params, x, rng=None):
        return x @ params["w"]


class Block(PipeLayer):
    """Shape-preserving residual block — the homogeneous pipeline body."""

    def init_params(self, rng, x):
        k1, k2 = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (HIDDEN, HIDDEN),
                                       jnp.float32) * 0.3,
                "b": jnp.zeros((HIDDEN,), jnp.float32)}

    def apply(self, params, x, rng=None):
        return x + jnp.tanh(x @ params["w"] + params["b"])


class HeadLayer(PipeLayer):
    def __init__(self, hidden=HIDDEN, out_dim=OUT_DIM):
        self.hidden, self.out_dim = hidden, out_dim

    def init_params(self, rng, x):
        return {"w": jax.random.normal(rng, (self.hidden, self.out_dim),
                                       jnp.float32) * 0.5}

    def apply(self, params, x, rng=None):
        return x @ params["w"]


def mse_loss(pred, target):
    return jnp.mean((pred - target.astype(pred.dtype)) ** 2)


def make_module(n_blocks=4, num_stages=None):
    layers = [LayerSpec(EmbedLayer)] + \
        [LayerSpec(Block) for _ in range(n_blocks)] + [LayerSpec(HeadLayer)]
    return PipelineModule(layers, num_stages=num_stages, loss_fn=mse_loss)


def make_data(n, rng_seed=0):
    rs = np.random.RandomState(rng_seed)
    w = rs.randn(IN_DIM, OUT_DIM).astype(np.float32)
    x = rs.randn(n, IN_DIM).astype(np.float32)
    y = x @ w
    return x, y


CONFIG = {
    "train_batch_size": 16,
    "train_micro_batch_size_per_gpu": 2,
    "gradient_accumulation_steps": 4,
    "steps_per_print": 100,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "mesh": {"pipe": 4, "data": -1},
}


def _engine(n_blocks=4, config=None):
    deepspeed_tpu.initialize_mesh(pipe=4, data=-1)
    module = make_module(n_blocks=n_blocks)
    cfg = dict(config or CONFIG)
    example = jnp.zeros((4, IN_DIM), jnp.float32)  # one global microbatch
    return PipelineEngine(model=module, config=cfg,
                          example_input=example,
                          rng=jax.random.PRNGKey(0))


def _batch_iter(x, y, micro_global):
    i = 0
    while True:
        xs = x[i:i + micro_global]
        ys = y[i:i + micro_global]
        if len(xs) < micro_global:
            i = 0
            continue
        i += micro_global
        yield (xs, ys)


class TestPipelineModule:
    def test_body_detection(self):
        module = make_module(n_blocks=4, num_stages=4)
        params = module.build(jax.random.PRNGKey(0),
                              jnp.zeros((4, IN_DIM), jnp.float32))
        assert module.body_range == (1, 5)
        leaf = jax.tree.leaves(params["blocks"])[0]
        assert leaf.shape[:2] == (4, 1)
        assert len(params["pre"]) == 1
        assert len(params["post"]) == 1

    def test_indivisible_body_raises(self):
        module = make_module(n_blocks=5, num_stages=4)
        with pytest.raises(ValueError, match="not\\s+divisible"):
            module.build(jax.random.PRNGKey(0),
                         jnp.zeros((4, IN_DIM), jnp.float32))

    def test_tied_layers_share_params(self):
        layers = [
            TiedLayerSpec("emb", EmbedLayer),
            LayerSpec(Block), LayerSpec(Block),
            TiedLayerSpec("emb", EmbedLayer,
                          forward_fn=lambda p, x: x @ p["w"].T),
        ]
        module = PipelineModule(layers, num_stages=2, loss_fn=mse_loss)
        params = module.build(jax.random.PRNGKey(0),
                              jnp.zeros((4, IN_DIM), jnp.float32))
        assert "emb" in params["tied"]
        assert params["pre"] == [None]
        assert params["post"] == [None]
        # forward through chain_apply uses the tied weight both times
        x = jnp.ones((4, IN_DIM), jnp.float32)
        h = module.chain_apply(range(0, 1), params["pre"], params["tied"], x)
        assert h.shape == (4, HIDDEN)
        out = module.chain_apply(range(3, 4), params["post"], params["tied"], h)
        assert out.shape == (4, IN_DIM)


class TestPipelineEngine:
    def test_parity_with_sequential(self):
        """The pipelined program computes exactly what the sequential layer
        chain computes."""
        engine = _engine()
        params = jax.device_get(engine.params)
        x, y = make_data(16, rng_seed=1)

        loss_pipe = float(engine.forward(x, y))

        # sequential reference: same params, plain layer chain
        M = engine.micro_batches
        xm = x.reshape(M, -1, IN_DIM)
        ym = y.reshape(M, -1, OUT_DIM)
        blocks = params["blocks"]
        total = 0.0
        for m in range(M):
            h = xm[m] @ params["pre"][0]["w"]
            S, k = jax.tree.leaves(blocks)[0].shape[:2]
            for s in range(S):
                for j in range(k):
                    lp = jax.tree.map(lambda a: a[s, j], blocks)
                    h = h + jnp.tanh(h @ lp["w"] + lp["b"])
            pred = h @ params["post"][0]["w"]
            total += float(mse_loss(pred, ym[m]))
        assert loss_pipe == pytest.approx(total / M, rel=1e-4)

    def test_train_batch_convergence(self):
        engine = _engine()
        x, y = make_data(256, rng_seed=2)
        it = _batch_iter(x, y, micro_global=4)
        losses = [engine.train_batch(it) for _ in range(30)]
        assert losses[-1] < losses[0] * 0.5, losses
        assert engine.global_steps == 30

    def test_eval_batch(self):
        engine = _engine()
        x, y = make_data(16, rng_seed=3)
        loss = engine.eval_batch(_batch_iter(x, y, micro_global=4))
        assert np.isfinite(loss)

    def test_checkpoint_roundtrip(self, tmp_path):
        engine = _engine()
        x, y = make_data(64, rng_seed=4)
        it = _batch_iter(x, y, micro_global=4)
        for _ in range(3):
            engine.train_batch(it)
        engine.save_checkpoint(str(tmp_path), tag="pipe_test")

        engine2 = _engine()
        engine2.load_checkpoint(str(tmp_path), tag="pipe_test")
        assert engine2.global_steps == 3
        p1 = jax.tree.leaves(jax.device_get(engine.params))
        p2 = jax.tree.leaves(jax.device_get(engine2.params))
        for a, b in zip(p1, p2):
            np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_block_params_sharded_over_pipe(self):
        engine = _engine()
        leaf = jax.tree.leaves(engine.params["blocks"])[0]
        spec = leaf.sharding.spec
        assert spec[0] == "pipe"
