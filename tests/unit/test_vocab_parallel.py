"""Vocab-parallel embedding + fused vocab-parallel cross-entropy
(ops/vocab_parallel.py) — the manual-TP aux chains of the gated 1F1B
executor (Megatron VocabParallelEmbedding / parallel-CE role).

Parity bar: exact agreement with the replicated lookup and with
optax.softmax_cross_entropy_with_integer_labels on full fp32 logits —
forward AND all grads, with no post-hoc correction (the custom VJPs
place the f/g collectives internally)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.ops.vocab_parallel import (
    vocab_parallel_embedding, vocab_parallel_linear_cross_entropy)

V, H, N = 64, 16, 24


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(0)
    return {
        "wte": jnp.asarray(rng.standard_normal((V, H)).astype(np.float32))
        * 0.1,
        "head": jnp.asarray(rng.standard_normal((H, V)).astype(np.float32))
        * 0.1,
        "ids": jnp.asarray(rng.randint(0, V, N).astype(np.int32)),
        "h": jnp.asarray(rng.standard_normal((N, H)).astype(np.float32)),
    }


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_embedding_and_ce_match_replicated(tp, data):
    wte, head, ids, h = (data["wte"], data["head"], data["ids"], data["h"])

    def ref_emb_loss(w):
        return (w[ids].astype(jnp.float32) ** 2).sum()

    def ref_ce(h_, w_):
        logits = (h_ @ w_).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, ids).mean()

    ref_emb = wte[ids]
    ref_gw = jax.grad(ref_emb_loss)(wte)
    ref_loss = ref_ce(h, head)
    ref_gh, ref_ghead = jax.grad(ref_ce, argnums=(0, 1))(h, head)

    mesh = Mesh(np.array(jax.devices()[:tp]).reshape(tp), ("model",))

    def region(wte_l, head_l, h_, ids_):
        emb = vocab_parallel_embedding(wte_l, ids_, "model")
        gw = jax.grad(
            lambda w: (vocab_parallel_embedding(w, ids_, "model")
                       .astype(jnp.float32) ** 2).sum())(wte_l)
        loss = vocab_parallel_linear_cross_entropy(h_, head_l, ids_,
                                                   "model")
        gh, ghead = jax.grad(
            lambda a, b: vocab_parallel_linear_cross_entropy(
                a, b, ids_, "model"), argnums=(0, 1))(h_, head_l)
        return emb, gw, loss, gh, ghead

    f = jax.jit(jax.shard_map(
        region, mesh=mesh,
        in_specs=(P("model", None), P(None, "model"), P(), P()),
        out_specs=(P(), P("model", None), P(), P(), P(None, "model")),
        axis_names=frozenset({"model"}), check_vma=False))
    emb, gw, loss, gh, ghead = f(wte, head, h, ids)

    np.testing.assert_allclose(np.asarray(emb), np.asarray(ref_emb),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ref_gw),
                               atol=1e-5)
    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(ref_gh),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ghead), np.asarray(ref_ghead),
                               atol=1e-5)


def test_indivisible_vocab_declines_aux_manual():
    """A vocab the model axis can't divide must fall back to replicated
    aux chains (tp_manual_aux_supports False) while the BLOCKS still
    gate with manual TP — not crash, not silently shard wrong."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2Config
    from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

    deepspeed_tpu.reset_mesh_context()
    deepspeed_tpu.initialize_mesh(pipe=2, model=2, data=-1)
    cfg = GPT2Config(vocab_size=65, n_positions=16, hidden_size=32,
                     num_layers=4, num_heads=4, bf16=False,
                     embd_dropout=0.0, attn_dropout=0.0,
                     hidden_dropout=0.0)
    engine = PipelineEngine(
        model=gpt2_pipeline_module(cfg, num_stages=2),
        config={"train_batch_size": 8,
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9},
        example_input=jnp.zeros((4, 16), jnp.int32),
        rng=jax.random.PRNGKey(0))
    assert engine.schedule_gated is True
    assert engine._tp_manual is True
    assert engine._tp_aux_manual is False
    ids = np.random.RandomState(0).randint(0, 65, size=(4, 16)).astype(
        np.int32)
    loss = engine.train_batch(iter([(ids, ids), (ids, ids)]))
    assert np.isfinite(loss)
    deepspeed_tpu.reset_mesh_context()


def test_gated_tp_bf16_smoke():
    """bf16 gated-TP with vocab-parallel aux: the manual branches cast
    params/activations at several boundaries (qkv einsum, psum merges,
    CE's fp32 logits accumulation) — all trajectory tests run fp32, so
    this is the only exercise of those casts.  One step, finite loss."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2Config
    from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

    deepspeed_tpu.reset_mesh_context()
    deepspeed_tpu.initialize_mesh(pipe=2, model=2, data=-1)
    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                     num_layers=4, num_heads=4, bf16=True,
                     embd_dropout=0.1, attn_dropout=0.1,
                     hidden_dropout=0.1)
    engine = PipelineEngine(
        model=gpt2_pipeline_module(cfg, num_stages=2),
        config={"train_batch_size": 8,
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "bf16": {"enabled": True},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9},
        example_input=jnp.zeros((4, 16), jnp.int32),
        rng=jax.random.PRNGKey(0))
    assert engine.schedule_gated and engine._tp_manual
    assert engine._tp_aux_manual
    ids = np.random.RandomState(0).randint(0, 64, size=(4, 16)).astype(
        np.int32)
    loss = engine.train_batch(iter([(ids, ids), (ids, ids)]))
    assert np.isfinite(loss)
    deepspeed_tpu.reset_mesh_context()


def test_untied_head_vocab_parallel_trajectory():
    """Untied-head GPT-2 (independent lm_head, vocab-sharded over the
    model axis through pre_s/post_s specs) under pipe=2 x tp=2 matches
    the pipe=1/tp=1 trajectory — the untied branch of
    _attach_vocab_parallel_aux (the 3D matrix covers the tied branch)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2Config
    from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

    def train(pipe, tp, steps=3):
        deepspeed_tpu.reset_mesh_context()
        mesh = deepspeed_tpu.initialize_mesh(pipe=pipe, model=tp, data=-1)
        dp = mesh.data_parallel_world_size
        cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                         num_layers=4, num_heads=4, bf16=False,
                         tie_word_embeddings=False,
                         embd_dropout=0.0, attn_dropout=0.0,
                         hidden_dropout=0.0)
        engine = PipelineEngine(
            model=gpt2_pipeline_module(cfg, num_stages=pipe),
            config={"train_batch_size": 16,
                    "train_micro_batch_size_per_gpu": 8 // dp,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "steps_per_print": 10 ** 9},
            example_input=jnp.zeros((8, 16), jnp.int32),
            rng=jax.random.PRNGKey(5))
        rs = np.random.RandomState(0)
        losses = []
        for _ in range(steps):
            micro = [(ids, ids) for ids in
                     (rs.randint(0, 64, size=(8, 16)).astype(np.int32)
                      for _ in range(2))]
            losses.append(float(engine.train_batch(iter(micro))))
        aux = engine._tp_aux_manual if tp > 1 else None
        deepspeed_tpu.reset_mesh_context()
        return losses, aux

    base, _ = train(1, 1)
    got, aux = train(2, 2)
    assert aux is True
    np.testing.assert_allclose(got, base, rtol=2e-5)
