"""Model-family tests — tiny GPT-2/BERT configs trained through the engine
(the analog of the reference's simple_model.py fixtures + model-level
convergence checks, tests/model/run_func_test.py)."""

import numpy as np

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models import BertConfig, BertModel, GPT2Config, GPT2Model


def tiny_gpt2(**kw):
    defaults = dict(vocab_size=256, n_positions=32, hidden_size=32,
                    num_layers=2, num_heads=2, bf16=False,
                    embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
    defaults.update(kw)
    return GPT2Config(**defaults)


def test_gpt2_loss_shape_and_initial_value():
    cfg = tiny_gpt2()
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    loss = model.loss(params, None, ids)
    assert loss.shape == ()
    # ~uniform prediction at init => loss ~ log(vocab)
    assert abs(float(loss) - np.log(256)) < 1.0


def test_gpt2_partition_specs_match_param_tree():
    cfg = tiny_gpt2()
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    specs = model.param_partition_specs()
    # identical tree structure (specs are leaves)
    from jax.sharding import PartitionSpec
    jax.tree.map(lambda p, s: None, params,
                 jax.tree.map(lambda s: s, specs,
                              is_leaf=lambda x: isinstance(x, PartitionSpec)))


def test_gpt2_num_params_matches_tree():
    cfg = tiny_gpt2()
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    actual = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
    assert cfg.num_params() == actual


def test_gpt2_trains_through_engine():
    cfg = tiny_gpt2()
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params)
    ids = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, 256))
    losses = []
    for _ in range(8):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_gpt2_activation_checkpointing_same_loss():
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    losses = {}
    for ckpt in (False, True):
        cfg = tiny_gpt2(activation_checkpointing=ckpt)
        model = GPT2Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, None, ids)))(params)
        losses[ckpt] = (float(loss),
                        float(jnp.mean(jnp.abs(grads["wte"]))))
    assert np.allclose(losses[False], losses[True], rtol=1e-5)


def test_bert_mlm_loss_ignores_unmasked_positions():
    cfg = BertConfig(vocab_size=128, max_position_embeddings=32,
                     hidden_size=32, num_layers=1, num_heads=2, bf16=False,
                     embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
    model = BertModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 128)
    labels_all_ignored = jnp.full((2, 8), -100)
    loss = model.mlm_loss(params, None, ids, labels_all_ignored)
    assert float(loss) == 0.0

    labels = labels_all_ignored.at[:, 0].set(5)
    loss2 = model.mlm_loss(params, None, ids, labels)
    assert float(loss2) > 0.0


def test_bert_attention_mask_changes_output():
    cfg = BertConfig(vocab_size=128, max_position_embeddings=32,
                     hidden_size=32, num_layers=1, num_heads=2, bf16=False,
                     embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
    model = BertModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 128)
    full = np.asarray(model.hidden_states(params, ids))
    masked = np.asarray(model.hidden_states(
        params, ids, attention_mask=jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]])))
    assert not np.allclose(full[:, 0], masked[:, 0])


def test_gpt2_tensor_parallel_training_on_mesh():
    """TP x DP: hidden sharded over model axis, batch over data axis."""
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=2, model=4)
    cfg = tiny_gpt2(hidden_size=64, num_heads=4, vocab_size=256)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config,
                                    model_parameters=params, mesh=mesh)
    # TP specs picked up from the model automatically
    assert engine.param_specs is not None
    ids = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 256))
    loss0 = engine.forward(ids)
    engine.backward(loss0)
    engine.step()
    loss1 = engine.forward(ids)
    engine.backward(loss1)
    engine.step()
    assert float(loss1) < float(loss0)


def test_bert_activation_checkpointing_same_loss_and_grads():
    """BertConfig.activation_checkpointing must be a pure memory knob —
    identical loss and gradients (it is what lets BERT-large fit 24 layers
    of seq-512 activations in HBM)."""
    cfg_kw = dict(vocab_size=128, max_position_embeddings=32,
                  hidden_size=32, num_layers=2, num_heads=2, bf16=False,
                  embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    out = {}
    for ckpt in (False, True):
        model = BertModel(BertConfig(activation_checkpointing=ckpt,
                                     **cfg_kw))
        params = model.init_params(jax.random.PRNGKey(0))
        loss, grads = jax.value_and_grad(
            lambda p: model.mlm_loss(p, None, ids, ids))(params)
        out[ckpt] = (float(loss),
                     float(jnp.mean(jnp.abs(jax.tree.leaves(grads)[0]))))
    assert np.allclose(out[False], out[True], rtol=1e-5)
