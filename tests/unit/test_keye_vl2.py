"""models/keye_vl2.py through ``deepspeed_tpu.initialize`` at a toy size on
the CPU: the objective and its counters, and the engine paths the model
refuses."""

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import KeyeVL2Config, KeyeVL2Model

SEQ = 128


def _config(**fields):
    return KeyeVL2Config(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
        experts_held=(2, 4), indexer_num_heads=2, indexer_head_dim=8,
        index_topk=16, initializer_range=0.1, bf16=False, **fields)


def _engine(config, pipe=1, **ds_fields):
    model = KeyeVL2Model(config)
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:pipe], data=1,
                              pipe=pipe)
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_batch_size": 1, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                **ds_fields})
    return engine


def test_trains_through_initialize():
    engine = _engine(_config())
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, SEQ), 0, 128), np.int32)
    before = jax.device_get(engine.params)
    losses = []
    for _ in range(4):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    counters = engine.model_counters()
    assert losses[-1] < losses[0]
    assert set(counters) == {"main_loss", "index_loss", "kept_share"}
    # sum_t min(t + 1, 16) over 128 x 129 / 2
    assert counters["kept_share"] == pytest.approx(
        (16 * 17 / 2 + 112 * 16) / (128 * 129 / 2), abs=1e-6)
    moved = jax.tree.map(lambda a, b: float(np.max(np.abs(a - b))),
                         before, jax.device_get(engine.params))
    assert all(m > 0 for m in jax.tree.leaves(moved))
    ds.reset_mesh_context()


@pytest.mark.parametrize("path, fields", [
    ("zero3_streaming", {"zero_optimization": {"stage": 3}}),
    ("pipeline", {"pipe": 2})])
def test_refuses_the_paths_it_has_not_been_run_under(path, fields):
    with pytest.raises(NotImplementedError, match=path):
        _engine(_config(), **fields)
    ds.reset_mesh_context()


def test_the_row_buffers_hold_two_even_shares():
    from deepspeed_tpu.moe.dropless import dispatch_capacity
    moe = KeyeVL2Model(KeyeVL2Config(experts_held=(0, 16))).moe
    # the cell's layer: 8 picks of 16,384 tokens, 16 of 128 experts held
    assert moe.first_chunk_always and moe.dispatch_headroom == 2.0
    assert moe.capacity(16384) == 2 * dispatch_capacity(16384, 8, 16, 128)
