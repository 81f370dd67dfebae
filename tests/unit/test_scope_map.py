"""deepspeed_tpu/profiling/scope_map.py: from a compiled program's text to
(scope, phase) per instruction, on hand-written lines and on the grad
program of a two-layer GPT-2 engine with recomputation on."""

import collections

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.profiling import scope_map

J = "jit(loss_and_grads)/"


@pytest.mark.parametrize("op_name, want", [
    (J + "jvp(head)/mul", ("head", "forward")),
    (J + "jvp(embed)/jit(_bernoulli)/jit(_uniform)/add",
     ("embed", "forward")),
    (J + "jvp(layer)/attn/dot_general", ("attn", "forward")),
    (J + "jvp(layer)/vmap()/while/body/closed_call/add",
     ("layer", "forward")),
    # nested scopes: the innermost one names the operation
    (J + "jvp()/while/body/closed_call/layer/layer/mlp/dot_general",
     ("mlp", "forward")),
    (J + "transpose(jvp())/while/body/closed_call/layer/layer/checkpoint/"
     "rematted_computation/mlp/dot_general", ("mlp", "recompute")),
    (J + "transpose(jvp(jvp()))/checkpoint/rematted_computation/layer/attn/"
     "jit(_randint)/vmap()/while/body/closed_call/shift_right_logical",
     ("attn", "recompute")),
    (J + "transpose(jvp(jvp()))/checkpoint/layer/attn/bhqd,bhkd->bhqk/"
     "dot_general", ("attn", "backward")),
    (J + "transpose(jvp(jvp()))/checkpoint/layer/transpose(jvp())/mul",
     ("layer", "backward")),
    (J + "transpose(jvp(head))/transpose(jvp())/dot_general",
     ("head", "backward")),
    (J + "transpose(jvp())/add_any", ("other", "backward")),
    # a scope's name inside another word is not that scope
    (J + "jvp()/layer_norm/headroom/mul", ("other", "forward")),
    ("reduce_sum", ("other", "forward")),
])
def test_tag_of_an_op_name(op_name, want):
    assert scope_map.tag(op_name) == want


def test_parse_reads_instructions_fusions_and_missing_metadata():
    text = '''
HloModule jit_loss_and_grads, entry_computation_layout={()->f32[]}

%fused_computation.3 (param_0.1: bf16[4,8]) -> bf16[4,8] {
  %param_0.1 = bf16[4,8]{1,0} parameter(0)
  %mul.7 = bf16[4,8]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(loss_and_grads)/jvp(layer)/mlp/mul" source_file="x.py" source_line=3}
  ROOT %tanh.2 = bf16[4,8]{1,0} tanh(%mul.7), metadata={op_name="jit(loss_and_grads)/jvp(layer)/mlp/tanh"}
}

ENTRY %main.9 (Arg_0.1: bf16[4,8]) -> bf16[4,8] {
  %Arg_0.1 = bf16[4,8]{1,0} parameter(0), metadata={op_name="params['wte']"}
  %fusion.648 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(loss_and_grads)/transpose(jvp(layer))/checkpoint/attn/dot_general"}
  %flash_fwd.19 = (bf16[4,20,1024,64]{3,2,1,0}, f32[4,20,1024,8]{3,2,1,0}) custom-call(%fusion.648), custom_call_target="tpu_custom_call", metadata={op_name="jit(loss_and_grads)/transpose(jvp(layer))/checkpoint/rematted_computation/attn/flash_fwd"}
  %copy.4 = bf16[4,8]{0,1} copy(%fusion.648)
  ROOT %tuple.1 = (bf16[4,8]{0,1}) tuple(%copy.4)
}
'''
    tags = scope_map.parse(text)
    # a fusion takes its own instruction's tag, not its body's
    assert tags["fusion.648"] == ("attn", "backward")
    assert tags["mul.7"] == tags["tanh.2"] == ("mlp", "forward")
    assert tags["flash_fwd.19"] == ("attn", "recompute")
    assert tags["copy.4"] == tags["tuple.1"] == ("other", "forward")
    assert tags["Arg_0.1"] == ("other", "forward")
    assert set(tags) == {"param_0.1", "mul.7", "tanh.2", "Arg_0.1",
                         "fusion.648", "flash_fwd.19", "copy.4", "tuple.1"}


@pytest.fixture(scope="module")
def engine():
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    ds.reset_mesh_context()
    model = GPT2Model(GPT2Config(
        vocab_size=64, n_positions=16, hidden_size=32, num_layers=2,
        num_heads=4, embd_dropout=0.1, attn_dropout=0.1, hidden_dropout=0.1,
        activation_checkpointing=True))
    engine, _, _, _ = ds.initialize(
        model=model, config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "steps_per_print": 10 ** 9},
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    return engine


def test_nothing_is_lowered_before_a_program_has_run(engine):
    assert engine.step_programs() == []


def test_the_grad_program_of_a_recomputing_gpt2(engine):
    ids = np.random.RandomState(0).randint(0, 64, (2, 16)).astype(np.int32)
    for _ in range(2):
        engine.backward(engine.forward(ids))
        engine.step()
    programs = dict(engine.step_programs())
    assert list(programs) == ["jit_loss_and_grads", "jit_accumulate",
                              "jit_apply_step"]
    seen = collections.defaultdict(set)
    for scope, phase in scope_map.parse(
            programs["jit_loss_and_grads"]()).values():
        seen[scope].add(phase)
    for scope in ("attn", "mlp"):
        assert seen[scope] == {"forward", "recompute", "backward"}, scope
    # the head and the embedding lie outside the checkpointed layers
    assert seen["head"] == {"forward", "backward"}
    assert seen["embed"] == {"forward", "backward"}
    assert "backward" in seen["layer"]
    # the other programs name no scope of the model
    assert set(scope_map.parse(programs["jit_apply_step"]()).values()) == {
        ("other", "forward")}


def test_live_gives_the_maps_and_keeps_no_engine_alive(engine):
    import gc
    import weakref
    maps = scope_map.live()
    assert {"jit_loss_and_grads", "jit_accumulate",
            "jit_apply_step"} <= set(maps)
    assert ("mlp", "recompute") in set(maps["jit_loss_and_grads"].values())
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    model = GPT2Model(GPT2Config(vocab_size=64, n_positions=16,
                                 hidden_size=32, num_layers=1, num_heads=4))
    other, _, _, _ = ds.initialize(
        model=model, mesh=engine.mesh_ctx, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "steps_per_print": 10 ** 9},
        model_parameters=model.init_params(jax.random.PRNGKey(1)))
    ref = weakref.ref(other)
    assert any(r() is other for r in scope_map._engines)
    del other
    gc.collect()
    assert ref() is None
