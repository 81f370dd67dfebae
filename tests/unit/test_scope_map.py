"""deepspeed_tpu/profiling/scope_map.py: from a compiled program's text to
(scope, phase) and to the part per instruction, on hand-written lines, on
the grad program of a two-layer GPT-2 engine with recomputation on, and
on the grad programs of small engines of the three families."""

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


R = "transpose(jvp())/while/body/closed_call/layer/layer/checkpoint/" \
    "rematted_computation/"


@pytest.mark.parametrize("op_name, want", [
    # a part inside its scope, in every pass
    (J + "jvp()/while/body/closed_call/layer/attn/attn_qkv/dot_general",
     "qkv"),
    (J + "jvp(layer)/attn/attn_core/flash_fwd", "core"),
    (J + "transpose(jvp(layer))/attn/attn_out/dot_general", "out"),
    (J + "transpose(jvp(jvp()))/checkpoint/layer/attn/attn_rotary/mul",
     "rotary"),
    (J + R + "attn/attn_qkv/dot_general", "qkv"),
    (J + R + "attn/attn_core/jit(_randint)/vmap()/while/body/closed_call/"
     "shift_right_logical", "core"),
    (J + "jvp(layer)/attn/attn_gate/logistic", "gate"),
    (J + "jvp(layer)/attn/attn_diff/sub", "diff"),
    # layout counts in attn and, the context's way back, in layer
    (J + "jvp(layer)/attn/attn_layout/transpose", "layout"),
    (J + R + "attn_layout/transpose", "layout"),
    (J + "transpose(jvp(layer))/attn_layout/transpose", "layout"),
    # the innermost part names the operation
    (J + "jvp(layer)/attn/attn_core/attn_layout/transpose", "layout"),
    # in its scope and in no part
    (J + "jvp(layer)/attn/dot_general", None),
    # the same words outside their scope are no part
    (J + "jvp()/attn_qkv/dot_general", None),
    (J + "jvp(layer)/mlp/attn_out/dot_general", None),
    (J + "jvp(layer)/attn_gate/mul", None),
    (J + "jvp(head)/attn_layout/transpose", None),
    (J + "jvp(layer)/attn/attn_qkv/mlp/dot_general", None),
    # a scope's word inside another is not that part
    (J + "jvp(layer)/attn/attn_qkvw/dot_general", None),
    # the weight cast: outside every scope only
    (J + "jvp(weight_cast)/convert_element_type", "cast"),
    (J + "transpose(jvp(weight_cast))/convert_element_type", "cast"),
    (J + "jvp(layer)/weight_cast/convert_element_type", None),
    # the stack traffic: what a scan writes directly in its while body
    # for its stacked operands, in no scope; by the path, never by the
    # instruction's name
    (J + "jvp()/while/body/dynamic_slice", "stack"),
    (J + "transpose(jvp())/while/body/squeeze", "stack"),
    (J + "jvp()/while/body/broadcast_in_dim", "stack"),
    (J + "transpose(jvp())/while/body/dynamic_update_slice", "stack"),
    (J + "jvp()/while/body/closed_call/layer/dynamic_slice", None),
    (J + "jvp()/while/body/closed_call/dynamic_slice", None),
    (J + "jvp()/dynamic_slice", None),
    (J + "transpose(jvp())/broadcast_in_dim", None),
    (J + "jvp()/while/cond/dynamic_slice", None),
    (J + "jvp()/while/body/add", None),
    (J + "jvp()/while/body/dynamic_slice/add", None),
    (J + "jvp(weight_cast)/while/body/dynamic_slice", "cast"),
    ("dynamic_update_slice", None),
    ("", None),
])
def test_part_of_an_op_name(op_name, want):
    assert scope_map.part(op_name) == want


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
    # the same instructions, and no part named anywhere in that text
    assert scope_map.parse_parts(text) == dict.fromkeys(tags)


def test_parse_parts_gives_a_fusion_the_part_of_its_root():
    text = '''
%fused_computation.1 (p: bf16[4,8]) -> bf16[8,4] {
  %p = bf16[4,8]{1,0} parameter(0)
  ROOT %transpose.3 = bf16[8,4]{1,0} transpose(%p), dimensions={1,0}, metadata={op_name="jit(loss_and_grads)/jvp(layer)/attn/attn_layout/transpose"}
}

ENTRY %main (a: bf16[4,8]) -> bf16[8,4] {
  %a = bf16[4,8]{1,0} parameter(0)
  %convert.2 = bf16[4,8]{1,0} convert(%a), metadata={op_name="jit(loss_and_grads)/jvp(weight_cast)/convert_element_type"}
  %dynamic-slice.5 = bf16[4,8]{1,0} copy(%convert.2), metadata={op_name="jit(loss_and_grads)/jvp(layer)/mlp/add"}
  ROOT %fusion.9 = bf16[8,4]{1,0} fusion(%dynamic-slice.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(loss_and_grads)/jvp(layer)/attn/attn_qkv/dot_general"}
}
'''
    parts = scope_map.parse_parts(text)
    # the transpose inside the projection's fusion is counted as qkv
    assert parts["fusion.9"] == "qkv" and parts["transpose.3"] == "layout"
    assert parts["convert.2"] == "cast"
    assert parts["dynamic-slice.5"] is None  # a name is not a path
    assert scope_map.parse(text)["fusion.9"] == ("attn", "forward")


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


def test_live_gives_the_maps_and_keeps_no_engine_alive(engine, monkeypatch):
    import gc
    import weakref
    lowered = []
    programs = engine.step_programs

    def counting():
        def text_of(name, text):
            return lambda: lowered.append(name) or text()
        return [(name, text_of(name, text)) for name, text in programs()]
    monkeypatch.setattr(engine, "step_programs", counting)
    maps = scope_map.live()
    assert {"jit_loss_and_grads", "jit_accumulate",
            "jit_apply_step"} <= set(maps)
    assert ("mlp", "recompute") in set(maps["jit_loss_and_grads"].values())
    # the parts come from the same texts: one lowering serves both
    parts = scope_map.live_parts()
    assert {p: set(m) for p, m in parts.items()} == {
        p: set(m) for p, m in maps.items()}
    assert {"qkv", "core", "out"} <= set(
        parts["jit_loss_and_grads"].values())
    assert set(parts["jit_apply_step"].values()) == {None}
    scope_map.live()
    assert sorted(lowered) == ["jit_accumulate", "jit_apply_step",
                               "jit_loss_and_grads"]
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


# ---------------------------------------------------------------------- #
# the grad programs of small engines: every part where the model has it
# ---------------------------------------------------------------------- #
DS_CONFIG = {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
             "steps_per_print": 10 ** 9,
             "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
             "bf16": {"enabled": True, "grads_in_compute_dtype": True},
             "zero_optimization": {"stage": 2}}


def _gpt2():
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    return GPT2Model(GPT2Config(
        vocab_size=256, n_positions=128, hidden_size=128, num_layers=4,
        num_heads=2, embd_dropout=0.1, attn_dropout=0.1, hidden_dropout=0.1,
        activation_checkpointing=True, scan_layers=True)), 128


def _laguna():
    from deepspeed_tpu.models.laguna import LagunaConfig, LagunaModel
    return LagunaModel(LagunaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
        sliding_window=8, num_attention_heads_per_layer=(4, 6, 6, 6, 4),
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, experts_held=(4, 8),
        yarn_factor=4.0, yarn_original_max_position_embeddings=16,
        activation_checkpointing=True)), 40


def _phi4flash():
    from deepspeed_tpu.models.phi4flash import (Phi4FlashConfig,
                                                Phi4FlashModel)
    return Phi4FlashModel(Phi4FlashConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
        self_pairs=1, cross_pairs=2, activation_checkpointing=True)), 40


@pytest.fixture
def flash_arm(monkeypatch):
    """impl="auto" takes the flash kernels (the interpreter's)."""
    from deepspeed_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "_interpret", True)
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")


def _parts_by_scope(build):
    """{scope: {part: instructions}} of the grad program of the engine
    of ``build()`` after one micro-batch."""
    model, seq = build()
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh, config=DS_CONFIG,
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    engine.forward(np.zeros((2, seq), np.int32))
    text = dict(engine.step_programs())["jit_loss_and_grads"]()
    ds.reset_mesh_context()
    tags, parts = scope_map.parse(text), scope_map.parse_parts(text)
    found = collections.defaultdict(collections.Counter)
    for name, (scope, _) in tags.items():
        found[scope][parts[name]] += 1
    return found


# what each family's attention has, of the seven parts of ``attn``
GPT2_PARTS = {"qkv", "layout", "core", "out"}
FAMILIES = {
    "gpt2": (_gpt2, GPT2_PARTS),  # the XLA arm; the flash arm is below
    "laguna": (_laguna, GPT2_PARTS | {"rotary", "gate"}),
    "phi4flash": (_phi4flash, GPT2_PARTS | {"diff"}),
}
# instructions of scope attn the parts may leave out: none is written
# outside a part today; a few, so that one new line refuses nobody
UNSPLIT_ALLOWED = 4


def _check_parts(found, want, casts=False):
    attn = found["attn"]
    layout = attn["layout"] + found["layer"]["layout"]
    named = {part for part in attn if part} | ({"layout"} if layout else
                                               set())
    assert named == want
    assert attn[None] <= UNSPLIT_ALLOWED, attn
    assert sum(attn.values()) > 100 * UNSPLIT_ALLOWED
    # outside every scope no part but the scan's, and the weight casts
    # where the model keeps them in its grad program (Laguna's does:
    # ``ExpertStack.casts_own_weights``); elsewhere they are the apply
    # program's (tests/unit/test_weight_copy.py)
    assert (found["other"]["cast"] > 0) == casts
    assert set(found["other"]) <= {"cast", "stack", None}
    for scope, parts in found.items():
        if scope not in ("attn", "layer", "other"):
            assert set(parts) == {None}, scope
    assert set(found["layer"]) <= {"layout", None}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_parts_of_a_grad_program(family):
    build, want = FAMILIES[family]
    found = _parts_by_scope(build)
    _check_parts(found, want, casts=family == "laguna")
    if family != "phi4flash":  # XLA unrolls its toy's two-trip scan
        assert found["other"]["stack"] > 0


def test_the_parts_of_a_grad_program_on_the_flash_arm(flash_arm):
    build, want = FAMILIES["gpt2"]
    found = _parts_by_scope(build)
    _check_parts(found, want)
    # the context's way back lies between two attn blocks, in layer
    assert found["layer"]["layout"] > 0
