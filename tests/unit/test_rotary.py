"""``ops/rotary.py`` (the Pallas interpreter on the CPU) against the
rotation it replaces in ``models/laguna.py``: ``jnp.split`` + ``by_head``
+ ``apply_rotary`` on the QKV product, which stays the plain definition.
Laguna's two attention kinds at a test's sequence length: sliding layers
(64 query heads, all 128 lanes of a head rotated) and full layers (48
query heads, 64 of 128 lanes, YaRN frequencies, the attention factor),
both on 8 key/value heads.  Then the model: which path a shape takes,
and that the stack's plan says so."""

import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import laguna
from deepspeed_tpu.models.laguna import (FULL, SLIDING, LagunaConfig,
                                         LagunaModel, apply_rotary)
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops import rotary
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    RematBudget, stack_plan_line)
from perf.families import laguna_reference as reference

BATCH, SEQ, KV, DIM = 2, 128, 8, 128
# (query heads, lanes of a head that turn)
KINDS = {SLIDING: (64, 128), FULL: (48, 64)}


@pytest.fixture(autouse=True)
def interpreter(monkeypatch):
    dispatch.set_pallas_interpret(True)
    # two blocks of positions a row, so that the position index maps work
    monkeypatch.setattr(rotary, "BLOCK_ROWS", 64)
    yield
    dispatch.set_pallas_interpret(False)


def _tables(kind):
    """The published tables of a layer kind, from the model's own code."""
    return LagunaModel(LagunaConfig(num_hidden_layers=1)).rotary_tables(
        SEQ)[kind]


def _qkv(heads, seed=0, kv=KV):
    return jax.random.normal(
        jax.random.PRNGKey(seed), (BATCH, SEQ, (heads + 2 * kv) * DIM),
        jnp.float32).astype(jnp.bfloat16)


def _plain(qkv, table, heads, kv=KV):
    """What ``_attention`` runs on a shape the kernels do not take."""
    q, k, v = jnp.split(qkv, [heads * DIM, (heads + kv) * DIM], axis=-1)

    def by_head(t, n):
        return t.reshape(BATCH, SEQ, n, DIM).transpose(0, 2, 1, 3)

    return (apply_rotary(by_head(q, heads), table),
            apply_rotary(by_head(k, kv), table), by_head(v, kv))


def _kernels(qkv, table, heads, kv=KV):
    return rotary.rotate_qkv(qkv, *rotary.lane_tables(*table, DIM),
                             table[0].shape[-1], heads, kv)


def _ulps(a, b):
    """Distance in representable bf16 values, elementwise."""
    def ordinal(x):
        bits = np.asarray(x).view(np.uint16).astype(np.int32)
        return np.where(bits & 0x8000, -(bits & 0x7FFF), bits)
    return np.abs(ordinal(a) - ordinal(b))


def _one_ulp(ours, want, scale):
    """Bit for bit on 99% and nowhere further than one bf16 value apart,
    but for what float32 itself loses on products of size ``scale``: the
    CPU contracts a multiply and an add where the TPU does not (there
    the two paths read equal everywhere: PERF.md section 6, PR 39), and
    where two products cancel that is more than one bf16 value of the
    small difference."""
    assert ours.shape == want.shape and ours.dtype == want.dtype
    distance = _ulps(ours, want)
    apart = np.abs(np.asarray(ours, np.float32) - np.asarray(want,
                                                             np.float32))
    assert np.all((distance <= 1) | (apart <= 2 ** -22 * scale))
    assert (distance == 0).mean() >= 0.99


def _scale(x, table):
    return float(jnp.max(jnp.abs(x))) * float(jnp.max(jnp.abs(table[0])))


@pytest.mark.parametrize("kind", KINDS)
def test_the_pass_equals_split_by_head_and_apply_rotary(kind):
    heads, _ = KINDS[kind]
    assert rotary.rotary_block(SEQ, DIM, heads, KV) == (64, 8)
    qkv, table = _qkv(heads), _tables(kind)
    ours = jax.jit(_kernels, static_argnums=2)(qkv, table, heads)
    want = jax.jit(_plain, static_argnums=2)(qkv, table, heads)
    for a, b in zip(ours[:2], want[:2]):
        _one_ulp(a, b, _scale(qkv, table))
    np.testing.assert_array_equal(_ulps(ours[2], want[2]), 0)   # v: a copy


@pytest.mark.parametrize("kind", KINDS)
def test_the_pass_equals_the_references_rotate(kind):
    """perf/families/laguna_reference.py, float32, on the same bf16
    values: the kernels' result is its rounding to bf16."""
    heads, rotated = KINDS[kind]
    qkv = _qkv(heads, seed=1)
    q, k, _ = _kernels(qkv, _tables(kind), heads)
    cos, sin, r = reference.rotary_angles(
        SEQ, "sliding" if kind == SLIDING else "full", reference.Spec(
            layers=()))
    assert r == rotated
    x = qkv.astype(jnp.float32).reshape(BATCH, SEQ, heads + 2 * KV, DIM)
    for ours, lo, hi in ((q, 0, heads), (k, heads, heads + KV)):
        want = jax.vmap(lambda row: reference.rotate(
            row[:, lo:hi], cos, sin, r))(x).transpose(0, 2, 1, 3)
        # half a bf16 ulp of the result, and the tables' own rounding
        np.testing.assert_allclose(ours.astype(jnp.float32), want,
                                   rtol=2 ** -8, atol=1e-5)


@pytest.mark.parametrize("kind", KINDS)
def test_the_backward_pass_equals_the_plain_paths_vjp(kind):
    heads, _ = KINDS[kind]
    qkv, table = _qkv(heads, seed=2), _tables(kind)
    cotangents = tuple(
        jax.random.normal(jax.random.PRNGKey(10 + i), (BATCH, n, SEQ, DIM),
                          jnp.float32).astype(jnp.bfloat16)
        for i, n in enumerate((heads, KV, KV)))

    def pulled(fn):
        return jax.jit(lambda x, ct: jax.vjp(
            lambda x: fn(x, table, heads), x)[1](ct)[0])(qkv, cotangents)

    _one_ulp(pulled(_kernels), pulled(_plain), _scale(cotangents[0], table))


def test_a_full_layers_unrotated_lanes_come_through_bit_for_bit():
    heads, rotated = KINDS[FULL]
    qkv = _qkv(heads, seed=3)
    q, k, _ = _kernels(qkv, _tables(FULL), heads)
    x = qkv.reshape(BATCH, SEQ, heads + 2 * KV, DIM).transpose(0, 2, 1, 3)
    for ours, lo, hi in ((q, 0, heads), (k, heads, heads + KV)):
        np.testing.assert_array_equal(
            _ulps(ours[..., rotated:], x[:, lo:hi, :, rotated:]), 0)
        assert (_ulps(ours[..., :rotated], x[:, lo:hi, :, :rotated])
                > 0).mean() > 0.9                 # and the others turned


@pytest.mark.parametrize("heads, kv, block", [
    (64, 8, 8), (48, 8, 8), (6, 2, 2), (12, 4, 4), (5, 5, 5), (3, 1, 1)])
def test_a_block_of_heads_starts_and_ends_inside_q_k_or_v(heads, kv, block):
    assert rotary.rotary_block(SEQ, DIM, heads, kv) == (64, block)


def test_fewer_heads_than_a_block_and_float32():
    """2 query heads on 1 key/value head, float32 activations (a CPU
    toy's dtype): a block is one head."""
    qkv, table = _qkv(2, seed=4, kv=1).astype(jnp.float32), _tables(FULL)
    ours, want = _kernels(qkv, table, 2, 1), _plain(qkv, table, 2, 1)
    for a, b in zip(ours, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------- #
# the model: which path a shape takes, and that the plan says so
# ---------------------------------------------------------------------- #
def _toy(**over):
    kw = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_key_value_heads=1, head_dim=128,
              sliding_window=8, num_attention_heads_per_layer=(2, 3),
              num_experts=8, num_experts_per_tok=2,
              moe_intermediate_size=32, shared_expert_intermediate_size=32,
              bf16=False)
    kw.update(over)
    return LagunaConfig(**kw)


@pytest.mark.parametrize("over, seq, path", [
    ({}, 128, ("kernel", 64, 1)),
    ({}, 64, ("kernel", 64, 1)),
    ({}, 40, ("xla",)),              # no whole loop iterations
    ({}, 160, ("xla",)),             # no whole blocks
    ({"head_dim": 64}, 128, ("xla",)),
    ({"head_dim": 256}, 128, ("xla",))],
    ids=["two blocks", "one block", "S=40", "S=160", "heads of 64",
         "heads of 256"])
def test_the_shape_decides_the_path_and_the_plan_says_which(over, seq, path):
    model = LagunaModel(_toy(activation_checkpointing=True, **over))
    budget = RematBudget(10 ** 12, working_set=0)
    model.install_remat_budget(budget)
    params = model.init_params(jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(model.loss)(
        params, None, jnp.zeros((2, seq), jnp.int32))
    plan = budget.take_plan()
    assert plan[R.M_STACK_ROTARY] == ((FULL, *path), (SLIDING, *path))
    line = stack_plan_line(plan)
    if path == ("xla",):
        assert "rotary: full_attention xla, sliding_attention xla" in line
        assert "rotary_fwd" not in str(jaxpr)
    else:
        assert ("rotary: full_attention kernel (blocks of 64 positions x 1 "
                "heads)") in line
        assert "rotary_fwd" in str(jaxpr)


def test_no_interpreter_and_no_tpu_is_the_plain_path():
    dispatch.set_pallas_interpret(False)
    assert rotary.rotary_block(8192, 128, 64, 8) is None
    assert set(LagunaModel(_toy()).rotary_plan(128).values()) == {None}


def test_the_model_through_the_kernels_equals_the_model_through_xla(
        monkeypatch):
    """Loss and every gradient leaf of a two-layer stack (one full, one
    sliding layer; float32), the kernels' path against apply_rotary's."""
    model = LagunaModel(_toy())
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 128)
    assert set(model.rotary_plan(128).values()) == {(64, 1)}
    ours = jax.jit(jax.value_and_grad(model.loss))(params, None, ids)
    monkeypatch.setattr(laguna, "rotary_block", lambda *a: None)
    want = jax.jit(jax.value_and_grad(model.loss))(params, None, ids)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_lagunas_grad_program_is_the_recorded_one():
    """The toy's value-and-gradient program through the kernels, as text,
    against the record of tests/unit/golden/laguna_grad_jaxpr.json: a
    change to what this model shares with another (``lane_tables`` with
    ops/latent_layout.py, ``ExpertStack`` and ``DroplessMoE`` with
    models/glm4_moe_lite.py) that was not meant for Laguna shows here.
    One that was records the new text's numbers in the file, with the
    commit they were read on."""
    model = LagunaModel(_toy())
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(
        jax.value_and_grad(model.loss))(
            params, None, jnp.zeros((2, 128), jnp.int32))))
    assert "rotary_fwd" in text and "rotary_bwd" in text
    assert "latent_" not in text
    with open(os.path.join(os.path.dirname(__file__), "golden",
                           "laguna_grad_jaxpr.json")) as f:
        want = json.load(f)
    assert (len(text), text.count("\n")) == (want["chars"], want["lines"])
    assert hashlib.sha256(text.encode()).hexdigest() == want["sha256"]
