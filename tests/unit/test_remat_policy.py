"""The scanned layer's recomputation policy (runtime/activation_checkpointing/
checkpointing.py ``checkpoint_layer``): which named residuals a byte
budget keeps, that keeping them spares the backward pass the flash forward
kernel and the gated FFN's first product and changes no number, and that
the carried ZeRO-3 stream keeps whole-layer recomputation.  The kernels run through the Pallas
interpreter; counts are taken on the DCE'd grad jaxpr (the CPU backend
drops ``jax.checkpoint``'s barriers, so compiled CPU text cannot show
them)."""

import importlib
import json
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.interpreters import partial_eval as pe

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.models import (BertConfig, BertModel, GPT2Config,
                                  GPT2Model)
from deepspeed_tpu.monitor import record as R
from deepspeed_tpu.ops import dispatch, flash_attention as _  # noqa: F401
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ck
from tests.unit.test_zero3_streaming import (_group_cfg, _tiny_engine,
                                             _tiny_ids, _weighted_prim_count)

fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")

FLASH, FFN = ck.RESIDUAL_ORDER[:2]
EVERYTHING = 10 ** 12


def budget_of(n):
    """A budget of exactly ``n`` bytes (None: a backend with no limit)."""
    return ck.RematBudget(n, state_bytes=0, working_set=0)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(dispatch, "_interpret", True)
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")


def _gpt2(seq, layers=2, dropout=0.0, **kw):
    return GPT2Model(GPT2Config(
        vocab_size=256, n_positions=seq, hidden_size=128, num_layers=layers,
        num_heads=2, bf16=False, embd_dropout=dropout, attn_dropout=dropout,
        hidden_dropout=dropout, activation_checkpointing=True,
        scan_layers=True, **kw))


def _bert(seq):
    return BertModel(BertConfig(
        vocab_size=256, max_position_embeddings=seq, hidden_size=128,
        num_layers=2, num_heads=2, intermediate_size=512, bf16=False,
        activation_checkpointing=True, scan_layers=True))


def _grad_jaxpr(loss, params):
    closed = jax.make_jaxpr(jax.grad(loss))(params)
    jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    return jaxpr


def _kernel_runs(jaxpr, kernel):
    """Runs of the Pallas kernel named ``kernel`` in one call of
    ``jaxpr``, scan lengths counted."""
    return sum(c.mult for c in iter_eqns(jaxpr)
               if c.eqn.primitive.name == "pallas_call"
               and c.eqn.params["name"] == kernel)


# -- (a) one flash forward a layer with the kernel's residuals kept -------- #

@pytest.mark.parametrize("family", ["gpt2", "bert"])
@pytest.mark.parametrize("budget,forwards", [(EVERYTHING, 1), (0, 2)])
def test_kept_kernel_residuals_spare_the_second_forward(interpret, family,
                                                        budget, forwards):
    """S=1,024 with the shipped 512 x 1,024 blocks, two scanned layers:
    with the kernel name kept the grad program runs ``flash_fwd`` once a
    layer, with a zero budget twice (forward pass and recomputation);
    the backward kernel once either way."""
    seq = 1024
    ids = jnp.zeros((1, seq), jnp.int32)
    if family == "gpt2":
        model = _gpt2(seq)
        params = model.init_params(jax.random.PRNGKey(0))

        def loss(p):
            return model.loss(p, jax.random.PRNGKey(3), ids)
    else:
        model = _bert(seq)
        params = model.init_params(jax.random.PRNGKey(0))

        def loss(p):  # no attention mask: a mask takes the XLA path
            return model.mlm_loss(p, jax.random.PRNGKey(3), ids, ids)
    model.install_remat_budget(budget_of(budget))
    jaxpr = _grad_jaxpr(loss, params)
    layers = model.config.num_layers
    assert _kernel_runs(jaxpr, "flash_fwd") == forwards * layers
    assert _kernel_runs(jaxpr, "flash_bwd_dkdv") == layers
    assert _kernel_runs(jaxpr, "flash_bwd_dq") == 0   # gone: PR 52
    kept = model._remat_budget.plan[R.M_REMAT_KEPT]
    assert kept == ((FLASH,) if budget else ())  # GPT-2's MLP offers no name


# -- (b) the same numbers ---------------------------------------------------- #

@pytest.mark.parametrize("pld", [False, True])
def test_kept_residuals_change_no_number(interpret, pld):
    """Loss and every gradient leaf, dropout 0.1 and one rng: a full
    budget against a zero one, bit for bit.  What is kept is what would
    have been recomputed by the same operations on the same operands
    (the kernel is deterministic in its operands and seed), and the CPU
    backend compiles both programs from the same primitives in the same
    order, so not even an ulp is allowed."""
    seq = 256
    model = _gpt2(seq, dropout=0.1)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0, 256)
    theta = 0.5 if pld else None

    def value_and_grads(budget):
        model.install_remat_budget(budget_of(budget))
        out = jax.jit(jax.value_and_grad(lambda p: model.loss(
            p, jax.random.PRNGKey(3), ids, pld_theta=theta)))(params)
        return jax.tree.leaves(out)

    full, zero = value_and_grads(EVERYTHING), value_and_grads(0)
    assert model._remat_budget.plan[R.M_REMAT_KEPT] == ()
    for a, b in zip(full, zero):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_budget_is_the_program_without_names(interpret, monkeypatch):
    """Nothing fits: the grad program is the one the code gave before it
    named anything, to the instruction (``name`` lowers to nothing)."""
    seq = 256
    ids = jnp.zeros((2, seq), jnp.int32)

    def lowered(budget):
        model = _gpt2(seq, dropout=0.1)
        model.install_remat_budget(budget)
        params = model.init_params(jax.random.PRNGKey(0))
        text = jax.jit(jax.grad(lambda p: model.loss(
            p, jax.random.PRNGKey(3), ids))).lower(params).as_text()
        # private functions are numbered as the lowering meets them
        return re.sub(r"(@\w+?)_\d+\b", r"\1", text)

    zero = lowered(budget_of(0))
    assert zero == lowered(None)  # no engine, no budget
    assert zero == lowered(budget_of(None))  # a backend with no limit
    assert zero != lowered(budget_of(EVERYTHING))
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert zero == lowered(None)


# -- (c) the budget function -------------------------------------------------- #

def _cell_offer(batch, seq, heads, flash):
    """What one layer of a cell offers, bytes: the flash output's minor
    dimension of 64 is laid out in 128 lanes (twice its elements), the
    log-sum-exp takes its elements' bytes; nothing where attention takes
    the XLA path."""
    return {FLASH: batch * heads * seq * (128 * 2 + 4)} if flash else {}


LARGE_S1024 = _cell_offer(4, 1024, 20, True)
LARGE_S128 = _cell_offer(32, 128, 20, False)
XL_B8 = _cell_offer(8, 1024, 25, True)
# heads of 128 fill their lanes; gate and up together, 2 x 5,632 wide
OURO_S4K = {FLASH: 16 * 4096 * (128 * 2 + 4), FFN: 4096 * 11264 * 2}
LAGUNA_S8K = {FLASH: 1_226_833_920, FFN: 16384 * 16384 * 2}
GB = 10 ** 9


@pytest.mark.parametrize("offer,layers,budget,kept", [
    # GPT-2 large, B=4, S=1,024: 0.767 GB of kernel residuals
    (LARGE_S1024, 36, 0, ()),
    (LARGE_S1024, 36, 0.7 * GB, ()),
    (LARGE_S1024, 36, 766_771_199, ()),
    (LARGE_S1024, 36, 766_771_200, (FLASH,)),
    (LARGE_S1024, 36, 6 * GB, (FLASH,)),
    # B=32, S=128 offers no name (XLA attention)
    (LARGE_S128, 36, 0, ()),
    (LARGE_S128, 36, 6 * GB, ()),
    # GPT-2 XL, B=8 a chip: 2.556 GB
    (XL_B8, 48, 2.5 * GB, ()),
    (XL_B8, 48, 2.6 * GB, (FLASH,)),
    # Ouro-2.6B, one row of 4,096: 32 applications of 17,039,360 B of
    # kernel residuals and 92,274,688 B of the FFN's first product
    (OURO_S4K, 32, 545_259_519, ()),
    (OURO_S4K, 32, 545_259_520, (FLASH,)),           # flash alone fits
    (OURO_S4K, 32, 3_498_049_535, (FLASH,)),         # the FFN one byte over
    (OURO_S4K, 32, 3_498_049_536, (FLASH, FFN)),     # both fit
    (OURO_S4K, 32, 4_811_279_596, (FLASH, FFN)),     # the cell's budget
    # the FFN's name without the kernel's (attention on the XLA path)
    ({FFN: OURO_S4K[FFN]}, 32, 2_952_790_015, ()),
    ({FFN: OURO_S4K[FFN]}, 32, 2_952_790_016, (FFN,)),
    # a later name never jumps the queue: the FFN would fit alone
    (OURO_S4K, 32, 3 * GB, (FLASH,)),
    # Laguna-XS.2's whole stack as one layer: layer 0's product is 31 MB
    # over what the kernels' residuals leave of the cell's budget
    (LAGUNA_S8K, 1, 1_732_365_564, (FLASH,)),
    (LAGUNA_S8K, 1, 1_763_704_832, (FLASH, FFN)),
])
def test_budget_keeps_a_prefix_of_the_fixed_order(offer, layers, budget,
                                                  kept):
    assert LARGE_S1024[FLASH] * 36 == 766_771_200  # the v5e's own figure
    assert ck.saved_residual_names(offer, layers, budget) == kept


def test_offered_bytes_are_read_off_the_traced_layer():
    """GPT-2 large's layer at B=4, S=1,024 offers, off its jaxpr, the
    bytes the v5e compiler assigns (``_cell_offer``), once; inside a
    manual region a name is read at its local shape, outside one it is
    traced at the global batch and divided by the batch's shards."""
    cfg = GPT2Config(vocab_size=512, n_positions=1024, hidden_size=1280,
                     num_layers=2, num_heads=20, bf16=True)
    layer = GPT2Model(cfg).layer
    params = jax.eval_shape(layer.init_params, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((4, 1024, 1280), jnp.bfloat16)

    def body(carry, layer_params):
        return layer(layer_params, carry, deterministic=True), None

    dispatch.set_pallas_interpret(True)
    try:
        assert ck.offered_residuals(body, x, params) == LARGE_S1024
        quarter = ck.offered_residuals(body, x, params, batch_shards=4)
    finally:
        dispatch.set_pallas_interpret(False)
    assert quarter == {FLASH: LARGE_S1024[FLASH] // 4}


# the grad program's compute-dtype copy of GPT-2 large's and XL's weights
LARGE_CAST, XL_CAST = 1_548_317_696, 3_135_079_424


@pytest.mark.parametrize("tokens,width,layers,cast,expected", [
    # GPT-2 large at the cells' 4,096 tokens: the 3.5 GB measured there
    (4096, 1280, 36, LARGE_CAST, 3_505_530_112),
    # twice and four times the batch: 375,296 B a token more
    (8192, 1280, 36, LARGE_CAST, 3_505_530_112 + 4096 * 375_296),
    (16384, 1280, 36, LARGE_CAST, 3_505_530_112 + 12288 * 375_296),
    # GPT-2 XL, B=8 a chip, the whole cast copy (an upper bound under
    # ZeRO-3): 2 * 1600 * (48 + 32) + 4 * 50304 a token
    (8192, 1600, 48, XL_CAST, XL_CAST + 420_000_000 + 8192 * 457_216),
    # fp32 compute: no cast copy, four-byte activations
    (4096, 1280, 36, 0, 420_000_000 + 4096 * (68 * 1280 * 4 + 201_216)),
])
def test_working_set_grows_with_the_traced_shapes(tokens, width, layers,
                                                  cast, expected):
    itemsize = 2 if cast else 4
    assert ck.working_set_bytes(tokens, width, layers, 50304, itemsize,
                                cast) == expected


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


# what the engine sums on the v5e for GPT-2 large (my chip runs, PR 33):
# fp32 parameters, two moments and one bf16 gradient tree; a second
# gradient tree under accumulation
V5E_LIMIT = 16_909_336_064
LARGE_STATE, LARGE_STATE_GAS4 = 10_837_263_364, 12_385_443_844


def _plan_of(limit, state, batch, seq, monkeypatch, working_set=None):
    """The plan ``checkpoint_layer`` makes for GPT-2 large's layer shapes
    (two layers traced, 36 reckoned) on a device of ``limit`` bytes."""
    budget = ck.RematBudget(limit, state_bytes=state, cast_bytes=LARGE_CAST,
                            working_set=working_set)
    offer = _cell_offer(batch, seq, 20, seq >= 512)
    monkeypatch.setattr(ck, "offered_residuals", lambda *a, **k: offer)
    ck.checkpoint_layer(
        lambda carry, xs: (carry, None), budget,
        jax.ShapeDtypeStruct((batch, seq, 1280), jnp.bfloat16),
        jax.ShapeDtypeStruct((36, 1), jnp.float32), head_width=50304)
    return budget.plan


@pytest.mark.parametrize("in_use", [0, 3 * GB, 12 * GB])
@pytest.mark.parametrize("state,batch,seq,kept", [
    (LARGE_STATE, 4, 1024, (FLASH,)),        # gpt2-large.s1024
    (LARGE_STATE_GAS4, 4, 1024, (FLASH,)),   # gpt2-large.gas4
    (LARGE_STATE, 32, 128, ()),              # gpt2-large.s128: none offered
    # twice the cells' batch: the parent's program has 1.5 GB to spare
    # there and the kernel's residuals would take it; they are refused
    (LARGE_STATE, 8, 1024, ()),
])
def test_budget_ignores_what_the_allocator_holds(monkeypatch, in_use, state,
                                                 batch, seq, kept):
    """The limit, the engine's state and the working set of the traced
    shapes: the same budget and the same names whatever ``bytes_in_use``
    reads; the three one-chip cells keep what they kept on the chip, and
    a batch that leaves no room keeps nothing."""
    device = _Device({"bytes_limit": V5E_LIMIT, "bytes_in_use": in_use,
                      "peak_bytes_in_use": in_use + GB})
    plan = _plan_of(ck.device_bytes_limit(device), state, batch, seq,
                    monkeypatch)
    working_set = ck.working_set_bytes(batch * seq, 1280, 36, 50304, 2,
                                       LARGE_CAST)
    assert plan[R.M_REMAT_WORKING_SET_BYTES] == working_set
    assert plan[R.M_REMAT_BUDGET_BYTES] == V5E_LIMIT - state - working_set
    assert plan[R.M_REMAT_KEPT] == kept
    assert plan[R.M_REMAT_KEPT_BYTES] == (766_771_200 if kept else 0)


@pytest.mark.parametrize("stats", [None, {}])
def test_no_memory_limit_means_no_budget(stats, monkeypatch):
    assert _plan_of(ck.device_bytes_limit(_Device(stats)), 0, 4, 1024,
                    monkeypatch) is None


def test_state_larger_than_the_limit_keeps_nothing(monkeypatch):
    plan = _plan_of(GB, 2 * GB, 4, 1024, monkeypatch, working_set=0)
    assert plan[R.M_REMAT_BUDGET_BYTES] == 0
    assert plan[R.M_REMAT_KEPT] == ()


def test_engine_hands_the_model_its_budget(interpret, monkeypatch, tmp_path):
    """``ds.initialize`` sums its own pytrees (fp32 parameters, two
    moments, one gradient tree, a second under accumulation) into the
    budget it installs on the model; the plan of the traced program is
    logged once and reaches the monitor's stream as a ``meta`` record."""
    limit = 16 * GB
    monkeypatch.setattr(ck, "device_bytes_limit", lambda device: limit)
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=jax.devices()[:1], data=1)
    model = _gpt2(256, dropout=0.1)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh, model_parameters=params, config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "steps_per_print": 10 ** 9,
            "monitor": {"enabled": True, "output_path": str(tmp_path),
                        "writers": ["jsonl"], "write_interval": 2}})
    n = sum(x.size for x in jax.tree.leaves(params))
    budget = model._remat_budget
    # parameters + Adam's two moments + its step count + two fp32
    # gradient trees (gas 2), every one on this device
    assert budget.state_bytes == 4 * n * 5 + 4
    assert budget.cast_bytes == 0  # fp32 compute: no second copy
    ids = np.zeros((2, 256), np.int32)
    for _ in range(4):
        engine.backward(engine.forward(ids))
        engine.step()
    engine.monitor.close()
    assert budget.plan[R.M_REMAT_KEPT] == (FLASH,)
    working_set = ck.working_set_bytes(2 * 256, 128, 2, 256, 4)
    assert budget.plan[R.M_REMAT_WORKING_SET_BYTES] == working_set
    metas = [r for r in map(json.loads, open(tmp_path / "metrics.jsonl"))
             if r[R.F_KIND] == R.KIND_META and R.M_REMAT_KEPT in r]
    assert len(metas) == 1
    assert metas[0][R.M_REMAT_KEPT] == [FLASH]
    assert metas[0][R.M_REMAT_BUDGET_BYTES] == (
        limit - budget.state_bytes - working_set)
    assert metas[0][R.M_REMAT_KEPT_BYTES] == 2 * metas[0][
        R.M_REMAT_KEPT_BYTES_PER_LAYER]
    ds.reset_mesh_context()


def test_data_parallel_engine_reckons_bytes_a_device(interpret, monkeypatch):
    """ZeRO-2 over four devices: the kernel's names sit inside the flash
    call's manual region at their local shape, and the working set is
    reckoned for the tokens of one of the batch's four shards; two steps
    with everything kept equal two steps with nothing kept, bit for
    bit."""
    def train(limit):
        monkeypatch.setattr(ck, "device_bytes_limit", lambda device: limit)
        ds.reset_mesh_context()
        mesh = ds.initialize_mesh(devices=jax.devices()[:4], data=4)
        model = _gpt2(256, dropout=0.1)
        engine, _, _, _ = ds.initialize(
            model=model, mesh=mesh, rng=jax.random.PRNGKey(7),
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "SGD", "params": {"lr": 1e-2}},
                    "zero_optimization": {"stage": 2},
                    "steps_per_print": 10 ** 9})
        ids = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (8, 256), 0, 256), np.int32)
        losses = []
        for _ in range(2):
            losses.append(float(engine.forward(ids)))
            engine.backward()
            engine.step()
        out = losses, jax.tree.leaves(engine.params), model._remat_budget
        ds.reset_mesh_context()
        return out

    # fp32, B=2 a device, S=256, width 128, 2 layers, 256 rows
    working_set = ck.working_set_bytes(2 * 256, 128, 2, 256, 4)
    kept = train(working_set + GB)
    none = train(working_set)  # state alone overdraws it
    assert kept[2].plan[R.M_REMAT_KEPT] == (FLASH,)
    assert kept[2].plan[R.M_REMAT_WORKING_SET_BYTES] == working_set
    assert none[2].plan[R.M_REMAT_KEPT] == ()
    # 2 heads of 64 (in 128 lanes) and their log-sum-exp
    assert kept[2].plan[R.M_REMAT_KEPT_BYTES_PER_LAYER] == (
        2 * 2 * 256 * (128 + 1) * 4)
    assert kept[0] == none[0]
    for a, b in zip(kept[1], none[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _engine(model, mesh):
    return ds.initialize(
        model=model, mesh=mesh,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "SGD", "params": {"lr": 1e-2}},
                "steps_per_print": 10 ** 9})[0]


def test_budget_reads_a_device_of_this_process(monkeypatch):
    """The mesh's first device may belong to another host, and
    ``memory_stats`` of a device this process cannot address raises: the
    limit is read from the process's own first device, the same chip
    kind on every host."""
    other_host, mine = jax.devices()[:2]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [mine])

    def limit(device):
        assert device is mine
        return 16 * GB

    monkeypatch.setattr(ck, "device_bytes_limit", limit)
    ds.reset_mesh_context()
    model = _gpt2(16)
    _engine(model, ds.initialize_mesh(devices=[other_host], data=1))
    assert model._remat_budget.bytes_limit == 16 * GB
    ds.reset_mesh_context()


def test_no_checkpointing_no_budget(monkeypatch):
    """An engine whose model recomputes nothing asks no device for its
    memory and installs no budget."""
    def limit(device):
        raise AssertionError("no budget is wanted")

    monkeypatch.setattr(ck, "device_bytes_limit", limit)
    ds.reset_mesh_context()
    model = _gpt2(16)
    model.config.activation_checkpointing = False
    engine = _engine(model, ds.initialize_mesh(devices=jax.devices()[:1],
                                               data=1))
    assert engine._remat_budget is None and model._remat_budget is None
    ds.reset_mesh_context()


# -- (d) the carried stream keeps whole-layer recomputation ------------------ #

def test_carried_stream_takes_no_names_policy():
    """Under the carried stream a full budget changes nothing: no plan is
    made, the grad jaxpr is the one with no budget, and every layer still
    runs forward twice (2L ``tanh``s)."""
    num_layers = 8

    def grad_jaxpr(budget):
        engine, model = _tiny_engine(_group_cfg("carried", 4),
                                     num_layers=num_layers,
                                     checkpointing=True)
        model.install_remat_budget(budget)
        ids = _tiny_ids()
        jaxpr = _grad_jaxpr(
            lambda p: model.loss(p, jax.random.PRNGKey(3), ids),
            engine.params)
        assert engine._zero3_stream.last_plan.prefetch
        ds.reset_mesh_context()
        return jaxpr

    budget = budget_of(EVERYTHING)
    kept, plain = grad_jaxpr(budget), grad_jaxpr(None)
    assert budget.plan is None
    assert _weighted_prim_count(kept, "tanh") == 2 * num_layers
    assert str(kept) == str(plain)


@pytest.mark.parametrize("names", [(FLASH,), (FLASH, FFN), (FFN,)])
@pytest.mark.parametrize("limit", [None, 1, EVERYTHING])
def test_a_stack_without_picks_lowers_as_it_did(limit, names):
    """Every policy ``checkpoint_layers`` hands out also names
    ``ALWAYS_KEPT`` (a router's picks), with a budget, with one that
    admits nothing and with none.  A stack that offers no such name (all
    but the mixture-of-experts models) lowers to the text it lowered to
    under the policy without it: plain ``jax.checkpoint`` with no budget
    or nothing kept, ``save_only_these_names`` of what was kept, whichever
    of the order's names the body offers."""
    from jax.ad_checkpoint import checkpoint_name

    def body(carry, w):
        h = jnp.tanh(carry @ w)
        for name in names:
            h = checkpoint_name(jnp.sin(h), name)
        return carry + h @ w.T, None

    carry = jnp.ones((4, 8, 16), jnp.float32)
    ws = jnp.full((3, 16, 16), 0.1, jnp.float32)
    budget = None if limit is None else budget_of(limit)
    wrap = ck.checkpoint_layers([(body, ws)], budget, carry, head_width=8)
    kept = () if budget is None else budget.plan[R.M_REMAT_KEPT]
    assert kept == (names if limit == EVERYTHING else ())
    before = jax.checkpoint if not kept else (lambda f: jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(*kept)))

    def text(wrapper):
        def loss(carry, ws):
            out, _ = jax.lax.scan(wrapper(body), carry, ws)
            return jnp.sum(out)
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            carry, ws).as_text()

    assert text(wrap) == text(before)


# -- (e) a sparse stack's row buffers leave room for the kernel residuals ---- #

def test_laguna_cell_keeps_the_flash_residuals(interpret):
    """The benchmark cell's stack (five layers at the published widths, 32
    of 256 experts held, two rows of 8,192 tokens) under the v5e's memory
    limit and the cell's state: a sparse layer's row buffers hold the held
    experts' even share of the picks, 16,384 rows and not the 131,072 of
    the worst case, so the budget admits the flash kernels' residuals (it
    admitted nothing while ``DroplessMoE.working_set_bytes`` counted 2.01
    GB of worst-case rows)."""
    from deepspeed_tpu.models.laguna import LagunaConfig, LagunaModel
    model = LagunaModel(LagunaConfig(
        num_hidden_layers=5, experts_held=(0, 32), vocab_size=12544,
        activation_checkpointing=True))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    entries = model.num_params()
    assert entries == 691_623_936
    # fp32 weights and two moments, bf16 gradients, the step count
    budget = ck.RematBudget(16_909_336_064, state_bytes=14 * entries + 4,
                            cast_bytes=2 * entries)
    model.install_remat_budget(budget)
    jax.eval_shape(model.loss, params, None,
                   jax.ShapeDtypeStruct((2, 8192), jnp.int32))
    plan = budget.plan
    rows = model.moe.working_set_bytes(2 * 8192, 2)
    assert rows == 16384 * 2 * 3 * (2048 + 512) + 16384 * 2048 * 4
    assert plan[R.M_STACK_DISPATCH_ROWS] == 16384
    assert plan[R.M_REMAT_STATE_BYTES] == 9_682_735_108
    assert plan[R.M_REMAT_WORKING_SET_BYTES] == 5_108_359_424 + rows
    assert plan[R.M_REMAT_KEPT] == (FLASH,)
    assert plan[R.M_REMAT_KEPT_BYTES] <= plan[R.M_REMAT_BUDGET_BYTES]
    assert 1.2e9 < plan[R.M_REMAT_KEPT_BYTES] < 1.4e9


# -- (f) the gated FFN's first product, the name after the flash residuals --- #

def _cell_model(cell):
    """The model of a benchmark cell, built from the cell's own files as
    ``perf/run.py`` builds it, with its batch shape and its traffic."""
    def read(kind, name):
        return json.loads(
            (pathlib.Path(ds.__file__).parents[1] / "perf" / kind
             / f"{name}.json").read_text())

    workload = read("workloads", cell)
    config = read("configs", workload["config"])
    traffic = read("traffic", workload["traffic"])
    family = importlib.import_module("perf.families." + config["family"])
    models = importlib.import_module(
        "deepspeed_tpu.models." + config["family"])
    model_class = {"phi4flash": "Phi4FlashModel", "ouro": "OuroModel",
                   "laguna": "LagunaModel",
                   "glm4_moe_lite": "Glm4MoeLiteModel"}[config["family"]]
    model = getattr(models, model_class)(
        family.model_config(config, workload["job"]))
    return model, (traffic["batch_per_chip"], traffic["seq"])


MB = 2 ** 20


@pytest.mark.parametrize("cell,budget,flash,ffn,kept", [
    # six FFNs of 8,192 x 20,480 (the three attention layers' residuals)
    ("phi4-mini-flash.s8k", 3_000_517_884, 511_180_800,
     6 * 8192 * 20480 * 2, (FLASH, FFN)),
    # 32 layer applications of 4,096 x 11,264
    ("ouro-2.6b.s4k", 4_811_279_596, 545_259_520,
     32 * 4096 * 11264 * 2, (FLASH, FFN)),
    # layer 0's dense FFN alone offers the name: 512 MiB where the kernels'
    # residuals leave 505,531,644 B
    ("laguna-xs2.s8k", 1_732_365_564, 1_226_833_920, 512 * MB, (FLASH,)),
    # 640 MiB where they leave 28,662,012 B
    ("glm47-flash.s8k", 1_043_159_292, 1_014_497_280, 640 * MB, (FLASH,)),
])
def test_cells_are_offered_the_ffn_product(interpret, monkeypatch, cell,
                                           budget, flash, ffn, kept):
    """The four drawn cells' stacks at the published widths, by shapes
    alone, under the v5e's memory limit and the state their engines sum
    (fp32 weights and two moments, bf16 gradients, the step count): every
    one offers the FFN's first product after the kernels' residuals,
    ``phi4`` and ``ouro`` have the room for it in every layer, ``laguna``
    (31 MB short) and ``glm47`` keep the plan they had.  The budgets are
    the chip's own log lines (PERF.md sections 5 and 6)."""
    model, batch = _cell_model(cell)
    entries = model.num_params()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    model.install_remat_budget(ck.RematBudget(
        V5E_LIMIT, state_bytes=14 * entries + 4, cast_bytes=2 * entries))
    seen = {}  # the stack's bytes by name, kept or not
    admit = ck.saved_residual_names
    monkeypatch.setattr(ck, "saved_residual_names", lambda offered, *a: (
        seen.update(offered) or admit(offered, *a)))
    jax.eval_shape(model.loss, params, None,
                   jax.ShapeDtypeStruct(batch, jnp.int32))
    plan = model._remat_budget.plan
    assert plan[R.M_REMAT_BUDGET_BYTES] == budget
    assert plan[R.M_REMAT_OFFERED] == (FLASH, FFN)
    assert (seen[FLASH], seen[FFN]) == (flash, ffn)
    assert plan[R.M_REMAT_KEPT] == kept
    if kept == (FLASH,):
        assert flash <= budget < flash + ffn
        assert plan[R.M_REMAT_KEPT_BYTES] == flash
        assert R.M_REMAT_KEPT_BYTES_BY_NAME not in plan
    else:
        assert plan[R.M_REMAT_KEPT_BYTES] == flash + ffn <= budget
        assert plan[R.M_REMAT_KEPT_BYTES_BY_NAME] == (
            (FLASH, flash), (FFN, ffn))
        assert ck._by_name_phrase(plan) == (
            f" ({FLASH} {flash:,} B, {FFN} {ffn:,} B)")


def _toy(family):
    """(model, ids, gated FFN applications a step) of a family's toy as
    its own tests build it, checkpointing on, attention on the XLA path:
    the FFN's name is the only one offered."""
    if family == "ouro":
        from deepspeed_tpu.models.ouro import OuroModel
        from tests.unit.test_ouro import _config
        model = OuroModel(_config(activation_checkpointing=True))
        ffns = model.config.num_hidden_layers * model.config.total_ut_steps
    else:
        from deepspeed_tpu.models.phi4flash import Phi4FlashModel
        from tests.unit.test_phi4flash import _config
        model = Phi4FlashModel(_config(activation_checkpointing=True))
        ffns = len(model.config.layer_plan())
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 250)
    return model, ids, ffns


def _dots(jaxpr):
    return sum(c.mult for c in iter_eqns(jaxpr)
               if c.eqn.primitive.name == "dot_general")


@pytest.mark.parametrize("family,ulps", [("ouro", 0), ("phi4flash", 16)])
def test_kept_ffn_product_spares_its_recomputation(family, ulps):
    """With the name kept the backward program runs one ``dot_general``
    fewer for every application of a gated FFN (``u @ w1`` is not run
    again: the gate, its product with ``up`` and what else the backward
    pass reads are rebuilt from the kept value), and loss and every
    gradient leaf are the zero budget's: bit for bit in ``ouro``; in
    ``phi4flash`` the CPU compiler sums the scanned pairs' weight
    gradients in another order once the product is a stacked operand
    (within 16 units in the last place of a leaf's largest entry).  A zero budget,
    no budget and a layer that names nothing lower to one text: what
    ``jax.checkpoint(body)`` lowers to."""
    model, ids, ffns = _toy(family)
    params = model.init_params(jax.random.PRNGKey(0))

    def loss(p):
        return model.loss(p, None, ids)

    def program(budget):
        model.install_remat_budget(budget)
        step = jax.jit(jax.value_and_grad(loss))
        text = re.sub(r"(@\w+?)_\d+\b", r"\1", step.lower(params).as_text())
        return (_dots(_grad_jaxpr(loss, params)), text,
                jax.tree.leaves(step(params)))

    full, zero = budget_of(EVERYTHING), budget_of(0)
    kept_dots, kept_text, kept_numbers = program(full)
    zero_dots, zero_text, zero_numbers = program(zero)
    assert full.plan[R.M_REMAT_KEPT] == (FFN,)
    assert zero.plan[R.M_REMAT_OFFERED] == (FFN,)
    assert zero.plan[R.M_REMAT_KEPT] == ()
    assert zero_dots - kept_dots == ffns
    for a, b in zip(kept_numbers, zero_numbers):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=ulps * 1.2e-7 * float(jnp.abs(b).max()))
    assert zero_text != kept_text
    assert zero_text == program(None)[1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr("deepspeed_tpu.models.laguna.checkpoint_name",
                      lambda x, name: x)
        assert zero_text == program(None)[1]
