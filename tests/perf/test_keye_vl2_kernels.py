"""ops/indexed_attention.py on the CPU at a small size (S = 256, topk
32): the exact select against ``lax.top_k``, each ``dsa_*`` kernel in
interpret mode against its blocked XLA form, forward and backward; the
sum of the eight expert shares; the Keye-VL-2.0 family's counts and the
cell's 16k traffic."""

import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.keye_vl2 import KeyeVL2Model
from deepspeed_tpu.moe.dropless import DroplessMoE
from deepspeed_tpu.ops import indexed_attention as ia
from deepspeed_tpu.ops.flash_attention import mha_reference
from perf.families import keye_vl2 as family
from perf.families import keye_vl2_reference as reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ, TOPK = 256, 32


def _published():
    return json.loads(
        (ROOT / "perf/configs/keye-vl2-30b-a3b.json").read_text())


# ---------------------------------------------------------------------- #
# the select, and each kernel against its blocked XLA form
# ---------------------------------------------------------------------- #
def _operands(ties=False, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    shapes = {"q": (1, 4, SEQ, 16), "k": (1, 2, SEQ, 16),
              "v": (1, 2, SEQ, 16), "q_idx": (1, 4, SEQ, 8),
              "k_idx": (1, SEQ, 8), "w": (1, 4, SEQ)}
    out = {name: jax.random.normal(key, shape)
           for key, (name, shape) in zip(keys, shapes.items())}
    out["w"] = 0.1 * out["w"]
    if ties:   # whole numbers: many equal scores, zeros among them
        out.update(q_idx=jnp.round(out["q_idx"]),
                   k_idx=jnp.round(out["k_idx"]),
                   w=jnp.round(10 * out["w"]))
    return {name: x.astype(jnp.float32 if name == "w" else dtype)
            for name, x in out.items()}


def _dense_keep(x):
    """The selection by ``lax.top_k`` on the whole [S, S] scores."""
    scores = jnp.einsum("bhs,bhsk->bsk", x["w"], jax.nn.relu(jnp.einsum(
        "bhsd,bkd->bhsk", x["q_idx"], x["k_idx"])))
    causal = jnp.tril(jnp.ones((SEQ, SEQ), bool))
    _, chosen = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), TOPK)
    keep = jnp.zeros((1, SEQ, SEQ), bool).at[
        0, jnp.arange(SEQ)[:, None], chosen[0]].set(True)
    return keep & causal


@pytest.mark.parametrize("ties", [False, True], ids=["random", "ties"])
@pytest.mark.parametrize("form", ["xla", "dsa_select"])
def test_select_keeps_exactly_k_and_agrees_with_top_k(form, ties):
    x = _operands(ties)
    if form == "xla":
        packed, lse = ia.index_select_xla(x["q_idx"], x["k_idx"], x["w"],
                                          TOPK, block_q=64)
    else:
        packed, lse = ia.index_select_pallas(
            x["q_idx"], x["k_idx"], x["w"], topk=TOPK, block_q=64,
            block_k=128, interpret=True)
    keep = ia.unpack_keep(packed, 64)
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(keep, axis=-1)[0]),
        np.minimum(np.arange(SEQ) + 1, TOPK))
    assert bool(jnp.all(keep == _dense_keep(x)))
    assert float(ia.kept_pairs(packed)) == family.selected_pairs(SEQ, TOPK)
    np.testing.assert_allclose(
        lse, ia.kept_lse(x["q_idx"], x["k_idx"], x["w"], packed, 64),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tall", [False, True],
                         ids=["one packed block a tile", "two"])
@pytest.mark.parametrize("kernel", ["dsa_attn_fwd", "dsa_attn_bwd",
                                    "dsa_align"])
def test_kernel_in_interpret_mode_equals_its_xla_form(kernel, tall):
    x = _operands()
    scale = 0.25
    blocks = {"block_q": 128 if tall else 64, "block_k": 128, "pack": 64,
              "interpret": True}
    packed, lse_idx = ia.index_select_xla(x["q_idx"], x["k_idx"], x["w"],
                                          TOPK, block_q=64)
    out, lse = ia.indexed_attention_xla(x["q"], x["k"], x["v"], packed,
                                        scale, block_q=64)
    if kernel == "dsa_attn_fwd":
        got, got_lse = ia.indexed_attention_fwd_pallas(
            x["q"], x["k"], x["v"], packed, sm_scale=scale, **blocks)
        np.testing.assert_allclose(got, out, atol=2e-6)
        np.testing.assert_allclose(got_lse, lse, atol=2e-6)
    elif kernel == "dsa_attn_bwd":
        do = jax.random.normal(jax.random.PRNGKey(9), out.shape)
        want = jax.grad(lambda q, k, v: jnp.sum(do * ia.indexed_attention_xla(
            q, k, v, packed, scale, block_q=64)[0]), (0, 1, 2))(
            x["q"], x["k"], x["v"])
        got = ia.indexed_attention_bwd_pallas(
            x["q"], x["k"], x["v"], packed, out, lse, do, sm_scale=scale,
            **blocks)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=1e-5)
    else:
        want, want_grads = jax.value_and_grad(
            lambda a, b, c: ia.index_alignment_xla(
                a, b, c, x["q"], x["k"], lse, packed, lse_idx, scale,
                block_q=64), (0, 1, 2))(x["q_idx"], x["k_idx"], x["w"])
        got, got_grads = ia.index_alignment_pallas(
            x["q_idx"], x["k_idx"], x["w"], x["q"], x["k"], lse, packed,
            lse_idx, sm_scale=scale, **blocks)
        assert float(got) == pytest.approx(float(want), rel=1e-5)
        for a, b in zip(got_grads, want_grads):
            np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-4)


def test_the_three_calls_on_the_kernels_equal_the_xla_forms():
    """At the kernels' own tiles (the shortest sequence they take), through
    the dispatch and the backward rules a model reaches them by."""
    from deepspeed_tpu.ops import dispatch
    seq, topk = ia.ATTN_BLOCK, 96
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    shapes = ((1, 2, seq, 128), (1, 1, seq, 128), (1, 1, seq, 128),
              (1, 2, seq, 8), (1, seq, 8), (1, 2, seq))
    operands = [jax.random.normal(key, shape)
                for key, shape in zip(keys, shapes)]
    operands[5] = 0.1 * operands[5]

    def objective(q, k, v, q_idx, k_idx, w, kernels):
        keep, lse_idx = ia.index_select(q_idx, k_idx, w, topk, kernels)
        out, lse = ia.indexed_attention(q, k, v, keep, kernels=kernels)
        align = ia.index_alignment(q_idx, k_idx, w, q, k, lse, keep, lse_idx,
                                   kernels=kernels)
        return 1e-3 * jnp.sum(out * out) + align, keep

    def both(kernels):
        return jax.value_and_grad(objective, tuple(range(6)), has_aux=True)(
            *operands, kernels)

    dispatch.set_pallas_interpret(True)
    try:
        assert ia.kernels_take(seq, 128, 8)
        (got, keep), got_grads = both(True)
    finally:
        dispatch.set_pallas_interpret(False)
    assert not ia.kernels_take(seq, 128, 8)
    (want, want_keep), want_grads = both(False)
    assert np.array_equal(keep, want_keep)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_with_every_key_kept_it_is_plain_causal_attention():
    """topk >= S: the selection keeps every causal key, the restricted
    attention is ``mha_reference`` and the picks of ``lax.top_k``."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (1, 4, SEQ, 16))
    k, v = (jax.random.normal(key, (1, 2, SEQ, 16)) for key in keys[1:3])
    q_idx = jax.random.normal(keys[3], (1, 4, SEQ, 8))
    k_idx = jax.random.normal(keys[4], (1, SEQ, 8))
    w = jax.random.normal(keys[5], (1, 4, SEQ))
    packed, _ = ia.index_select(q_idx, k_idx, w, SEQ)
    causal = jnp.tril(jnp.ones((SEQ, SEQ), bool))
    assert bool(jnp.all(ia.unpack_keep(packed) == causal))
    out, _ = ia.indexed_attention(q, k, v, packed)
    plain = mha_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - plain))) < 1e-5


def test_packing_round_trips():
    keep = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3, (2, SEQ, 128))
    for block in (32, 64, 256):
        assert bool(jnp.all(ia.unpack_keep(ia.pack_keep(keep, block), block)
                            == keep))


# ---------------------------------------------------------------------- #
# the expert shares, the counts, the traffic
# ---------------------------------------------------------------------- #
def test_eight_expert_shares_sum_to_the_uncut_layer():
    """The routed parts that eight ranks of two experts each compute add
    up to the uncut reference's expert layer (nothing else is shared:
    this family has no shared expert)."""
    hid, width, experts, picked = 32, 16, 16, 4
    whole = DroplessMoE(hid, experts, picked, width, None, score="softmax",
                        renormalize=True)
    params = whole.init_params(jax.random.PRNGKey(2))
    z = jax.random.normal(jax.random.PRNGKey(4), (96, hid))
    gate, up = jnp.split(params["experts"]["w1"], 2, axis=-1)
    spec = reference.Spec(layers=1, picked=picked)
    uncut, (_, picks) = reference.sparse_ffn(
        {"Wr": params["router"], "experts": {
            "Wgate": gate, "Wup": up, "Wdown": params["experts"]["w2"]}},
        z, spec)
    total = 0.0
    for rank in range(8):
        share = DroplessMoE(hid, experts, picked, width, None,
                            score="softmax", renormalize=True,
                            experts_held=(2 * rank, 2),
                            first_chunk_always=True)
        held = {"router": params["router"], "experts": jax.tree.map(
            lambda a: a[2 * rank:2 * rank + 2], params["experts"])}
        part, routing = share.apply(held, z)
        assert bool(jnp.all(jnp.sort(routing.picks) == jnp.sort(picks)))
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=2e-6)


def test_counts_by_hand():
    config = _published()
    job = {"batch_per_chip": 1, "seq": 16384}
    model = KeyeVL2Model(family.model_config(
        config, {"activation_checkpointing": False}))
    assert model.num_params() == 659_517_696
    assert family.causal_pairs(16384) == 134_225_920
    assert family.selected_pairs(16384, 2048) == 31_458_304
    assert family.kept_share(config, job) == pytest.approx(0.2344, abs=5e-5)
    # no engine has run: what a router that favours nobody would send
    assert family.held_share(config) == 1 / 8
    matrices = 18_874_368 + 2_260_992 + 262_144 + 4_718_592
    assert family.layer_matrices(config, 1 / 8) == matrices
    attention = 3 * 2 * 2 * (31_458_304 / 16384) * 32 * 128
    index = 2 * 16 * 64 * (134_225_920 + 2 * 31_458_304) / 16384
    assert family.flops_per_token(config, job) == pytest.approx(
        6 * 6 * matrices + 6 * (attention + index) + 6 * 2048 * 19072)
    work, moved = family.dsa_attn_call_cost("dsa_attn_fwd", config, job)
    assert work == 2 * 2 * 32 * 128 * 31_458_304
    assert moved == (2 * 32 + 2 * 4) * 16384 * 128 * 2 + 16384 * 16384 // 8
    assert family.dsa_attn_call_cost("dsa_attn_bwd_dkdv", config, job)[
        0] == 2 * work
    work, moved = family.dsa_index_call_cost("dsa_select", config, job)
    assert work == 2 * 134_225_920 * 16 * 64
    assert moved == 16384 * (2048 + 128 + 64 + 4) + 16384 * 16384 // 8
    ops, _ = family.gmm_call_cost("gmm_rows", config, job, 16384)
    assert ops == 1.5 * 2 * 16384 * 2048 * 768


def test_the_16k_traffic():
    from perf import run
    traffic = json.loads(
        (ROOT / "perf/traffic/zipf.b1.s16384.json").read_text())
    generator = run.load_module(str(ROOT), "traffic", traffic["generator"])
    rows = family.vocab_rows(_published())
    assert rows == 19072 and traffic["seq"] == 16384
    # Zipf(1) over 19,072 rows: ln H + sum(ln r / r) / H
    ranks = np.arange(1, rows + 1)
    harmonic = np.sum(1.0 / ranks)
    by_hand = math.log(harmonic) + np.sum(np.log(ranks) / ranks) / harmonic
    assert generator.entropy(traffic, rows) == pytest.approx(by_hand,
                                                             rel=1e-9)
    traffic = {**traffic, "pool_steps": 2}
    pool = generator.make(traffic, 1, rows, 2 ** 31 + 5)
    assert pool.shape == (2, 1, 16384) and pool.max() < rows
    assert np.array_equal(pool, generator.make(traffic, 1, rows,
                                               2 ** 31 + 5))


def test_the_configuration_file_holds_the_catalog_row():
    config = _published()
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "Keye-VL-2.0-30B-A3B")
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 6, "num_experts": 16,
               "num_local_experts": 16, "vocab_size": 19072}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == row["config"][key]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl2-30b-a3b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    cell = next(w for w in bench["workloads"] if w["config"] == entry["name"])
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "keye-vl2-30b-a3b.s16k", "zipf.b1.s16384", 1)
