"""The ZAYA family's benchmark files: the program against the plain
reference at a small size (loss, logits, gradients by kind of leaf, with
and without recomputation, stacked and unrolled), the two shares of an
expert layer against the uncut reference, the configuration file against
the catalog row, the kept and the published parameter counts, what the
step, its kernels and its mixing are counted to require against hand
counts, the family's comparison passing the engine's side and refusing a
lower precision (a bf16 router, bf16 sums in the mixing), the eight
readers on a hand-made trace, and the cell's own files at a small size
through ``perf/run.py``'s entry.  Every entry of ``BENCHMARK.json`` is
looked up by name."""

import importlib
import json
import math
import pathlib
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.zaya import ZayaModel
from perf import flops
from perf.families import zaya as family
from perf.families import zaya_reference as reference
from tests.perf.test_manifest import restore_compile_cache  # noqa: F401

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME, CELL = "zaya1-8b", "zaya1-8b.s8k"
CONFIG = ROOT / f"perf/configs/{NAME}.json"
READERS = ("cca_mix_ms", "cca_mix_roofline_pct", "cca_flash_roofline_pct",
           "zaya_router_ms", "moe_top1_ms", "gmm_top1_roofline_pct",
           "tied_head_ms", "zaya_held_pick_share_pct")
JOB = {"batch_per_chip": 2, "seq": 8192}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _published():
    return json.loads(CONFIG.read_text())


def _toy(held=(1, 2), experts=4, layers=3):
    """The cell's configuration at a small size: ``layers`` layers of 64,
    4 query heads on 2 key/value heads of 16, ``experts`` experts of
    which ``held`` (first, count) are here, a router state of 16."""
    config = _published()
    config.update(
        hidden_size=64, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, num_experts=held[1],
        moe_intermediate_size=32, router_hidden_size=16, vocab_size=250,
        num_hidden_layers=layers)
    config["published"] = {**config["published"], "num_experts": experts}
    config["kept"] = {**config["kept"], "experts_first": held[0]}
    config["assumed"] = {**config["assumed"], "initializer_range": 0.3}
    return config


def _model(config, **over):
    model = ZayaModel(family.model_config(
        config, {"activation_checkpointing": False}))
    model.config.bf16 = False
    for key, value in over.items():
        setattr(model.config, key, value)
    return model


@jax.jit
def _scattered(params, seed=5, by=0.1):
    """Every leaf off its initial value, so that no scale is 1, no bias 0
    and no temperature 1 in what is compared."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + by * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def _reader(name):
    return importlib.import_module(f"perf.layer_metrics.{name}")


# ---------------------------------------------------------------------- #
# program against reference
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def sides():
    """The reference's loss, gradients, scores, picks and logits on two
    rows of 24 tokens, on seeded weights scattered off their start."""
    config = _toy()
    params = _scattered(jax.jit(_model(config).init_params)(
        jax.random.PRNGKey(0)))
    spec = family.reference_spec(config)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 250)
    weights = family.reference_params(params, spec)
    (loss, (scores, picks)), grads = jax.jit(
        lambda w: reference.loss_and_grads(w, ids, spec))(weights)
    return {"config": config, "params": params, "spec": spec, "ids": ids,
            "loss": float(loss), "scores": scores, "picks": picks,
            "grads": grads,
            "logits": jax.jit(
                lambda w: reference.logits(w, ids, spec))(weights)}


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["kept", "recomputed"])
@pytest.mark.parametrize("scan", [True, False], ids=["stacked", "unrolled"])
def test_loss_logits_gradients_and_picks_are_the_references(
        sides, recompute, scan):
    model = _model(sides["config"], activation_checkpointing=recompute,
                   scan_layers=scan)
    params, ids, spec = sides["params"], sides["ids"], sides["spec"]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, None, ids)))(params)
    assert float(loss) == pytest.approx(sides["loss"], rel=2e-6)
    scores, picks = jax.jit(model.routing)(params, ids)
    np.testing.assert_array_equal(picks, sides["picks"])
    np.testing.assert_allclose(scores, sides["scores"], rtol=2e-5,
                               atol=1e-7)
    np.testing.assert_allclose(jax.jit(model.logits)(params, ids),
                               sides["logits"], rtol=2e-4, atol=2e-5)
    got = family.reference_params(grads, spec)
    for name, leaves in family.LEAVES.items():
        want = leaves(sides["grads"])
        apart = reference.global_norm(jax.tree.map(
            lambda a, b: a - b, leaves(got), want))
        size = float(reference.global_norm(want))
        assert size > 0, name
        assert float(apart) <= 2e-5 * size, name
    assert float(reference.global_norm(family.gate_biases(got))) == 0.0
    # the kinds leave out the final norm's gain and, a layer, two norm
    # gains, W_k, the two value matrices, W_o and the balancing bias
    kinds = sum(len(jax.tree.leaves(leaves(got)))
                for leaves in family.LEAVES.values())
    assert kinds + 1 + 3 * 7 == len(jax.tree.leaves(got))


def test_the_logits_at_sampled_positions_are_the_full_logits_rows(sides):
    model = _model(sides["config"])
    params, ids = sides["params"], sides["ids"]
    positions = family.sampled_positions(24, seed=3)
    assert len(positions) == 24 and list(positions) == sorted(positions)
    some = np.asarray([1, 7, 23], np.int32)
    np.testing.assert_allclose(
        model.logits(params, ids, positions=some),
        model.logits(params, ids)[:, some], rtol=1e-5, atol=1e-6)
    assert len(family.sampled_positions(8192, seed=3)) == 64


@pytest.mark.parametrize("fault,moves", [
    ("no value shift", lambda x, first=None: x),
    ("zeros before conv1", lambda x, first=None: jnp.concatenate(
        [jnp.zeros_like(x[:1]), x[:-1]])),
])
def test_the_comparison_sees_a_wrong_term(sides, fault, moves, monkeypatch):
    """One layer's attention sublayer on one row: the program's is the
    reference's, and is told from the reference with a term of the mixing
    changed (the position before read as this one; conv0's bias before
    the sequence taken for zeros)."""
    model, spec = _model(sides["config"]), sides["spec"]
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 64))
    p = jax.tree.map(lambda a: a[1], sides["params"]["layers"])
    got = model._attention(p["attn"], u, model.rotary_tables(24))[0]
    layer = family.reference_params(sides["params"], spec)["layers"][1]
    with jax.default_matmul_precision("highest"):
        want = reference.attention(layer, u[0], spec)
        monkeypatch.setattr(reference, "before", moves)
        wrong = reference.attention(layer, u[0], spec)
    size = float(jnp.linalg.norm(want))
    assert float(jnp.linalg.norm(got - want)) <= 1e-5 * size
    assert float(jnp.linalg.norm(got - wrong)) > 1e-2 * size, fault


# ---------------------------------------------------------------------- #
# the share test
# ---------------------------------------------------------------------- #
def test_the_two_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The expert sublayer of one layer in float32: what the share (0, 2)
    of four experts gives plus what (2, 2) gives is what the uncut
    reference gives for the whole layer (the router is computed alike by
    every share and picks alike; nothing is shared between the shares'
    sums but the picks)."""
    whole = _toy(held=(0, 4))
    model = _model(whole)
    params = _scattered(jax.jit(model.init_params)(jax.random.PRNGKey(7)))
    p = jax.tree.map(lambda a: a[1], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64))
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(9), (2, 24, 4))
    spec = family.reference_spec(whole)
    layer = family.reference_params(params, spec)["layers"][1]
    scores = jax.nn.softmax(logits.reshape(-1, 4), axis=-1)
    with jax.default_matmul_precision("highest"):
        want, picks = reference.experts(layer, x.reshape(-1, 64), scores,
                                        spec)
    assert len(set(np.asarray(picks).ravel())) == 4
    total = jnp.zeros_like(want)
    for first in (0, 2):
        share = _model(_toy(held=(first, 2)))
        experts = jax.tree.map(lambda a: a[first:first + 2],
                               p["moe"]["experts"])
        y, routing = share.moe.apply(
            {"experts": experts, "bias": p["moe"]["bias"]}, x, logits=logits)
        np.testing.assert_array_equal(routing.picks, picks)
        assert float(jnp.max(jnp.abs(y))) > 0
        total = total + y.reshape(-1, 64)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------- #
# the configuration's file, the manifest's entries
# ---------------------------------------------------------------------- #
def test_the_configuration_file_holds_the_catalog_row():
    config = _published()
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "ZAYA1-8B")
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 6, "num_experts": 8, "vocab_size": 32784}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == row["config"][key]
    # as released: 40 entries, of which the family reads the first six
    assert config["layer_types"] == ["hybrid"] * 40
    assert config["max_position_embeddings"] == 131072
    assert config["kept"]["published_layers"] == list(range(6))
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["num_experts"] * 2 == row["config"]["num_experts"]
    for key in ("initializer_range", "initialisation", "residual_scaling",
                "cca", "router", "bias_update_rate", "skip_expert",
                "ignored_keys", "optimizer"):
        assert key in config["assumed"], key
    assert "arXiv:2510.04476" in config["assumed"]["cca"]
    assert "NOT built" in config["assumed"]["skip_expert"]
    assert "eight TPU v5e chips" in config["deployment"]
    assert "708,660,492" in config["kept"]["parameters"]
    assert "8,303,337,296" in config["published"]["parameters"]


def test_the_manifest_names_the_configuration_the_cell_and_eight_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = _published()
    entry = _by_name(bench["configs"], NAME)
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == f"perf/configs/{NAME}.json"
    cell = _by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "zipf.b2.s8192", 1)
    body = json.loads((ROOT / "perf/workloads" / f"{CELL}.json").read_text())
    assert body["why"] == cell["why"] and len(cell["why"]) <= 200
    for said in ("1 of 2", "8 experts", "1,024", "8x"):
        assert said in cell["why"], said
    assert body["job"]["parity"] == {"layers": 6, "rows_per_chip": 2}
    # nemotron-3-nano-30b-a3b.s8k's job, key for key, and beside it the
    # linear warm-up of the rate that keeps a layer's tokens on picks of
    # their own (PERF.md section 6, PR 64): keye-vl2-30b-a3b.s16k's
    # scheduler over 2,000 steps
    others = {name: json.loads(
        (ROOT / f"perf/workloads/{name}.json").read_text())["job"]
        for name in ("nemotron-3-nano-30b-a3b.s8k", "keye-vl2-30b-a3b.s16k")}
    scheduler = body["job"]["ds_config"]["scheduler"]
    assert scheduler == {
        "type": "WarmupLR", "params": {
            **others["keye-vl2-30b-a3b.s16k"]["ds_config"]["scheduler"][
                "params"], "warmup_num_steps": 2000}}
    assert scheduler["params"]["warmup_max_lr"] == body["job"]["ds_config"][
        "optimizer"]["params"]["lr"]
    job = {**body["job"], "ds_config": {
        k: v for k, v in body["job"]["ds_config"].items()
        if k != "scheduler"}}
    assert {k: v for k, v in job.items() if k != "parity"} == {
        k: v for k, v in others["nemotron-3-nano-30b-a3b.s8k"].items()
        if k != "parity"}
    assert "warm-up" in cell["why"]
    assert len(body["per_layer"]) == 9 + len(READERS)
    for name in READERS:
        metric = _by_name(bench["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "step_ms_p50"
        assert name in body["per_layer"]
        reader = _reader(name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["source"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_kept_and_the_published_parameters_are_what_the_file_says():
    config = _published()
    assert family.attention_parameters(config) == 5_575_682
    assert family.router_parameters(config) == 660_736
    assert family.layer_parameters(config, 0) == 6_256_898
    assert family.layer_parameters(config, 8) == 6_256_898 + 8 * 12_582_912
    assert family.first_layer_lacks(config) == 4_352
    assert family.parameters(config) == 708_660_492 == (
        6 * (6_256_898 + 8 * 12_582_912) + 32_784 * 2_048 + 2_048 - 4_352)
    assert family.published_parameters(config) == 8_303_337_296
    assert family.published_parameters(config, table=True) == (
        8_303_337_296 + 262_272 * 2_048)
    model = ZayaModel(family.model_config(
        config, {"activation_checkpointing": False}))
    # the program's tree: the count, and the balancing biases beside it
    assert model.num_params() == 708_660_492 + 6 * 16
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    layers = shapes["layers"]
    assert layers["attn"]["qkv_w"].shape == (6, 2048, 1024 + 256 + 256)
    assert layers["attn"]["conv0_w"].shape == (6, 1280, 2)
    assert layers["attn"]["conv1_w"].shape == (6, 10, 2, 128, 128)
    assert layers["attn"]["tau"].shape == (6, 2)
    assert layers["attn"]["out_w"].shape == (6, 1024, 2048)
    assert layers["router"]["down_w"].shape == (6, 2048, 256)
    assert layers["router"]["w3"].shape == (6, 256, 16)
    assert layers["moe"]["experts"]["w1"].shape == (6, 8, 2048, 4096)
    assert layers["moe"]["experts"]["w2"].shape == (6, 8, 2048, 2048)
    assert layers["moe"]["bias"].shape == (6, 16)
    assert "router" not in layers["moe"] and "head" not in shapes
    assert shapes["wte"].shape == (32784, 2048)
    assert {k: v.shape for k, v in shapes["entry"].items()} == {
        "a": (5, 2048), "c": (5, 2048), "gamma": (5, 256)}


def test_flops_per_token_and_the_costs_against_hand_counts(monkeypatch):
    config = _published()
    monkeypatch.setattr(family.glm, "_ENGINE", None)
    monkeypatch.setattr(family.glm, "_ROUTING", None)
    # with no engine run the held share is the even 8 / 16
    assert family.held_share(config) == 0.5
    layer = (2048 * 1536 + 1024 * 2048            # the four projections
             + 1280 * 128 * 2                     # the conv within a head
             + 2048 * 256 + 2 * 256 * 256 + 256 * 16      # the router
             + 0.5 * 3 * 2048 * 2048)             # half the picks land here
    want = 6 * (6 * layer + 2048 * 32784) + 6 * 3 * 2 * 2 * 4096.5 * 8 * 128
    assert family.flops_per_token(config, JOB) == pytest.approx(
        want, rel=1e-12)
    assert want == pytest.approx(1.156e9, rel=1e-3)
    # the head is 35% of it
    assert 6 * 2048 * 32784 / want == pytest.approx(0.35, abs=0.005)
    assert family.flash_operand(config, JOB) == (2, 8, 8192, 128)
    work, moved = family.flash_call_cost("flash_fwd", config, JOB)
    assert work == 2 * 2 * 2 * 8 * 8192 * 8192 * 128 / 2
    assert moved == (2 * 8 + 2 * 2) * 2 * 8192 * 128 * 2
    back, back_moved = family.flash_call_cost("flash_bwd_dkdv", config, JOB)
    assert back == 2 * work and back_moved == 2 * moved
    rows = 8192
    for kernel, weight_bytes in (("gmm_rows", 2), ("gmm_rows_t", 2),
                                 ("gmm_weights", 4)):
        work, moved = family.gmm_call_cost(kernel, config, JOB, rows)
        assert work == 1.5 * 2 * rows * 2048 * 2048
        assert moved == (2 * (rows * (2048 + 4096) + rows * (2048 + 2048))
                         / 2 + weight_bytes * 8 * 3 * 2048 * 2048 / 2)
    # the mixing: 1,536 channels a token each way, the conv's products
    tokens = 2 * 8192
    work, moved = family.cca_mix_cost("forward", config, JOB)
    assert work == 2 * tokens * 10 * 2 * 128 * 128
    assert moved == 2 * tokens * 1536 * 2
    assert family.cca_mix_cost("recompute", config, JOB) == (work, moved)
    assert family.cca_mix_cost("backward", config, JOB) == (
        2 * work, 3 * tokens * 1536 * 2)
    assert family.cca_calls_per_step(config) == 6
    for phase in ("forward", "backward"):
        _, bound = flops.roofline_seconds(
            *family.cca_mix_cost(phase, config, JOB), PEAK)
        assert bound == "memory"


# ---------------------------------------------------------------------- #
# the comparison
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def program():
    """The model's own float32 side of the comparison on a batch of two
    rows, as ``program_side`` hands it to ``judge``, once for every case;
    the balancing biases off zero."""
    config = _toy(layers=2)
    model = _model(config)
    spec = family.reference_spec(config)
    params = family.glm.seeded_bias(
        jax.jit(model.init_params)(jax.random.PRNGKey(2)), 5, spec.gamma)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(11), (2, 48), 0, config["vocab_size"]), np.int32)
    scores, picks, read = jax.jit(
        lambda p: model.routing(p, ids, with_inputs=True))(params)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, None, ids, picks=picks)))(params)
    grads = jax.device_get(family.reference_params(grads, spec))
    positions = family.sampled_positions(48, 5)
    out = {"scores": scores, "picks": picks, "read": read,
           "loss": float(loss), "grads": grads,
           "timed_loss": float(loss), "timed_grads": grads,
           "positions": positions,
           "logits": jax.jit(model.logits)(params, ids, picks, positions),
           "weights": jax.device_get(family.reference_params(params, spec))}
    mix_err = float(jax.jit(lambda p: family.mix_error(
        model, p, ids, spec))(params))
    return config, ids, out, mix_err


def _router_error(out, spec):
    """``program_side``'s own check of the router: the program's scores
    against the reference's score function on what each router read, the
    state carried from layer to layer."""
    with jax.default_matmul_precision("highest"):
        own = family.own_router_scores(out["weights"], out["read"], spec)
    return float(family.laguna.rms_error(out["scores"], own))


def _bf16_product(a, b):
    return (a.astype(jnp.bfloat16) @ b.astype(jnp.bfloat16)).astype(
        jnp.float32)


def _bf16_sum(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def test_the_mixing_rounded_once_is_told_from_sums_kept_in_bf16(
        program, monkeypatch):
    """``mix_error`` on the toy: a float32 program reads rounding noise;
    on the compute-dtype copy the op reads ONE bf16 rounding (under the
    chip's limit), and the reference's own mixing with every sum handed
    on in bf16 reads a quarter more and over (at the toy's widths a third
    of the channels are the values, which are exact either way; at the
    published widths a sixth, and the chip's two readings are 1.5e-3 and
    2.4e-3 about a limit of 1.95e-3)."""
    config, ids, out, exact = program
    assert exact < 1e-6
    model, spec = _model(config), family.reference_spec(config)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(2))
    cast = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    once = float(family.mix_error(model, cast, ids, spec))
    assert 5e-4 < once < family.MIX_RTOL
    monkeypatch.setattr(reference, "summed", _bf16_sum)
    # the reference with bf16 sums against the PROGRAM's float32 op: the
    # same distance as from the float32 reference, seen from the other side
    kept = float(family.mix_error(model, params, ids, spec))
    assert kept > 1.25 * once


FAULTS = {
    "sound": (None, set()),
    # the router's four products on operands rounded to bf16: a float32
    # program is told from it by the score function on what the router
    # read and by the router's own gradients
    "bf16 router": (("router_mm", _bf16_product), {
        "router_err_rel", "router_mlp_err_rel", "router_down_err_rel",
        "gamma_err_rel"}),
    # every float32 sum of the mixing and the unit norm handed on in bf16
    "bf16 mixing": (("summed", _bf16_sum), {
        "conv0_err_rel", "conv1_err_rel", "tau_err_rel", "w_q_err_rel"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_passes_the_engine_and_refuses_a_lower_precision(
        program, fault, monkeypatch):
    """The toy model's own float32 loss, gradients, logits, scores and
    picks pass the family's ``judge`` at limits a hundredth of the
    chip's (a float32 program agrees to 1e-5); judged against a reference
    whose router multiplies bf16 operands, or whose mixing hands its sums
    on in bf16, they fail, and ``failed`` names numbers of that part."""
    config, ids, out = program[:3]
    spec = family.reference_spec(config)
    patch, named = FAULTS[fault]
    if patch is not None:
        monkeypatch.setattr(reference, *patch)
    for name in ("GRAD_ERR_RTOL", "GRAD_NORM_RTOL", "LOGITS_RTOL",
                 "SCORE_RTOL"):
        monkeypatch.setattr(family, name, getattr(family, name) / 100)
    monkeypatch.setattr(family, "LEAF_RTOL", {
        k: v / 100 for k, v in family.LEAF_RTOL.items()})
    out = {**out, "router_err_rel": _router_error(out, spec),
           "mix_err_rel": program[3]}
    got = family.judge(config, out, ids, jax.devices()[0])
    print(fault, json.dumps(got))
    assert got["bias_grad_norm"] == 0.0
    if fault == "sound":
        assert got["ok"] and not got["failed"], got
    else:
        assert not got["ok"], got
        assert set(got["failed"]) & named, got


# ---------------------------------------------------------------------- #
# the readers, on a hand-made trace
# ---------------------------------------------------------------------- #
def _trace():
    ops, at = [], 0
    for name, ns in (("gmm_rows", 100_000), ("gmm_rows.3", 100_000),
                     ("flash_fwd", 300_000), ("flash_bwd_dkdv.2", 900_000),
                     ("gmm_rows_t", 150_000), ("gmm_weights", 250_000)):
        ops.append([name, "", at, at + ns])
        at += 2_000_000         # apart: a leaf operation holds no other
    return {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}


def test_the_readers_on_a_hand_made_trace(monkeypatch):
    config = _published()
    run = {"family": family, "config": config, "job": JOB,
           "steps_traced": 1, "peak": PEAK}
    least = sum(flops.roofline_seconds(
        *family.flash_call_cost(k, config, JOB), PEAK)[0]
        for k in family.FLASH_KERNELS)
    share = _reader("cca_flash_roofline_pct").reduce(_trace(), run)
    assert share == pytest.approx(100 * least / 1.2e-3)
    # no engine has run: no counter, so the two routing readers say
    # nothing and do not raise
    monkeypatch.setattr(family.glm, "_ENGINE", None)
    monkeypatch.setattr(family.glm, "_ROUTING", None)
    assert _reader("zaya_held_pick_share_pct").reduce(_trace(), run) is None
    assert _reader("gmm_top1_roofline_pct").reduce(_trace(), run) is None
    # with the counter at the even share: 50, and the grouped product's
    # four calls against 1.5 x 2 rows k n at 8,192 rows
    monkeypatch.setattr(family.glm, "_ROUTING", {"held_pick_share": 0.5})
    assert _reader("zaya_held_pick_share_pct").reduce(_trace(), run) == 50.0
    least = sum(calls * flops.roofline_seconds(
        *family.gmm_call_cost(k, config, JOB, 8192), PEAK)[0]
        for k, calls in (("gmm_rows", 2), ("gmm_rows_t", 1),
                         ("gmm_weights", 1)))
    got = _reader("gmm_top1_roofline_pct").reduce(_trace(), run)
    assert got == pytest.approx(100 * least / 0.6e-3)
    # the readers of scopes and parts, on hand-made maps: the mixing's
    # two parts in every pass against its cost, the router, the table
    from perf import program_trace as pt
    from perf import scope_parts as sp
    times = {"jit_loss_and_grads": {
        ("attn", "mix", "forward"): 2_000_000,
        ("attn", "qk_norm", "forward"): 1_000_000,
        ("attn", "mix", "recompute"): 3_000_000,
        ("attn", "mix", "backward"): 6_000_000,
        ("attn", "core", "forward"): 9_000_000,
        ("router", None, "forward"): 500_000,
        ("router", None, "backward"): 700_000,
        ("experts", None, "forward"): 4_000_000,
        ("embed", None, "backward"): 250_000,
        ("head", None, "forward"): 1_250_000}}
    monkeypatch.setattr(sp, "by_part", lambda trace: times)
    scoped = {name: {(s, ph): ns for (s, _, ph), ns in tags.items()
                     if s != "attn"} for name, tags in times.items()}
    monkeypatch.setattr(pt, "scoped", lambda trace: scoped)
    assert _reader("cca_mix_ms").reduce(_trace(), run) == pytest.approx(12.0)
    least = 6 * sum(flops.roofline_seconds(
        *family.cca_mix_cost(phase, config, JOB), PEAK)[0]
        for phase in ("forward", "recompute", "backward"))
    assert _reader("cca_mix_roofline_pct").reduce(
        _trace(), run) == pytest.approx(100 * least / 12e-3)
    assert _reader("zaya_router_ms").reduce(_trace(), run) == pytest.approx(
        1.2)
    assert _reader("moe_top1_ms").reduce(_trace(), run) == pytest.approx(5.2)
    assert _reader("tied_head_ms").reduce(_trace(), run) == pytest.approx(1.5)
    monkeypatch.undo()
    # a trace with no scope map (a program from before the scopes, as the
    # parent commit's), a family with no such names: nothing, no error

    class Other:
        pass
    for name in READERS:
        assert _reader(name).reduce(
            _trace(), {**run, "family": Other}) is None, name
    for name in ("cca_mix_ms", "cca_mix_roofline_pct", "zaya_router_ms",
                 "moe_top1_ms", "tied_head_ms"):
        assert _reader(name).reduce(_trace(), run) is None, name


# ---------------------------------------------------------------------- #
# the cell's own files through the harness
# ---------------------------------------------------------------------- #
def test_the_cell_runs_through_the_harness_at_a_small_size(
        tmp_path, restore_compile_cache, monkeypatch):  # noqa: F811
    """The cell's own files at the toy's sizes through ``perf/run.py``'s
    entry on the CPU, traced: parity, the loss check, a common reader and
    this cell's eight (those of the device trace find no device plane and
    say nothing; the counter is read)."""
    from perf import run
    # ``build`` leaves its engine, and the readers their one read of its
    # counters, where the GLM-4.7-Flash family looks: put back afterwards
    # for the families that share them in this process
    monkeypatch.setattr(family.glm, "_ENGINE", None)
    monkeypatch.setattr(family.glm, "_ROUTING", None)
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "perf", root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / f"perf/configs/{NAME}.json").write_text(
        json.dumps(_toy(layers=2)))
    traffic = json.loads(
        (ROOT / "perf/traffic/zipf.b2.s8192.json").read_text())
    traffic.update(seq=64, pool_steps=16)
    (root / "perf/traffic/zipf.b2.s8192.json").write_text(json.dumps(traffic))
    cell = json.loads(
        (ROOT / "perf/workloads" / f"{CELL}.json").read_text())
    cell["job"]["ds_config"]["monitor"]["output_path"] = str(
        tmp_path / "monitor")
    cell["loss_check"] = {"steps": [3, 7], "rise": 5.0}
    assert set(READERS) < set(cell["per_layer"])
    # (the readers of a share of a peak need a chip's peaks)
    cell["per_layer"] = ["compiles_in_window", *READERS]
    (root / f"perf/workloads/{CELL}.json").write_text(json.dumps(cell))
    # the harness loads the family by path.  At a width of 64 the engine's
    # bf16 is coarser against the signal than at 2,048 (a toy's own
    # readings: loss 4e-4, leaves to 2e-2), so the toy gets a loss limit
    # ten times the chip's
    loaded = run.load_module

    def load(root_, kind, name):
        module = loaded(root_, kind, name)
        if (kind, name) == ("families", "zaya"):
            module.LOSS_RTOL = 2e-3
        return module

    monkeypatch.setattr(run, "load_module", load)
    traced = run.run_cell(CELL, seed=2147485001, seconds=0.5, trace=True,
                          root=str(root), platform="cpu")
    assert traced["correct"], traced
    assert traced["failed"] == 0 and traced["attempted"] >= run.TRACED_STEPS
    assert traced["metrics"]["compiles_in_window"]["value"] == 0.0
    share = traced["metrics"]["zaya_held_pick_share_pct"]
    assert share["unit"] == "%" and 0.0 < share["value"] < 100.0
    for name in READERS[:7]:
        assert name not in traced["metrics"]
    assert traced["device"]["platform"] == "cpu"
    assert not math.isnan(share["value"])
