"""The Nemotron-H family's benchmark files: the configuration file
against the catalog row, the parameter table, what the step and its
kernels are counted to require against hand counts (the scan's against
the ``dot_general``s of the op's XLA twin at the cell's shapes and eight
groups), the family's comparison passing the engine and refusing a
lower precision (an fp8 carried state, bf16 router scores), the five
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

from deepspeed_tpu.models.nemotron_h import NemotronHModel
from deepspeed_tpu.ops import ssd_scan as ssd
from perf.families import nemotron_h as family
from perf.families import nemotron_h_reference as reference
from tests.perf.test_granite_hybrid_reference import _dot_flops
from tests.perf.test_manifest import restore_compile_cache  # noqa: F401

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME, CELL = "nemotron-3-nano-30b-a3b", "nemotron-3-nano-30b-a3b.s8k"
CONFIG = ROOT / f"perf/configs/{NAME}.json"
READERS = ("ssd_grouped_ms", "ssd_grouped_roofline_pct", "moe_relu2_ms",
           "gmm_uneven_roofline_pct", "held_pick_share_pct")
JOB = {"batch_per_chip": 2, "seq": 8192}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _published():
    return json.loads(CONFIG.read_text())


def _toy():
    """The cell's configuration at a small size: 8 Mamba heads of 8 in 4
    groups, 16 experts of which 4 are held from the fourth on, top-3."""
    config = _published()
    config.update(
        hidden_size=64, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, mamba_num_heads=8, mamba_head_dim=8,
        ssm_state_size=16, n_groups=4, chunk_size=16, n_routed_experts=4,
        num_experts_per_tok=3, intermediate_size=32,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
        vocab_size=256)
    config["published"] = {**config["published"], "n_routed_experts": 16}
    config["kept"] = {**config["kept"], "experts_first": 4}
    config["assumed"] = {**config["assumed"], "initializer_range": 0.3}
    return config


def _by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def _reader(name):
    return importlib.import_module(f"perf.layer_metrics.{name}")


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
               if json.loads(line)["name"]
               == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
               "n_routed_experts": 8, "vocab_size": 16384}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == row["config"][key]
    pattern = row["config"]["hybrid_override_pattern"]
    assert len(pattern) == 52 and pattern.startswith("MEMEM*EME")
    assert config["kept"]["published_layers"] == list(range(9))
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["n_routed_experts"] * 16 == row["config"][
        "n_routed_experts"]
    for key in ("initializer_range", "initialisation", "positions", "router",
                "bias_update_rate", "expand", "mixer", "optimizer"):
        assert key in config["assumed"], key
    assert "arXiv:2504.03624" in config["assumed"]["positions"]
    assert "sixteen TPU v5e chips" in config["deployment"]
    assert "666,963,456" in config["kept"]["parameters"]


def test_the_manifest_names_the_configuration_the_cell_and_five_metrics():
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
    for said in ("1 of 16", "8 of 128", "6.25%", "768", "1/16"):
        assert said in cell["why"], said
    assert body["job"]["parity"] == {"layers": 9, "rows_per_chip": 2}
    # glm47-flash.s8k's job, key for key
    glm = json.loads(
        (ROOT / "perf/workloads/glm47-flash.s8k.json").read_text())
    assert {k: v for k, v in body["job"].items() if k != "parity"} == {
        k: v for k, v in glm["job"].items() if k != "parity"}
    assert len(body["per_layer"]) == 14
    for name in READERS:
        metric = _by_name(bench["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "step_ms_p50"
        assert name in body["per_layer"]
        reader = _reader(name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["source"])
    assert len(bench["workloads"]) == 14
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_kept_parameters_are_what_the_file_says():
    config = _published()
    per_kind = family.layer_parameters(config)
    assert per_kind == {"M": 38_744_896, "*": 23_399_040, "E": 100_125_440}
    assert per_kind["E"] - 8 * 9_977_856 == 20_302_592
    assert family.parameters(config) == 666_963_456
    model = NemotronHModel(family.model_config(
        config, {"activation_checkpointing": False}))
    assert model.num_params() == 666_963_456
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    mixer = shapes["layers_00"]["mixer"]
    assert mixer["in_w"].shape[1:] == (2688, 4096 + 6144)
    assert mixer["dt_w"].shape[1:] == (2688, 64)
    assert mixer["conv_w"].shape[1:] == (6144, 4)
    assert mixer["out_w"].shape[1:] == (4096, 2688)
    experts = shapes["layers_01"]["moe"]
    # no leaf is stored padded: the published 1,856 and 3,712
    assert experts["experts"]["w1"].shape[1:] == (8, 2688, 1856)
    assert experts["experts"]["w2"].shape[1:] == (8, 1856, 2688)
    assert experts["shared"]["w1"].shape[1:] == (2688, 3712)
    assert experts["router"].shape[1:] == (2688, 128)
    assert experts["bias"].shape[1:] == (128,)
    assert shapes["layers_05"]["attn"]["qkv_w"].shape[1:] == (
        2688, (32 + 2 * 2) * 128)
    assert shapes["head"].shape == (2688, 16384)
    assert sorted(k for k in shapes if k.startswith("layers_")) == [
        f"layers_{i:02d}" for i in range(9)]
    # the published model: 23 M, 23 E with all 128 experts, 6 attention
    whole = (23 * per_kind["M"] + 6 * per_kind["*"]
             + 23 * (20_302_592 + 128 * 9_977_856)
             + 2 * 131072 * 2688 + 2688)
    assert whole == pytest.approx(31.6e9, rel=5e-3)


def test_flops_per_token_and_the_call_costs_against_hand_counts():
    config = _published()
    assert family.scan_flops_per_token(config) == (
        2 * 128 * 128 * 8 + 2 * 128 * 4096 + 4 * 128 * 4096) == 3_407_872
    # with no engine run the held share is the even 8 / 128
    assert family.held_share(config) == 0.0625
    active = (4 * 38_744_896 + 23_399_040
              + 4 * (2688 * 128 + 2 * 2688 * 3712
                     + 6 * 0.0625 * 2 * 2688 * 1856)
              + 2688 * 16384)
    want = (6 * active + 3 * 2 * 2 * 4096.5 * 32 * 128
            + 4 * 3 * 3_407_872)
    assert family.flops_per_token(config, JOB) == pytest.approx(want,
                                                                rel=1e-12)
    assert want == pytest.approx(2.16e9, rel=1e-2)
    assert family.flash_operand(config, JOB) == (2, 32, 8192, 128)
    work, moved = family.flash_call_cost("flash_fwd", config, JOB)
    assert work == 2 * 2 * 2 * 32 * 8192 * 8192 * 128 / 2
    assert moved == (2 * 32 + 2 * 2) * 2 * 8192 * 128 * 2
    back, back_moved = family.flash_call_cost("flash_bwd_dkdv", config, JOB)
    assert back == 2 * work and back_moved == 2 * moved
    # a grouped product at 1,856: 2 rows k n whatever the blocks
    rows = 6144
    for kernel, weight_bytes in (("gmm_rows", 2), ("gmm_rows_t", 2),
                                 ("gmm_weights", 4)):
        work, moved = family.gmm_call_cost(kernel, config, JOB, rows)
        assert work == 2 * rows * 2688 * 1856
        assert moved == (2 * rows * (2688 + 1856)
                         + weight_bytes * 8 * 2688 * 1856)


def test_the_scan_kernels_count_is_the_twins_products_at_eight_groups():
    """At the cell's shapes, by shapes alone: ``ssd_call_cost`` of the
    forward kernel EQUALS the products of the XLA twin's forward mapped
    over the eight groups (G products C B^T a chunk), and the backward's
    is no more than the twin's backward performs."""
    config = _published()
    seq, heads, dim, states, chunk, groups = 8192, 64, 64, 128, 128, 8
    assert (chunk, groups) == (config["chunk_size"], config["n_groups"])
    n = seq // chunk
    f32 = jnp.float32
    shapes = (jax.ShapeDtypeStruct((n, chunk, heads, dim), f32),
              jax.ShapeDtypeStruct((n, chunk, heads), f32),
              jax.ShapeDtypeStruct((n, chunk, heads), f32),
              jax.ShapeDtypeStruct((n, chunk, groups, states), f32),
              jax.ShapeDtypeStruct((n, chunk, groups, states), f32))
    forward = _dot_flops(jax.make_jaxpr(ssd._xla_fwd_groups)(*shapes))
    work, moved = family.ssd_call_cost("ssd_fwd", config, JOB)
    assert work == 2 * forward == 3_407_872 * 2 * seq     # two rows
    entries = jax.ShapeDtypeStruct((n, heads, dim, states), f32)
    backward = _dot_flops(jax.make_jaxpr(ssd._xla_bwd_groups)(
        *shapes, entries, shapes[0]))
    back_work, back_moved = family.ssd_call_cost("ssd_bwd", config, JOB)
    assert back_work == 2 * work <= 2 * backward
    tokens = 2 * seq
    assert moved == (2 * tokens * 4096 * 2 + tokens * 64 * 4
                     + 2 * tokens * 8 * 128 * 2
                     + (tokens // 128) * 4096 * 128 * 4)
    assert back_moved > moved
    assert family.ssd_call_cost("ssd_other", config, JOB) == (0, 0)
    from perf import flops
    for kernel in ("ssd_fwd", "ssd_bwd"):
        _, bound = flops.roofline_seconds(
            *family.ssd_call_cost(kernel, config, JOB), PEAK)
        assert bound == "memory"


# ---------------------------------------------------------------------- #
# the comparison
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def program():
    """The model's own float32 side of the comparison on a batch of two
    rows, as ``program_side`` hands it to ``judge``, once for every case;
    the selection biases off zero."""
    config = _toy()
    model = NemotronHModel(family.model_config(
        config, {"activation_checkpointing": False}))
    model.config.bf16 = False
    spec = family.reference_spec(config)._replace(pos_block=8)
    params = family.glm.seeded_bias(
        model.init_params(jax.random.PRNGKey(2)), 5, spec.gamma)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(11), (2, 48), 0, config["vocab_size"]), np.int32)
    scores, picks, read = model.routing(params, ids, with_inputs=True)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, None, ids, picks=picks)))(params)
    grads = jax.device_get(family.reference_params(grads, spec))
    out = {"scores": scores, "picks": picks, "read": read,
           "loss": float(loss), "grads": grads,
           "timed_loss": float(loss), "timed_grads": grads,
           "weights": jax.device_get(family.reference_params(params, spec))}
    return config, ids, out


def _router_error(out):
    """``program_side``'s own check of the router: the program's scores
    against the reference's score function on what each router read."""
    routers = [p["Wr"] for p in out["weights"]["layers"] if "Wr" in p]
    with jax.default_matmul_precision("highest"):
        own = jnp.stack([reference.router_scores(u, w)
                         for u, w in zip(out["read"], routers)])
    return float(family.laguna.rms_error(out["scores"], own))


def _fp8(state):
    return state.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _bf16_scores(u, w_router):
    return jax.nn.sigmoid((u.astype(jnp.bfloat16) @ w_router.astype(
        jnp.bfloat16)).astype(jnp.float32))


FAULTS = {
    "sound": (None, set()),
    # the recurrence carrying its state in fp8 between positions
    "fp8 state": (("carried", _fp8), {
        "a_log_err_rel", "dt_bias_err_rel", "d_skip_err_rel",
        "conv_err_rel", "gate_norm_err_rel", "grad_err_rel"}),
    # the router's product on operands rounded to bf16: a float32 program
    # is told from it by the score function on what the router read (on
    # the chip the engine's routers read bf16 already and this control
    # coincides with the engine: perf/families/nemotron_h.py)
    "bf16 router": (("router_scores", _bf16_scores), {"router_err_rel"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_passes_the_engine_and_refuses_a_lower_precision(
        program, fault, monkeypatch):
    """The toy model's own float32 loss, gradients, scores and picks pass
    the family's ``judge`` at the chip's limits; judged against a
    reference whose scan carries an fp8 state, or whose router scores come
    from a bf16 product, they fail, and ``failed`` names numbers of that
    part."""
    config, ids, out = program
    patch, named = FAULTS[fault]
    if patch is not None:
        monkeypatch.setattr(reference, *patch)
    out = {**out, "router_err_rel": _router_error(out)}
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
    for name, ns in (("ssd_fwd", 400_000), ("gmm_rows", 100_000),
                     ("gmm_rows.3", 100_000), ("flash_fwd", 300_000),
                     ("ssd_bwd.2", 1_200_000), ("gmm_rows_t", 150_000),
                     ("gmm_weights", 250_000)):
        ops.append([name, "", at, at + ns])
        at += 2_000_000         # apart: a leaf operation holds no other
    return {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}


def test_the_readers_on_a_hand_made_trace(monkeypatch):
    config = _published()
    run = {"family": family, "config": config, "job": JOB,
           "steps_traced": 1, "peak": PEAK}
    share = _reader("ssd_grouped_roofline_pct").reduce(_trace(), run)
    from perf import flops
    least = sum(flops.roofline_seconds(
        *family.ssd_call_cost(k, config, JOB), PEAK)[0]
        for k in ("ssd_fwd", "ssd_bwd"))
    assert share == pytest.approx(100 * least / 1.6e-3)
    # no engine has run: no counter, so the two routing readers say
    # nothing and do not raise
    monkeypatch.setattr(family.glm, "_ENGINE", None)
    monkeypatch.setattr(family.glm, "_ROUTING", None)
    assert _reader("held_pick_share_pct").reduce(_trace(), run) is None
    assert _reader("gmm_uneven_roofline_pct").reduce(_trace(), run) is None
    # with the counter at the even share: 6.25, and the grouped product's
    # four calls against 2 rows k n at 6,144 rows
    monkeypatch.setattr(family.glm, "_ROUTING", {"held_pick_share": 0.0625})
    assert _reader("held_pick_share_pct").reduce(_trace(), run) == 6.25
    rows = 2 * 8192 * 6 * 0.0625
    assert rows == 6144
    least = sum(calls * flops.roofline_seconds(
        *family.gmm_call_cost(k, config, JOB, rows), PEAK)[0]
        for k, calls in (("gmm_rows", 2), ("gmm_rows_t", 1),
                         ("gmm_weights", 1)))
    got = _reader("gmm_uneven_roofline_pct").reduce(_trace(), run)
    assert got == pytest.approx(100 * least / 0.6e-3)
    # a trace with no scope map (a program from before the scopes), a
    # family with no such names: nothing, no error
    class Other:
        pass
    for name in READERS:
        assert _reader(name).reduce(_trace(), {**run, "family": Other}) \
            is None or name in ("ssd_grouped_ms", "moe_relu2_ms")
    for name in ("ssd_grouped_ms", "moe_relu2_ms"):
        assert _reader(name).reduce(_trace(), run) is None


# ---------------------------------------------------------------------- #
# the cell's own files through the harness
# ---------------------------------------------------------------------- #
def test_the_cell_runs_through_the_harness_at_a_small_size(
        tmp_path, restore_compile_cache, monkeypatch):  # noqa: F811
    """The cell's own files at the toy's sizes through ``perf/run.py``'s
    entry on the CPU, traced: parity, the loss check, a common reader and
    this cell's five (those of the device trace find no device plane and
    say nothing; the counter is read)."""
    from perf import run
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "perf", root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / f"perf/configs/{NAME}.json").write_text(json.dumps(_toy()))
    traffic = json.loads(
        (ROOT / "perf/traffic/zipf.b2.s8192.json").read_text())
    traffic.update(seq=64, pool_steps=16)
    (root / "perf/traffic/zipf.b2.s8192.json").write_text(json.dumps(traffic))
    cell = json.loads(
        (ROOT / "perf/workloads" / f"{CELL}.json").read_text())
    cell["job"]["ds_config"]["monitor"]["output_path"] = str(
        tmp_path / "monitor")
    cell["loss_check"] = {"steps": [3, 7], "rise": 4.0}
    assert set(READERS) < set(cell["per_layer"])
    # (the readers of a share of a peak need a chip's peaks)
    cell["per_layer"] = ["compiles_in_window", *READERS]
    (root / f"perf/workloads/{CELL}.json").write_text(json.dumps(cell))
    # the harness loads the family by path.  At a width of 64 the
    # engine's bf16 is coarser against the signal than at 2,688 (a toy's
    # own readings: scores 7e-3, leaves to 7e-2), so the toy gets limits
    # twice to four times the chip's where it needs them
    loaded = run.load_module

    def load(root_, kind, name):
        module = loaded(root_, kind, name)
        if (kind, name) == ("families", "nemotron_h"):
            module.SCORE_RTOL, module.UNEXPLAINED_MAX = 1.5e-2, 2e-2
            module.GRAD_ERR_RTOL, module.GRAD_NORM_RTOL = 0.08, 2e-2
            module.LOSS_RTOL, module.PICK_SHARE_MAX = 1e-3, 0.2
            module.LEAF_RTOL = {k: 0.2 for k in module.LEAF_RTOL}
        return module

    monkeypatch.setattr(run, "load_module", load)
    traced = run.run_cell(CELL, seed=2147485001, seconds=0.5, trace=True,
                          root=str(root), platform="cpu")
    assert traced["correct"], traced
    assert traced["failed"] == 0 and traced["attempted"] >= run.TRACED_STEPS
    assert traced["metrics"]["compiles_in_window"]["value"] == 0.0
    share = traced["metrics"]["held_pick_share_pct"]
    assert share["unit"] == "%" and 0.0 < share["value"] < 100.0
    for name in READERS[:4]:
        assert name not in traced["metrics"]
    assert traced["device"]["platform"] == "cpu"
    assert not math.isnan(share["value"])
