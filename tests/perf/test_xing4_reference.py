"""The Xing4.0 family's comparison with its plain reference
(perf/families/xing4.py, xing4_reference.py) on the CPU at a small size
with real ratios (query/key heads one and a half times the value heads,
YaRN from a short original length, 4 streams, 20 rounds): the engine
(bf16 compute, float32 router, selection bias and mixes) passes its four
parts, and each fault put into the REFERENCE'S side makes at least one
limit fail, so the comparison can tell the architecture's terms apart;
the configuration file holds the catalog's keys; the family's counts;
the new readers on a trace without their scope."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.families import glm4_moe_lite_reference as base
from perf.families import xing4 as family
from tests.perf.test_manifest import restore_compile_cache  # noqa: F401

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIG = ROOT / "perf/configs/xing4.0-29b-a4b.json"
CELL = ROOT / "perf/workloads/xing4.0-29b-a4b.s4k.json"
NAME = "xing4.0-29b-a4b"


def _config():
    """The cell's configuration at a small size: a query latent narrower
    than the hidden size, nope 2 x rope, v = nope, 16 experts of which 4
    are held from the fourth on, top-4, YaRN by 8 from 64 positions."""
    config = json.loads(CONFIG.read_text())
    config.update(hidden_size=128, intermediate_size=256,
                  num_attention_heads=2, num_key_value_heads=2,
                  q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=64,
                  qk_rope_head_dim=32, v_head_dim=64, rope_theta=100,
                  n_routed_experts=4, moe_intermediate_size=128,
                  vocab_size=256, num_hidden_layers=3)
    config["rope_scaling"] = {**config["rope_scaling"], "factor": 8,
                              "original_max_position_embeddings": 64}
    config["published"] = {**config["published"], "n_routed_experts": 16}
    config["kept"] = {**config["kept"], "experts_first": 4}
    # at width 128 the published 0.02 leaves every attention nearly
    # uniform, and no fault of the positions could show
    # (tests/perf/test_glm4_moe_lite_reference.py)
    config["assumed"] = {**config["assumed"], "initializer_range": 0.12}
    return config


JOB = {"gradient_accumulation_steps": 1, "activation_checkpointing": True,
       "batch_per_chip": 2, "seq": 192,
       "ds_config": {
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
           "bf16": {"enabled": True, "grads_in_compute_dtype": True},
           "zero_optimization": {"stage": 2}}}


@pytest.fixture(scope="module")
def program():
    """The engine's side of the comparison on a batch of two rows, once
    for every case."""
    import deepspeed_tpu as ds
    config = _config()
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(11), (2, JOB["seq"]), 0, config["vocab_size"]),
        np.int32)
    out = family.program_side(config, JOB, jax.devices()[:1], 5, ids)
    ds.reset_mesh_context()
    return config, ids, out


def _fp8(a, b):
    def cast(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return cast(a) @ cast(b)


def _spec_with(**fields):
    sound = family.reference_spec

    def faulty(config):
        return sound(config)._replace(**fields)
    return faulty


FAULTS = {
    "sound": None,
    "one Sinkhorn round": _spec_with(rounds=1),
    "no clamp": _spec_with(clamp=None),
    "H_post without its 2": _spec_with(post_scale=1.0),
    "the dynamic term dropped": _spec_with(dynamic=False),
    "softmax scale without YaRN's factor": _spec_with(softmax_factor=1.0),
    "plain for YaRN frequencies": _spec_with(yarn=None),
    "bf16 mixing matrices": _spec_with(mix_dtype="bfloat16"),
    "no 2 on the routed experts": _spec_with(scale=1.0),
}


@pytest.mark.parametrize("fault", [*FAULTS, "fp8 products"])
def test_engine_parity_and_the_faults_that_must_each_fail(
        fault, program, monkeypatch):
    config, ids, out = program
    # the limits that are means over tokens are set at the cell's 4,096:
    # over the toy's 384 a mean scatters sqrt(4096 / 384) = 3.3 times as
    # much, and one token of its two gates is 1.3e-3 of the picks
    monkeypatch.setattr(family, "LOSS_RTOL", 3.3 * family.LOSS_RTOL)
    monkeypatch.setattr(family, "UNEXPLAINED_MAX", 2e-2)
    if fault == "fp8 products":
        monkeypatch.setattr(base, "mm", _fp8)
    elif FAULTS[fault] is not None:
        monkeypatch.setattr(family, "reference_spec", FAULTS[fault])
    got = family.judge(config, out, ids, jax.devices()[0])
    print(fault, json.dumps(got))
    if fault == "sound":
        assert got["ok"], got
        # the program the window times chose the picks it was then handed
        assert got["timed_loss"] == pytest.approx(got["loss"], rel=1e-4)
        assert got["bias_grad_norm"] == 0.0
        assert got["hc_res_col_err_max"] < 1e-5
    else:
        assert not got["ok"] and got["failed"], got


def test_the_judged_engine_mixes_by_the_biases_it_was_given(program):
    """The seeded ``b`` are whole eighths away from where they start,
    three logits a sublayer lie beyond the clamp, and the mixes the
    program reports are no identity."""
    _, _, out = program
    blocks = out["weights"]["layers"]
    assert len(blocks) == 3
    for p in blocks:
        for name in ("hc_attn", "hc_ffn"):
            b = np.asarray(p[name]["b"])
            np.testing.assert_allclose(b * 8, np.round(b * 8), atol=1e-5)
            assert (b[8:] > 30).sum() == family.MIX_BEYOND
            assert np.abs(b[8:]).min() <= 3.0
    pre, post, res = out["mixes"]
    assert pre.shape == (3, 2, 384, 4) and res.shape == (3, 2, 384, 4, 4)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-4)  # columns
    assert np.abs(res - np.eye(4)).max() > 0.5
    assert 0.0 < pre.min() and pre.max() < 1.0 and post.max() < 2.0


def test_the_configuration_file_holds_the_catalog_row():
    config = json.loads(CONFIG.read_text())
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "Xing4.0-29B-A4B")
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
               "n_routed_experts": 8, "vocab_size": 16384,
               "num_nextn_predict_layers": 0}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == row["config"][key]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == str(CONFIG.relative_to(ROOT))


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = json.loads(CELL.read_text())
    entry = next(w for w in bench["workloads"]
                 if w["name"] == NAME + ".s4k")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "zipf.b1.s4096", 1)
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    keye = json.loads(
        (ROOT / "perf/workloads/keye-vl2-30b-a3b.s16k.json").read_text())
    assert cell["job"]["ds_config"] == keye["job"]["ds_config"]
    assert cell["job"]["parity"] == {"layers": 5, "rows_per_chip": 1}
    own = {"mhc_ms", "mhc_roofline_pct", "mla_uneven_flash_roofline_pct",
           "mla_uneven_proj_ms"}
    everywhere = {m["name"] for m in bench["per_layer"]
                  if "workloads" not in m and m["moves"] != "setup_s"}
    assert set(cell["per_layer"]) == own | everywhere
    for metric in bench["per_layer"]:
        if metric["name"] in own:
            assert metric["workloads"] == [NAME + ".s4k"]
            assert metric["moves"] == "step_ms_p50"
            assert (ROOT / "perf/layer_metrics"
                    / (metric["name"] + ".py")).exists()


def test_the_kept_parameters_are_what_the_file_says():
    from deepspeed_tpu.models.xing4 import Xing4Model
    config = json.loads(CONFIG.read_text())
    model = Xing4Model(family.model_config(
        config, {"activation_checkpointing": False}))
    attention = family.attention_matrices(config)
    assert attention == 28_409_856
    hid = 3584
    expert = 3 * hid * 1024
    streams = 2 * (4 * hid * 24 + 24 + 3)
    norms = 2 * hid + 768 + 512
    sparse = (attention + 8 * expert + expert + hid * 64 + 64 + norms
              + streams)
    dense = attention + 3 * hid * 9216 + norms + streams
    assert model.num_params() == (
        dense + 4 * sparse + 2 * 16384 * hid + hid) == 759_346_446
    assert "759,346,446" in config["kept"]["parameters"]
    # the published model, from the same terms
    whole = sparse + 56 * expert
    module = whole + 2 * hid * hid + 3 * hid
    assert (2 * dense + 38 * whole + module + 2 * 131072 * hid
            + hid) == 30_276_195_174
    assert "30,276,195,174" in config["published"]["parameters"]


def test_counts_follow_the_mathematics(monkeypatch):
    config = json.loads(CONFIG.read_text())
    job = {"batch_per_chip": 1, "seq": 4096}
    monkeypatch.setattr(family.glm, "_ENGINE", None)
    monkeypatch.setattr(family.glm, "_ROUTING", None)
    assert family.held_share(config) == 1 / 8
    per_token = family.flops_per_token(config, job)
    # scores at 192 and values at 128 over half the square, 32 heads,
    # five calls, forward and twice backward
    attention = 5 * 3 * 2 * 2048.5 * 32 * (192 + 128)
    assert per_token - attention == pytest.approx(6 * (
        5 * (28_409_856 + 2 * 4 * 3584 * 24) + 3 * 3584 * 9216
        + 4 * (3584 * 64 + 3 * 3584 * 1024 * (1 + 4 / 8))
        + 3584 * 16384))
    # 11.7 TFLOP a step of 4,096 tokens (ISSUE 58 reckoned about 15)
    assert 2.8e9 < per_token < 2.9e9
    work, moved = family.flash_call_cost("flash_fwd", config, job)
    assert work == 2 * 32 * 4096 * 4096 / 2 * (192 + 128)
    assert moved == 32 * 4096 * 2 * (2 * 192 + 2 * 128)
    work, moved = family.flash_call_cost("flash_bwd_dkdv", config, job)
    assert work == 2 * 32 * 4096 * 4096 / 2 * (3 * 192 + 2 * 128)
    assert moved == 32 * 4096 * 2 * (4 * 192 + 4 * 128)
    # padding the values to 192 would be charged: the count is smaller
    # than a one-size count at 192
    from perf import flops
    assert work < flops.flash_call_flops("flash_bwd_dkdv", 1, 32, 4096,
                                         192) * 5 / 4
    _, forward = family.mhc_call_cost("forward", config, job)
    _, again = family.mhc_call_cost("recompute", config, job)
    _, backward = family.mhc_call_cost("backward", config, job)
    assert forward == again == 14 * 3584 * 2 * 4096
    assert backward == 27 * 3584 * 2 * 4096
    assert family.mhc_calls_per_step(config) == 10


def test_new_readers_say_nothing_where_the_program_has_nothing(monkeypatch):
    """On a parent without the scope, the parts or the counts, each new
    reader returns None and does not raise; with a scope map that names
    no ``hc`` the two hyper-connection readers say nothing either."""
    from perf import program_trace, run, scope_parts

    class Bare:
        FLASH_KERNELS = family.FLASH_KERNELS

    info = {"family": Bare, "steps_traced": 5, "config": {}, "job": {},
            "peak": {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}}
    monkeypatch.setattr(program_trace, "scoped", lambda trace: None)
    monkeypatch.setattr(scope_parts, "by_part", lambda trace: None)
    names = ("mhc_ms", "mhc_roofline_pct", "mla_uneven_flash_roofline_pct",
             "mla_uneven_proj_ms")
    empty = {"devices": {}}      # a trace without a device plane
    for name in names:
        reader = run.load_module(str(ROOT), "layer_metrics", name)
        assert reader.MOVES == "step_ms_p50"
        assert reader.reduce(empty, info) is None
    # a program that names other scopes and no ``hc``
    monkeypatch.setattr(program_trace, "scoped", lambda trace: {
        "jit_loss_and_grads": {("attn", "forward"): 5_000_000}})
    config = json.loads(CONFIG.read_text())
    info.update(family=family, config=config,
                job={"batch_per_chip": 1, "seq": 4096})
    for name in names[:2]:
        reader = run.load_module(str(ROOT), "layer_metrics", name)
        assert reader.reduce(empty, info) is None
    # and one that does: time a step, and a share of the roofline
    monkeypatch.setattr(program_trace, "scoped", lambda trace: {
        "jit_loss_and_grads": {("hc", "forward"): 50_000_000,
                               ("hc", "backward"): 100_000_000}})
    info["peak"] = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    took = run.load_module(str(ROOT), "layer_metrics", "mhc_ms").reduce(
        empty, info)
    assert took == pytest.approx(30.0)
    share = run.load_module(
        str(ROOT), "layer_metrics", "mhc_roofline_pct").reduce(empty, info)
    least = 5 * 10 * (14 + 27) * 3584 * 2 * 4096 / 819e9
    assert share == pytest.approx(100 * least / 0.15)


def test_the_cell_runs_through_the_harness_at_a_small_size(
        tmp_path, restore_compile_cache, monkeypatch):
    """The cell's own files at the toy's sizes through ``perf/run.py``'s
    entry on the CPU, traced: parity, the loss check, the common readers
    and this cell's four, which find no device plane and say nothing."""
    import shutil
    from perf import run
    monkeypatch.setattr(family, "LOSS_RTOL", 3.3 * family.LOSS_RTOL)
    monkeypatch.setattr(family, "UNEXPLAINED_MAX", 2e-2)
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "perf", root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / f"perf/configs/{NAME}.json").write_text(json.dumps(_config()))
    traffic = json.loads(
        (ROOT / "perf/traffic/zipf.b1.s4096.json").read_text())
    traffic.update(seq=96, pool_steps=16)
    (root / "perf/traffic/zipf.b1.s4096.json").write_text(json.dumps(traffic))
    cell = json.loads(CELL.read_text())
    assert cell["job"]["gradient_accumulation_steps"] == 1
    assert cell["job"]["activation_checkpointing"] is True
    cell["job"]["ds_config"]["monitor"]["output_path"] = str(
        tmp_path / "monitor")
    cell["loss_check"] = {"steps": [3, 7], "rise": 4.0}
    own = ["mhc_ms", "mhc_roofline_pct", "mla_uneven_flash_roofline_pct",
           "mla_uneven_proj_ms"]
    assert set(own) < set(cell["per_layer"])
    # (the readers of a share of a peak need a chip's peaks)
    cell["per_layer"] = ["compiles_in_window", *own]
    (root / f"perf/workloads/{NAME}.s4k.json").write_text(json.dumps(cell))
    # the harness loads the family by path: the limits are set there too
    loaded = run.load_module

    def load(root_, kind, name):
        module = loaded(root_, kind, name)
        if (kind, name) == ("families", "xing4"):
            module.LOSS_RTOL = family.LOSS_RTOL
            module.UNEXPLAINED_MAX = family.UNEXPLAINED_MAX
        return module

    monkeypatch.setattr(run, "load_module", load)
    traced = run.run_cell(NAME + ".s4k", seed=2147485001, seconds=0.5,
                          trace=True, root=str(root), platform="cpu")
    assert traced["correct"], traced
    assert traced["failed"] == 0 and traced["attempted"] >= run.TRACED_STEPS
    assert traced["metrics"]["compiles_in_window"]["value"] == 0.0
    for name in own:
        assert name not in traced["metrics"]
    assert traced["device"]["platform"] == "cpu"
