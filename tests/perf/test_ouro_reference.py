"""The Ouro family's comparison with its plain reference
(perf/families/ouro.py, ouro_reference.py) on the CPU at a small size:
the engine (bf16 compute, the gate and the exit distribution in float32)
passes, and each fault put into the REFERENCE'S side makes at least one
limit fail, so the comparison can tell the architecture's terms and a
lower precision apart; the configuration file holds the catalog's keys;
the family's counts; the new reader says nothing on a parent."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.families import ouro as family
from perf.families import ouro_reference as reference
from tests.perf.test_manifest import restore_compile_cache  # noqa: F401

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIG = ROOT / "perf/configs/ouro-2.6b.json"


def _config():
    """The cell's configuration at a small size: 2 layers of width 64, 4
    heads of 16, 4 passes, 250 rows."""
    config = json.loads(CONFIG.read_text())
    config.update(hidden_size=64, intermediate_size=96,
                  num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                  rope_theta=100, vocab_size=250, num_hidden_layers=2)
    # at width 64 the published 0.02 leaves every attention nearly
    # uniform, and no fault of the positions or the scores could show
    config["assumed"] = {**config["assumed"], "initializer_range": 0.12}
    return config


JOB = {"gradient_accumulation_steps": 1, "activation_checkpointing": True,
       "batch_per_chip": 2, "seq": 96,
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


def _bf16(x):
    return x.astype(jnp.bfloat16)


def _bf16_gate(h, w, b):
    return jax.nn.sigmoid(_bf16(_bf16(h) @ _bf16(w) + _bf16(b))).astype(
        jnp.float32)


def _bf16_exits(lams):
    return _sound_exits(_bf16(lams)).astype(jnp.float32)


def _no_kl(p):
    return jnp.zeros(p.shape[1:], p.dtype)


def _pre_norm_only(p, x, spec):
    a = x + reference.attention(
        p, reference.rms_norm(x, p["norm1"], spec.eps), spec)
    return a + reference.ffn(p, reference.rms_norm(a, p["norm3"], spec.eps))


def _no_norm_between_passes(params, ids, spec):
    """The final norm on what the head and the gate read alone: pass
    t + 1 starts from the stack's own output."""
    raw = params["embed"][ids]
    losses, lams = [], []
    for t in range(spec.passes):
        for p in params["layers"]:
            raw = reference.block(p, raw, spec)
        h = reference.rms_norm(raw, params["norm"], spec.eps)
        losses.append(reference.token_losses(h[:-1], params["head"],
                                             ids[1:], spec))
        if t < spec.passes - 1:
            lams.append(reference.exit_gate(h[:-1], params["gate_w"],
                                            params["gate_b"]))
    return jnp.stack(losses), reference.exit_distribution(jnp.stack(lams))


def _last_exit_takes_lam(lams):
    """p_T = lam_{T-1} prod (1 - lam_j) once more instead of what is
    left: the masses no longer sum to one."""
    p = _sound_exits(lams)
    return p.at[-1].set(p[-2])


_sound_exits = reference.exit_distribution


def _spec_with(**fields):
    sound = family.reference_spec

    def faulty(config):
        return sound(config)._replace(**fields)
    return faulty


FAULTS = {
    "sound": None,
    "no KL term": (reference, "kl_to_uniform", _no_kl),
    "beta 0.2": (family, "reference_spec", _spec_with(beta=0.2)),
    "three passes": (family, "reference_spec", _spec_with(passes=3)),
    "no norm between passes": (reference, "row_terms",
                               _no_norm_between_passes),
    "pre-norm only": (reference, "block", _pre_norm_only),
    "the last exit is no remainder": (reference, "exit_distribution",
                                      _last_exit_takes_lam),
    "theta 10000": (family, "reference_spec", _spec_with(theta=1e4)),
    "a bf16 gate": (reference, "exit_gate", _bf16_gate),
    "a bf16 exit distribution": (reference, "exit_distribution",
                                 _bf16_exits),
    "fp8 products": (reference, "mm", _fp8),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_engine_parity_and_the_faults_that_must_each_fail(
        fault, program, monkeypatch):
    config, ids, out = program
    # the loss limits are means over tokens and set at the cell's 4,095:
    # over the toy's 190 a mean scatters sqrt(4095 / 190) = 4.6 times as
    # much
    for name in ("LOSS_RTOL", "EXIT_LOSS_RTOL", "KL_ATOL", "MASS_ATOL"):
        monkeypatch.setattr(family, name, 4.6 * getattr(family, name))
    if FAULTS[fault] is not None:
        monkeypatch.setattr(*FAULTS[fault])
    got = family.judge(config, out, ids, jax.devices()[0])
    print(fault, json.dumps(got))
    if fault == "sound":
        assert got["ok"], got
    else:
        assert not got["ok"] and got["failed"], got


def test_the_rolled_reference_is_the_plain_one():
    """The two scans the chip runs give the Python loops' loss, terms and
    gradients."""
    from deepspeed_tpu.models.ouro import OuroModel
    config = _config()
    model = OuroModel(family.model_config(
        config, {"activation_checkpointing": False}))
    weights = family.reference_params(family.seeded_gate(
        model.init_params(jax.random.PRNGKey(2)), 2, 0.12))
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 48), 0, 250)
    spec = family.reference_spec(config)._replace(row_block=16)
    assert not spec.rolled
    config["num_hidden_layers"] = 8
    assert family.reference_spec(config).rolled
    (loss, terms), grads = reference.loss_and_grads(weights, ids, spec)
    (r_loss, r_terms), r_grads = reference.loss_and_grads(
        weights, ids, spec._replace(rolled=True))
    # and on the layers' arrays stacked, as the chip's parity hands them
    stacked = {**weights, "layers": jax.tree.map(
        lambda *leaves: jnp.stack(leaves), *weights["layers"])}
    (s_loss, _), s_grads = reference.loss_and_grads(
        stacked, ids, spec._replace(rolled=True))
    assert float(s_loss) == float(r_loss)
    np.testing.assert_allclose(
        s_grads["layers"]["Wq"],
        jnp.stack([g["Wq"] for g in r_grads["layers"]]), rtol=1e-5,
        atol=1e-9)
    assert float(r_loss) == pytest.approx(float(loss), rel=1e-6)
    for name in terms:
        np.testing.assert_allclose(r_terms[name], terms[name], rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(r_grads),
                            jax.tree.leaves(grads)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-6 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_judged_engines_gate_is_seeded_and_its_exits_differ_by_token(
        program):
    config, _, out = program
    gate = family.gate_of(out["weights"])
    assert np.abs(gate["w"]).max() > 0 and float(gate["b"]) != 0.0
    masses = [out["counters"][f"exit_mass_{t}"] for t in (1, 2, 3, 4)]
    assert max(abs(m - e) for m, e in zip(
        masses, (0.5, 0.25, 0.125, 0.125))) > 1e-3
    assert sum(masses) == pytest.approx(1.0, abs=1e-4)


def test_the_configuration_file_holds_the_catalog_row():
    config = json.loads(CONFIG.read_text())
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "Ouro-2.6B")
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 8}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == row["config"][key]
    assert config["kept"]["published_layers"] == list(range(8))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert bench["configs"][-1] == entry
    assert bench["workloads"][-1]["name"] == "ouro-2.6b.s4k"
    assert bench["per_layer"][-1]["name"] == "exit_head_ms"


def test_the_kept_parameters_are_what_the_file_says():
    from deepspeed_tpu.models.ouro import OuroModel
    config = json.loads(CONFIG.read_text())
    model = OuroModel(family.model_config(
        config, {"activation_checkpointing": False}))
    assert family.layer_matrices(config) == 51_380_224
    assert model.num_params() == 612_438_017
    assert "612,438,017" in config["kept"]["parameters"]
    assert model.config.total_ut_steps == 4
    assert model.config.exit_kl_weight == 0.1


def test_counts_use_a_layer_four_times_and_the_head_four_times():
    config = json.loads(CONFIG.read_text())
    job = {"batch_per_chip": 1, "seq": 4096}
    per_token = family.flops_per_token(config, job)
    layers = 6 * 4 * 8 * 51_380_224
    head = 6 * 4 * 2048 * 49152
    attention = 32 * 3 * 2 * 2 * 2048.5 * 2048
    assert per_token == layers + head + attention + 6 * 3 * 2048
    # ISSUE 45's reckoning: 9.87 + 1.61 + 2.42 = 13.9 GFLOP a token
    assert 9.85e9 < layers < 9.88e9 and 2.41e9 < head < 2.42e9
    assert 1.60e9 < attention < 1.62e9 and 13.8e9 < per_token < 14.0e9
    # one use of every parameter would be under a third of it
    once = 6 * (8 * 51_380_224 + 2048 * 49152)
    assert per_token > 3 * once
    work, moved = family.flash_call_cost("flash_fwd", config, job)
    assert work == 2 * 2 * 16 * 128 * 4096 * 4096 / 2
    assert moved == 4 * 16 * 4096 * 128 * 2
    work, moved = family.flash_call_cost("flash_bwd_dkdv", config, job)
    assert work == 4 * 2 * 16 * 128 * 4096 * 4096 / 2
    assert moved == 7 * 16 * 4096 * 128 * 2
    assert family.vocab_rows(config) == 49152


def test_the_new_reader_says_nothing_where_the_program_has_nothing():
    """On a parent without the region, the new reader returns None and
    does not raise."""
    from perf import run

    class Bare:
        FLASH_KERNELS = family.FLASH_KERNELS

    info = {"family": Bare, "steps_traced": 5, "config": {}, "job": {},
            "peak": {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}}
    reader = run.load_module(str(ROOT), "layer_metrics", "exit_head_ms")
    assert reader.MOVES == "step_ms_p50"
    assert reader.reduce(None, info) is None


def test_the_cell_runs_through_the_harness_at_a_small_size(
        tmp_path, restore_compile_cache):
    """The cell's own files at the toy's sizes through ``perf/run.py``'s
    entry on the CPU, traced: parity, the loss check, a common reader
    and ``exit_head_ms``."""
    import shutil
    from perf import run
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "perf", root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "perf/configs/ouro-2.6b.json").write_text(json.dumps(_config()))
    traffic = json.loads(
        (ROOT / "perf/traffic/zipf.b1.s4096.json").read_text())
    traffic.update(seq=96, pool_steps=16)
    (root / "perf/traffic/zipf.b1.s4096.json").write_text(json.dumps(traffic))
    cell = json.loads((ROOT / "perf/workloads/ouro-2.6b.s4k.json").read_text())
    assert cell["job"]["gradient_accumulation_steps"] == 1
    assert cell["job"]["activation_checkpointing"] is True
    assert cell["job"]["parity"] == {"layers": 8, "rows_per_chip": 1}
    cell["job"]["ds_config"]["monitor"]["output_path"] = str(
        tmp_path / "monitor")
    cell["loss_check"] = {"steps": [3, 7], "rise": 4.0}
    cell["per_layer"] = ["compiles_in_window", "exit_head_ms"]
    (root / "perf/workloads/ouro-2.6b.s4k.json").write_text(json.dumps(cell))
    traced = run.run_cell("ouro-2.6b.s4k", seed=2147485001, seconds=0.5,
                          trace=True, root=str(root), platform="cpu")
    assert traced["failed"] == 0 and traced["attempted"] >= run.TRACED_STEPS
    assert traced["metrics"]["compiles_in_window"]["value"] == 0.0
    # the CPU's trace has no device plane to join the region to: the
    # reader ran, found nothing and said nothing
    assert "exit_head_ms" not in traced["metrics"]
    assert traced["device"]["platform"] == "cpu"
