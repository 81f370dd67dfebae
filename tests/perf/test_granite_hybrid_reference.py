"""The Granite 4.0-H family's benchmark files: the configuration file
against the catalog row, the parameter table, what the step and the
scan's kernels are counted to require (the count of the forward kernel
against the ``dot_general``s of the op's XLA twin at the cell's shapes),
the rolled reference against the plain one, the family's comparison
refusing a low-precision scan, and the four readers on a hand-made
trace.  Every entry of ``BENCHMARK.json`` is looked up by name."""

import importlib
import json
import math
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.models.granite_hybrid import GraniteHybridModel
from deepspeed_tpu.ops import ssd_scan as ssd
from perf.families import granite_hybrid as family
from perf.families import granite_hybrid_reference as reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIG = ROOT / "perf/configs/granite-4.0-h-micro.json"
NAME, CELL = "granite-4.0-h-micro", "granite-4.0-h-micro.s4k"
READERS = ("mamba2_ms", "mamba2_around_ms", "ssd_ms", "ssd_roofline_pct")
JOB = {"batch_per_chip": 1, "seq": 4096}


def _published():
    return json.loads(CONFIG.read_text())


def _toy():
    config = _published()
    config.update(
        hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
        vocab_size=256, num_hidden_layers=3,
        layer_types=["mamba", "attention", "mamba"])
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
               if json.loads(line)["name"] == NAME)
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 10, "vocab_size": 12544}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == row["config"][key]
    assert config["kept"]["published_layers"] == list(range(10))
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    # the kept layers are one whole period of the published pattern
    kinds = config["layer_types"]
    assert kinds[:10] == kinds[10:20] == kinds[20:30] == kinds[30:40]
    assert kinds[:10].count("attention") == 1 and kinds[5] == "attention"
    for key in ("time_step_limit", "initialisation", "initializer_range",
                "optimizer", "sequence", "conv", "gated_norm"):
        assert key in config["assumed"], key
    assert "eight v5e chips" in config["deployment"]


def test_the_manifest_names_the_configuration_the_cell_and_four_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = _published()
    entry = _by_name(bench["configs"], NAME)
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "perf/configs/granite-4.0-h-micro.json"
    cell = _by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "zipf.b1.s4096", 1)
    body = json.loads(
        (ROOT / "perf/workloads" / f"{CELL}.json").read_text())
    assert body["why"] == cell["why"] and len(cell["why"]) <= 200
    assert body["job"]["parity"] == {"layers": 10, "rows_per_chip": 1}
    for name in READERS:
        metric = _by_name(bench["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "step_ms_p50"
        assert name in body["per_layer"]
        reader = _reader(name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["source"])


def test_the_kept_parameters_are_what_the_file_says():
    config = _published()
    per_kind = family.layer_parameters(config)
    assert per_kind == {"mamba": 76_182_976, "attention": 60_821_504}
    assert 9 * per_kind["mamba"] + per_kind["attention"] == 746_468_288
    assert family.parameters(config) == 772_160_448
    model = GraniteHybridModel(family.model_config(
        config, {"activation_checkpointing": False}))
    assert model.num_params() == 772_160_448
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    mixer = shapes["runs"][0]["mixer"]
    assert sum(math.prod(a.shape[1:]) for a in jax.tree.leaves(mixer)) \
        == 25_847_232
    assert mixer["in_w"].shape[1:] == (2048, 4096 + 4352)
    assert mixer["dt_w"].shape[1:] == (2048, 64)
    assert mixer["conv_w"].shape[1:] == (4352, 4)
    attention = shapes["runs"][1]["mixer"]
    assert sum(math.prod(a.shape[1:]) for a in jax.tree.leaves(attention)) \
        == 10_485_760
    assert "772,160,448" in config["kept"]["parameters"]
    assert [a.shape[0] for a in (shapes["runs"][0]["ln1"],
                                 shapes["runs"][1]["ln1"],
                                 shapes["runs"][2]["ln1"])] == [5, 1, 4]


def test_flops_per_token():
    config = _published()
    assert family.scan_flops_per_token(config) == (
        2 * 256 * 128 + 2 * 256 * 4096 + 4 * 128 * 4096) == 4_259_840
    want = (6 * (746_468_288 + 2048)            # outside the table
            + 6 * 2048 * 12544                  # the head over 12,544 rows
            + 3 * 2 * 2 * 2048.5 * 2048         # one causal layer at half
            + 9 * 3 * 4_259_840)                # nine scans, three passes
    assert family.flops_per_token(config, JOB) == want
    assert want == pytest.approx(4.80e9, rel=2e-3)
    assert family.flash_operand(config, JOB) == (1, 32, 4096, 64)
    work, moved = family.flash_call_cost("flash_fwd", config, JOB)
    assert work == 2 * 2 * 32 * 4096 * 4096 * 64 / 2
    assert moved == (2 * 32 + 2 * 8) * 4096 * 64 * 2


# ---------------------------------------------------------------------- #
# the scan kernels' count against the op's XLA twin
# ---------------------------------------------------------------------- #
def _dot_flops(jaxpr):
    """2 x (output elements) x (contracted extent) of every
    ``dot_general``, each as often as its enclosing scans run it."""
    total = 0
    for ctx in iter_eqns(jaxpr):
        if ctx.eqn.primitive.name != "dot_general":
            continue
        (contract, _), _ = ctx.eqn.params["dimension_numbers"]
        lhs = ctx.eqn.invars[0].aval.shape
        depth = math.prod(lhs[d] for d in contract)
        total += ctx.mult * 2 * ctx.eqn.outvars[0].aval.size * depth
    return total


def test_the_kernels_count_is_the_twins_products():
    """At the cell's shapes, by shapes alone: ``ssd_call_cost`` of the
    forward kernel EQUALS the products of the XLA twin's forward (C B^T,
    the masked scores on the values, the chunk's state, the state's share
    of the output), and the backward's is no more than the twin's
    backward performs (two transposes a forward product; the twin runs
    one forward product again for da, which is not counted)."""
    config = _published()
    batch, seq, heads, dim, states, chunk = 1, 4096, 64, 64, 128, 256
    assert chunk == config["mamba_chunk_size"]
    n = seq // chunk
    f32 = jnp.float32
    shapes = (jax.ShapeDtypeStruct((n, chunk, heads, dim), f32),
              jax.ShapeDtypeStruct((n, chunk, heads), f32),
              jax.ShapeDtypeStruct((n, chunk, heads), f32),
              jax.ShapeDtypeStruct((n, chunk, states), f32),
              jax.ShapeDtypeStruct((n, chunk, states), f32))
    forward = _dot_flops(jax.make_jaxpr(ssd._xla_fwd)(*shapes))
    work, moved = family.ssd_call_cost("ssd_fwd", config, JOB)
    assert work == forward == 4_259_840 * seq
    entries = jax.ShapeDtypeStruct((n, heads, dim, states), f32)
    backward = _dot_flops(jax.make_jaxpr(ssd._xla_bwd)(
        *shapes, entries, shapes[0]))
    back_work, back_moved = family.ssd_call_cost("ssd_bwd", config, JOB)
    assert back_work == 2 * work <= backward
    assert backward - back_work == 2 * chunk * states * seq + (
        2 * states * heads * dim * seq)      # C B^T and G C again
    # bytes: x and y, dt, B and C, the sixteen entry states
    assert moved == (2 * seq * 4096 * 2 + seq * 64 * 4 + 2 * seq * 128 * 2
                     + 33_554_432)
    assert back_moved > moved
    assert family.ssd_call_cost("ssd_other", config, JOB) == (0, 0)
    # at the published peaks the HBM bound is the larger for both: 127 us
    # of traffic against 89 us of products, forward
    assert moved / 819e9 > work / 197e12
    assert back_moved / 819e9 > back_work / 197e12


# ---------------------------------------------------------------------- #
# the reference, rolled and plain; the comparison
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def toy():
    config = _toy()
    model = GraniteHybridModel(family.model_config(
        config, {"activation_checkpointing": False}))
    model.config.bf16 = False
    params = model.init_params(jax.random.PRNGKey(2))
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (1, 48), 0, 256), np.int32)
    spec = family.reference_spec(config)._replace(row_block=16, pos_block=8)
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(p, None, ids))(params)
    program = {"loss": float(loss),
               "grads": jax.device_get(family.reference_params(grads, spec)),
               "weights": jax.device_get(
                   family.reference_params(params, spec))}
    return config, ids, spec, program


def test_the_rolled_reference_is_the_plain_one(toy):
    config, ids, spec, program = toy
    assert not spec.rolled
    assert family.reference_spec(_published()).rolled
    assert family.reference_spec(_published()).kinds == (
        "mamba", "attention", "mamba")
    loss, grads = reference.loss_and_grads(program["weights"], ids, spec)
    r_loss, r_grads = reference.loss_and_grads(
        program["weights"], ids, spec._replace(rolled=True))
    assert float(r_loss) == pytest.approx(float(loss), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(r_grads),
                            jax.tree.leaves(grads)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-6 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


def _fp8(state):
    return state.astype(jnp.float8_e4m3fn).astype(jnp.float32)


_RECURRENCE = reference.recurrence


def _half_skip(x, dt, a, b_mat, c_mat, d, spec):
    return _RECURRENCE(x, dt, a, b_mat, c_mat, 0.5 * d, spec)


FAULTS = {
    "sound": None,
    # the recurrence carrying its state in fp8 between positions
    "fp8 state": (reference, "carried", _fp8),
    # a wrong term: half the D skip
    "half skip": (reference, "recurrence", _half_skip),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_refuses_a_low_precision_scan(toy, fault, monkeypatch):
    """The toy model's own float32 loss and gradients pass the family's
    ``judge`` at the chip's limits; judged against a reference whose scan
    carries an fp8 state, or has half the D term, they fail, and ``failed``
    names numbers of the scan's own leaves."""
    config, ids, _, program = toy
    if FAULTS[fault] is not None:
        monkeypatch.setattr(*FAULTS[fault])
    got = family.judge(config, program, ids, jax.devices()[0])
    print(fault, json.dumps(got))
    if fault == "sound":
        assert got["ok"] and not got["failed"], got
    else:
        assert not got["ok"], got
        assert set(got["failed"]) & {
            "a_log_err_rel", "dt_bias_err_rel", "d_skip_err_rel",
            "conv_err_rel", "gate_norm_err_rel"}, got


# ---------------------------------------------------------------------- #
# the readers, on a hand-made trace
# ---------------------------------------------------------------------- #
def _trace():
    """One chip, one 'step': two forward calls and one backward call of
    the scan, a flash call and a fusion, 1 ms apart."""
    ops, at = [], 0
    for name, ns in (("ssd_fwd", 400_000), ("fusion.1", 100_000),
                     ("ssd_fwd.2", 400_000), ("flash_fwd", 300_000),
                     ("ssd_bwd", 1_200_000)):
        ops.append([name, "", at, at + ns])
        at += 1_000_000
    return {"devices": {"0": {"ops": ops, "modules": []}}, "host": []}


def test_the_kernel_readers_read_by_prefix_and_cost_by_name():
    config = _published()
    run = {"family": family, "config": config, "job": JOB,
           "steps_traced": 1,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    calls = _reader("ssd_ms").calls(_trace(), run)
    assert calls == {"ssd_fwd": (800_000, 2), "ssd_bwd": (1_200_000, 1)}
    assert _reader("ssd_ms").reduce(_trace(), run) == pytest.approx(2.0)
    from perf import flops
    least = {k: flops.roofline_seconds(
        *family.ssd_call_cost(k, config, JOB), run["peak"])
        for k in ("ssd_fwd", "ssd_bwd")}
    assert {bound for _, bound in least.values()} == {"memory"}
    share = _reader("ssd_roofline_pct").reduce(_trace(), run)
    assert share == pytest.approx(
        100 * (2 * least["ssd_fwd"][0] + least["ssd_bwd"][0]) / 2e-3)
    assert 0 < share < 100
    # a family with no such kernels, a trace with none: nothing, no error
    class Other:
        pass
    for name in ("ssd_ms", "ssd_roofline_pct", "mamba2_around_ms"):
        assert _reader(name).reduce(_trace(), {**run, "family": Other}) \
            is None
    empty = {"devices": {"0": {"ops": [["fusion.1", "", 0, 10]],
                               "modules": []}}, "host": []}
    assert _reader("ssd_ms").reduce(empty, run) is None
    assert _reader("ssd_roofline_pct").reduce(empty, run) is None
