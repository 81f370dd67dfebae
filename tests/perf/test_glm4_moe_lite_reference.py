"""The GLM-4 MoE Lite family's comparison with its plain reference
(perf/families/glm4_moe_lite.py, glm4_moe_lite_reference.py) on the CPU
at a small size with real ratios: the engine (bf16 compute, float32
router and selection bias) passes its three parts, and each fault put
into the REFERENCE'S side makes at least one limit fail, so the
comparison can tell the architecture's terms apart; the expanded
attention equals the absorbed form; the configuration file holds the
catalog's keys; the family's counts."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.families import glm4_moe_lite as family
from perf.families import glm4_moe_lite_reference as reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIG = ROOT / "perf/configs/glm47-flash.json"


def _config():
    """The cell's configuration at a small size: a query latent narrower
    than the hidden size, nope 3 x rope, v = nope + rope, 16 experts of
    which 4 are held from the fourth on, top-4."""
    config = json.loads(CONFIG.read_text())
    config.update(hidden_size=128, intermediate_size=256,
                  num_attention_heads=2, num_key_value_heads=2,
                  q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=96,
                  qk_rope_head_dim=32, v_head_dim=128, rope_theta=100,
                  n_routed_experts=4, moe_intermediate_size=128,
                  vocab_size=256, num_hidden_layers=3)
    config["published"] = {**config["published"], "n_routed_experts": 16}
    config["kept"] = {**config["kept"], "experts_first": 4}
    # at width 128 the published 0.02 leaves every attention nearly
    # uniform, and no fault of the positions could show (32 rotated
    # dimensions of 128: at 0.05 the three rotary faults move nothing by
    # more than twice the sound engine's own error)
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


def _softmax_scores(u, w_router):
    return jax.nn.softmax(reference.mm(u, w_router), axis=-1)


def _unbiased_choice(scores, bias, picked):
    return jax.lax.top_k(scores, picked)[1]


def _a_key_a_head(k_rope, heads):
    """Not one rotated key for all heads: each head's scaled by its
    index, as if the heads did not share it."""
    scale = 1.0 + jnp.arange(heads, dtype=k_rope.dtype)[None, :, None]
    return k_rope * scale


def _normed_rotary_key(p, u, spec):
    cq, ckv, k_rope = _sound_latents(p, u, spec)
    return cq, ckv, reference.rms_norm(k_rope, 1.0, spec.eps)


def _this_token(ids_row):
    return ids_row


_sound_latents = reference.latents


def _spec_with(**fields):
    sound = family.reference_spec

    def faulty(config):
        return sound(config)._replace(**fields)
    return faulty


FAULTS = {
    "sound": None,
    "no 1.8": (family, "reference_spec", _spec_with(scale=1.0)),
    "softmax for sigmoid": (reference, "router_scores", _softmax_scores),
    "lambda 0.1": (family, "reference_spec", _spec_with(mtp_weight=0.1)),
    "the rotated key normed": (reference, "latents", _normed_rotary_key),
    "a rotated key a head": (reference, "shared_key", _a_key_a_head),
    "theta 10000": (family, "reference_spec", _spec_with(theta=1e4)),
    "the module reads this token": (reference, "mtp_inputs", _this_token),
    "fp8 products": (reference, "mm", _fp8),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_engine_parity_and_the_faults_that_must_each_fail(
        fault, program, monkeypatch):
    config, ids, out = program
    # two limits are means over tokens and set at the cell's 16,384: over
    # the toy's 384 a mean scatters sqrt(16384 / 384) = 6.5 times as
    # much, and one token of its three gates is 9e-4 of the picks
    monkeypatch.setattr(family, "LOSS_RTOL", 6.5 * family.LOSS_RTOL)
    monkeypatch.setattr(family, "UNEXPLAINED_MAX", 2e-2)
    if FAULTS[fault] is not None:
        monkeypatch.setattr(*FAULTS[fault])
    got = family.judge(config, out, ids, jax.devices()[0])
    print(fault, json.dumps(got))
    if fault == "sound":
        assert got["ok"], got
        # the program the window times chose the picks it was then handed
        assert got["timed_loss"] == pytest.approx(got["loss"], rel=1e-4)
        assert got["bias_grad_norm"] == 0.0
    else:
        assert not got["ok"] and got["failed"], got


def test_the_judged_engine_chooses_by_the_bias_it_was_given(program):
    """The seeded biases are whole multiples of gamma, not all zero, and
    the program's picks are the top 4 of ITS scores + bias, not of the
    scores alone, for some token."""
    config, _, out = program
    bias = np.asarray(family.gate_biases(out["weights"]))
    assert bias.shape == (3, 16) and np.abs(bias).max() > 0
    np.testing.assert_allclose(bias / 0.001, np.round(bias / 0.001),
                               atol=1e-4)
    scores, picks = out["scores"], out["picks"]
    biased = np.sort(np.argsort(-(scores + bias[:, None]), -1)[..., :4], -1)
    plain = np.sort(np.argsort(-scores, -1)[..., :4], -1)
    assert (np.sort(picks, -1) == biased).all()
    assert (biased != plain).any()


def test_the_expanded_attention_equals_the_absorbed_form():
    spec = reference.Spec(sparse=(True,), heads=4, kv_rank=32, nope=24,
                          rope=8, theta=100.0)
    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    hid, dim = 64, spec.nope + spec.rope
    p = {"Wqa": jax.random.normal(keys[0], (hid, 48)) / 8,
         "q_norm": 1 + 0.1 * jax.random.normal(keys[1], (48,)),
         "Wqb": jax.random.normal(keys[2], (48, spec.heads * dim)) / 7,
         "Wkva": jax.random.normal(keys[3], (hid, spec.kv_rank + spec.rope))
         / 8,
         "kv_norm": 1 + 0.1 * jax.random.normal(keys[4], (spec.kv_rank,)),
         "Wkvb": jax.random.normal(
             keys[5], (spec.kv_rank, spec.heads * (spec.nope + dim))) / 6,
         "Wo": jax.random.normal(keys[6], (spec.heads * dim, hid)) / 11}
    u = jax.random.normal(keys[7], (40, hid))
    with jax.default_matmul_precision("highest"):
        expanded = reference.attention(p, u, spec)
        absorbed = reference.absorbed_attention(p, u, spec)
    assert float(jnp.max(jnp.abs(expanded))) > 0.1
    np.testing.assert_allclose(expanded, absorbed, rtol=2e-4, atol=2e-5)


def test_the_configuration_file_holds_the_catalog_row():
    config = json.loads(CONFIG.read_text())
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "GLM-4.7-Flash")
    assert config["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 5, "n_routed_experts": 8,
               "vocab_size": 19456}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in row["config"].items():
        assert config[key] == reduced.get(key, value), key
    for key in reduced:
        assert config["published"][key] == row["config"][key]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "glm47-flash")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]


def test_the_kept_parameters_are_what_the_file_says():
    from deepspeed_tpu.models.glm4_moe_lite import Glm4MoeLiteModel
    config = json.loads(CONFIG.read_text())
    model = Glm4MoeLiteModel(family.model_config(
        config, {"activation_checkpointing": False}))
    attention = family.attention_matrices(config)
    assert attention == 21_757_952
    expert = 3 * 2048 * 1536
    sparse = attention + 8 * expert + expert + 2048 * 64 + 64 + (
        2 * 2048 + 768 + 512)
    dense = attention + 3 * 2048 * 10240 + 2 * 2048 + 768 + 512
    module = sparse + 2 * 2048 * 2048 + 3 * 2048
    assert model.num_params() == (dense + 4 * sparse + module
                                  + 2 * 19456 * 2048 + 2048) == 706_912_064
    assert "706.9M" in config["kept"]["parameters"]


def test_counts_follow_the_rows_the_routing_sends_here(monkeypatch):
    config = json.loads(CONFIG.read_text())
    job = {"batch_per_chip": 2, "seq": 8192}
    # no engine has run: what a router that favours nobody would send
    monkeypatch.setattr(family, "_ENGINE", None)
    monkeypatch.setattr(family, "_ROUTING", None)
    assert family.routing_counters() is None
    assert family.held_share(config) == 1 / 8
    per_token = family.flops_per_token(config, job)
    # ISSUE 42's reckoning: about 2.1 GFLOP in matrices, 1.5 to 1.8 in
    # the six causal attention calls
    attention = 6 * 3 * 2 * 2 * 4096.5 * 20 * 256
    assert 1.4e9 < attention < 1.9e9
    assert 1.9e9 < per_token - attention < 2.3e9
    # the run's counter says half of that landed here: the routed
    # experts' term halves (five gates), and nothing else moves
    routed = 6 * 3 * 4 * 2048 * 1536 * 5 / 8
    monkeypatch.setattr(family, "routing_counters",
                        lambda: {"held_pick_share": 1 / 16})
    assert per_token - family.flops_per_token(config, job) == pytest.approx(
        routed / 2)
    monkeypatch.undo()
    work, moved = family.flash_call_cost("flash_fwd", config, job)
    assert work == 2 * 2 * 2 * 20 * 256 * 8192 * 8192 / 2
    assert moved == 4 * 2 * 20 * 8192 * 256 * 2
    work, _ = family.flash_call_cost("flash_bwd_dkdv", config, job)
    assert work == 4 * 2 * 2 * 20 * 256 * 8192 * 8192 / 2
    ops, _ = family.gmm_call_cost("gmm_rows", config, job, 8192)
    assert ops == 1.5 * 2 * 8192 * 2048 * 1536


def test_new_readers_say_nothing_where_the_program_has_nothing():
    """On a parent without the regions, the parts or the counter, each
    new reader returns None and does not raise."""
    from perf import run

    class Bare:
        FLASH_KERNELS = family.FLASH_KERNELS

    info = {"family": Bare, "steps_traced": 5, "config": {}, "job": {},
            "peak": {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}}
    for name in ("mla_proj_ms", "mtp_ms", "expert_load_max_over_mean"):
        reader = run.load_module(str(ROOT), "layer_metrics", name)
        assert reader.MOVES == "step_ms_p50"
        assert reader.reduce(None, info) is None
