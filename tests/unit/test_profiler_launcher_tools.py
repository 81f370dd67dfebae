"""Flops profiler, 1-bit optimizers, launcher, state-dict factory,
env report (reference tests: test_flops_profiler.py:115, test_onebit.py,
test_run.py:108 launcher arg parsing, test_configurable_parallel.py MP
resize)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds


# ---------------------------------------------------------------------- #
# flops profiler
# ---------------------------------------------------------------------- #
def test_flops_count_matmul_exact():
    from deepspeed_tpu.profiling import get_model_profile

    def f(a, b):
        return a @ b

    a = jnp.zeros((64, 128))
    b = jnp.zeros((128, 32))
    flops, macs, _ = get_model_profile(f, (a, b))
    assert macs == 64 * 128 * 32
    assert flops >= 2 * macs


def test_flops_scan_multiplies():
    from deepspeed_tpu.profiling import get_model_profile

    w = jnp.zeros((4, 16, 16))

    def stacked(x):
        def body(c, wi):
            return c @ wi, None
        out, _ = jax.lax.scan(body, x, w)
        return out

    flops, macs, _ = get_model_profile(stacked, (jnp.zeros((8, 16)),))
    assert macs == 4 * 8 * 16 * 16  # scan length multiplies the body


def test_profiler_on_gpt2_matches_analytic():
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.profiling import get_model_profile

    cfg = GPT2Config(vocab_size=256, n_positions=64, hidden_size=64,
                     num_layers=2, num_heads=4, bf16=False, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 32), jnp.int32)
    flops, macs, n_params = get_model_profile(
        lambda p: model.loss(p, None, ids), (params,), params=params)
    assert n_params == cfg.num_params()
    # forward MACs ~ tokens * (2N_layer + head) — sanity band, not exact
    tokens = 2 * 32
    rough = tokens * cfg.num_params(include_embeddings=False)
    assert 0.5 * rough < macs < 6 * rough


def test_module_tree_attention_matches_analytic():
    """Per-module tree (round 5 — the reference's module-hierarchy dump,
    profiler.py:11): the layer/attn scope must carry the analytic
    attention FLOPs (qkv + scores + ctx + out-proj) within the
    elementwise slack, and the printed profile must show the hierarchy."""
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.profiling import FlopsProfiler

    B, S, H, L = 2, 128, 64, 3
    cfg = GPT2Config(vocab_size=512, n_positions=S, hidden_size=H,
                     num_layers=L, num_heads=4, bf16=False,
                     embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((B, S), jnp.int32)

    prof = FlopsProfiler()
    prof.set_params(params)
    prof.start_profile()
    prof.profile_fn(lambda p: model.loss(p, None, ids), params)
    prof.stop_profile()

    tree = prof.module_tree()
    # embed / layer / head all present, layer split into attn + mlp
    for key in ("embed", "layer", "head", "layer/attn", "layer/mlp"):
        assert key in tree and tree[key] > 0, (key, sorted(tree))
    # attention: qkv (6BSH^2) + scores/ctx (4BS^2H) + out-proj (2BSH^2)
    analytic_attn = L * (8 * B * S * H * H + 4 * B * S * S * H)
    assert abs(tree["layer/attn"] - analytic_attn) / analytic_attn < 0.10
    # mlp: 2 matmuls of [S,H]x[H,4H] per layer = 16BSH^2
    analytic_mlp = L * 16 * B * S * H * H
    assert abs(tree["layer/mlp"] - analytic_mlp) / analytic_mlp < 0.10
    # hierarchy: the layer scope contains its children
    assert tree["layer"] >= tree["layer/attn"] + tree["layer/mlp"]

    import tempfile

    with tempfile.NamedTemporaryFile("r", suffix=".txt") as f:
        prof.print_model_profile(detailed=True, top_modules=4,
                                 output_file=f.name)
        out = open(f.name).read()
    assert "per-module tree" in out
    assert "layer/attn" in out and "layer/mlp" in out


def test_engine_flops_profiler_integration(capsys):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=-1)

    def model(params, rng, x, y):
        return jnp.mean((x @ params["w"] - y) ** 2)

    params = {"w": np.zeros((8, 4), np.float32)}
    cfg = {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "flops_profiler": {"enabled": True, "profile_step": 1},
        "steps_per_print": 10 ** 9,
    }
    eng, _, _, _ = ds.initialize(model=model, config=cfg,
                                 model_parameters=params, mesh=mesh)
    x = np.zeros((8, 8), np.float32)
    y = np.zeros((8, 4), np.float32)
    for _ in range(3):
        loss = eng.forward(x, y); eng.backward(loss); eng.step()
    assert getattr(eng, "flops_profiler", None) is not None
    assert eng.flops_profiler.flops > 0
    assert eng.flops_profiler.params == 32


# ---------------------------------------------------------------------- #
# 1-bit optimizers
# ---------------------------------------------------------------------- #
def test_onebit_adam_matches_adam_during_warmup():
    import optax
    from deepspeed_tpu.runtime.comm.onebit import onebit_adam

    params = {"w": jnp.ones((8,)) * 0.5}
    tx1 = onebit_adam(0.1, freeze_step=100)
    tx2 = optax.adam(0.1)
    s1, s2 = tx1.init(params), tx2.init(params)
    p1 = p2 = params
    for i in range(5):
        g = {"w": jnp.sin(jnp.arange(8.0) + i)}
        u1, s1 = tx1.update(g, s1, p1)
        u2, s2 = tx2.update(g, s2, p2)
        p1 = optax.apply_updates(p1, u1)
        p2 = optax.apply_updates(p2, u2)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-5)


def test_onebit_adam_converges_after_freeze():
    import optax
    from deepspeed_tpu.runtime.comm.onebit import onebit_adam

    target = jnp.asarray(np.random.RandomState(0).randn(16), jnp.float32)
    params = {"w": jnp.zeros((16,))}
    tx = onebit_adam(0.05, freeze_step=10)
    state = tx.init(params)

    def loss(p):
        return jnp.mean((p["w"] - target) ** 2)

    for i in range(120):
        g = jax.grad(loss)(params)
        u, state = tx.update(g, state, params)
        params = optax.apply_updates(params, u)
    assert float(loss(params)) < 0.05  # compressed stage still converges
    assert int(state.count) == 120


def test_compressed_allreduce_error_feedback():
    from deepspeed_tpu.parallel import initialize_mesh, reset_mesh_context
    from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce

    reset_mesh_context()
    mesh = initialize_mesh(data=-1)
    w = mesh.data_parallel_world_size
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(w, 64), jnp.float32)  # per-worker rows
    err = jnp.zeros_like(x)
    true_mean = np.asarray(x).mean(axis=0)

    # repeated reduction of the same tensors: error feedback must drive the
    # accumulated average toward the true mean (1-bit Adam's core property,
    # bias ~ O(1/n)); check the error actually SHRINKS with more rounds.
    def avg_err(n):
        acc = np.zeros(64)
        e = err
        for _ in range(n):
            red, e = compressed_allreduce(x, e, mesh_ctx=mesh)
            acc += np.asarray(red)[0]
        return np.abs(acc / n - true_mean).max()

    e8, e64 = avg_err(8), avg_err(64)
    assert e64 < e8 / 2, (e8, e64)
    assert e64 < 0.25, e64
    # a single uncompensated round is much worse than the 64-round average
    single = np.abs(np.asarray(compressed_allreduce(
        x, jnp.zeros_like(x), mesh_ctx=mesh)[0])[0] - true_mean).max()
    assert e64 < single
    reset_mesh_context()


def test_compressed_allreduce_int8_wire():
    """The int8 wire format (shared scale, sign rides as int8 — the
    variant with an actual 4x wire-width win, benchmarks/onebit_cost.py)
    keeps the error-feedback convergence property and stays close to the
    full-width variant."""
    from deepspeed_tpu.parallel import initialize_mesh, reset_mesh_context
    from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce

    reset_mesh_context()
    mesh = initialize_mesh(data=-1)
    w = mesh.data_parallel_world_size
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(w, 64), jnp.float32)
    true_mean = np.asarray(x).mean(axis=0)

    # one jitted region for the 73 calls: un-jitted, every call builds and
    # dispatches a fresh shard_map, op by op, over the eight devices
    sync = jax.jit(lambda a, b: compressed_allreduce(a, b, mesh_ctx=mesh,
                                                     wire="int8"))

    def avg_err(n):
        acc = np.zeros(64)
        e = jnp.zeros_like(x)
        for _ in range(n):
            red, e = sync(x, e)
            acc += np.asarray(red)[0]
        return np.abs(acc / n - true_mean).max()

    e8, e64 = avg_err(8), avg_err(64)
    assert e64 < e8 / 2, (e8, e64)
    assert e64 < 0.3, e64
    # every worker sees the identical reduced tensor (psum symmetry)
    red, _ = sync(x, jnp.zeros_like(x))
    red = np.asarray(red)
    np.testing.assert_array_equal(red[0], red[-1])
    reset_mesh_context()


def test_engine_accepts_onebit_adam():
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=-1)

    def model(params, rng, x, y):
        return jnp.mean((x @ params["w"] - y) ** 2)

    params = {"w": np.random.RandomState(0).randn(8, 4).astype(np.float32)}
    cfg = {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "OneBitAdam",
                      "params": {"lr": 1e-2, "freeze_step": 2}},
        "steps_per_print": 10 ** 9,
    }
    eng, _, _, _ = ds.initialize(model=model, config=cfg,
                                 model_parameters=params, mesh=mesh)
    rs = np.random.RandomState(1)
    x, y = rs.randn(8, 8).astype(np.float32), rs.randn(8, 4).astype(
        np.float32)
    losses = []
    for _ in range(8):
        loss = eng.forward(x, y); eng.backward(loss); eng.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------- #
# launcher
# ---------------------------------------------------------------------- #
def test_hostfile_parse_and_filter(tmp_path):
    from deepspeed_tpu.launcher.runner import (fetch_hostfile,
                                               parse_resource_filter)
    hf = tmp_path / "hostfile"
    hf.write_text("# comment\nworker-0 slots=4\nworker-1 slots=4\n"
                  "worker-2 slots=8\n")
    res = fetch_hostfile(str(hf))
    assert list(res) == ["worker-0", "worker-1", "worker-2"]
    assert res["worker-2"] == 8

    inc = parse_resource_filter(res, include_str="worker-0@worker-2:0,1")
    assert list(inc) == ["worker-0", "worker-2"]
    assert inc["worker-2"] == [0, 1]

    exc = parse_resource_filter(res, exclude_str="worker-1")
    assert list(exc) == ["worker-0", "worker-2"]

    with pytest.raises(ValueError):
        parse_resource_filter(res, include_str="a", exclude_str="b")
    with pytest.raises(ValueError):
        parse_resource_filter(res, include_str="missing-host")


def test_launcher_dry_run_emits_env(tmp_path, capsys):
    from deepspeed_tpu.launcher.runner import main
    hf = tmp_path / "hostfile"
    hf.write_text("nodeA slots=4\nnodeB slots=4\n")
    rc = main(["--hostfile", str(hf), "--master_port", "12345",
               "--dry_run", "train.py", "--foo", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "ssh" in out and "nodeB" in out
    assert "DS_COORDINATOR=nodeA:12345" in out
    assert "DS_NUM_PROCESSES=2" in out
    assert "DS_PROCESS_ID=1" in out
    assert "train.py --foo 1" in out


def test_world_info_roundtrip():
    from deepspeed_tpu.launcher.runner import (decode_world_info,
                                               encode_world_info)
    info = {"a": [0, 1], "b": [0]}
    assert decode_world_info(encode_world_info(info)) == info


def test_env_report_runs():
    from deepspeed_tpu.env_report import get_report_lines
    lines = get_report_lines()
    text = "\n".join(lines)
    assert "cpu_adam" in text and "async_io" in text and "jax" in text


# ---------------------------------------------------------------------- #
# state-dict factory (MP resize)
# ---------------------------------------------------------------------- #
def test_qkv_split_merge_roundtrip():
    from deepspeed_tpu.runtime.state_dict_factory import merge_qkv, split_qkv
    qkv = np.arange(4 * 12, dtype=np.float32).reshape(4, 12)  # H=4, 3H=12
    shards = split_qkv(qkv, mp=2)
    assert shards[0].shape == (4, 6)
    # each shard holds its half of q, k, AND v — not the naive first half
    np.testing.assert_array_equal(shards[0][:, :2], qkv[:, 0:2])   # q half
    np.testing.assert_array_equal(shards[0][:, 2:4], qkv[:, 4:6])  # k half
    np.testing.assert_array_equal(shards[0][:, 4:6], qkv[:, 8:10])  # v half
    np.testing.assert_array_equal(merge_qkv(shards), qkv)


def test_mp_resize_2_to_4(tmp_path):
    """Save at mp=2, reload at mp=4 (reference:
    test_configurable_parallel.py:458)."""
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.runtime.state_dict_factory import (
        MegatronSDLoader, SDLoaderFactory, merge_state_dicts,
        split_state_dict)

    cfg = GPT2Config(vocab_size=64, n_positions=16, hidden_size=32,
                     num_layers=2, num_heads=4, bf16=False)
    model = GPT2Model(cfg)
    params = jax.tree.map(np.asarray,
                          model.init_params(jax.random.PRNGKey(0)))
    specs = model.param_partition_specs()

    # split -> per-rank files -> reload merged at a different degree
    paths = MegatronSDLoader.save_shards(
        params, specs, 2, str(tmp_path / "mp_rank_{:02d}.npz"))
    loader = SDLoaderFactory.get_sd_loader(paths)
    rank0_of_4 = loader.load(4, 0, specs, params)
    full = loader.load(1, 0, specs, params)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    # mp=4 shard has quarter-width qkv columns
    assert rank0_of_4["h"]["attn_qkvw"].shape[-1] == \
        params["h"]["attn_qkvw"].shape[-1] // 4
    # splitting then merging is identity
    again = merge_state_dicts(split_state_dict(params, specs, 4), specs)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_init_distributed_single_process(monkeypatch):
    from deepspeed_tpu.utils import distributed as dist_mod
    monkeypatch.setattr(dist_mod, "_INITIALIZED", False)
    for var in ("DS_COORDINATOR", "MASTER_ADDR", "RANK"):
        monkeypatch.delenv(var, raising=False)
    dist_mod.init_distributed()  # no env: single-process no-op
    assert dist_mod._INITIALIZED


def test_ds_ssh_local_fallback(tmp_path, capsys):
    """ds_ssh (reference: bin/ds_ssh): no hostfile -> run locally; with a
    hostfile it fans out over ssh/pdsh (not exercisable here)."""
    from deepspeed_tpu.launcher.ds_ssh import build_parser, main

    rc = main(["-H", str(tmp_path / "none"), "echo", "hello_ds_ssh"])
    assert rc == 0
    # parser surfaces the hostfile flag and trailing command
    args = build_parser().parse_args(["-H", "hf", "uptime", "-a"])
    assert args.hostfile == "hf" and args.command == ["uptime", "-a"]


# --------------------------------------------------------------------- #
# TPU-pod launcher discovery (round 5 — the multinode_runner.py:35
# family's TPU form, launcher/tpu_discovery.py)
# --------------------------------------------------------------------- #
def test_tpu_metadata_discovery_mocked():
    from deepspeed_tpu.launcher.tpu_discovery import discover_from_metadata

    meta = {
        "worker-network-endpoints":
            "8833c7a:10.164.0.2:8470,9b01d22:10.164.0.3:8470,"
            "77aa001:10.164.0.4:8470,45cc9ef:10.164.0.5:8470",
        "agent-worker-number": "2",
        "accelerator-type": "v5litepod-16",
    }
    pod = discover_from_metadata(fetch=lambda attr: meta[attr])
    assert pod.workers == ["10.164.0.2", "10.164.0.3",
                           "10.164.0.4", "10.164.0.5"]
    assert pod.my_index == 2
    assert pod.accelerator_type == "v5litepod-16"
    assert list(pod.resources().items()) == [
        ("10.164.0.2", 1), ("10.164.0.3", 1),
        ("10.164.0.4", 1), ("10.164.0.5", 1)]


def test_tpu_metadata_discovery_bad_payload():
    import pytest as _pytest

    from deepspeed_tpu.launcher.tpu_discovery import discover_from_metadata

    with _pytest.raises(RuntimeError, match="no worker IPs"):
        discover_from_metadata(fetch=lambda attr: "not-an-endpoint-list")


def test_tpu_metadata_missing_worker_number():
    """Absent agent-worker-number: unknowable on a multi-worker pod
    (None — never a silent worker-0 claim), trivially 0 on one worker."""
    from deepspeed_tpu.launcher.tpu_discovery import discover_from_metadata

    multi = {"worker-network-endpoints": "a:10.0.0.1:1,b:10.0.0.2:1"}
    pod = discover_from_metadata(fetch=lambda a: multi[a])
    assert pod.my_index is None
    single = {"worker-network-endpoints": "a:10.0.0.1:1"}
    pod = discover_from_metadata(fetch=lambda a: single[a])
    assert pod.my_index == 0


def test_tpu_gcloud_discovery_mocked():
    import json as _json
    import subprocess as _sp

    from deepspeed_tpu.launcher.tpu_discovery import discover_from_gcloud

    desc = {
        "acceleratorType": "v4-16",
        "networkEndpoints": [
            # external IP preferred (off-pod launches can't route 10.x);
            # internal is the in-VPC fallback
            {"ipAddress": "10.130.0.9",
             "accessConfig": {"externalIp": "10.130.0.10"}},
            {"ipAddress": "10.130.0.11"},
        ],
    }
    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        return _sp.CompletedProcess(cmd, 0, stdout=_json.dumps(desc),
                                    stderr="")

    pod = discover_from_gcloud("my-pod", zone="us-central2-b",
                               project="proj", run=fake_run)
    assert pod.workers == ["10.130.0.10", "10.130.0.11"]
    assert pod.accelerator_type == "v4-16"
    assert calls[0][:6] == ["gcloud", "compute", "tpus", "tpu-vm",
                            "describe", "my-pod"]
    assert "--zone" in calls[0] and "us-central2-b" in calls[0]


def test_dslaunch_tpu_dry_run(monkeypatch, capsys, tmp_path):
    """dslaunch --tpu <name> end-to-end (dry run): discovery feeds the
    per-host ssh commands, coordinator = worker 0."""
    from deepspeed_tpu.launcher import runner, tpu_discovery

    pod = tpu_discovery.PodInfo(
        workers=["10.0.0.5", "10.0.0.6"], my_index=None,
        accelerator_type="v5litepod-8")
    monkeypatch.setattr(tpu_discovery, "discover",
                        lambda *a, **k: pod)
    script = tmp_path / "train.py"
    script.write_text("pass\n")
    rc = runner.main(["--tpu", "my-pod", "--dry_run", str(script)])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 2
    assert "ssh" in out[0] and "10.0.0.5" in out[0]
    assert "DS_COORDINATOR=10.0.0.5:29500" in out[0]
    assert "DS_NUM_PROCESSES=2" in out[1] and "DS_PROCESS_ID=1" in out[1]
