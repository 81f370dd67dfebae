"""ZeRO-Infinity carried NVMe prefetch (ISSUE 8): the streaming engine's
double-buffered swap-in schedule must be compute-invariant (prefetch on/off
parity), measurable (overlap stats), honest under faults (a torn swap file
fails loudly, never a silent half-stale read), and degrade gracefully to
the Python sync path when no native aio lib builds.

Reference shapes: stage3.py:546 backward re-fetch + the PR 7 carried
double-buffer discipline one tier down (docs/zero_infinity.md)."""

import os

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.resilience.chaos import (InjectedCrash,
                                                    crash_after_bytes)
from deepspeed_tpu.runtime.swap_tensor import aio_handle as aio_handle_mod
from deepspeed_tpu.runtime.swap_tensor import (NVMeOffloadOptimizer,
                                               PartitionedParamSwapper)
from deepspeed_tpu.runtime.zero.infinity import (ZeroInfinityEngine,
                                                 load_sweep_ceiling)

SEQ = 32
BATCH = 4


def _model(bf16=False):
    cfg = GPT2Config(vocab_size=128, n_positions=SEQ, hidden_size=32,
                     num_layers=4, num_heads=4, bf16=bf16, embd_dropout=0.0,
                     attn_dropout=0.0, hidden_dropout=0.0)
    return GPT2Model(cfg)


def _data():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(5),
                                         (BATCH, SEQ), 0, 128), np.int32)


def _build(tmp_path, prefetch_depth, bf16=False, steps=0, **zo_extra):
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(data=1, devices=jax.devices()[:1])
    model = _model(bf16=bf16)
    zo = {
        "stage": 3,
        "offload_param": {"device": "nvme", "nvme_path": str(tmp_path),
                          "buffer_count": 2,
                          "prefetch_depth": prefetch_depth},
        "offload_optimizer": {"device": "nvme",
                              "nvme_path": str(tmp_path)},
    }
    zo.update(zo_extra)
    conf = {
        "train_micro_batch_size_per_gpu": BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": zo,
        "steps_per_print": 10 ** 9,
    }
    if bf16:
        conf["bf16"] = {"enabled": True}
    engine, _, _, _ = ds.initialize(
        model=model, config=conf,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, rng=jax.random.PRNGKey(9))
    assert isinstance(engine, ZeroInfinityEngine)
    ids = _data()
    losses = []
    for _ in range(steps):
        loss = engine.forward(ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return engine, losses


@pytest.mark.parametrize("bf16", [False, True], ids=["fp32", "bf16"])
def test_prefetch_parity(tmp_path, bf16):
    """The carried swap-in schedule moves bytes earlier, never changes the
    arithmetic: prefetch-on and prefetch-off trajectories must match
    exactly, while only the on-mode hides its swap traffic."""
    _, losses_off = _build(tmp_path / "off", prefetch_depth=0, bf16=bf16,
                           steps=3)
    engine_on, losses_on = _build(tmp_path / "on", prefetch_depth=2,
                                  bf16=bf16, steps=3)
    np.testing.assert_allclose(losses_on, losses_off, rtol=0, atol=0)
    stats = engine_on.swap_stats()
    assert stats["prefetch_depth"] == 2
    assert stats["read_bytes"] > 0
    # the double buffer hides most swap bytes even on a toy model
    assert stats["overlap_fraction"] > 0.5
    ds.reset_mesh_context()


def test_prefetch_off_reports_serialized(tmp_path):
    """With prefetch disabled every read is paid at use: the stats must
    say so (near-zero overlap), not flatter the schedule."""
    engine, _ = _build(tmp_path, prefetch_depth=0, steps=2)
    stats = engine.swap_stats()
    assert stats["prefetch_depth"] == 0
    assert stats["overlap_fraction"] < 0.2
    assert stats["read_exposed_s"] > 0
    ds.reset_mesh_context()


def test_swap_stats_shape_and_ceiling(tmp_path):
    """The honesty report carries achieved bytes/s and, when the sweep
    artifact exists, the ceiling it is compared against."""
    engine, _ = _build(tmp_path, prefetch_depth=2, steps=2)
    stats = engine.swap_stats()
    for key in ("aio_backend", "read_bytes", "read_gbps", "overlap_bytes",
                "overlap_fraction", "serialized_swap_ins", "write_bytes",
                "step_wall_s", "read_vs_ceiling", "optimizer_sweep"):
        assert key in stats, key
    ceiling = load_sweep_ceiling(engine.aio_backend)
    if ceiling is not None:  # benchmarks/aio_sweep_results.txt in repo
        assert stats["sweep_read_gbps"] == ceiling["read_gbps"]
        assert stats["read_vs_ceiling"] is not None
    assert stats["optimizer_sweep"]["leaves"] > 0
    ds.reset_mesh_context()


def test_crash_mid_swap_write_fails_loudly(tmp_path, monkeypatch):
    """A crash mid write-back (resilience's crash-after-N-bytes wrapper,
    on the Python aio path where open() is interceptable) must propagate
    out of step() — and the torn group file must then REFUSE to be
    consumed: the next forward raises instead of training on a half-old
    half-new layer."""
    monkeypatch.setattr(aio_handle_mod, "get_aio_lib", lambda: None)
    engine, _ = _build(tmp_path, prefetch_depth=2, steps=1)
    assert not engine._swapper.write_handle.using_native
    ids = _data()
    loss = engine.forward(ids)
    engine.backward(loss)
    # budget: enough for the optimizer tier's leaf write-backs to begin
    # param-group write-back, then die mid-group-file
    with pytest.raises(InjectedCrash):
        with crash_after_bytes(10_000, path_prefix=str(
                tmp_path / "zero_stage_3" / "params")):
            engine.step()
    # the interrupted write left a truncated group file somewhere — the
    # engine must fail loudly on it, not consume a torn read
    with pytest.raises(OSError):
        for _ in range(2):  # sweep all groups (first may be resident)
            loss = engine.forward(ids)
            engine.backward(loss)
    ds.reset_mesh_context()


def test_truncated_group_file_fails_loudly_native(tmp_path):
    """Same torn-read refusal on the NATIVE engines: a group file
    truncated under the engine (torn write-back, disk eviction) turns
    into -EIO at the next swap-in, raised as OSError."""
    engine, _ = _build(tmp_path, prefetch_depth=2, steps=1)
    assert engine._swapper.write_handle.using_native
    params_dir = tmp_path / "zero_stage_3" / "params"
    victim = params_dir / "param_group_layer2.bin"
    with open(victim, "r+b") as f:
        f.truncate(max(1, os.path.getsize(victim) // 2))
    ids = _data()
    with pytest.raises(OSError):
        loss = engine.forward(ids)
        engine.backward(loss)
    ds.reset_mesh_context()


def test_python_sync_fallback_parity(tmp_path, monkeypatch):
    """No native lib: the whole streaming stack (param swapper, optimizer
    tier, prefetch handles) must still train, on synchronous Python I/O,
    with the same trajectory as the native engines."""
    _, losses_native = _build(tmp_path / "native", prefetch_depth=2,
                              steps=2)
    monkeypatch.setattr(aio_handle_mod, "get_aio_lib", lambda: None)
    engine, losses_py = _build(tmp_path / "py", prefetch_depth=2, steps=2)
    assert not engine._swapper.write_handle.using_native
    assert engine.aio_backend == "python"
    np.testing.assert_allclose(losses_py, losses_native, rtol=0, atol=0)
    ds.reset_mesh_context()


def test_write_during_pending_prefetch_is_coherent(tmp_path):
    """ISSUE 8 bugfix: write() to a group whose prefetch read is still in
    flight must not race the file — the read completes first, then the
    window slot AND the file get the new bytes."""
    rs = np.random.RandomState(0)
    groups = {"a": {"w": rs.randn(64, 64).astype(np.float32)},
              "b": {"w": rs.randn(64, 64).astype(np.float32)}}
    sw = PartitionedParamSwapper(str(tmp_path), groups, buffer_count=2)
    sw.write("a", groups["a"])
    sw.write("b", groups["b"])
    sw.prefetch("a")                      # read in flight
    new_a = {"w": rs.randn(64, 64).astype(np.float32)}
    sw.write("a", new_a, async_op=True)   # overlaps the pending read
    sw.flush_writes()
    got = sw.get("a")
    np.testing.assert_array_equal(got["w"], new_a["w"])
    sw.release("a")
    got2 = sw.get("a")                    # re-read from the file
    np.testing.assert_array_equal(got2["w"], new_a["w"])


def test_optimizer_pipeline_depth_parity(tmp_path):
    """Depth-3 rotating buffer sets must produce the exact depth-2
    results — deeper pipelining moves reads earlier, never changes the
    Adam math."""
    rs = np.random.RandomState(0)
    params = {f"w{i}": rs.randn(32, 16).astype(np.float32)
              for i in range(6)}
    import jax.numpy as jnp
    outs = {}
    for depth in (2, 3):
        opt = NVMeOffloadOptimizer(params, str(tmp_path / f"d{depth}"),
                                   pipeline_depth=depth)
        for s in range(3):
            g = {k: np.random.RandomState(100 + s).randn(32, 16)
                 .astype(np.float32) for k in params}
            out = opt.apply(g, 1.0, None, jnp.float32)
            assert out is not None
        assert opt.last_sweep_stats["pipeline_depth"] == depth
        outs[depth] = opt.gather_master()
    for k in params:
        np.testing.assert_array_equal(outs[2][k], outs[3][k])


def test_config_validation_rejects_bad_knobs():
    """aio.backend / queue depths / prefetch depth are validated at the
    config boundary with constants single-sourced (PR 7 review pattern)."""
    from deepspeed_tpu.config import DeepSpeedConfig

    def cfg(aio=None, op=None, oo=None):
        c = {"train_micro_batch_size_per_gpu": 1,
             "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
        if aio:
            c["aio"] = aio
        zo = {"stage": 3}
        if op:
            zo["offload_param"] = op
        if oo:
            zo["offload_optimizer"] = oo
        c["zero_optimization"] = zo
        return DeepSpeedConfig(c)

    with pytest.raises(DeepSpeedConfigError, match="backend"):
        cfg(aio={"backend": "libaio"})
    with pytest.raises(DeepSpeedConfigError, match="queue_depth"):
        cfg(aio={"queue_depth": 0})
    with pytest.raises(DeepSpeedConfigError, match="block_size"):
        cfg(aio={"block_size": 512})
    with pytest.raises(DeepSpeedConfigError, match="thread_count"):
        cfg(aio={"thread_count": 0})
    with pytest.raises(DeepSpeedConfigError, match="prefetch_depth"):
        cfg(op={"device": "nvme", "prefetch_depth": -1})
    with pytest.raises(DeepSpeedConfigError, match="prefetch_depth"):
        cfg(op={"device": "nvme", "buffer_count": 2, "prefetch_depth": 5})
    with pytest.raises(DeepSpeedConfigError, match="pipeline_depth"):
        cfg(oo={"device": "nvme", "pipeline_depth": 1})
    # valid composite passes and lands on the dataclasses
    c = cfg(aio={"backend": "batched", "queue_depth": 16},
            op={"device": "nvme", "buffer_count": 4, "prefetch_depth": 3},
            oo={"device": "nvme", "pipeline_depth": 4})
    assert c.aio_config.backend == "batched"
    assert c.zero_config.offload_param.prefetch_depth == 3
    assert c.zero_config.offload_optimizer.pipeline_depth == 4
