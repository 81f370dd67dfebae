"""Config parsing tests (modeled on reference tests/unit/test_config.py)."""

import json

import pytest

from deepspeed_tpu.config import (REMOVED_KEYS, DeepSpeedConfig,
                                  DeepSpeedConfigError)


def base_config():
    return {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 0.001}},
        "fp16": {"enabled": True},
    }


def test_batch_triple_all_given():
    cfg = DeepSpeedConfig(base_config(), world_size=4)
    assert cfg.train_batch_size == 16
    assert cfg.train_micro_batch_size_per_gpu == 2
    assert cfg.gradient_accumulation_steps == 2


def test_batch_infer_gas():
    d = base_config()
    del d["gradient_accumulation_steps"]
    cfg = DeepSpeedConfig(d, world_size=4)
    assert cfg.gradient_accumulation_steps == 2


def test_batch_infer_micro():
    d = base_config()
    del d["train_micro_batch_size_per_gpu"]
    cfg = DeepSpeedConfig(d, world_size=4)
    assert cfg.train_micro_batch_size_per_gpu == 2


def test_batch_infer_train():
    d = base_config()
    del d["train_batch_size"]
    cfg = DeepSpeedConfig(d, world_size=4)
    assert cfg.train_batch_size == 16


def test_batch_only_train_given():
    cfg = DeepSpeedConfig({"train_batch_size": 8}, world_size=4)
    assert cfg.train_micro_batch_size_per_gpu == 2
    assert cfg.gradient_accumulation_steps == 1


def test_batch_only_micro_given():
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 3}, world_size=4)
    assert cfg.train_batch_size == 12
    assert cfg.gradient_accumulation_steps == 1


def test_batch_none_given():
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({}, world_size=1)


def test_batch_inconsistent():
    d = base_config()
    d["train_batch_size"] = 17
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig(d, world_size=4)


def test_config_from_file(tmp_path):
    p = tmp_path / "ds.json"
    p.write_text(json.dumps(base_config()))
    cfg = DeepSpeedConfig(str(p), world_size=4)
    assert cfg.optimizer_name == "adam"
    assert cfg.optimizer_params["lr"] == 0.001


def test_config_duplicate_keys(tmp_path):
    p = tmp_path / "dup.json"
    p.write_text('{"train_batch_size": 8, "train_batch_size": 4}')
    with pytest.raises(ValueError):
        DeepSpeedConfig(str(p), world_size=1)


def test_fp16_defaults():
    cfg = DeepSpeedConfig(base_config(), world_size=4)
    assert cfg.fp16.enabled
    assert cfg.fp16.dynamic_loss_scale
    assert cfg.fp16.initial_scale_power == 32
    assert cfg.fp16.loss_scale_window == 1000


def test_zero_config_stages():
    for stage in (0, 1, 2, 3):
        d = base_config()
        d["zero_optimization"] = {"stage": stage}
        cfg = DeepSpeedConfig(d, world_size=4)
        assert cfg.zero_optimization_stage == stage
        assert cfg.zero_enabled == (stage > 0)


def test_zero_overlap_comm_stage_default():
    d = base_config()
    d["zero_optimization"] = {"stage": 3}
    assert DeepSpeedConfig(d, world_size=4).zero_config.overlap_comm
    d["zero_optimization"] = {"stage": 2}
    assert not DeepSpeedConfig(d, world_size=4).zero_config.overlap_comm


def test_zero_offload_legacy_flag():
    d = base_config()
    d["zero_optimization"] = {"stage": 2, "cpu_offload": True}
    cfg = DeepSpeedConfig(d, world_size=4)
    assert cfg.zero_config.offload_optimizer is not None
    assert cfg.zero_config.offload_optimizer.device == "cpu"


def test_zero_offload_dicts():
    d = base_config()
    d["zero_optimization"] = {
        "stage": 3,
        "offload_param": {"device": "nvme", "nvme_path": "/tmp/nvme",
                          "buffer_count": 7},
        "offload_optimizer": {"device": "nvme", "pipeline_read": True},
    }
    cfg = DeepSpeedConfig(d, world_size=4)
    assert cfg.zero_config.offload_param.device == "nvme"
    assert cfg.zero_config.offload_param.buffer_count == 7
    assert cfg.zero_config.offload_optimizer.pipeline


def test_scheduler_config():
    d = base_config()
    d["scheduler"] = {"type": "WarmupLR",
                      "params": {"warmup_num_steps": 10}}
    cfg = DeepSpeedConfig(d, world_size=4)
    assert cfg.scheduler_name == "WarmupLR"
    assert cfg.scheduler_params["warmup_num_steps"] == 10


def test_bf16_config():
    d = base_config()
    del d["fp16"]
    d["bf16"] = {"enabled": True}
    cfg = DeepSpeedConfig(d, world_size=4)
    assert cfg.bf16.enabled and not cfg.fp16.enabled


def test_aio_defaults():
    cfg = DeepSpeedConfig(base_config(), world_size=4)
    assert cfg.aio_config.block_size == 1048576
    assert cfg.aio_config.queue_depth == 8
    assert cfg.aio_config.overlap_events


def test_mesh_config():
    d = base_config()
    d["mesh"] = {"model": 2, "pipe": 2}
    cfg = DeepSpeedConfig(d, world_size=4)
    assert cfg.mesh_config.model == 2
    assert cfg.mesh_config.pipe == 2
    assert cfg.mesh_config.data == -1


def test_add_config_arguments_roundtrip():
    """CLI argument surface (reference: deepspeed/__init__.py:216 +
    tests/unit/test_ds_arguments.py): add_config_arguments wires
    --deepspeed/--deepspeed_config into an existing parser without
    clobbering user args."""
    import argparse

    import deepspeed_tpu as ds

    parser = argparse.ArgumentParser()
    parser.add_argument("--user_flag", type=int, default=3)
    parser = ds.add_config_arguments(parser)
    args = parser.parse_args(
        ["--user_flag", "7", "--deepspeed", "--deepspeed_config", "c.json"])
    assert args.user_flag == 7
    assert args.deepspeed is True
    assert args.deepspeed_config == "c.json"
    # defaults: off
    args2 = parser.parse_args([])
    assert args2.deepspeed is False and args2.deepspeed_config is None


def test_prng_impl_config_knob():
    """prng_impl selects the default engine PRNG stream implementation
    (rbg = fast on TPU; threefry = bit-reproducible across backends)."""

    from deepspeed_tpu.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                           "prng_impl": "threefry"}, world_size=1)
    assert cfg.prng_impl == "threefry"
    # default stays the measured-fast TPU choice
    cfg2 = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1},
                           world_size=1)
    assert cfg2.prng_impl == "rbg"


# a removed key: (a value it once took, what its message has to name
# besides its table entry)
_REMOVED = {
    "stage3_prefetch_mode": ("off", "stage3_prefetch_bucket_size"),
    "prefetch_modes": (["off"], "stage3_prefetch_bucket_size"),
    "fused_step": ({"enabled": False}, "forward / backward / step"),
    "fused": ([False], "forward / backward / step")}


@pytest.mark.parametrize(
    "section,key", sorted(REMOVED_KEYS),
    ids=["-".join(filter(None, k)) for k in sorted(REMOVED_KEYS)])
def test_removed_keys_are_refused_and_name_their_replacement(section, key):
    """A key the package no longer reads is refused, not ignored: a
    silently ignored "stage3_prefetch_mode": "off" would turn the
    prefetch ON.  Every message names what to write instead."""
    value, instead = _REMOVED[key]
    block = {section: {key: value}} if section else {key: value}
    with pytest.raises(DeepSpeedConfigError) as refused:
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1, **block},
                        world_size=1)
    message = str(refused.value)
    assert (f"{section}.{key}" if section else key) in message
    assert REMOVED_KEYS[(section, key)] in message
    assert instead in message


def test_the_prefetch_structure_follows_the_bucket():
    """stage3_prefetch_bucket_size: 0 is the at-use plan, the default the
    carried one."""
    from deepspeed_tpu.runtime.zero.stage3_streaming import (
        plan_layer_streaming)

    def plan(zero):
        z = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                             "zero_optimization": dict(stage=3, **zero)},
                            world_size=1).zero_config
        return plan_layer_streaming(8, 1000, z.max_live_parameters,
                                    z.prefetch_bucket_size)

    assert plan({}).prefetch
    at_use = plan({"stage3_prefetch_bucket_size": 0})
    assert not at_use.prefetch and at_use.forfeited is None
