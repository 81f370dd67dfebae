"""deepspeed_tpu — a TPU-native large-model training framework with the
capability surface of DeepSpeed v0.5.2 (reference: deepspeed/__init__.py),
built on JAX/XLA/pjit/Pallas.

Public entry points mirror the reference:
  - initialize(...)        (reference: deepspeed/__init__.py:61)
  - init_inference(...)    (reference: deepspeed/__init__.py:232)
  - add_config_arguments() (reference: deepspeed/__init__.py:216)
"""

from .version import __version__
from .config import DeepSpeedConfig, DeepSpeedConfigError
from .parallel import (MeshContext, get_mesh_context, initialize_mesh,
                       reset_mesh_context)
from .parallel import groups
from .utils import logger, log_dist
from .utils.distributed import init_distributed
from . import moe
from .runtime import zero  # deepspeed.zero.Init / GatheredParameters parity
from .monitor import trace as _trace

# the compile record starts here: every program JAX is asked to compile
# from now on, whoever asks (monitor/trace.py)
_trace.install()


def initialize(args=None, model=None, config=None, config_params=None,
               optimizer=None, model_parameters=None, lr_scheduler=None,
               mesh=None, dist_init_required=None, collate_fn=None,
               training_data=None, mpu=None, rng=None, example_input=None,
               param_partition_specs=None):
    """Create a TPU-backed training engine (reference: deepspeed/__init__.py:61).

    Returns (engine, optimizer, dataloader, lr_scheduler) like the reference.
    `model` is a flax module or an apply-style callable; see
    deepspeed_tpu.runtime.engine for details.
    """
    with _trace.span("initialize") as init:
        init.phase("config")
        engine = _initialize(args, model, config, config_params, optimizer,
                             model_parameters, lr_scheduler, mesh,
                             collate_fn, training_data, mpu, rng,
                             example_input, param_partition_specs)
    marks = getattr(engine, "trace_marks", None)
    if marks is not None:
        marks["initialize_ns"] = _trace.last_span()[1:3]
    return (engine, engine.optimizer, engine.training_dataloader,
            engine.lr_scheduler)


def _initialize(args, model, config, config_params, optimizer,
                model_parameters, lr_scheduler, mesh, collate_fn,
                training_data, mpu, rng, example_input,
                param_partition_specs):
    """The engine ``initialize`` returns, under its ``ds.initialize``."""
    from .runtime.engine import DeepSpeedEngine
    from .runtime.pipe.module import PipelineModule

    cfg = config if config is not None else config_params
    if cfg is None and args is not None:
        cfg = getattr(args, "deepspeed_config", None)
    if cfg is None:
        raise DeepSpeedConfigError("DeepSpeed requires a config (dict or path)")

    # ZeRO-Infinity param offload: layer-streaming engine for models whose
    # params should never be fully HBM-resident (reference: stage3 +
    # offload_param — stage3.py:932; see runtime/zero/infinity.py).
    # Parse the zero block through ZeroConfig so legacy keys
    # (cpu_offload_params) and device defaults dispatch identically to the
    # full config parse.
    from .config import ZeroConfig
    from .config_utils import load_config_dict
    raw = cfg if isinstance(cfg, dict) else (
        load_config_dict(cfg) if isinstance(cfg, str) else
        getattr(cfg, "_param_dict", {}))
    zc = ZeroConfig.from_dict(raw.get("zero_optimization"))
    op = zc.offload_param
    if op is not None and (op.device or "none") != "none":
        if not hasattr(model, "layerwise_api"):
            raise ValueError(
                "zero_optimization.offload_param requires a model exposing "
                "layerwise_api() (streaming groups); GPT2Model does")
        from .runtime.zero.infinity import ZeroInfinityEngine
        engine = ZeroInfinityEngine(
            model=model, config=cfg, model_parameters=model_parameters,
            optimizer=optimizer, lr_scheduler=lr_scheduler, mesh=mesh,
            rng=rng, mpu=mpu, training_data=training_data,
            collate_fn=collate_fn)
        return engine

    if isinstance(model, PipelineModule):
        from .runtime.pipe.engine import PipelineEngine
        if param_partition_specs is not None:
            raise ValueError(
                "param_partition_specs is not supported with a "
                "PipelineModule — declare specs on the stage layers "
                "(PipeLayer.param_partition_specs) instead")
        engine = PipelineEngine(model=model, config=cfg, optimizer=optimizer,
                                lr_scheduler=lr_scheduler, mesh=mesh, mpu=mpu,
                                training_data=training_data,
                                collate_fn=collate_fn, rng=rng,
                                example_input=example_input)
    else:
        engine = DeepSpeedEngine(model=model, config=cfg, optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 lr_scheduler=lr_scheduler, mesh=mesh, mpu=mpu,
                                 training_data=training_data,
                                 collate_fn=collate_fn, rng=rng,
                                 param_partition_specs=param_partition_specs)
    return engine


def init_inference(model, mp_size=1, mesh=None, checkpoint=None, dtype=None,
                   injection_policy=None, replace_method="auto",
                   quantization_setting=None, **kwargs):
    """Create an inference engine (reference: deepspeed/__init__.py:232)."""
    from .inference.engine import InferenceEngine
    return InferenceEngine(model, mp_size=mp_size, mesh=mesh,
                           checkpoint=checkpoint, dtype=dtype,
                           injection_policy=injection_policy,
                           replace_method=replace_method,
                           quantization_setting=quantization_setting, **kwargs)


def add_config_arguments(parser):
    """Add --deepspeed / --deepspeed_config args (reference: __init__.py:216)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag to ease transition)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated enable flag (kept for parity)")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated config path (kept for parity)")
    return parser
