"""deepspeed_tpu — a TPU-native large-model training & inference framework.

Public API mirrors the reference's surface (ref: deepspeed/__init__.py:50
initialize, :204 add_config_arguments, :220 init_inference) re-designed for
JAX/XLA: models are loss functions over parameter pytrees, parallelism is a
device mesh, and ZeRO stages are sharding specs.
"""

from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

from deepspeed_tpu.version import __version__
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.parallel import mesh as _mesh_lib
from deepspeed_tpu.utils.logging import logger, log_dist


def _infer_world_size(mesh=None, config_dict=None) -> int:
    import jax
    if mesh is not None:
        return _mesh_lib.dp_world_size(mesh)
    n = len(jax.devices())
    if config_dict:
        mc = (config_dict.get("mesh") or {})
        fixed = (mc.get("tensor_parallel_size", 1) *
                 mc.get("pipeline_parallel_size", 1) *
                 mc.get("sequence_parallel_size", 1))
        return max(1, n // fixed)
    return n


def initialize(args=None,
               model: Optional[Callable] = None,
               optimizer=None,
               model_parameters: Optional[Any] = None,
               training_data=None,
               lr_scheduler=None,
               mesh=None,
               partition_rules: Optional[Sequence] = None,
               config: Optional[Union[str, Dict]] = None,
               config_params: Optional[Union[str, Dict]] = None,
               has_aux: bool = False,
               collate_fn=None):
    """Initialize the training engine (ref: deepspeed/__init__.py:50).

    Parameters
    ----------
    model : callable(params, batch, rng) -> loss | (loss, aux)
        The loss function. (The torch reference takes an nn.Module; the
        jax-native contract is a pure function + a parameter pytree.)
        ``deepspeed_tpu.models`` provides ready models exposing this.
    model_parameters : the fp32 parameter pytree.
    config : path to a JSON config or a dict (same schema as the reference).
    mesh : optional prebuilt jax.sharding.Mesh.
    partition_rules : optional tensor-parallel PartitionRules.

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)`` for
    tuple-compatibility with the reference; optimizer/lr_scheduler are the
    engine-owned objects.
    """
    from deepspeed_tpu.utils import setup_compile_cache
    setup_compile_cache()
    config = config if config is not None else config_params
    assert config is not None, "deepspeed_tpu.initialize requires a config"
    assert model is not None, "deepspeed_tpu.initialize requires a loss function"
    assert model_parameters is not None, "model_parameters (param pytree) required"

    # LayeredModel -> parameter-streaming engine (the analog of the
    # reference's PipelineModule dispatch at deepspeed/__init__.py:118-142;
    # here the layered form enables the ZeRO-Infinity param tier,
    # ref: runtime/zero/partitioned_param_swapper.py). Single-chip by
    # design: the whole point is capacity beyond one chip's HBM.
    from deepspeed_tpu.runtime.zero.param_offload import (
        InfinityParamEngine, LayeredModel)
    if isinstance(model, LayeredModel):
        if optimizer is not None or mesh is not None or partition_rules:
            raise ValueError(
                "LayeredModel (param-streaming) engine owns its host "
                "optimizer and runs single-chip — optimizer/mesh/"
                "partition_rules are not supported; configure the "
                "optimizer via the JSON config instead")
        from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule
        ds_config = DeepSpeedConfig(config, world_size=1)
        base_lr = (ds_config.optimizer.params or {}).get("lr", 1e-3)
        sched = lr_scheduler if callable(lr_scheduler) else get_lr_schedule(
            ds_config.scheduler.type, ds_config.scheduler.params,
            base_lr=base_lr)
        engine = InfinityParamEngine(model, model_parameters, ds_config,
                                     lr_schedule=sched)
        dataloader = None
        if training_data is not None:
            from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
            dataloader = DeepSpeedDataLoader(
                training_data, batch_size=ds_config.train_batch_size,
                collate_fn=collate_fn)
        return engine, None, dataloader, sched

    config_dict = config if isinstance(config, dict) else None
    world_size = _infer_world_size(mesh, config_dict)
    ds_config = DeepSpeedConfig(config, world_size=world_size)

    engine = DeepSpeedEngine(
        loss_fn=model,
        params=model_parameters,
        config=ds_config,
        mesh=mesh,
        partition_rules=partition_rules,
        optimizer=optimizer,
        lr_schedule=lr_scheduler if callable(lr_scheduler) else None,
        has_aux=has_aux)

    dataloader = None
    if training_data is not None:
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
        dataloader = DeepSpeedDataLoader(
            training_data,
            batch_size=ds_config.train_batch_size,
            collate_fn=collate_fn)

    return engine, engine.optimizer, dataloader, engine.lr_schedule


def init_inference(model=None, **kwargs):
    """Inference engine entry (ref: deepspeed/__init__.py:220)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.utils import setup_compile_cache
    setup_compile_cache()
    return InferenceEngine(model, **kwargs)


# activation checkpointing API, importable as deepspeed_tpu.checkpointing
# (ref: deepspeed.checkpointing re-export in deepspeed/__init__.py)
from deepspeed_tpu.runtime.activation_checkpointing import (  # noqa: E402
    checkpointing)


def add_config_arguments(parser):
    """Add --deepspeed / --deepspeed_config CLI args
    (ref: deepspeed/__init__.py:153-204)."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag to wire configs)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed-TPU json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse
    return argparse.SUPPRESS


# zero namespace (ref: deepspeed.zero.Init re-export, deepspeed/__init__.py)
from deepspeed_tpu.runtime import zero  # noqa: E402
