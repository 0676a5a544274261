"""deepspeed_tpu — a TPU-native training & inference framework with the DeepSpeed
feature surface (reference: gwsshs22/DeepSpeed v0.13.2), built on JAX/XLA/Pallas.

Top-level API parity with ``deepspeed/__init__.py``:
``initialize()`` (:64), ``init_inference()`` (:263), ``add_config_arguments()``
(:240), ``init_distributed`` re-export (:38).
"""

import argparse
import os
import sys
from typing import Optional, Union

from deepspeed_tpu import comm as comm
from deepspeed_tpu import ops
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.comm.comm import init_distributed
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.runtime import DeepSpeedOptimizer, ZeROOptimizer
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
from deepspeed_tpu.runtime.lr_schedules import add_tuning_arguments
from deepspeed_tpu.utils import groups, logger, log_dist
from deepspeed_tpu.utils.init_on_device import OnDevice
from deepspeed_tpu.version import __version__, git_branch, git_hash

dist = comm


def __getattr__(name):
    # everything that imports jax or flax at module level is re-exported
    # LAZILY (reference deepspeed/__init__.py exports eagerly): `import
    # deepspeed_tpu` itself must not import jax, because the launcher
    # (launcher/runner.py, a submodule of this package) is a parent that
    # starts the processes which own the chips
    lazy = {
        "module_inject": ("deepspeed_tpu.module_inject", None),
        "replace_transformer_layer": ("deepspeed_tpu.module_inject",
                                      "replace_transformer_layer"),
        "revert_transformer_layer": ("deepspeed_tpu.module_inject",
                                     "revert_transformer_layer"),
        "DeepSpeedTransformerConfig": ("deepspeed_tpu.ops.transformer",
                                       "DeepSpeedTransformerConfig"),
        "DeepSpeedTransformerLayer": ("deepspeed_tpu.ops.transformer",
                                      "DeepSpeedTransformerLayer"),
        "DeepSpeedConfig": ("deepspeed_tpu.runtime.config", "DeepSpeedConfig"),
        "DeepSpeedConfigError": ("deepspeed_tpu.runtime.config", "DeepSpeedConfigError"),
        "zero": ("deepspeed_tpu.runtime.zero", None),
        "DeepSpeedEngine": ("deepspeed_tpu.runtime.engine", "DeepSpeedEngine"),
        "DeepSpeedHybridEngine": ("deepspeed_tpu.runtime.hybrid_engine",
                                  "DeepSpeedHybridEngine"),
        "PipelineEngine": ("deepspeed_tpu.runtime.pipe.engine", "PipelineEngine"),
        "PipelineModule": ("deepspeed_tpu.runtime.pipe.module", "PipelineModule"),
        "InferenceEngine": ("deepspeed_tpu.inference.engine", "InferenceEngine"),
        "InferenceEngineV2": ("deepspeed_tpu.inference.v2.engine_v2", "InferenceEngineV2"),
    }
    if name in lazy:
        import importlib
        mod, attr = lazy[name]
        module = importlib.import_module(mod)
        return module if attr is None else getattr(module, attr)
    raise AttributeError(f"module 'deepspeed_tpu' has no attribute {name!r}")


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               mesh=None,
               loss_fn=None,
               param_specs=None,
               rng_seed=0,
               example_batch=None,
               config_params=None):
    """Initialize the DeepSpeed-TPU engine (reference deepspeed/__init__.py:64).

    Differences forced by the functional SPMD model:
      - ``model`` is a flax module (whose ``apply(params, batch)`` returns the
        scalar loss) or a pure ``loss_fn(params, batch[, rng])`` callable.
      - ``model_parameters`` is the *initial parameter pytree* (the torch version
        takes a parameter list off an already-materialized module).
      - ``mesh``/``param_specs`` optionally override topology/TP placement.

    Returns the reference's 4-tuple: (engine, optimizer, dataloader, lr_scheduler).
    """
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    log_dist(f"DeepSpeed-TPU info: version={__version__}, git-hash={git_hash}, git-branch={git_branch}", ranks=[0])

    if config is None:
        config = config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config") and args.deepspeed_config:
        config = args.deepspeed_config
    assert config is not None, "DeepSpeed requires --deepspeed_config to specify configuration file"

    # Pipeline-parallel models route to the pipeline engine; hybrid_engine.enabled
    # routes to the RLHF train↔generate engine (reference :156-196)
    engine_cls = DeepSpeedEngine
    try:
        from deepspeed_tpu.runtime.pipe.module import PipelineModule
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        if isinstance(model, PipelineModule):
            engine_cls = PipelineEngine
    except ImportError:
        pass
    if engine_cls is DeepSpeedEngine:
        cfg_dict = config
        if isinstance(config, str):  # JSON config files route too
            try:
                import json
                with open(config) as f:
                    cfg_dict = json.load(f)
            except Exception:
                cfg_dict = {}
        if isinstance(cfg_dict, dict) and cfg_dict.get("hybrid_engine", {}).get("enabled", False):
            from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine
            engine_cls = DeepSpeedHybridEngine

    engine = engine_cls(args=args,
                        model=model,
                        optimizer=optimizer,
                        model_parameters=model_parameters,
                        training_data=training_data,
                        lr_scheduler=lr_scheduler,
                        mpu=mpu,
                        dist_init_required=dist_init_required,
                        collate_fn=collate_fn,
                        config=config,
                        mesh=mesh,
                        loss_fn=loss_fn,
                        param_specs=param_specs,
                        rng_seed=rng_seed,
                        example_batch=example_batch)

    return_items = [engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler]
    return tuple(return_items)


def add_config_arguments(parser):
    """Reference deepspeed/__init__.py:240."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed",
                       default=False,
                       action="store_true",
                       help="Enable DeepSpeed (helper flag for user code, no impact on DeepSpeed backend)")
    group.add_argument("--deepspeed_config", default=None, type=str, help="DeepSpeed json configuration file.")
    group.add_argument("--deepscale",
                       default=False,
                       action="store_true",
                       help="Deprecated enable DeepSpeed (helper flag for user code, no impact)")
    group.add_argument("--deepscale_config", default=None, type=str, help="Deprecated DeepSpeed json config file.")
    return parser


def default_inference_config():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    return DeepSpeedInferenceConfig().model_dump()


def init_inference(model=None, config=None, checkpoint=None, **kwargs):
    """Reference deepspeed/__init__.py:263.

    ``model`` may be a flax module / {"module","params"} dict, OR a HuggingFace
    checkpoint directory path (equivalently pass ``checkpoint=...``): the
    injection-policy registry (module_inject/containers.py — the reference's
    containers/ + replace_module tier) detects the architecture from
    config.json, builds the native model and converts the weights.
    """
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine

    log_dist(f"DeepSpeed-TPU info: version={__version__}", ranks=[0])
    if isinstance(config, dict):
        config = DeepSpeedInferenceConfig(**{**config, **kwargs})
    elif config is None:
        config = DeepSpeedInferenceConfig(**kwargs)
    if checkpoint is None and isinstance(model, str):
        checkpoint = model
        model = None
    if model is None and checkpoint is None:
        raise ValueError("init_inference requires a model or a checkpoint directory")
    if model is not None and checkpoint is not None:
        raise ValueError("pass model OR checkpoint, not both — the checkpoint path "
                         "builds its own module and would silently ignore the model")
    if checkpoint is not None:
        import os
        if not (isinstance(checkpoint, str) and os.path.isdir(checkpoint)):
            raise ValueError(f"checkpoint must be a HF checkpoint directory, got {checkpoint!r}")
        from deepspeed_tpu.module_inject.containers import load_hf_checkpoint
        module, params, _cfg = load_hf_checkpoint(checkpoint)
        param_specs = None
        if config.tensor_parallel.tp_size > 1:
            from deepspeed_tpu.module_inject.auto_tp import auto_tp_specs
            param_specs = auto_tp_specs(params)
        return InferenceEngine({"module": module, "params": params}, config=config,
                               param_specs=param_specs)
    return InferenceEngine(model, config=config)
