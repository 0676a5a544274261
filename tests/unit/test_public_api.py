"""Top-level API parity with ``deepspeed/__init__.py`` — a reference user's
imports must resolve (VERDICT-standard surface check)."""

import numpy as np
import pytest

import deepspeed_tpu


def test_reference_top_level_names_exist():
    names = ["initialize", "init_inference", "init_distributed",
             "add_config_arguments", "add_tuning_arguments",
             "default_inference_config", "DeepSpeedEngine",
             "DeepSpeedHybridEngine", "PipelineEngine", "InferenceEngine",
             "InferenceEngineV2", "DeepSpeedInferenceConfig", "DeepSpeedConfig",
             "DeepSpeedConfigError", "checkpointing", "zero", "PipelineModule",
             "ops", "module_inject", "get_accelerator", "log_dist", "OnDevice",
             "logger", "comm", "dist", "DeepSpeedOptimizer", "ZeROOptimizer",
             "DeepSpeedTransformerLayer", "DeepSpeedTransformerConfig",
             "replace_transformer_layer", "revert_transformer_layer",
             "__version__", "git_hash", "git_branch"]
    missing = [n for n in names if not hasattr(deepspeed_tpu, n)]
    assert not missing, missing
    with pytest.raises(AttributeError):
        deepspeed_tpu.definitely_not_a_real_name


def test_lazy_engine_classes_resolve_to_real_classes():
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    assert deepspeed_tpu.DeepSpeedEngine is DeepSpeedEngine
    assert issubclass(deepspeed_tpu.PipelineEngine, DeepSpeedEngine)


def test_replace_transformer_layer_points_at_checkpoint_path():
    with pytest.raises(NotImplementedError, match="init_inference"):
        deepspeed_tpu.replace_transformer_layer()
    with pytest.raises(NotImplementedError, match="checkpoint"):
        deepspeed_tpu.revert_transformer_layer()


def test_on_device_scopes_default_device():
    import jax
    import jax.numpy as jnp

    cpu0 = jax.devices()[0]
    with deepspeed_tpu.OnDevice(dtype=jnp.bfloat16, device=cpu0):
        x = jnp.ones(4)
        assert deepspeed_tpu.OnDevice.current_dtype() == jnp.bfloat16
    assert list(x.devices()) == [cpu0]
    assert deepspeed_tpu.OnDevice.current_dtype() is None

    with pytest.raises(NotImplementedError, match="zero.Init"):
        deepspeed_tpu.OnDevice(dtype=jnp.bfloat16, device="meta")

    # disabled is a no-op passthrough
    with deepspeed_tpu.OnDevice(dtype=jnp.float32, device="meta", enabled=False):
        assert deepspeed_tpu.OnDevice.current_dtype() is None


def test_on_device_casts_init_dtype_and_is_reentrant():
    """The dtype knob must actually act (module.init leaves cast) and nested
    scopes must unwind correctly."""
    import jax
    import jax.numpy as jnp
    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(x)

    cpu0 = jax.devices()[0]
    od = deepspeed_tpu.OnDevice(dtype=jnp.bfloat16, device=cpu0)
    with od:
        with od:  # reentrant: same instance nested
            v = M().init(jax.random.PRNGKey(0), jnp.ones((1, 4)))
        assert deepspeed_tpu.OnDevice.current_dtype() == jnp.bfloat16
    kernel = v["params"]["Dense_0"]["kernel"]
    assert kernel.dtype == jnp.bfloat16
    # the patch is unwound: init outside the scope is fp32 again
    v2 = M().init(jax.random.PRNGKey(0), jnp.ones((1, 4)))
    assert v2["params"]["Dense_0"]["kernel"].dtype == jnp.float32


def test_zero_engine_optimizer_isinstance_markers():
    """Reference-style isinstance checks on engine.optimizer must hold:
    DeepSpeedOptimizer always, ZeROOptimizer exactly when ZeRO shards."""
    from deepspeed_tpu.utils import groups

    from .simple_model import make_simple_model, random_batches

    groups.initialize_mesh(force=True)
    model, params = make_simple_model(hidden_dim=16, batch_size=8)

    def eng(stage):
        groups.initialize_mesh(force=True)
        e, opt, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": stage}})
        return e, opt

    e0, opt0 = eng(0)
    assert isinstance(opt0, deepspeed_tpu.DeepSpeedOptimizer)
    assert not isinstance(opt0, deepspeed_tpu.ZeROOptimizer)
    e2, opt2 = eng(2)
    assert isinstance(opt2, deepspeed_tpu.ZeROOptimizer)
    # the remix keeps the optimizer functional
    float(e2.train_batch(batch=random_batches(1, 8, 16)[0]))


def test_user_supplied_optimizer_not_mutated_by_zero_marker():
    """A user-supplied optimizer object (any init/update duck type) must not
    have its class rewritten by the ZeRO marker mixin."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.utils import groups
    from .simple_model import make_simple_model, random_batches

    class UserSGD:
        def __init__(self):
            self.lr = 1e-2
            self.weight_decay = 0.0

        def init(self, params):
            return ()

        def update(self, grads, state, params, lr):
            return jax.tree.map(lambda g: -lr * g, grads), state

        def get_lr(self):
            return self.lr

        def set_lr(self, lr):
            self.lr = lr

    groups.initialize_mesh(force=True)
    model, params = make_simple_model(hidden_dim=16, batch_size=8)
    opt = UserSGD()
    cls_before = type(opt)
    eng, ret_opt, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, optimizer=opt,
        config={"train_micro_batch_size_per_gpu": 8,
                "zero_optimization": {"stage": 2}})
    assert type(opt) is cls_before  # untouched
    assert not isinstance(ret_opt, deepspeed_tpu.ZeROOptimizer)
    loss = float(eng.train_batch(batch=random_batches(1, 8, 16)[0]))
    assert np.isfinite(loss)


def test_get_accelerator_raises_when_the_backend_cannot_be_reached(monkeypatch):
    """No quiet "cpu" for a TPU that failed to start: the failure is the answer."""
    import jax

    from deepspeed_tpu.accelerator import real_accelerator

    def unreachable():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(real_accelerator, "ds_accelerator", None)
    monkeypatch.delenv("DS_ACCELERATOR", raising=False)
    monkeypatch.setattr(jax, "default_backend", unreachable)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        real_accelerator.get_accelerator()
    assert real_accelerator.ds_accelerator is None
