import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig,
                                                               MemoryConfig)
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel


@pytest.fixture(autouse=True)
def fresh_telemetry():
    """Telemetry state is process-global: serving tests must neither inherit a
    leaked session nor leave one behind (same contract as tests/unit/telemetry)."""
    telemetry.shutdown()
    telemetry.state.registry = None
    yield
    telemetry.shutdown()
    telemetry.state.registry = None


@pytest.fixture(scope="package")
def llama_setup():
    # package scope: one model init for the whole serving suite, not one per
    # test file — the params are read-only inputs to every engine build
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = LlamaModel(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = {"model": model.init(jax.random.PRNGKey(0), ids)["params"]}
    return cfg, model, params


@pytest.fixture
def make_engine(llama_setup):
    """Engine factory with a small, test-controllable KV pool; every engine
    built through it is closed at teardown (scheduler detach)."""
    cfg, _, params = llama_setup
    engines = []

    def _make(num_blocks=64, block_size=16, **mgr_kw):
        mgr_kw.setdefault("max_context", 512)
        mgr = DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=num_blocks),
            **mgr_kw)
        engine = build_engine(params, cfg,
                              RaggedInferenceEngineConfig(state_manager=mgr,
                                                          kv_block_size=block_size))
        engines.append(engine)
        return engine

    yield _make
    for engine in engines:
        engine.close()
