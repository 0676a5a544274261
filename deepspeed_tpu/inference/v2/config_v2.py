"""Ragged inference engine config.

Reference: ``deepspeed/inference/v2/config_v2.py`` (RaggedInferenceEngineConfig:29,
DeepSpeedTPConfig:12, the fork's DeepSpeedEPConfig:18 with ``replica_num``, and the
``simulated_gating`` fork flag; in place of the fork's ``trace_enabled`` flag the
models carry named scopes and telemetry spans land in a ``jax.profiler`` trace).
"""

from typing import Optional

from pydantic import Field

from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel
from deepspeed_tpu.telemetry.config import TelemetryConfig


class DeepSpeedTPConfig(DeepSpeedConfigModel):
    """Tensor-parallel settings: model params sharded over the ``model`` mesh axis."""

    tp_size: int = 1


class DeepSpeedEPConfig(DeepSpeedConfigModel):
    """Expert-parallel settings (fork addition). Each replica serves
    ``num_experts // replica_num`` experts; the dispatch/return all-to-alls run
    over the ``expert`` mesh axis."""

    enabled: bool = False
    replica_num: int = 1
    capacity_factor: float = 2.0
    """Fixed-capacity slack for the XLA (shape-static) all-to-all; the reference's
    variable-size a2a needs no capacity but pays a host-side size exchange."""


class QuantizationConfig(DeepSpeedConfigModel):
    """ZeRO-Inference weight quantization (reference README.md:17 news item +
    deepspeed/inference/quantization): int8 at-rest weights, dequantized
    inside the jitted forward so the convert fuses into each consumer."""

    enabled: bool = False
    bits: int = 8
    min_size: int = 4096
    """Leaves smaller than this (norms, biases) stay full precision."""


class RaggedInferenceEngineConfig(DeepSpeedConfigModel):
    """Top-level FastGen engine config."""

    tensor_parallel: DeepSpeedTPConfig = Field(default_factory=DeepSpeedTPConfig, alias="tp")
    quantization: QuantizationConfig = Field(default_factory=QuantizationConfig,
                                             alias="weight_quantization")
    expert_parallel: DeepSpeedEPConfig = Field(default_factory=DeepSpeedEPConfig, alias="ep")
    state_manager: DSStateManagerConfig = Field(default_factory=DSStateManagerConfig, alias="manager")

    kv_block_size: int = 64
    # Pallas blocked-attention kernel (reference blocked_flash role):
    # True/False force it; None = auto (on a TPU, every bucket of a model
    # without a sliding window: modules/heuristics.py)
    use_paged_kernel: Optional[bool] = None

    simulated_gating: bool = False
    simulated_gating_temperature: float = 1.0

    telemetry: TelemetryConfig = TelemetryConfig()
    """Unified telemetry: batch/token/KV gauges, per-phase spans, and the
    ``/metrics`` + ``/healthz`` endpoint when ``telemetry.http.enabled``."""
