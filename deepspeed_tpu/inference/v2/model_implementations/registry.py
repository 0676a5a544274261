"""Inference-v2 model policy registry.

Reference: ``deepspeed/inference/v2/engine_factory.py:66-120`` — the
``model_type``→policy dispatch table covering llama / mistral / mixtral / opt
/ falcon / phi / qwen. Registered here by model-config class AND by the HF
``model_type`` string, so both ``build_engine(params, config)`` and
``build_hf_engine(path)`` resolve through one table.

Served beside them, each registered below with a line on what it asks of the
engine: mellum, afmoe, deepseek_v32, nemotron_h, falcon_h1, sdar_moe,
solar_open2, kimi_linear (the first to keep latent rows and per-sequence slots
in one cache).
"""

from typing import Callable, Dict, Tuple

_BY_CONFIG: Dict[type, type] = {}
_BY_NAME: Dict[str, Tuple[type, type]] = {}
# model_type -> a function that imports the family and registers it: a family
# no other shares code with is imported when a config first names it
_ON_FIRST_USE: Dict[str, Callable[[], None]] = {}


def register_policy(model_type: str, config_cls, model_cls) -> None:
    _BY_NAME[model_type] = (config_cls, model_cls)
    # config-class dispatch falls back on model_type when one config class
    # serves several model types (llama family)
    _BY_CONFIG.setdefault(config_cls, model_cls)


def model_cls_for(model_config) -> type:
    mt = getattr(model_config, "model_type", None)
    if mt in _ON_FIRST_USE:
        _ON_FIRST_USE.pop(mt)()
    if mt in _BY_NAME:
        return _BY_NAME[mt][1]
    for cfg_cls, model_cls in _BY_CONFIG.items():
        if isinstance(model_config, cfg_cls):
            return model_cls
    raise ValueError(f"no inference-v2 policy for {type(model_config).__name__} "
                     f"(model_type={mt!r}); known: {sorted(_BY_NAME)}")


def supported_model_types():
    return sorted(set(_BY_NAME) | set(_ON_FIRST_USE))


def _register_deepseek_v32():
    from deepspeed_tpu.models.deepseek_v32 import DeepseekV32Config
    from deepspeed_tpu.inference.v2.model_implementations.deepseek_v32_v2 import DeepseekV32V2Model
    register_policy("deepseek_v32", DeepseekV32Config, DeepseekV32V2Model)


def _register_nemotron_h():
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    from deepspeed_tpu.inference.v2.model_implementations.nemotron_h_v2 import NemotronHV2Model
    register_policy("nemotron_h", NemotronHConfig, NemotronHV2Model)


def _register_falcon_h1():
    from deepspeed_tpu.models.falcon_h1 import FalconH1Config
    from deepspeed_tpu.inference.v2.model_implementations.falcon_h1_v2 import FalconH1V2Model
    register_policy("falcon_h1", FalconH1Config, FalconH1V2Model)


def _register_sdar_moe():
    from deepspeed_tpu.models.sdar_moe import SdarMoeConfig
    from deepspeed_tpu.inference.v2.model_implementations.sdar_moe_v2 import SdarMoeV2Model
    register_policy("sdar_moe", SdarMoeConfig, SdarMoeV2Model)


def _register_solar_open2():
    from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
    from deepspeed_tpu.inference.v2.model_implementations.solar_open2_v2 import SolarOpen2V2Model
    register_policy("solar_open2", SolarOpen2Config, SolarOpen2V2Model)


def _register_kimi_linear():
    from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
    from deepspeed_tpu.inference.v2.model_implementations.kimi_linear_v2 import KimiLinearV2Model
    register_policy("kimi_linear", KimiLinearConfig, KimiLinearV2Model)


def _register_builtin():
    from deepspeed_tpu.models.afmoe import AfmoeConfig
    from deepspeed_tpu.models.decoder import DecoderConfig
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.models.mellum import MellumConfig
    from deepspeed_tpu.models.mixtral import MixtralConfig
    from deepspeed_tpu.inference.v2.model_implementations.afmoe_v2 import AfmoeV2Model
    from deepspeed_tpu.inference.v2.model_implementations.decoder_v2 import DecoderV2Model
    from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import (LlamaV2Model,
                                                                           MistralV2Model,
                                                                           Qwen2V2Model)
    from deepspeed_tpu.inference.v2.model_implementations.mellum_v2 import MellumV2Model
    from deepspeed_tpu.inference.v2.model_implementations.mixtral_v2 import MixtralV2Model

    register_policy("llama", LlamaConfig, LlamaV2Model)
    register_policy("mistral", LlamaConfig, MistralV2Model)
    register_policy("qwen2", LlamaConfig, Qwen2V2Model)
    register_policy("mixtral", MixtralConfig, MixtralV2Model)
    # serving only: window and full layers side by side (KV layer groups), top-k
    # of many experts; dense MLP layers and other RoPE types are refused by the
    # config's constructor
    register_policy("mellum", MellumConfig, MellumV2Model)
    # serving only: sigmoid-scored experts beside a shared one, leading dense
    # layers, gated attention with q/k norm, rotary on the window layers alone
    register_policy("afmoe", AfmoeConfig, AfmoeV2Model)
    # serving only, and as one chip's share of a layer that several chips share:
    # a latent cache with absorbed decode, a learned index of keys that selects
    # what attention reads, group-limited sigmoid routing
    _ON_FIRST_USE["deepseek_v32"] = _register_deepseek_v32
    # serving only, as one chip's share: Mamba-2 blocks whose state is a
    # sequence's (a per-sequence state group beside the K/V array), relu^2
    # experts, attention without position encoding, one mixer a block
    _ON_FIRST_USE["nemotron_h"] = _register_nemotron_h
    # serving only: a Mamba-2 mixer beside attention in EVERY layer (K/V and a
    # per-sequence state in each), fourteen forward multipliers, one sequence
    # bucket; nothing in common with "falcon" below but the name
    _ON_FIRST_USE["falcon_h1"] = _register_falcon_h1
    # serving only: generation by diffusion over blocks — attention under a
    # block mask, a decode step that rewrites a block of rows and commits its
    # K/V once, several tokens a sequence a step — on softmax top-k experts
    _ON_FIRST_USE["sdar_moe"] = _register_sdar_moe
    # serving only, as one chip's share: gated delta-rule linear attention in
    # three layers of four (a matrix state a head in the per-sequence state
    # group, decayed by channel), gated position-free GQA in the fourth, SwiGLU
    # experts beside a shared one in every layer
    _ON_FIRST_USE["solar_open2"] = _register_solar_open2
    # serving only, as one chip's share: the same delta rule (beta = sigmoid alone)
    # in three layers of four and position-free LATENT attention in the fourth, so a
    # latent pool and a slot pool in one cache; a leading dense layer, SwiGLU experts
    # beside a shared one after it
    _ON_FIRST_USE["kimi_linear"] = _register_kimi_linear
    register_policy("opt", DecoderConfig, DecoderV2Model)
    register_policy("falcon", DecoderConfig, DecoderV2Model)
    register_policy("phi", DecoderConfig, DecoderV2Model)
    register_policy("gptj", DecoderConfig, DecoderV2Model)
    register_policy("gpt_neox", DecoderConfig, DecoderV2Model)
    # bloom (alibi) deliberately unregistered: DecoderV2Model raises with a
    # pointer at the v1 path rather than serving wrong logits


_register_builtin()
