"""Inference-v2 model policy registry.

Reference: ``deepspeed/inference/v2/engine_factory.py:66-120`` — the
``model_type``→policy dispatch table covering llama / mistral / mixtral / opt
/ falcon / phi / qwen. One table, by the HF ``model_type`` string AND by
model-config class, so both ``build_engine(params, config)`` and
``build_hf_engine(path)`` resolve through it. A family is imported when a
config first names it: a program that serves one family pays for no other.
"""

from importlib import import_module
from typing import Dict, Tuple

_CONFIGS = "deepspeed_tpu.models."
_MODELS = "deepspeed_tpu.inference.v2.model_implementations."
# model_type -> (config module, config class, model module, model class); a family's
# comment is what it asks of the engine. Where one config class serves several model
# types, a config that names none of them is served by the first.
_FAMILIES: Dict[str, Tuple[str, str, str, str]] = {
    "llama": ("llama", "LlamaConfig", "llama_v2", "LlamaV2Model"),
    "mistral": ("llama", "LlamaConfig", "llama_v2", "MistralV2Model"),
    "qwen2": ("llama", "LlamaConfig", "llama_v2", "Qwen2V2Model"),
    "mixtral": ("mixtral", "MixtralConfig", "mixtral_v2", "MixtralV2Model"),
    # serving only: window and full layers side by side (KV layer groups), top-k
    # of many experts; dense MLP layers and other RoPE types are refused by the
    # config's constructor
    "mellum": ("mellum", "MellumConfig", "mellum_v2", "MellumV2Model"),
    # serving only: sigmoid-scored experts beside a shared one, leading dense
    # layers, gated attention with q/k norm, rotary on the window layers alone
    "afmoe": ("afmoe", "AfmoeConfig", "afmoe_v2", "AfmoeV2Model"),
    # serving only, and as one chip's share of a layer that several chips share:
    # a latent cache with absorbed decode, a learned index of keys that selects
    # what attention reads, group-limited sigmoid routing
    "deepseek_v32": ("deepseek_v32", "DeepseekV32Config", "deepseek_v32_v2", "DeepseekV32V2Model"),
    # serving only, as one chip's share: Mamba-2 blocks whose state is a
    # sequence's (a per-sequence state group beside the K/V array), relu^2
    # experts, attention without position encoding, one mixer a block
    "nemotron_h": ("nemotron_h", "NemotronHConfig", "nemotron_h_v2", "NemotronHV2Model"),
    # serving only: a Mamba-2 mixer beside attention in EVERY layer (K/V and a
    # per-sequence state in each), fourteen forward multipliers, one sequence
    # bucket; nothing in common with "falcon" below but the name
    "falcon_h1": ("falcon_h1", "FalconH1Config", "falcon_h1_v2", "FalconH1V2Model"),
    # serving only: generation by diffusion over blocks — attention under a
    # block mask, a decode step that rewrites a block of rows and commits its
    # K/V once, several tokens a sequence a step — on softmax top-k experts
    "sdar_moe": ("sdar_moe", "SdarMoeConfig", "sdar_moe_v2", "SdarMoeV2Model"),
    # serving only, as one chip's share: gated delta-rule linear attention in
    # three layers of four (a matrix state a head in the per-sequence state
    # group, decayed by channel), gated position-free GQA in the fourth, SwiGLU
    # experts beside a shared one in every layer
    "solar_open2": ("solar_open2", "SolarOpen2Config", "solar_open2_v2", "SolarOpen2V2Model"),
    # serving only, as one chip's share: the same delta rule (beta = sigmoid alone)
    # in three layers of four and position-free LATENT attention in the fourth, so a
    # latent pool and a slot pool in one cache (the first to keep both); a leading
    # dense layer, SwiGLU experts beside a shared one after it
    "kimi_linear": ("kimi_linear", "KimiLinearConfig", "kimi_linear_v2", "KimiLinearV2Model"),
    # serving only, as one chip's share: a layer of TWO latent-attention halves (two
    # latent layers of the pool a model layer) and two dense halves, one routed
    # branch carried from the first half to the end of the second, and a router
    # some of whose outputs are experts without a bank (they return their input)
    "longcat_flash": ("longcat_flash", "LongcatFlashConfig", "longcat_flash_v2",
                      "LongcatFlashV2Model"),
    # serving only, as one chip's share: a Mamba-2 OR a position-free softmax mixer and
    # THEN routed experts beside a shared one in every layer (two phases a layer from
    # different mixins), four scalar multipliers (the softmax scale on the queries), a
    # tied head: the embedding contracted on its last axis
    "granitemoehybrid": ("granitemoehybrid", "GraniteMoeHybridConfig", "granitemoehybrid_v2",
                         "GraniteMoeHybridV2Model"),
    "opt": ("decoder", "DecoderConfig", "decoder_v2", "DecoderV2Model"),
    "falcon": ("decoder", "DecoderConfig", "decoder_v2", "DecoderV2Model"),
    "phi": ("decoder", "DecoderConfig", "decoder_v2", "DecoderV2Model"),
    "gptj": ("decoder", "DecoderConfig", "decoder_v2", "DecoderV2Model"),
    "gpt_neox": ("decoder", "DecoderConfig", "decoder_v2", "DecoderV2Model"),
    # bloom (alibi) deliberately not in the table: its config is a DecoderConfig, and
    # DecoderV2Model raises with a pointer at the v1 path rather than serving wrong logits
}
# what register_policy added beside the table: model_type -> (config class, model class)
_REGISTERED: Dict[str, Tuple[type, type]] = {}


def register_policy(model_type: str, config_cls, model_cls) -> None:
    """Serve ``model_type`` (and, where no earlier entry has its config class,
    any ``config_cls`` that names no type) by ``model_cls``."""
    _REGISTERED[model_type] = (config_cls, model_cls)


def _model_cls(model_type: str) -> type:
    if model_type in _REGISTERED:
        return _REGISTERED[model_type][1]
    _, _, module, name = _FAMILIES[model_type]
    return getattr(import_module(_MODELS + module), name)


def model_cls_for(model_config) -> type:
    mt = getattr(model_config, "model_type", None)
    if mt in _REGISTERED or mt in _FAMILIES:
        return _model_cls(mt)
    # by config class: the table's rows name theirs, so nothing is imported to ask
    classes = [(c.__module__, c.__name__) for c in type(model_config).__mro__]
    for model_type, (module, name, _, _) in _FAMILIES.items():
        if (_CONFIGS + module, name) in classes:
            return _model_cls(model_type)
    for config_cls, model_cls in _REGISTERED.values():
        if isinstance(model_config, config_cls):
            return model_cls
    raise ValueError(f"no inference-v2 policy for {type(model_config).__name__} "
                     f"(model_type={mt!r}); known: {supported_model_types()}")


def supported_model_types():
    return sorted(set(_FAMILIES) | set(_REGISTERED))
