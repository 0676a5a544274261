"""Mixtral-style MoE causal LM (milestone config #4: Mixtral-8x7B EP ZeRO-3).

Reference serves Mixtral through inference-v2 policies with the fork's disaggregated
EP MoE (``cutlass_multi_gemm_ep.py``); for training this composes the Llama backbone
with the MoE FFN (``deepspeed_tpu/moe``) — top-2 gating like Mixtral's router.
"""

from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import (LlamaAttention, LlamaConfig, RMSNorm, cross_entropy_loss,
                                        random_params, rotary_embedding)
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.utils import groups


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    capacity_factor: float = 1.25
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = True
    gated_experts: bool = True  # Mixtral experts are SwiGLU (HF w1/w3 fused)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2, num_local_experts=4,
                    max_position_embeddings=128, remat=False)
        base.update(kw)
        return MixtralConfig(**base)

    def as_llama(self) -> LlamaConfig:
        return LlamaConfig(vocab_size=self.vocab_size, hidden_size=self.hidden_size,
                           intermediate_size=self.intermediate_size,
                           num_hidden_layers=self.num_hidden_layers,
                           num_attention_heads=self.num_attention_heads,
                           num_key_value_heads=self.num_key_value_heads,
                           max_position_embeddings=self.max_position_embeddings,
                           rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
                           dtype=self.dtype, remat=False)


class MixtralBlock(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        x = x + LlamaAttention(cfg.as_llama(), name="self_attn")(h, cos, sin)
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        moe_out, l_aux, _ = MoE(hidden_size=cfg.hidden_size,
                                num_experts=cfg.num_local_experts,
                                ffn_hidden_size=cfg.intermediate_size,
                                k=cfg.num_experts_per_tok,
                                capacity_factor=cfg.capacity_factor,
                                activation=nn.silu,
                                dtype=cfg.dtype,
                                gated=cfg.gated_experts,
                                name="block_sparse_moe")(h)
        return x + moe_out, l_aux


class MixtralForCausalLM(nn.Module):
    """Loss = CE + aux_loss_weight * sum(router aux losses)."""
    cfg: MixtralConfig
    aux_loss_weight: float = 0.01

    @nn.compact
    def __call__(self, batch):
        input_ids, labels = batch
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="embed_tokens")(input_ids)
        D = cfg.hidden_size // cfg.num_attention_heads
        cos, sin = rotary_embedding(input_ids.shape[1], D, cfg.rope_theta)

        block = nn.remat(MixtralBlock, policy=jax.checkpoint_policies.nothing_saveable) if cfg.remat \
            else MixtralBlock
        total_aux = 0.0
        for i in range(cfg.num_hidden_layers):
            x, l_aux = block(cfg, name=f"layers_{i}")(x, cos, sin)
            total_aux = total_aux + l_aux
        x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype, name="lm_head")(x)
        ce = cross_entropy_loss(logits, labels)
        return ce + self.aux_loss_weight * total_aux


def init_params(cfg: MixtralConfig, rng=None, batch_size=1, seq_len=16, param_dtype=None,
                mesh=None):
    """``(model, params)`` with random weights; full-width trees are made on the
    device by asking for ``param_dtype`` and/or ``mesh``
    (:func:`deepspeed_tpu.models.llama.random_params`). With ``mesh``, leaves
    are placed by :func:`mixtral_param_specs`: the stacked expert banks over the
    ``expert`` axis, attention/embedding/lm_head over ``model``."""
    model = MixtralForCausalLM(cfg)
    return model, random_params(model, rng, batch_size, seq_len, param_dtype, mesh,
                                mixtral_param_specs)


def mixtral_param_specs(params, model_axis=groups.MODEL_AXIS, expert_axis=groups.EXPERT_AXIS):
    """TP over attention/embed/lm_head + EP over the stacked expert banks,
    derived structurally by AutoTP (reference module_inject/auto_tp.py:188)."""
    from deepspeed_tpu.module_inject.auto_tp import auto_tp_specs
    return auto_tp_specs(params, model_axis=model_axis, expert_axis=expert_axis)
