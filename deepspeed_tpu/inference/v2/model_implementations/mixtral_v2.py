"""Mixtral ragged inference model with expert parallelism (fork feature).

Reference: ``deepspeed/inference/v2/model_implementations/mixtral/`` + the fork's
``DSMultiGemmMoEEp`` MoE path (``cutlass_multi_gemm_ep.py:32``).

Consumes the TRAINING param tree of :class:`deepspeed_tpu.models.mixtral.
MixtralForCausalLM` (``layers_i.block_sparse_moe.{gate, ExpertFFN_0.{wi,wo}}``),
so EP inference logits can be tested against the single-device training forward.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import LlamaV2Model, _rms, _root
from deepspeed_tpu.inference.v2.model_implementations.routed_experts import RoutedExperts
from deepspeed_tpu.models.mixtral import MixtralConfig
from deepspeed_tpu.utils import groups


class MixtralV2Model(RoutedExperts, LlamaV2Model):

    def __init__(self, params, config: MixtralConfig, engine_config, state_manager=None):
        super().__init__(params, config.as_llama(), engine_config, state_manager)
        self._moe_config = config
        self._build_moes(range(config.num_hidden_layers), config.num_local_experts,
                         config.num_experts_per_tok, config.intermediate_size)

    def _expert_parallel(self):
        if not groups.mesh_is_initialized():
            return 1
        return int(groups.get_mesh().shape.get(self._moes[0].expert_axis, 1))

    @jax.named_scope("moe")
    def _ffn_phase(self, params, li, x, batch=None):
        lp = _root(params)[f"layers_{li}"]
        h = _rms(x, lp["post_attention_layernorm"]["weight"], self._moe_config.rms_norm_eps)
        mp = lp["block_sparse_moe"]
        return x + self._routed_beside_shared(li, h, mp["gate"], mp["ExpertFFN_0"], None, None,
                                              batch)

    @staticmethod
    def _gating_inputs(batch):
        """``token_valid``, ``gate_seed`` and ``banks_out`` of a step's batch, as
        ``RaggedMoE`` takes them. The seed is data-dependent: live token
        positions differ every decode step, so simulated-gating routing varies
        across forwards (the fork's load-testing intent) without threading a
        host counter through jit. ``banks_out`` is the program's list of the
        banks each grouped expert layer touched (``_forward_impl`` returns it
        stacked; a verify step keeps none)."""
        if batch is None:
            return {"token_valid": None, "gate_seed": None}
        return {"token_valid": batch["token_valid"],
                "gate_seed": jnp.sum(jnp.where(batch["token_valid"], batch["token_pos"],
                                               0)).astype(jnp.int32),
                "banks_out": batch.get("moe_banks")}

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        x, cache = self._attn_phase(params, li, x, cache, attn_fn, batch)
        return self._ffn_phase(params, li, x, batch=batch), cache
