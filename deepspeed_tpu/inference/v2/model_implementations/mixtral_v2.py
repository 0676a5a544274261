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
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.models.mixtral import MixtralConfig
from deepspeed_tpu.utils import groups


class MixtralV2Model(LlamaV2Model):

    def __init__(self, params, config: MixtralConfig, engine_config, state_manager=None):
        super().__init__(params, config.as_llama(), engine_config, state_manager)
        self._moe_config = config
        self._moes = self._build_moes(engine_config, config.num_hidden_layers,
                                      config.num_local_experts, config.num_experts_per_tok)
        self._expert_width = config.intermediate_size

    @staticmethod
    def _build_moes(engine_config, num_layers, num_experts, top_k, **router):
        """One ``RaggedMoE`` a layer; the capacity factor is the engine's
        ``expert_parallel`` one, ``router`` what the model says of its routing
        beyond the default (softmax, renormalised over the chosen, unscaled)."""
        ep_cfg = getattr(engine_config, "expert_parallel", None)
        return [
            RaggedMoE(num_experts=num_experts, top_k=top_k,
                      capacity_factor=(ep_cfg.capacity_factor if ep_cfg is not None else 2.0),
                      layer_id=li, **router) for li in range(num_layers)
        ]

    def _expert_parallel(self):
        if not groups.mesh_is_initialized():
            return 1
        return int(groups.get_mesh().shape.get(self._moes[0].expert_axis, 1))

    def moe_path(self, n_padded):
        """``grouped`` / ``capacity``: how an ``n_padded``-token bucket's
        program routes (``modules/heuristics.py``; one answer for every layer,
        the layers being alike)."""
        return self._moes[0].path(n_padded, self._expert_width, self._expert_parallel())

    def dispatch_counts(self, n_padded, n_tokens, steps=1):
        """``moe_rows``: rows the expert GEMMs compute this step on the path
        the bucket takes (``moe_path``), summed over the layers: every
        expert's every slot on the capacity path, a row a padded assignment on
        the grouped one; ``moe_assignments``: live tokens x top-k x layers,
        what had to be. Both over the ``steps`` of a ``decode_loop`` chunk. On
        the capacity path also ``moe_banks``, the expert banks the GEMMs read:
        every expert of every expert layer, every step. On the grouped path
        that count is the routing's, out of the device with the step's result
        (``RaggedMoE.__call__``'s ``banks_out``), and whoever fetches the
        result adds it."""
        ep, path = self._expert_parallel(), self.moe_path(n_padded)
        counts = {"moe_path": path,
                  "moe_rows": steps * sum(m.expert_rows(n_padded, ep, path) for m in self._moes),
                  "moe_assignments": steps * n_tokens * sum(m.top_k for m in self._moes)}
        if path == "capacity":
            counts["moe_banks"] = steps * sum(m.num_experts for m in self._moes)
        return counts

    @property
    def num_layers(self):
        return self._moe_config.num_hidden_layers

    def _moe_params(self, params, li):
        mp = _root(params)[f"layers_{li}"]["block_sparse_moe"]
        return mp["gate"], mp["ExpertFFN_0"]["wi"], mp["ExpertFFN_0"]["wo"]

    @jax.named_scope("moe")
    def _ffn_phase(self, params, li, x, batch=None):
        cfg = self._moe_config
        lp = _root(params)[f"layers_{li}"]
        h = _rms(x, lp["post_attention_layernorm"]["weight"], cfg.rms_norm_eps)
        gate_w, wi, wo = self._moe_params(params, li)
        out = self._moes[li](h, gate_w, wi, wo, activation=jax.nn.silu,
                             **self._gating_inputs(batch))
        return x + out.astype(x.dtype)

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
