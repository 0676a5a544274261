"""Solar Open 2 ragged inference model (``model_type="solar_open2"``), over the
parameter tree of :mod:`deepspeed_tpu.models.solar_open2`.

A layer is a mixer and the experts, each under its own norm and residual; the
mixer is softmax attention where the layer is in ``gqa_layers`` and the gated
delta rule everywhere else; a layer's index in its cache is its ordinal among
the layers of its kind. What the architecture asks of the engine:

- **a per-sequence state group** and **two forms of the delta rule**, both
  in the pool: what every model with delta-rule mixers shares, ``kda_base.py``;
- **the K/V array holds the softmax layers only** (``num_kv_layers``), ONE
  layer group; no rotary embedding; an output gate (``attn/gate``);
- **one chip's share of the experts** (``routed_experts.py``): ``RaggedMoE``
  told ``held`` / ``first_held``, SwiGLU banks, a shared expert beside them;
- **one block-table bucket** (``one_table_bucket``): the whole table. One
  layer in four reads it.

Scopes in the device trace: ``kda/...`` (``kda_base.py``); ``attn`` with
``attn/gate``; ``moe`` with ``moe/shared`` beside ``RaggedMoE``'s own.
"""

from deepspeed_tpu.inference.v2.model_implementations.kda_base import GatedDeltaRule
from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import PositionFreeGQA, _rms, _root
from deepspeed_tpu.inference.v2.model_implementations.routed_experts import RoutedExperts
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.models.solar_open2 import SolarOpen2Config


class SolarOpen2V2Model(GatedDeltaRule, PositionFreeGQA, RoutedExperts, DSTransformerModelBase):
    one_table_bucket = True

    def __init__(self, params, config: SolarOpen2Config, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        if not config.gqa_here or not config.kda_here:
            raise NotImplementedError(
                f"gqa_layers {config.gqa_layers} over {config.num_hidden_layers} layers: the "
                f"engine's pool is a K/V array beside a per-sequence state group, and a model "
                f"without a softmax layer or without a delta-rule layer would leave one empty")
        # a layer's index among the layers of its kind: its cache index
        self._ordinal = {li: n for kind in (config.gqa_here, config.kda_here)
                         for n, li in enumerate(kind)}
        self._build_moes(range(config.num_hidden_layers), config.n_routed_experts,
                         config.num_experts_per_tok, config.moe_intermediate_size,
                         held=config.experts_held, first_held=config.first_expert_held,
                         norm_topk_prob=config.norm_topk_prob, score_func="sigmoid",
                         route_scale=config.routed_scaling_factor)

    @property
    def num_kv_layers(self):
        return len(self._config.gqa_here)

    def batch_counts(self, ragged_batch, steps=1):
        """Beside the attention kernels' passes, the delta rule's counts
        (``GatedDeltaRule._kda_counts``)."""
        counts = super().batch_counts(ragged_batch, steps)
        counts.update(self._kda_counts(ragged_batch, steps))
        return counts

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        h = _rms(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
        kv, *pools = cache
        if cfg.is_gqa(li):
            out, kv = self._attn_phase(lp["self_attn"], self._ordinal[li], h, kv, attn_fn)
        else:
            out, pools = self._kda_phase(lp["linear_attn"], self._ordinal[li], h, pools, batch)
        x = x + out.astype(x.dtype)
        return self._ffn_phase(lp, li, x, batch), (kv, *pools)
