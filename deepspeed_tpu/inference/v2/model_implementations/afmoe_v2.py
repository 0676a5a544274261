"""Afmoe ragged inference model (``model_type="afmoe"``: Trinity-Mini).

Llama's attention phase and Mixtral's routed experts over the parameter tree
of :mod:`deepspeed_tpu.models.afmoe`, with what the architecture adds read
from its config and its tree layer by layer: ``layer_types`` gives each layer
its attention window (the KV pool groups the layers by it) and says whether it
rotates q and k at all (a full layer carries no position encoding); the
tree's ``q_norm`` / ``k_norm`` / ``gate_proj`` are the attention phase's per-head
norm and output gate; each branch is normed coming out as well as going in; a
layer's feed-forward is the dense SwiGLU (the first ``num_dense_layers``) or
the routed experts (sigmoid scores, a selection bias, ``route_scale``) plus the
shared expert, which is the same dense SwiGLU; the embedding is multiplied by
sqrt(hidden).

Scopes in the device trace: ``attn/qk_norm``, ``attn/gate``, ``mlp/`` (a dense
layer), ``moe/`` with ``moe/shared`` (the shared expert) beside ``RaggedMoE``'s
own.
"""

import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root, _swiglu
from deepspeed_tpu.inference.v2.model_implementations.mellum_v2 import LayerTypedMoEModel
from deepspeed_tpu.models.afmoe import AfmoeConfig


class AfmoeV2Model(LayerTypedMoEModel):

    def __init__(self, params, config: AfmoeConfig, engine_config, state_manager=None):
        # one RaggedMoE a SPARSE layer: layer li's is _moes[li - num_dense_layers]
        super().__init__(params, config, engine_config, state_manager,
                         sparse_layers=config.num_hidden_layers - config.num_dense_layers,
                         norm_topk_prob=config.route_norm, score_func=config.score_func,
                         route_scale=config.route_scale, n_group=config.n_group,
                         topk_group=config.topk_group)

    def _attn_out(self, lp, y):
        return _rms(y, lp["post_attention_layernorm"]["weight"], self._config.rms_norm_eps)

    # --------------------------------------------------------------- phases --
    @jax.named_scope("embed")
    def embed(self, params, ids):
        x = _root(params)["embed_tokens"]["embedding"][ids].astype(self._config.dtype)
        if self._config.mup_enabled:
            x = x * jnp.asarray(math.sqrt(self._config.hidden_size), x.dtype)
        return x

    def _ffn_phase(self, params, li, x, batch=None):
        cfg = self._moe_config
        lp = _root(params)[f"layers_{li}"]
        with jax.named_scope("mlp" if cfg.is_dense(li) else "moe"):
            h = _rms(x, lp["pre_mlp_layernorm"]["weight"], cfg.rms_norm_eps)
            if cfg.is_dense(li):
                out = _swiglu(h, lp["mlp"])
            else:
                mp = lp["block_sparse_moe"]
                out = self._routed_beside_shared(
                    li - cfg.num_dense_layers, h, mp["gate"], mp["ExpertFFN_0"],
                    mp.get("expert_bias"), mp.get("shared_experts"), batch)
            return x + _rms(out, lp["post_mlp_layernorm"]["weight"], cfg.rms_norm_eps)
