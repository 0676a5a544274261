"""Granite 4.0-H ragged inference model (``model_type="granitemoehybrid"``),
over the parameter tree of :mod:`deepspeed_tpu.models.granitemoehybrid`.

EVERY layer is a mixer — Mamba-2 or softmax attention, by ``layer_types`` — and
then routed experts beside a shared one, under two norms and two scaled
residuals. What the architecture asks of the engine, and where each lives:

- **two phases a layer from different mixins**: the mixer by its kind, its
  cache index its ordinal among the layers of its kind (``mamba2_base.py``: the
  per-sequence state group and the two forms of the scan;
  ``llama_v2.PositionFreeGQA``: the K/V array holds the attention layers only,
  ``num_kv_layers``), then ``routed_experts.py``'s layer in EVERY layer, one
  chip's share of the experts (``RaggedMoE`` told ``held`` / ``first_held``;
  its default router IS this family's: the top-k of the logits, a softmax over
  the chosen);
- **the four multipliers** where the published code applies them, each on a
  float32 product, none folded into a weight: the embedding's rows (the
  base's ``embed`` reads ``embedding_multiplier``); the queries (``query_scale``: ``attention_multiplier`` over the 1 / sqrt(head_dim)
  that the attention kernels apply, on the q projection's product, so that no
  kernel takes an argument); both residual adds; the logits;
- **a tied head**: the embedding contracted on its last axis — no second
  ``[vocabulary, hidden]`` matrix is made, held or read;
- **one block-table bucket** and **one sequence bucket** (``one_table_bucket``,
  ``one_sequence_bucket``): one layer in ten reads the table, and the mixers'
  state is a large share of a step only at many live sequences.

Scopes in the device trace: ``embed``; ``ssm/*`` (``mamba2_base.py``) or
``attn``; ``moe`` with ``moe/shared`` beside ``RaggedMoE``'s own; ``unembed``.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import PositionFreeGQA, _rms, _root
from deepspeed_tpu.inference.v2.model_implementations.mamba2_base import Mamba2Model, Mamba2Shape
from deepspeed_tpu.inference.v2.model_implementations.routed_experts import RoutedExperts
from deepspeed_tpu.models.granitemoehybrid import ATTENTION, MAMBA, GraniteMoeHybridConfig


class GraniteMoeHybridV2Model(PositionFreeGQA, RoutedExperts, Mamba2Model):
    one_table_bucket = True
    one_sequence_bucket = True

    def __init__(self, params, config: GraniteMoeHybridConfig, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        if not config.layers_of(ATTENTION) or not config.layers_of(MAMBA):
            raise NotImplementedError(
                f"layer_types {config.layer_types}: the engine's pool is a K/V array beside a "
                f"per-sequence state group, and a model without an attention layer or without a "
                f"Mamba-2 layer would leave one of them empty")
        # a layer's index among the layers of its kind: its cache index
        self._ordinal = {li: n for kind in (MAMBA, ATTENTION)
                         for n, li in enumerate(config.layers_of(kind))}
        self._build_moes(range(config.num_hidden_layers), config.num_local_experts,
                         config.num_experts_per_tok, config.intermediate_size,
                         held=config.experts_held, first_held=config.first_expert_held)
        self.query_scale = config.query_scale

    # ----------------------------------------------------------- properties --
    @property
    def num_kv_layers(self):
        return len(self._config.layers_of(ATTENTION))

    @property
    def mamba2(self):
        cfg = self._config
        return Mamba2Shape(mixers=len(cfg.layers_of(MAMBA)), heads=cfg.mamba_n_heads,
                           head_dim=cfg.mamba_d_head, groups=cfg.mamba_n_groups,
                           state=cfg.mamba_d_state, conv_kernel=cfg.mamba_d_conv,
                           chunk=cfg.mamba_chunk_size, eps=cfg.rms_norm_eps)

    # --------------------------------------------------------------- phases --
    @jax.named_scope("unembed")
    def unembed(self, params, x):
        r, cfg = _root(params), self._config
        x = _rms(x, r["norm"]["weight"], cfg.rms_norm_eps)
        table = r["embed_tokens"]["embedding"]  # [vocabulary, hidden]: the head, untransposed
        logits = jax.lax.dot_general(x, table.astype(x.dtype), (((1, ), (1, )), ((), ())),
                                     preferred_element_type=jnp.float32)
        return (logits / cfg.logits_scaling).astype(x.dtype)

    def _add(self, x, branch):
        """``x + residual_multiplier x branch``, the product and the sum in
        float32: one rounding."""
        scaled = branch.astype(jnp.float32) * self._config.residual_multiplier
        return (x.astype(jnp.float32) + scaled).astype(x.dtype)

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        kv, *pools = cache
        mamba = cfg.layer_types[li] == MAMBA
        # a mixer's norm and its add under the mixer's scope: no operation of a layer is nobody's
        mixer = jax.named_scope("ssm" if mamba else "attn")
        with mixer:
            h = _rms(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
        if mamba:
            out, pools = self._mamba_phase(lp["mamba"], self._ordinal[li], h, pools, batch)
        else:
            out, kv = self._attn_phase(lp["self_attn"], self._ordinal[li], h, kv, attn_fn)
        with mixer:
            x = self._add(x, out)
        with jax.named_scope("moe"):
            f = _rms(x, lp["post_attention_layernorm"]["weight"], cfg.rms_norm_eps)
            mp = lp["mlp"]
            out = self._routed_beside_shared(li, f, mp["gate"], mp["experts"], None,
                                             mp["shared_experts"], batch)
            return self._add(x, out), (kv, *pools)
