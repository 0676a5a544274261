"""Nemotron-H ragged inference model (``model_type="nemotron_h"``), over the
parameter tree of :mod:`deepspeed_tpu.models.nemotron_h`.

A block is ONE mixer under one norm and one residual, its kind read from
``hybrid_override_pattern``; a block's index in the cache is its ordinal among
the blocks of its kind. What the architecture asks of the engine:

- **a per-sequence state group** and **two forms of the scan**: what every
  model with Mamba-2 mixers shares, ``mamba2_base.py``;
- **the K/V array holds the attention blocks only** (``num_kv_layers``), at
  ``num_key_value_heads`` heads; no rotary embedding;
- **one chip's share of the experts** (``routed_experts.py``): ``RaggedMoE``
  told ``held`` / ``first_held``; relu squared is its ``activation`` over an
  ungated bank;
- **one block-table bucket** (``one_table_bucket``): the whole table. Two
  blocks in fourteen read it, and the kernels walk a sequence's live blocks,
  not the table's width: a program a bucket would be compiled for nothing.

Scopes in the device trace: ``ssm/in_proj``, ``ssm/conv``, ``ssm/scan`` (the
chunked form) or ``ssm/step`` (the recurrence), ``ssm/gate_norm``,
``ssm/out_proj``; ``attn``; ``moe`` with ``moe/shared`` beside ``RaggedMoE``'s
own; ``mlp`` (a dense block).
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import PositionFreeGQA, _rms, _root
from deepspeed_tpu.inference.v2.model_implementations.mamba2_base import Mamba2Model, Mamba2Shape
from deepspeed_tpu.inference.v2.model_implementations.routed_experts import RoutedExperts
# the mixer's own reader is mamba2_base.py; the benchmark's control test looks ``ssm`` up here
from deepspeed_tpu.inference.v2.modules import ssm  # noqa: F401
from deepspeed_tpu.models.nemotron_h import ATTENTION, EXPERTS, MAMBA, NemotronHConfig


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _relu2_mlp(h, mp):
    return relu2(h @ mp["up_proj"]["kernel"].astype(h.dtype)) \
        @ mp["down_proj"]["kernel"].astype(h.dtype)


class NemotronHV2Model(PositionFreeGQA, RoutedExperts, Mamba2Model):
    final_norm = ("norm_f", "layer_norm_epsilon")
    one_table_bucket = True

    def __init__(self, params, config: NemotronHConfig, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        if not config.layers_of(ATTENTION) or not config.layers_of(MAMBA):
            raise NotImplementedError(
                f"hybrid_override_pattern {config.hybrid_override_pattern!r}: the engine's pool "
                f"is a K/V array beside a per-sequence state group, and a model without an "
                f"attention block or without a Mamba-2 block would leave one of them empty")
        # a block's index among the blocks of its kind: its cache index
        self._ordinal = {li: n for kind in set(config.hybrid_override_pattern)
                         for n, li in enumerate(config.layers_of(kind))}
        self._build_moes(config.layers_of(EXPERTS), config.n_routed_experts,
                         config.num_experts_per_tok, config.bank_width,
                         held=config.experts_held, first_held=config.first_expert_held,
                         norm_topk_prob=config.norm_topk_prob, score_func="sigmoid",
                         route_scale=config.routed_scaling_factor)
        self._params = self._banks_in_lane_tiles(self._params)

    def _banks_in_lane_tiles(self, params):
        """The tree with every expert block's banks ``bank_width`` wide: as
        given where :func:`init_params` made them (so already), padded once
        here where a checkpoint brings them at the published width — never
        left to the grouped matmul's silent ``ragged_dot``."""
        cfg, root = self._config, dict(_root(params))
        narrow = [li for li in cfg.layers_of(EXPERTS)
                  if root[f"layers_{li}"]["mixer"]["experts"]["wo"].shape[-2] != cfg.bank_width]
        if not narrow:
            return params
        for li in narrow:
            layer = dict(root[f"layers_{li}"])
            banks = layer["mixer"]["experts"]
            wi, wo = self._moes[0].banks_in_lane_tiles(banks["wi"], banks["wo"])
            layer["mixer"] = dict(layer["mixer"], experts=dict(banks, wi=wi, wo=wo))
            root[f"layers_{li}"] = layer
        return dict(params, model=root) if "model" in params else root

    # ----------------------------------------------------------- properties --
    @property
    def num_kv_layers(self):
        return len(self._config.layers_of(ATTENTION))

    @property
    def mamba2(self):
        cfg = self._config
        return Mamba2Shape(mixers=len(cfg.layers_of(MAMBA)), heads=cfg.mamba_num_heads,
                           head_dim=cfg.mamba_head_dim, groups=cfg.n_groups,
                           state=cfg.ssm_state_size, conv_kernel=cfg.conv_kernel,
                           chunk=cfg.chunk_size, eps=cfg.layer_norm_epsilon)

    # --------------------------------------------------------------- phases --
    @jax.named_scope("moe")
    def _experts_phase(self, mp, ei, h, batch):
        return self._routed_beside_shared(ei, h, mp["gate"], mp["experts"],
                                          mp["e_score_correction_bias"], mp.get("shared_experts"),
                                          batch, activation=relu2, dense=_relu2_mlp)

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        kind, n = cfg.hybrid_override_pattern[li], self._ordinal[li]
        h = _rms(x, lp["norm"]["weight"], cfg.layer_norm_epsilon)
        kv, *pools = cache
        if kind == MAMBA:
            out, pools = self._mamba_phase(lp["mixer"], n, h, pools, batch)
        elif kind == ATTENTION:
            out, kv = self._attn_phase(lp["mixer"], n, h, kv, attn_fn)
        elif kind == EXPERTS:
            out = self._experts_phase(lp["mixer"], n, h, batch)
        else:
            with jax.named_scope("mlp"):
                out = _relu2_mlp(h, lp["mixer"])
        return x + out.astype(x.dtype), (kv, *pools)
