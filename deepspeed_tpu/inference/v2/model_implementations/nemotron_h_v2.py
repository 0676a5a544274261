"""Nemotron-H ragged inference model (``model_type="nemotron_h"``), over the
parameter tree of :mod:`deepspeed_tpu.models.nemotron_h`.

A block is ONE mixer under one norm and one residual, its kind read from
``hybrid_override_pattern``; a block's index in the cache is its ordinal among
the blocks of its kind. What the architecture asks of the engine:

- **a per-sequence state group** and **two forms of the scan**: what every
  model with Mamba-2 mixers shares, ``mamba2_base.py``;
- **the K/V array holds the attention blocks only** (``num_kv_layers``), at
  ``num_key_value_heads`` heads; no rotary embedding;
- **one chip's share of the experts**: ``RaggedMoE`` told ``held`` /
  ``first_held``; relu squared is its ``activation`` over an ungated bank;
- **one block-table bucket** (``min_table_bucket``): the whole table. Two
  blocks in fourteen read it, and the kernels walk a sequence's live blocks,
  not the table's width: a program a bucket would be compiled for nothing.

Scopes in the device trace: ``ssm/in_proj``, ``ssm/conv``, ``ssm/scan`` (the
chunked form) or ``ssm/step`` (the recurrence), ``ssm/gate_norm``,
``ssm/out_proj``; ``attn``; ``moe`` with ``moe/shared`` beside ``RaggedMoE``'s
own; ``mlp`` (a dense block).
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root
from deepspeed_tpu.inference.v2.model_implementations.mamba2_base import Mamba2Model, Mamba2Shape
# the mixer's own reader is mamba2_base.py; the benchmark's control test looks ``ssm`` up here
from deepspeed_tpu.inference.v2.modules import ssm  # noqa: F401
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import _pow2_pad
from deepspeed_tpu.models.nemotron_h import ATTENTION, EXPERTS, MAMBA, NemotronHConfig


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _relu2_mlp(h, mp):
    return relu2(h @ mp["up_proj"]["kernel"].astype(h.dtype)) \
        @ mp["down_proj"]["kernel"].astype(h.dtype)


class NemotronHV2Model(Mamba2Model):

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
        ep_cfg = getattr(engine_config, "expert_parallel", None)
        share = config.experts_held < config.n_routed_experts
        self._moes = [
            RaggedMoE(num_experts=config.n_routed_experts, top_k=config.num_experts_per_tok,
                      capacity_factor=(ep_cfg.capacity_factor if ep_cfg is not None else 2.0),
                      layer_id=li, norm_topk_prob=config.norm_topk_prob, score_func="sigmoid",
                      route_scale=config.routed_scaling_factor,
                      held=config.experts_held if share else None,
                      first_held=config.first_expert_held)
            for li in config.layers_of(EXPERTS)]
        if share:
            self.moe_count_names = ("moe_banks", "moe_assignments_local")
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
            wi, wo = RaggedMoE.banks_in_lane_tiles(banks["wi"], banks["wo"])
            layer["mixer"] = dict(layer["mixer"], experts=dict(banks, wi=wi, wo=wo))
            root[f"layers_{li}"] = layer
        return dict(params, model=root) if "model" in params else root

    # ----------------------------------------------------------- properties --
    @property
    def num_layers(self):
        return self._config.num_hidden_layers

    @property
    def num_kv_layers(self):
        return len(self._config.layers_of(ATTENTION))

    @property
    def num_heads(self):
        return self._config.num_attention_heads

    @property
    def num_kv_heads(self):
        return self._config.num_key_value_heads

    @property
    def head_dim(self):
        return self._config.head_dim

    @property
    def vocab_size(self):
        return self._config.vocab_size

    @property
    def mamba2(self):
        cfg = self._config
        return Mamba2Shape(mixers=len(cfg.layers_of(MAMBA)), heads=cfg.mamba_num_heads,
                           head_dim=cfg.mamba_head_dim, groups=cfg.n_groups,
                           state=cfg.ssm_state_size, conv_kernel=cfg.conv_kernel,
                           chunk=cfg.chunk_size, eps=cfg.layer_norm_epsilon)

    @property
    def min_table_bucket(self):
        """The whole table (``max_context``), a power of two of blocks."""
        sm = self._engine_config.state_manager
        return _pow2_pad(-(-sm.max_context // self._engine_config.kv_block_size))

    # -------------------------------------------------------------- counters --
    def moe_path(self, n_padded):
        if not self._moes:
            return None
        return self._moes[0].path(n_padded, self._config.bank_width)

    def dispatch_counts(self, n_padded, n_tokens, steps=1):
        """As ``DeepseekV32V2Model.dispatch_counts``."""
        if not self._moes:
            return {}
        path = self.moe_path(n_padded)
        counts = {"moe_path": path,
                  "moe_rows": steps * sum(m.expert_rows(n_padded, 1, path) for m in self._moes),
                  "moe_assignments": steps * n_tokens * sum(m.top_k for m in self._moes)}
        if path == "capacity":
            counts["moe_banks"] = steps * sum(m.experts_here for m in self._moes)
        return counts

    # --------------------------------------------------------------- phases --
    @jax.named_scope("embed")
    def embed(self, params, ids):
        return _root(params)["embed_tokens"]["embedding"][ids].astype(self._config.dtype)

    @jax.named_scope("unembed")
    def unembed(self, params, x):
        r = _root(params)
        x = _rms(x, r["norm_f"]["weight"], self._config.layer_norm_epsilon)
        return x @ r["lm_head"]["kernel"].astype(x.dtype)

    @jax.named_scope("attn")
    def _attn_phase(self, mp, ai, h, kv, attn_fn):
        """Attention block ``ai`` (its ordinal: its layer of the K/V array):
        grouped-query, causal, no position encoding."""
        T = h.shape[0]
        H, KVH, D = self.num_heads, self.num_kv_heads, self.head_dim
        q = (h @ mp["q_proj"]["kernel"].astype(h.dtype)).reshape(T, H, D)
        k = (h @ mp["k_proj"]["kernel"].astype(h.dtype)).reshape(T, KVH, D)
        v = (h @ mp["v_proj"]["kernel"].astype(h.dtype)).reshape(T, KVH, D)
        out, kv = attn_fn(q, k, v, kv, ai)
        return out.reshape(T, H * D).astype(h.dtype) @ mp["o_proj"]["kernel"].astype(h.dtype), kv

    @jax.named_scope("moe")
    def _experts_phase(self, mp, ei, h, batch):
        out = self._moes[ei](h, mp["gate"], mp["experts"]["wi"], mp["experts"]["wo"],
                             activation=relu2, select_bias=mp["e_score_correction_bias"],
                             token_valid=batch["token_valid"],
                             banks_out=batch.get("moe_banks")).astype(h.dtype)
        if "shared_experts" in mp:  # always on: every token, once
            with jax.named_scope("shared"):
                out = out + _relu2_mlp(h, mp["shared_experts"])
        return out

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
