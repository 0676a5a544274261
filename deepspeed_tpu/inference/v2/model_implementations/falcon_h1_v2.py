"""Falcon-H1 ragged inference model (``model_type="falcon_h1"``), over the
parameter tree of :mod:`deepspeed_tpu.models.falcon_h1`.

Every layer runs a Mamba-2 mixer and an attention mixer SIDE BY SIDE on the
same normed rows and adds both to the stream before one residual, then a gated
feed-forward. What the architecture asks of the engine:

- **every layer holds both kinds of cache**: layer ``li`` writes layer ``li``
  of the K/V array (``num_kv_layers == num_layers``) AND slot pools ``[li]`` of
  the per-sequence state group (``mamba2_base.py``: the group, the two forms of
  the scan, the counters), from one normed input;
- **the multipliers** where the published code applies them, the product in
  float32 (``mamba2_base.scaled_dot``): none is folded into a weight;
- **rotary embedding** over the whole head at ``rope_theta`` (1e11), the keys
  scaled by ``key_multiplier`` before it; 20 query heads over 4 K/V heads;
- **one block-table bucket** (``one_table_bucket``: the whole table) and **one
  sequence bucket** (``one_sequence_bucket``: ``max_ragged_sequence_count``):
  the mixers' state is a large share of a step only at many live sequences,
  and a forward program a sequence bucket would be four times the programs of
  a model this deep to compile; a step of few sequences pays for the padding
  rows' projections, which a dead row's kernels skip.

Scopes in the device trace: ``ssm/*`` (``mamba2_base.py``) and ``attn`` as
siblings inside a layer; ``mlp/gate_up``, ``mlp/down``; ``embed``, ``unembed``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root, _rotary_at
from deepspeed_tpu.inference.v2.model_implementations.mamba2_base import (Mamba2Model,
                                                                          Mamba2Shape,
                                                                          scaled_dot)
from deepspeed_tpu.models.falcon_h1 import FalconH1Config
from deepspeed_tpu.models.llama import rotary_embedding


class FalconH1V2Model(Mamba2Model):
    one_table_bucket = True
    one_sequence_bucket = True

    def __init__(self, params, config: FalconH1Config, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        self._rope = rotary_embedding(engine_config.state_manager.max_context, config.head_dim,
                                      config.rope_theta, jnp.float32)
        self._mamba2 = Mamba2Shape(
            mixers=config.num_hidden_layers, heads=config.mamba_n_heads,
            head_dim=config.mamba_d_head, groups=config.mamba_n_groups,
            state=config.mamba_d_state, conv_kernel=config.mamba_d_conv,
            chunk=config.mamba_chunk_size, eps=config.rms_norm_eps,
            in_scale=config.ssm_in_multiplier,
            column_scale=np.concatenate([np.full(width, m, np.float32)
                                         for width, m in config.in_proj_columns]),
            out_scale=config.ssm_out_multiplier)

    # ----------------------------------------------------------- properties --
    @property
    def mamba2(self):
        return self._mamba2

    # --------------------------------------------------------------- phases --
    @jax.named_scope("unembed")
    def unembed(self, params, x):
        r, cfg = _root(params), self._config
        x = _rms(x, r["final_layernorm"]["weight"], cfg.rms_norm_eps)
        return scaled_dot(x, r["lm_head"]["kernel"], cfg.lm_head_multiplier)

    @jax.named_scope("attn")
    def _attn_phase(self, ap, li, u, kv, attn_fn, batch):
        cfg = self._config
        T = u.shape[0]
        H, KVH, D = self.num_heads, self.num_kv_heads, self.head_dim
        if cfg.attention_in_multiplier != 1.0:
            u = u * jnp.asarray(cfg.attention_in_multiplier, u.dtype)
        q = (u @ ap["q_proj"]["kernel"].astype(u.dtype)).reshape(T, H, D)
        k = scaled_dot(u, ap["k_proj"]["kernel"], cfg.key_multiplier).reshape(T, KVH, D)
        v = (u @ ap["v_proj"]["kernel"].astype(u.dtype)).reshape(T, KVH, D)
        pos = batch["token_pos"]
        q, k = _rotary_at(q, pos, *self._rope), _rotary_at(k, pos, *self._rope)
        out, kv = attn_fn(q, k, v, kv, li)
        out = out.reshape(T, H * D).astype(u.dtype)
        return scaled_dot(out, ap["o_proj"]["kernel"], cfg.attention_out_multiplier), kv

    @jax.named_scope("mlp")
    def _ffn_phase(self, lp, x):
        cfg = self._config
        gate_m, down_m = cfg.mlp_multipliers
        f = _rms(x, lp["pre_ff_layernorm"]["weight"], cfg.rms_norm_eps)
        mp = lp["feed_forward"]
        with jax.named_scope("gate_up"):
            gate = scaled_dot(f, mp["gate_proj"]["kernel"], gate_m)
            y = (f @ mp["up_proj"]["kernel"].astype(f.dtype)) * jax.nn.silu(gate)
        with jax.named_scope("down"):
            return x + scaled_dot(y, mp["down_proj"]["kernel"], down_m)

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        lp = _root(params)[f"layers_{li}"]
        u = _rms(x, lp["input_layernorm"]["weight"], self._config.rms_norm_eps)
        kv, *pools = cache
        mamba, pools = self._mamba_phase(lp["mamba"], li, u, pools, batch)
        attn, kv = self._attn_phase(lp["self_attn"], li, u, kv, attn_fn, batch)
        x = x + (mamba + attn).astype(x.dtype)
        return self._ffn_phase(lp, x), (kv, *pools)
