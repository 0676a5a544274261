"""LongCat-Flash ragged inference model (``model_type="longcat_flash"``), over
the parameter tree of :mod:`deepspeed_tpu.models.longcat_flash`.

A layer is two half-layers — each latent attention and a dense SwiGLU under
norms and residuals of their own — and ONE routed branch that reads the first
half's post-attention norm and is added at the end of the second half. What
the architecture asks of the engine, and where each lives:

- **a latent KV group** and **absorbed attention** over it
  (``latent_rows.py``; one width: there is no index of keys), the shared key's
  dims of query and row rotated by interleaved pairs, every causal row read;
  a model layer holds TWO latent layers of the pool (``num_kv_layers`` = 2 x
  layers; half ``i`` of layer ``l`` is cache layer ``2 l + i``);
- **a routed branch that outlives a half-layer** (``layer_forward``): computed
  beside the first dense half, carried past the second attention and the
  second dense half;
- **one chip's share of the experts, and experts without a bank**
  (``routed_experts.py``: ``RaggedMoE`` told ``held`` / ``first_held`` /
  ``zero_experts``): the router's last ``zero_expert_num`` outputs return
  their input, reach no sort and no bank, and are computed whole where the
  token lives.

ONE block-table bucket, the whole table (``one_table_bucket``), and one
sequence bucket (``one_sequence_bucket``): a replica that a router keeps full.

Scopes in the device trace, under ``attn`` (both halves): ``latent_q``,
``latent_kv`` (the projections, the norms, rotary, the pool's write),
``latent_kernel``, ``latent_out`` (``W_UV``, ``o_proj``); ``mlp`` (both dense
halves); ``moe`` with ``RaggedMoE``'s own (``moe/zero`` among them).
"""

import jax

from deepspeed_tpu.inference.v2.model_implementations.latent_rows import (LatentRows,
                                                                         _rotate_pairs)
from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root, _swiglu
from deepspeed_tpu.inference.v2.model_implementations.routed_experts import RoutedExperts
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.models.longcat_flash import LongcatFlashConfig
from deepspeed_tpu.models.mellum import rotary_cos_sin


class LongcatFlashV2Model(LatentRows, RoutedExperts, DSTransformerModelBase):
    one_table_bucket = True
    one_sequence_bucket = True

    def __init__(self, params, config: LongcatFlashConfig, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        self._build_moes(range(config.num_layers), config.n_routed_experts, config.moe_topk,
                         config.expert_ffn_hidden_size, held=config.experts_held,
                         first_held=config.first_expert_held, norm_topk_prob=False,
                         score_func="softmax", route_scale=config.routed_scaling_factor,
                         zero_experts=config.zero_expert_num)
        self._rope = config.rope()

    @property
    def num_kv_layers(self):
        return 2 * self._config.num_layers

    def batch_counts(self, ragged_batch, steps=1):
        """What the latent kernels' rooflines are held to
        (``LatentRows._latent_counts``), over both halves of every layer."""
        return self._latent_counts(ragged_batch, steps)

    # --------------------------------------------------------------- phases --
    @jax.named_scope("attn")
    def _latent_phase(self, ap, ai, h, latent_pool, batch):
        """Latent layer ``ai`` of the pool over the step's normed rows ``h``:
        a query through its bottleneck, both published factors, the shared
        key's dims of query and key rotated."""
        cfg = self._config
        T, H = h.shape[0], cfg.num_attention_heads
        N, R, C, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        eps = cfg.rms_norm_eps
        cos, sin = rotary_cos_sin(self._rope, batch["token_pos"], R)
        cos, sin = cos[:, None, :], sin[:, None, :]
        kv_b = ap["kv_b_proj"]["kernel"].reshape(C, H, N + V)

        def lin(x, name):
            return x @ ap[name]["kernel"].astype(x.dtype)

        with jax.named_scope("latent_q"):
            # the factor rides the norm's float32 gain: one rounding, not two
            c_q = _rms(lin(h, "q_a_proj"), ap["q_a_layernorm"]["weight"] * cfg.q_lora_scale, eps)
            q = lin(c_q, "q_b_proj").reshape(T, H, N + R)
            q_row = self._query_row(q, kv_b, latent_pool.shape[-1],
                                    _rotate_pairs(q[..., N:], cos, sin))
        with jax.named_scope("latent_kv"):
            kv = lin(h, "kv_a_proj_with_mqa")
            c_kv = _rms(kv[:, :C], ap["kv_a_layernorm"]["weight"] * cfg.kv_lora_scale, eps)
            k_pe = _rotate_pairs(kv[:, None, C:], cos, sin)[:, 0]
            latent_pool = self._keep_row(latent_pool, ai, c_kv, k_pe, batch)
        out = self._latent_attend(q_row, latent_pool, ai, *self._latent_meta(T, batch))
        return self._latent_out(out, kv_b, ap["o_proj"]), latent_pool

    def _half(self, lp, li, half, x, latent_pool, batch):
        """``(x + attention, the post-attention norm of that)`` of one half."""
        h = _rms(x, lp[f"input_layernorm_{half}"]["weight"], self._config.rms_norm_eps)
        out, latent_pool = self._latent_phase(lp[f"self_attn_{half}"], 2 * li + half, h,
                                              latent_pool, batch)
        x = x + out.astype(x.dtype)
        h = _rms(x, lp[f"post_attention_layernorm_{half}"]["weight"], self._config.rms_norm_eps)
        return x, h, latent_pool

    @staticmethod
    @jax.named_scope("mlp")
    def _dense(h, mp):
        return _swiglu(h, mp)

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        lp = _root(params)[f"layers_{li}"]
        (latent_pool, ) = cache
        x, h, latent_pool = self._half(lp, li, 0, x, latent_pool, batch)
        with jax.named_scope("moe"):
            # the routed branch: read HERE, added at the end of the second half
            routed = self._routed_beside_shared(li, h, lp["mlp"]["gate"], lp["mlp"]["experts"],
                                                lp["mlp"]["e_score_correction_bias"], None, batch)
        x = x + self._dense(h, lp["mlps_0"])
        x, h, latent_pool = self._half(lp, li, 1, x, latent_pool, batch)
        return x + self._dense(h, lp["mlps_1"]) + routed, (latent_pool, )
