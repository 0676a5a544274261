"""Kimi Linear ragged inference model (``model_type="kimi_linear"``), over the
parameter tree of :mod:`deepspeed_tpu.models.kimi_linear`.

A layer is a mixer and a feed-forward, each under its own norm and residual;
the mixer is the gated delta rule where the layer is in ``kda_layers`` and
latent attention without positions where it is in ``full_attn_layers``; a
layer's index in its cache is its ordinal among the layers of its kind. What
the architecture asks of the engine is what two families asked before it, for
the first time in ONE cache:

- **a latent KV group** (``latent_rows.py``; one width: there is no index of
  keys): a token keeps one latent row a LATENT layer (``num_kv_layers``), in a
  pool under the sequence's block table; absorbed attention over every causal
  row, by ``ops/pallas/latent_attention.py``'s two grids without scores, the
  shared key's dims of query and row as they are;
- **a per-sequence state group** (``sequence_state``) beside it: the delta
  rule's float32 state and its three convolutions' tails a sequence a
  delta-rule layer, in slots; both forms of the rule in the pool
  (``kda_base.py``, with beta = sigmoid alone).

The cache pytree is ``((latent pool, ), state pool, conv pool)``. One chip's
share of the experts (``routed_experts.py``: ``RaggedMoE`` told ``held`` /
``first_held``) beside a shared expert; a dense SwiGLU in the leading layers;
ONE block-table bucket, the whole table (``one_table_bucket``): one layer in
four reads it.

Scopes in the device trace: ``kda/...`` (``kda_base.py``); under ``attn``:
``latent_q``, ``latent_kv`` (the projections, the norm, the pool's write),
``latent_kernel``, ``latent_out`` (``W_UV``, ``o_proj``); ``mlp`` (a dense
layer), ``moe`` with ``moe/shared`` beside ``RaggedMoE``'s own.
"""

import jax

from deepspeed_tpu.inference.v2.model_implementations.kda_base import GatedDeltaRule
from deepspeed_tpu.inference.v2.model_implementations.latent_rows import LatentRows
from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root
from deepspeed_tpu.inference.v2.model_implementations.routed_experts import RoutedExperts
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.models.kimi_linear import KimiLinearConfig


class KimiLinearV2Model(GatedDeltaRule, LatentRows, RoutedExperts, DSTransformerModelBase):
    one_table_bucket = True

    def __init__(self, params, config: KimiLinearConfig, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        if not config.mla_here or not config.kda_here:
            raise NotImplementedError(
                f"kda_layers {config.kda_layers} / full_attn_layers {config.full_attn_layers} "
                f"over {config.num_hidden_layers} layers: the engine's pool is a latent group "
                f"beside a per-sequence state group, and a model without a latent layer or "
                f"without a delta-rule layer would leave one empty")
        # a layer's index among the layers of its kind: its cache index
        self._ordinal = {li: n for kind in (config.mla_here, config.kda_here)
                         for n, li in enumerate(kind)}
        self._build_moes(range(config.first_k_dense_replace, config.num_hidden_layers),
                         config.n_routed_experts, config.num_experts_per_tok,
                         config.moe_intermediate_size, dense_layers=config.first_k_dense_replace,
                         held=config.experts_held, first_held=config.first_expert_held,
                         norm_topk_prob=config.norm_topk_prob, score_func=config.scoring_func,
                         route_scale=config.routed_scaling_factor)

    @property
    def num_kv_layers(self):
        return len(self._config.mla_here)

    # -------------------------------------------------------------- counters --
    def batch_counts(self, ragged_batch, steps=1):
        """The delta rule's counts (``GatedDeltaRule._kda_counts``: ``kda_rows``,
        ``kda_segments``, ``kda_chunk_visits``, ...), and what the latent
        kernels' rooflines are held to (``LatentRows._latent_counts``:
        ``latent_rows``, ``latent_context_rows``)."""
        return {**self._kda_counts(ragged_batch, steps),
                **self._latent_counts(ragged_batch, steps)}

    # --------------------------------------------------------------- phases --
    @jax.named_scope("attn")
    def _latent_phase(self, ap, ai, h, latent_pool, batch):
        """Latent layer ``ai`` (its ordinal: its layer of the latent pool) over
        the step's normed rows ``h``: a full-rank query, no rotation of the
        shared key's dims and no index."""
        cfg = self._config
        T, H = h.shape[0], cfg.num_attention_heads
        N, R, C, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        kv_b = ap["kv_b_proj"]["kernel"].reshape(C, H, N + V)

        def lin(x, name):
            return x @ ap[name]["kernel"].astype(x.dtype)

        with jax.named_scope("latent_q"):
            q_row = self._query_row(lin(h, "q_proj").reshape(T, H, N + R), kv_b,
                                    latent_pool.shape[-1])
        with jax.named_scope("latent_kv"):
            kv = lin(h, "kv_a_proj_with_mqa")
            c_kv = _rms(kv[:, :C], ap["kv_a_layernorm"]["weight"], cfg.rms_norm_eps)
            latent_pool = self._keep_row(latent_pool, ai, c_kv, kv[:, C:], batch)
        out = self._latent_attend(q_row, latent_pool, ai, *self._latent_meta(T, batch))
        return self._latent_out(out, kv_b, ap["o_proj"]), latent_pool

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        h = _rms(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
        (latent_pool, ), *pools = cache
        if cfg.is_kda(li):
            out, pools = self._kda_phase(lp["linear_attn"], self._ordinal[li], h, pools, batch)
        else:
            out, latent_pool = self._latent_phase(lp["self_attn"], self._ordinal[li], h,
                                                  latent_pool, batch)
        x = x + out.astype(x.dtype)
        return self._ffn_phase(lp, li, x, batch), ((latent_pool, ), *pools)
