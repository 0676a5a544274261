"""Kimi Linear ragged inference model (``model_type="kimi_linear"``), over the
parameter tree of :mod:`deepspeed_tpu.models.kimi_linear`.

A layer is a mixer and a feed-forward, each under its own norm and residual;
the mixer is the gated delta rule where the layer is in ``kda_layers`` and
latent attention without positions where it is in ``full_attn_layers``; a
layer's index in its cache is its ordinal among the layers of its kind. What
the architecture asks of the engine is what two families asked before it, for
the first time in ONE cache:

- **a latent KV group** (``kv_state_widths``, one width: there is no index of
  keys): a token keeps one latent row a LATENT layer (``num_kv_layers``), in a
  pool under the sequence's block table; absorbed attention over every causal
  row, by ``ops/pallas/latent_attention.py``'s two grids without scores
  (``deepseek_v32_v2.py``'s call, the rotation left out of query and row);
- **a per-sequence state group** (``sequence_state``) beside it: the delta
  rule's float32 state and its three convolutions' tails a sequence a
  delta-rule layer, in slots; both forms of the rule in the pool
  (``solar_open2_v2.py``'s phase, with beta = sigmoid alone).

The cache pytree is ``((latent pool, ), state pool, conv pool)``. One chip's
share of the experts (``RaggedMoE`` told ``held`` / ``first_held``) beside a
shared expert; a dense SwiGLU in the leading layers; ONE block-table bucket,
the whole table (``min_table_bucket``): one layer in four reads it.

Scopes in the device trace: ``kda/...`` as Solar Open 2's; under ``attn``:
``latent_q``, ``latent_kv`` (the projections, the norm, the pool's write),
``latent_kernel``, ``latent_out`` (``W_UV``, ``o_proj``); ``mlp`` (a dense
layer), ``moe`` with ``moe/shared`` beside ``RaggedMoE``'s own.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.model_implementations.deepseek_v32_v2 import DeepseekV32V2Model
from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root
from deepspeed_tpu.inference.v2.model_implementations.solar_open2_v2 import SolarOpen2V2Model
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
from deepspeed_tpu.ops.pallas import latent_attention


class KimiLinearV2Model(DSTransformerModelBase):

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
        ep_cfg = getattr(engine_config, "expert_parallel", None)
        share = config.experts_held < config.n_routed_experts
        # one RaggedMoE a SPARSE layer: layer li's is _moes[li - first_k_dense_replace]
        self._moes = [
            RaggedMoE(num_experts=config.n_routed_experts, top_k=config.num_experts_per_tok,
                      capacity_factor=(ep_cfg.capacity_factor if ep_cfg is not None else 2.0),
                      layer_id=li, norm_topk_prob=config.norm_topk_prob,
                      score_func=config.scoring_func, route_scale=config.routed_scaling_factor,
                      held=config.experts_held if share else None,
                      first_held=config.first_expert_held)
            for li in range(config.first_k_dense_replace, config.num_hidden_layers)]
        if share:
            self.moe_count_names = ("moe_banks", "moe_assignments_local")

    # ----------------------------------------------------------- properties --
    @property
    def num_layers(self):
        return self._config.num_hidden_layers

    @property
    def num_kv_layers(self):
        return len(self._config.mla_here)

    @property
    def num_heads(self):
        return self._config.num_attention_heads

    @property
    def num_kv_heads(self):
        return 1  # every head reads the one latent row

    @property
    def head_dim(self):
        return self._config.qk_head_dim

    @property
    def vocab_size(self):
        return self._config.vocab_size

    @property
    def kv_state_widths(self):
        return (latent_attention.padded_width(self._config.latent_width), )

    # the delta rule's two pools at this model's heads, the whole table as the one bucket,
    # the kernels in the pool where it lies: Solar Open 2's, which read the config's
    # ``linear_*`` / ``kda_*`` fields and the state manager alone
    sequence_state = SolarOpen2V2Model.sequence_state
    min_table_bucket = SolarOpen2V2Model.min_table_bucket
    _in_the_pool = SolarOpen2V2Model._in_the_pool
    _kda_phase = SolarOpen2V2Model._kda_phase
    _kda_counts = SolarOpen2V2Model._kda_counts
    embed, unembed = SolarOpen2V2Model.embed, SolarOpen2V2Model.unembed
    # the latent kernels' two grids by bucket, the rows' write, the share's counters, and the
    # feed-forward (a dense SwiGLU in the leading layers, then the held experts beside a shared
    # one, under ``mlp`` / ``moe``): DeepSeek's
    attention_arm = DeepseekV32V2Model.attention_arm
    _ffn_phase = DeepseekV32V2Model._ffn_phase
    moe_path = DeepseekV32V2Model.moe_path
    dispatch_counts = DeepseekV32V2Model.dispatch_counts
    _write_rows = DeepseekV32V2Model._write_rows

    # -------------------------------------------------------------- counters --
    def batch_counts(self, ragged_batch, steps=1):
        """The delta rule's counts under Solar Open 2's names and meaning
        (``kda_rows``, ``kda_segments``, ``kda_chunk_visits``, ...), and what
        the latent kernels' rooflines are held to, over the step's rows and
        latent layers (over the ``steps`` of a chunk a row's position advances
        by one a step): ``latent_rows``, the causal rows the queries attend to
        (a row at position p, p + 1: there is no selection, every causal row
        counts), and ``latent_context_rows``, the rows of the pool the step
        needs at all (a sequence's context once a step, however many of its
        rows ask)."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        counts = self._kda_counts(ragged_batch, steps)
        tok, seq = np.asarray(batch["tok_meta"]), np.asarray(batch["seq_meta"])
        ahead = np.arange(steps, dtype=np.int64)[None, :] + 1
        last = seq[(seq[:, 3] > 0) & (seq[:, 1] > 0), 2]
        layers = self.num_kv_layers
        counts.update(
            latent_rows=int((tok[2][tok[3] > 0].astype(np.int64)[:, None] + ahead).sum()) * layers,
            latent_context_rows=int((tok[2][last].astype(np.int64)[:, None] + ahead).sum())
            * layers)
        return counts

    # --------------------------------------------------------------- phases --
    @jax.named_scope("attn")
    def _latent_phase(self, ap, ai, h, latent_pool, batch):
        """Latent layer ``ai`` (its ordinal: its layer of the latent pool) over
        the step's normed rows ``h``: ``DeepseekV32V2Model._attn_phase`` with a
        full-rank query, no rotation of the shared key's dims and no index."""
        cfg = self._config
        T, H = h.shape[0], cfg.num_attention_heads
        N, R, C, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        W = latent_pool.shape[-1]
        kv_b = ap["kv_b_proj"]["kernel"].reshape(C, H, N + V)

        def lin(x, name):
            return x @ ap[name]["kernel"].astype(x.dtype)

        with jax.named_scope("latent_q"):
            q = lin(h, "q_proj").reshape(T, H, N + R)
            # absorbed: a key's logit is one dot product with its latent row
            q_abs = jnp.einsum("thn,chn->thc", q[..., :N], kv_b[..., :N].astype(q.dtype))
            q_row = jnp.concatenate([q_abs, q[..., N:]], axis=-1).astype(jnp.float32) \
                * cfg.softmax_scale
            q_row = jnp.pad(q_row, ((0, 0), (0, 0), (0, W - C - R))).astype(h.dtype)
        with jax.named_scope("latent_kv"):
            kv = lin(h, "kv_a_proj_with_mqa")
            c_kv = _rms(kv[:, :C], ap["kv_a_layernorm"]["weight"], cfg.rms_norm_eps)
            row = jnp.pad(jnp.concatenate([c_kv, kv[:, C:]], axis=-1), ((0, 0), (0, W - C - R)))
            latent_pool = self._write_rows(latent_pool, ai, row, batch)
        with jax.named_scope("latent_kernel"):
            if self.attention_arm(T) != "latent_xla":
                out = latent_attention.latent_paged_attention(
                    q_row, latent_pool, ai, batch["block_table"], batch["seq_seen"],
                    batch["seq_ntok"], batch["last_tok"], value_width=C)
            else:
                out = latent_attention.latent_paged_attention_xla(
                    q_row, latent_pool, ai, batch["block_table"], batch["token_seq"],
                    batch["token_pos"], batch["token_valid"], value_width=C)
        with jax.named_scope("latent_out"):
            out = jnp.einsum("thc,chv->thv", out, kv_b[..., N:].astype(out.dtype))
            return lin(out.reshape(T, H * V), "o_proj"), latent_pool

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
        return self._ffn_phase(params, li, x, batch), ((latent_pool, ), *pools)
