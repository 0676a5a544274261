"""DeepSeek-V3.2 ragged inference model (``model_type="deepseek_v32"``), over
the parameter tree of :mod:`deepspeed_tpu.models.deepseek_v32`.

What the architecture asks of the engine, and where each lives:

- **a latent KV group** and **absorbed attention** over it
  (``latent_rows.py``), the shared key's dims of query and row rotated by
  interleaved pairs; beside the latent row a token keeps an index key a layer,
  in a second pool under the sequence's ONE block table;
- **the learned selection**: the indexer scores every cached index key of the
  sequence and attention keeps the ``index_topk`` largest. A bucket whose block
  table holds no more than ``index_topk`` keys selects everything: its program
  has no indexer scores and no threshold (the index keys are still written),
  and ``min_table_bucket`` makes that ONE bucket;
- **one chip's share of the experts** and the group limit
  (``routed_experts.py``): ``RaggedMoE`` told ``held`` / ``first_held`` /
  ``n_group`` / ``topk_group``.

Scopes in the device trace, under ``attn``: ``latent_q``, ``latent_kv`` (down-
and up-projections, norms, rotary, the pools' writes), ``index`` (the indexer's
projections and scores), ``index_topk`` (the threshold), ``latent_kernel``,
``latent_out`` (``W_UV``, ``wo``); ``mlp`` (a dense layer), ``moe`` with
``moe/shared`` beside ``RaggedMoE``'s own.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.model_implementations.latent_rows import (LatentRows,
                                                                         _rotate_pairs)
from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root, _rotate_half
from deepspeed_tpu.inference.v2.model_implementations.routed_experts import RoutedExperts
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.models.deepseek_v32 import DeepseekV32Config
from deepspeed_tpu.models.mellum import rotary_cos_sin
from deepspeed_tpu.ops.pallas import latent_attention


def _layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.square(x32 - mean).mean(axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * p["weight"] + p["bias"]).astype(x.dtype)


class DeepseekV32V2Model(LatentRows, RoutedExperts, DSTransformerModelBase):

    def __init__(self, params, config: DeepseekV32Config, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        self._build_moes(range(config.num_hidden_layers - config.first_k_dense_replace),
                         config.n_routed_experts, config.num_experts_per_tok,
                         config.moe_intermediate_size, dense_layers=config.first_k_dense_replace,
                         held=config.experts_held, first_held=config.first_expert_held,
                         norm_topk_prob=config.norm_topk_prob, score_func=config.scoring_func,
                         route_scale=config.routed_scaling_factor, n_group=config.n_group,
                         topk_group=config.topk_group)
        self._rope = config.rope()

    # ----------------------------------------------------------- properties --
    @property
    def kv_state_widths(self):
        return super().kv_state_widths + (self._config.index_head_dim, )

    @property
    def min_table_bucket(self):
        """The smallest power of two of blocks that holds ``index_topk`` keys:
        every shorter table selects everything, in one program. Where the
        whole table (``max_context``) is at most four times that, the whole
        table: the selecting program is one code at every length and a short
        context in it is scored and keeps every key, so the one or two
        buckets between are programs to compile and to load for nothing."""
        block = self._engine_config.kv_block_size

        def bucket(keys):
            blocks = 4
            while blocks * block < keys:
                blocks *= 2
            return blocks

        floor = bucket(self._config.index_topk)
        whole = bucket(self._engine_config.state_manager.max_context)
        return whole if whole <= 4 * floor else floor

    def selects(self, max_blocks: int) -> bool:
        """A bucket of ``max_blocks`` table entries can hold more keys than
        ``index_topk``: its program scores and selects."""
        return max_blocks * self._engine_config.kv_block_size > self._config.index_topk

    # -------------------------------------------------------------- counters --
    def batch_counts(self, ragged_batch, steps=1):
        """``index_keys``: keys the indexer scores over the step's rows and
        layers (a row at position p scores p + 1; none in a bucket that selects
        everything); ``index_selected``: keys attention then reads. Over the
        ``steps`` of a chunk a row's position advances by one a step. Beside
        them the tiled grid's passes (``LatentRows._latent_passes``)."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        tok = np.asarray(batch["tok_meta"])
        keys = (tok[2][tok[3] > 0].astype(np.int64)[:, None] + 1 + np.arange(steps)[None, :])
        attended = int(np.minimum(keys, self._config.index_topk).sum()) * self.num_layers
        scored = int(keys.sum()) * self.num_layers if self.selects(self._bucket_of(batch)[2]) \
            else 0
        return {"index_keys": scored, "index_selected": attended if scored else 0,
                **self._latent_passes(batch, steps)}

    # --------------------------------------------------------------- phases --
    @jax.named_scope("attn")
    def _attn_phase(self, lp, li, x, cache, batch):
        cfg = self._config
        ap = lp["self_attn"]
        ip = ap["indexer"]
        T, H = x.shape[0], cfg.num_attention_heads
        N, R, C, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        eps = cfg.rms_norm_eps
        latent_pool, index_pool = cache
        cos, sin = rotary_cos_sin(self._rope, batch["token_pos"], R)
        cos, sin = cos[:, None, :], sin[:, None, :]
        kv_b = ap["wkv_b"]["kernel"].reshape(C, H, N + V)

        def lin(h, p):
            return h @ p["kernel"].astype(h.dtype)

        h = _rms(x, lp["input_layernorm"]["weight"], eps)
        with jax.named_scope("latent_q"):
            c_q = _rms(lin(h, ap["wq_a"]), ap["q_norm"]["weight"], eps)
            q = lin(c_q, ap["wq_b"]).reshape(T, H, N + R)
            q_pe = _rotate_pairs(q[..., N:], cos, sin)
            q_row = self._query_row(q, kv_b, latent_pool.shape[-1], q_pe)
        with jax.named_scope("latent_kv"):
            kv = lin(h, ap["wkv_a"])
            c_kv = _rms(kv[:, :C], ap["kv_norm"]["weight"], eps)
            k_pe = _rotate_pairs(kv[:, None, C:], cos, sin)[:, 0]
            latent_pool = self._keep_row(latent_pool, li, c_kv, k_pe, batch)
        selects = self.selects(batch["block_table"].shape[1])
        with jax.named_scope("index"):
            k_i = _layer_norm(lin(h, ip["wk"]), ip["k_norm"], eps)
            k_i = jnp.concatenate([_rotate_half(k_i[:, None, :R], cos, sin)[:, 0], k_i[:, R:]],
                                  axis=-1)
            index_pool = self._write_rows(index_pool, li, k_i, batch)
            if selects:
                q_i = lin(c_q, ip["wq_b"]).reshape(T, cfg.index_n_heads, cfg.index_head_dim)
                q_i = jnp.concatenate([_rotate_half(q_i[..., :R], cos, sin), q_i[..., R:]],
                                      axis=-1)
                w_i = lin(h, ip["weights_proj"]).astype(jnp.float32) \
                    * (cfg.index_n_heads**-0.5 * cfg.index_head_dim**-0.5)
        kernel, meta = self._latent_meta(T, batch)
        selection = ()
        if selects:
            score = latent_attention.latent_index_scores if kernel \
                else latent_attention.latent_index_scores_xla
            with jax.named_scope("index"):
                scores = score(q_i, w_i, index_pool, li, *meta)
            with jax.named_scope("index_topk"):
                selection = (scores, latent_attention.kth_largest(scores, cfg.index_topk))
        out = self._latent_attend(q_row, latent_pool, li, kernel, meta, *selection)
        return x + self._latent_out(out, kv_b, ap["wo"]), (latent_pool, index_pool)

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        lp = _root(params)[f"layers_{li}"]
        x, cache = self._attn_phase(lp, li, x, cache, batch)
        return self._ffn_phase(lp, li, x, batch), cache
