"""DeepSeek-V3.2 ragged inference model (``model_type="deepseek_v32"``), over
the parameter tree of :mod:`deepspeed_tpu.models.deepseek_v32`.

What the architecture asks of the engine, and where each lives:

- **a latent KV group** (``kv_state_widths``): a token keeps a latent row
  (``kv_lora_rank`` + the shared rotary key, padded to whole lane tiles) and an
  index key a layer, in two pools under the sequence's ONE block table
  (``ragged/kv_cache.py``). The new rows are scattered into the pools in place,
  then read back by the kernels with the rest of the context;
- **absorbed attention**: ``W_UK`` is folded into the queries and ``W_UV``
  applied to the heads' outputs, so every head reads the one row a key
  (``ops/pallas/latent_attention.py``: per token for the decode buckets, per
  query tile above them; ``jax.numpy`` off the TPU);
- **the learned selection**: the indexer scores every cached index key of the
  sequence and attention keeps the ``index_topk`` largest. A bucket whose block
  table holds no more than ``index_topk`` keys selects everything: its program
  has no indexer scores and no threshold (the index keys are still written),
  and ``min_table_bucket`` makes that ONE bucket;
- **one chip's share of the experts** and the group limit: ``RaggedMoE`` told
  ``held`` / ``first_held`` / ``n_group`` / ``topk_group``.

Scopes in the device trace, under ``attn``: ``latent_q``, ``latent_kv`` (down-
and up-projections, norms, rotary, the pools' writes), ``index`` (the indexer's
projections and scores), ``index_topk`` (the threshold), ``latent_kernel``,
``latent_out`` (``W_UV``, ``wo``); ``mlp`` (a dense layer), ``moe`` with
``moe/shared`` beside ``RaggedMoE``'s own.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import (_rms, _root, _rotate_half,
                                                                       _swiglu)
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.models.deepseek_v32 import DeepseekV32Config
from deepspeed_tpu.models.mellum import rotary_cos_sin
from deepspeed_tpu.ops.pallas import latent_attention


def _rotate_pairs(x, cos, sin):
    """x: [T, H, D]; cos, sin: [T, 1, D/2]; rotates the INTERLEAVED pairs
    (x[2i], x[2i + 1])."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape) \
        .astype(x.dtype)


def _layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.square(x32 - mean).mean(axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * p["weight"] + p["bias"]).astype(x.dtype)


class DeepseekV32V2Model(DSTransformerModelBase):

    def __init__(self, params, config: DeepseekV32Config, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        ep_cfg = getattr(engine_config, "expert_parallel", None)
        share = config.experts_held < config.n_routed_experts
        # one RaggedMoE a SPARSE layer: layer li's is _moes[li - first_k_dense_replace]
        self._moes = [
            RaggedMoE(num_experts=config.n_routed_experts, top_k=config.num_experts_per_tok,
                      capacity_factor=(ep_cfg.capacity_factor if ep_cfg is not None else 2.0),
                      layer_id=li, norm_topk_prob=config.norm_topk_prob,
                      score_func=config.scoring_func, route_scale=config.routed_scaling_factor,
                      n_group=config.n_group, topk_group=config.topk_group,
                      held=config.experts_held if share else None,
                      first_held=config.first_expert_held)
            for li in range(config.num_hidden_layers - config.first_k_dense_replace)]
        if share:
            self.moe_count_names = ("moe_banks", "moe_assignments_local")
        self._rope = config.rope()

    # ----------------------------------------------------------- properties --
    @property
    def num_layers(self):
        return self._config.num_hidden_layers

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
        return (latent_attention.padded_width(self._config.latent_width),
                self._config.index_head_dim)

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
    def attention_arm(self, T):
        """``latent_token`` / ``latent_tiled`` (the kernels' two grids) or
        ``latent_xla``: an explicit ``use_paged_kernel`` wins, else the kernel
        wherever the backend is a TPU and the pools sit on one device (a Mosaic
        kernel cannot be partitioned, and a latent row has no head to shard)."""
        flag = getattr(self._engine_config, "use_paged_kernel", None)
        if flag is None:
            placed = None if self._state_manager is None else self._state_manager.kv_cache.sharding
            flag = jax.default_backend() == "tpu" and (placed is None or placed.mesh.size == 1)
        if not flag:
            return "latent_xla"
        return "latent_token" if latent_attention.tile_tokens(T) == 1 else "latent_tiled"

    def moe_path(self, n_padded):
        return self._moes[0].path(n_padded, self._config.moe_intermediate_size)

    def dispatch_counts(self, n_padded, n_tokens, steps=1):
        """As ``MixtralV2Model.dispatch_counts``; ``moe_assignments`` counts
        every assignment the router made, held here or not (what landed here
        is the device's to say: ``moe_assignments_local``)."""
        path = self.moe_path(n_padded)
        counts = {"moe_path": path,
                  "moe_rows": steps * sum(m.expert_rows(n_padded, 1, path) for m in self._moes),
                  "moe_assignments": steps * n_tokens * sum(m.top_k for m in self._moes)}
        if path == "capacity":
            counts["moe_banks"] = steps * sum(m.experts_here for m in self._moes)
        return counts

    def batch_counts(self, ragged_batch, steps=1):
        """``index_keys``: keys the indexer scores over the step's rows and
        layers (a row at position p scores p + 1; none in a bucket that selects
        everything); ``index_selected``: keys attention then reads. Over the
        ``steps`` of a chunk a row's position advances by one a step."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        tok = np.asarray(batch["tok_meta"])
        keys = (tok[2][tok[3] > 0].astype(np.int64)[:, None] + 1 + np.arange(steps)[None, :])
        attended = int(np.minimum(keys, self._config.index_topk).sum()) * self.num_layers
        scored = int(keys.sum()) * self.num_layers if self.selects(self._bucket_of(batch)[2]) \
            else 0
        return {"index_keys": scored, "index_selected": attended if scored else 0}

    # --------------------------------------------------------------- phases --
    @jax.named_scope("embed")
    def embed(self, params, ids):
        return _root(params)["embed_tokens"]["embedding"][ids].astype(self._config.dtype)

    @jax.named_scope("unembed")
    def unembed(self, params, x):
        r = _root(params)
        x = _rms(x, r["norm"]["weight"], self._config.rms_norm_eps)
        return x @ r["lm_head"]["kernel"].astype(x.dtype)

    def _write_rows(self, pool, li, rows, batch):
        """Scatter ``rows`` [T, width] into ``pool`` layer ``li`` at the
        tokens' positions, in place; padding and unallocated table slots route
        to a positive out-of-bounds block and are dropped."""
        NB, bs = pool.shape[1], pool.shape[2]
        table, pos = batch["block_table"], batch["token_pos"]
        ids = table[batch["token_seq"], jnp.minimum(pos // bs, table.shape[1] - 1)]
        ids = jnp.where(batch["token_valid"] & (ids >= 0), ids, NB)
        return pool.at[li, ids, pos % bs].set(rows.astype(pool.dtype), mode="drop")

    @jax.named_scope("attn")
    def _attn_phase(self, params, li, x, cache, batch):
        cfg = self._config
        ap = _root(params)[f"layers_{li}"]["self_attn"]
        ip = ap["indexer"]
        T, H = x.shape[0], cfg.num_attention_heads
        N, R, C, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        eps = cfg.rms_norm_eps
        latent_pool, index_pool = cache
        W = latent_pool.shape[-1]
        pos = batch["token_pos"]
        cos, sin = rotary_cos_sin(self._rope, pos, R)
        cos, sin = cos[:, None, :], sin[:, None, :]
        kv_b = ap["wkv_b"]["kernel"].reshape(C, H, N + V)

        def lin(h, p):
            return h @ p["kernel"].astype(h.dtype)

        h = _rms(x, _root(params)[f"layers_{li}"]["input_layernorm"]["weight"], eps)
        with jax.named_scope("latent_q"):
            c_q = _rms(lin(h, ap["wq_a"]), ap["q_norm"]["weight"], eps)
            q = lin(c_q, ap["wq_b"]).reshape(T, H, N + R)
            q_pe = _rotate_pairs(q[..., N:], cos, sin)
            # absorbed: a key's logit is one dot product with its latent row
            q_abs = jnp.einsum("thn,chn->thc", q[..., :N], kv_b[..., :N].astype(q.dtype))
            q_row = jnp.concatenate([q_abs, q_pe], axis=-1).astype(jnp.float32) \
                * cfg.softmax_scale
            q_row = jnp.pad(q_row, ((0, 0), (0, 0), (0, W - C - R))).astype(x.dtype)
        with jax.named_scope("latent_kv"):
            kv = lin(h, ap["wkv_a"])
            c_kv = _rms(kv[:, :C], ap["kv_norm"]["weight"], eps)
            k_pe = _rotate_pairs(kv[:, None, C:], cos, sin)[:, 0]
            row = jnp.pad(jnp.concatenate([c_kv, k_pe], axis=-1), ((0, 0), (0, W - C - R)))
            latent_pool = self._write_rows(latent_pool, li, row, batch)
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
        kernel = self.attention_arm(T) != "latent_xla"
        if kernel:
            meta = (batch["block_table"], batch["seq_seen"], batch["seq_ntok"], batch["last_tok"])
            score, attend = (latent_attention.latent_index_scores,
                             latent_attention.latent_paged_attention)
        else:
            meta = (batch["block_table"], batch["token_seq"], pos, batch["token_valid"])
            score, attend = (latent_attention.latent_index_scores_xla,
                             latent_attention.latent_paged_attention_xla)
        selection = ()
        if selects:
            with jax.named_scope("index"):
                scores = score(q_i, w_i, index_pool, li, *meta)
            with jax.named_scope("index_topk"):
                selection = (scores, latent_attention.kth_largest(scores, cfg.index_topk))
        with jax.named_scope("latent_kernel"):
            out = attend(q_row, latent_pool, li, *meta, *selection, value_width=C)
        with jax.named_scope("latent_out"):
            out = jnp.einsum("thc,chv->thv", out, kv_b[..., N:].astype(out.dtype))
            out = lin(out.reshape(T, H * V), ap["wo"])
        return x + out, (latent_pool, index_pool)

    def _ffn_phase(self, params, li, x, batch):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        with jax.named_scope("mlp" if cfg.is_dense(li) else "moe"):
            h = _rms(x, lp["post_attention_layernorm"]["weight"], cfg.rms_norm_eps)
            mp = lp["mlp"]
            if cfg.is_dense(li):
                return x + _swiglu(h, mp)
            out = self._moes[li - cfg.first_k_dense_replace](
                h, mp["gate"], mp["experts"]["wi"], mp["experts"]["wo"], activation=jax.nn.silu,
                select_bias=mp["e_score_correction_bias"], token_valid=batch["token_valid"],
                banks_out=batch.get("moe_banks")).astype(x.dtype)
            if "shared_experts" in mp:  # always on: every token, once
                with jax.named_scope("shared"):
                    out = out + _swiglu(h, mp["shared_experts"])
            return x + out

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        x, cache = self._attn_phase(params, li, x, cache, batch)
        return self._ffn_phase(params, li, x, batch), cache
