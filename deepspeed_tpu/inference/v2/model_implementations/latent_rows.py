"""What the ragged models whose attention keeps LATENT rows share
(``deepseek_v32_v2.py``: rotated, under a learned selection;
``kimi_linear_v2.py``: position-free, every causal row;
``longcat_flash_v2.py``: rotated, every causal row, two latent layers a model
layer): the latent KV group they ask of the engine and the steps of absorbed
attention over it.

- **a latent KV group** (``kv_state_widths``): a token keeps one latent row a
  latent layer (``kv_lora_rank`` + the shared key's ``qk_rope_head_dim``,
  padded to whole lane tiles) in a pool under the sequence's block table
  (``ragged/kv_cache.py``). The new rows are scattered into the pool in place
  (:meth:`_write_rows`), then read back by the kernels with the rest of the
  context;
- **absorbed attention**: ``W_UK`` is folded into the queries
  (:meth:`_query_row`) and ``W_UV`` applied to the heads' outputs
  (:meth:`_latent_out`), so every head reads the one row a key
  (``ops/pallas/latent_attention.py``: per token for the decode buckets, per
  query tile above them; ``jax.numpy`` off the TPU). What a family does to the
  shared key's dims of query and row (a rotation, or nothing) and what it
  selects are its own: it hands these steps the values.

Reads the config's ``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``qk_head_dim``
/ ``kv_lora_rank`` / ``v_head_dim`` / ``latent_width`` / ``softmax_scale``, the
engine config's ``use_paged_kernel`` and where the state manager placed the
cache.

Scopes in the device trace: ``latent_kernel``, ``latent_out`` (``W_UV``, the
output projection); ``latent_q`` and ``latent_kv`` are the caller's.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.pallas import latent_attention


def _rotate_pairs(x, cos, sin):
    """x: [T, H, D]; cos, sin: [T, 1, D/2]; rotates the INTERLEAVED pairs
    (x[2i], x[2i + 1])."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape) \
        .astype(x.dtype)


class LatentRows:

    @property
    def num_kv_heads(self):
        return 1  # every head reads the one latent row

    @property
    def head_dim(self):
        return self._config.qk_head_dim

    @property
    def kv_state_widths(self):
        return (latent_attention.padded_width(self._config.latent_width), )

    def _latent_counts(self, ragged_batch, steps=1):
        """What the latent kernels' rooflines are held to where attention
        reads EVERY causal row (no selection), over the step's rows and the
        ``num_kv_layers`` latent layers (over the ``steps`` of a chunk a row's
        position advances by one a step): ``latent_rows``, the causal rows the
        queries attend to (a row at position p, p + 1), and
        ``latent_context_rows``, the rows of the pool the step needs at all (a
        sequence's context once a step, however many of its rows ask). Beside
        them the tiled grid's passes (:meth:`_latent_passes`)."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        tok, seq = np.asarray(batch["tok_meta"]), np.asarray(batch["seq_meta"])
        ahead = np.arange(steps, dtype=np.int64)[None, :] + 1
        last = seq[(seq[:, 3] > 0) & (seq[:, 1] > 0), 2]
        layers = self.num_kv_layers
        return {"latent_rows":
                int((tok[2][tok[3] > 0].astype(np.int64)[:, None] + ahead).sum()) * layers,
                "latent_context_rows":
                int((tok[2][last].astype(np.int64)[:, None] + ahead).sum()) * layers,
                **self._latent_passes(batch, steps)}

    def _latent_passes(self, batch, steps=1):
        """A bucket on the tiled latent grid: ``latent_passes``, the (sequence,
        tile) passes attention's kernel makes over the latent layers (and a
        chunk's ``steps``, all alike), and ``latent_rider_passes``, those of
        them that own ONE token of their tile and walk as the per-token grid
        does (``ops/pallas/latent_attention.py:tiled_passes``)."""
        bucket_tokens = batch["tok_meta"].shape[1]
        if self.attention_arm(bucket_tokens) != "latent_tiled":
            return {}
        seq = np.asarray(batch["seq_meta"])
        counts = latent_attention.tiled_passes(seq[:, 1], seq[:, 2], bucket_tokens,
                                               self._config.num_attention_heads)
        return {name: n * self.num_kv_layers * steps
                for name, n in zip(("latent_passes", "latent_rider_passes"), counts)}

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

    def _write_rows(self, pool, li, rows, batch):
        """Scatter ``rows`` [T, width] into ``pool`` layer ``li`` at the
        tokens' positions, in place; padding and unallocated table slots route
        to a positive out-of-bounds block and are dropped."""
        NB, bs = pool.shape[1], pool.shape[2]
        table, pos = batch["block_table"], batch["token_pos"]
        ids = table[batch["token_seq"], jnp.minimum(pos // bs, table.shape[1] - 1)]
        ids = jnp.where(batch["token_valid"] & (ids >= 0), ids, NB)
        return pool.at[li, ids, pos % bs].set(rows.astype(pool.dtype), mode="drop")

    def _query_row(self, q, kv_b, width, q_pe=None):
        """The heads' queries ``q`` [T, H, nope + rope] as rows to score latent
        rows with, [T, H, ``width``]: ``W_UK`` (``kv_b``'s key half) absorbed,
        so a key's logit is one dot product with its latent row; ``q_pe``: the
        shared key's dims as the family rotated them (None: as they are);
        scaled in float32, padded to the pool's width."""
        N = self._config.qk_nope_head_dim
        q_abs = jnp.einsum("thn,chn->thc", q[..., :N], kv_b[..., :N].astype(q.dtype))
        if q_pe is None:
            q_pe = q[..., N:]
        q_row = jnp.concatenate([q_abs, q_pe], axis=-1).astype(jnp.float32) \
            * self._config.softmax_scale
        return jnp.pad(q_row, ((0, 0), (0, 0), (0, width - q_row.shape[-1]))).astype(q.dtype)

    def _keep_row(self, pool, li, c_kv, k_pe, batch):
        """The step's latent rows (the normed latent ``c_kv`` beside the shared
        key ``k_pe``, padded to the pool's width) written into layer ``li``."""
        row = jnp.concatenate([c_kv, k_pe], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, pool.shape[-1] - row.shape[-1])))
        return self._write_rows(pool, li, row, batch)

    def _latent_meta(self, T, batch):
        """``(kernel, meta)``: whether a ``T``-token bucket runs the kernels,
        and the batch's arrays the arm it runs takes after ``(.., pool, li)``."""
        if self.attention_arm(T) != "latent_xla":
            return True, (batch["block_table"], batch["seq_seen"], batch["seq_ntok"],
                          batch["last_tok"])
        return False, (batch["block_table"], batch["token_seq"], batch["token_pos"],
                       batch["token_valid"])

    @jax.named_scope("latent_kernel")
    def _latent_attend(self, q_row, pool, li, kernel, meta, *selection):
        attend = latent_attention.latent_paged_attention if kernel \
            else latent_attention.latent_paged_attention_xla
        return attend(q_row, pool, li, *meta, *selection, value_width=self._config.kv_lora_rank)

    @jax.named_scope("latent_out")
    def _latent_out(self, out, kv_b, wo):
        """``W_UV`` (``kv_b``'s value half) on the heads' latent outputs, then
        the output projection ``wo``."""
        N = self._config.qk_nope_head_dim
        out = jnp.einsum("thc,chv->thv", out, kv_b[..., N:].astype(out.dtype))
        out = out.reshape(out.shape[0], -1)
        return out @ wo["kernel"].astype(out.dtype)
