"""Nemotron-H ragged inference model (``model_type="nemotron_h"``), over the
parameter tree of :mod:`deepspeed_tpu.models.nemotron_h`.

A block is ONE mixer under one norm and one residual, its kind read from
``hybrid_override_pattern``; a block's index in the cache is its ordinal among
the blocks of its kind. What the architecture asks of the engine:

- **a per-sequence state group** (``sequence_state``): a Mamba-2 block keeps,
  for each live sequence and whatever its length, a float32 state ``[heads,
  head_dim, state]`` and the last ``conv_kernel - 1`` rows of its convolution's
  input. Two pools ``[M blocks, slots, ...]`` ride beside the K/V array in the
  one cache pytree (``ragged/kv_cache.py``); a sequence's slot is a column of
  ``seq_meta``. A slot's content counts from the sequence's first token: a
  sequence with nothing seen reads zeros whatever the slot held. Padding rows
  point one past the last slot and their writes drop;
- **two forms of the scan** (``modules/ssm.py``): a ``put`` step runs the
  chunked form over the ragged batch, each sequence's segment starting from
  its slot's state, gathered, and leaving its final state there, scattered; a
  ``decode_loop`` step (``one_token_rows``) runs the recurrence, one token a
  sequence, IN the pool: one kernel a block reads a row's slot, updates it
  and writes it back (``ops/pallas/ssm_step.py``), so no ``[rows, H, P, N]``
  exists in that program. A pool off the kernel's shape rule
  (``ssm.in_place``) runs ``ssm.step`` between a gather and a scatter;
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
import numpy as np

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.inference.v2.modules import ssm
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.manager_configs import SequenceStateSpec
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import _pow2_pad
from deepspeed_tpu.models.nemotron_h import ATTENTION, EXPERTS, MAMBA, NemotronHConfig


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _relu2_mlp(h, mp):
    return relu2(h @ mp["up_proj"]["kernel"].astype(h.dtype)) \
        @ mp["down_proj"]["kernel"].astype(h.dtype)


class NemotronHV2Model(DSTransformerModelBase):

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
    def sequence_state(self):
        cfg = self._config
        blocks = len(cfg.layers_of(MAMBA))
        return (SequenceStateSpec(name="ssm", layers=blocks, dtype="float32",
                                  shape=(cfg.mamba_num_heads, cfg.mamba_head_dim,
                                         cfg.ssm_state_size)),
                SequenceStateSpec(name="conv", layers=blocks, dtype=np.dtype(cfg.dtype).name,
                                  shape=(cfg.conv_kernel - 1, cfg.conv_dim)))

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

    def batch_counts(self, ragged_batch, steps=None):
        """Beside the attention kernels' passes: ``ssm_tokens``, rows that went
        through a Mamba-2 block (live tokens x such blocks, over the ``steps``
        of a chunk); ``ssm_segments``, sequence segments scanned (a segment a
        live sequence a block a step); ``ssm_slots_live`` / ``ssm_slots_total``,
        the per-sequence state group's slots held as the step is dispatched;
        on a ``decode_loop`` chunk (the engine gives its ``steps``; a ``put``
        gives none) ``ssm_rows_in_place``, those of ``ssm_tokens`` whose state
        the kernel updated in its slot: all of them, or 0 where the pool is off
        its shape rule."""
        chunk, steps = steps is not None, steps or 1
        counts = super().batch_counts(ragged_batch, steps)
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        blocks = len(self._config.layers_of(MAMBA))
        kv = self._state_manager.kv_cache
        counts.update(ssm_tokens=steps * int(batch["n_tokens"]) * blocks,
                      ssm_segments=steps * int(batch["n_seqs"]) * blocks,
                      ssm_slots_live=kv.num_slots - (kv.free_slots or 0),
                      ssm_slots_total=kv.num_slots)
        if chunk:
            in_place = ssm.in_place(kv.cache[1], self._config.n_groups)
            counts["ssm_rows_in_place"] = counts["ssm_tokens"] if in_place else 0
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

    def _step_in_place(self, pool, mi, *rows):
        """``ssm.step_in_place`` on block ``mi`` of the pool. The SPMD
        partitioner cannot split a Mosaic kernel: on a mesh every device runs
        it over the pool it holds whole (``kv_cache._pool_sharding``), as
        ``_paged_attention`` runs its kernel."""
        placed = None if self._state_manager is None else self._state_manager.kv_cache.sharding
        if placed is None or placed.mesh.size == 1 or not ssm.in_place(pool, rows[-1].shape[1]):
            return ssm.step_in_place(pool, mi, *rows)
        from jax.sharding import PartitionSpec as P
        return jax.shard_map(ssm.step_in_place, mesh=placed.mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(pool, jnp.int32(mi), *rows)

    @jax.named_scope("ssm")
    def _mamba_phase(self, mp, mi, h, pools, batch):
        """Mamba-2 block ``mi`` (its ordinal) over the step's rows ``h`` [T, M];
        ``pools`` = (ssm [blocks, slots, H, P, N], conv [blocks, slots, K - 1,
        C]). Returns the mixer's output and the pools with the step's states."""
        cfg = self._config
        T = h.shape[0]
        H, P, G, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size
        D = cfg.d_inner
        ssm_pool, conv_pool = pools
        n_slots = ssm_pool.shape[1]
        with jax.named_scope("in_proj"):
            zxbcdt = h @ mp["in_proj"]["kernel"].astype(h.dtype)
            z, xbc, dt = jnp.split(zxbcdt, [D, D + cfg.conv_dim], axis=-1)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"][None, :])
        A = -jnp.exp(mp["A_log"].astype(jnp.float32))
        slot = batch["state_slot"]
        # a sequence with nothing seen starts from zero whatever its slot held
        started = batch["seq_valid"] & (batch["seq_seen"] > 0)
        one_token = batch["one_token_rows"]
        if one_token:  # decode_loop: row t is sequence token_seq[t]'s one token
            of = batch["token_seq"]
            slot, started = slot[of], started[of]
            write = jnp.where(batch["token_valid"], slot, n_slots)
        else:
            write = jnp.where(batch["seq_valid"] & (batch["seq_ntok"] > 0), slot, n_slots)
        read = jnp.minimum(slot, n_slots - 1)

        with jax.named_scope("conv"):
            tail = jnp.where(started[:, None, None], conv_pool[mi, read], 0)
            w, b = mp["conv1d"]["kernel"], mp["conv1d"]["bias"]
            if one_token:
                xbc, tail = ssm.conv_step(xbc, w, b, tail)
            else:
                xbc, tail = ssm.conv_ragged(xbc, w, b, tail, batch["token_seq"],
                                            batch["last_tok"] - batch["seq_ntok"] + 1,
                                            batch["seq_ntok"])
            conv_pool = conv_pool.at[mi, write].set(tail, mode="drop")
            xbc = jax.nn.silu(xbc).astype(h.dtype)
            x, B, C = jnp.split(xbc, [D, D + G * N], axis=-1)
            x, B, C = x.reshape(T, H, P), B.reshape(T, G, N), C.reshape(T, G, N)
        with jax.named_scope("step" if one_token else "scan"):
            if one_token:
                y, ssm_pool = self._step_in_place(ssm_pool, mi, slot, batch["token_valid"],
                                                  started, x, dt, A, B, C)
            else:
                state = jnp.where(started[:, None, None, None], ssm_pool[mi, read], 0.0)
                onehot = ssm.segments(batch["token_seq"], batch["token_valid"], slot.shape[0])
                y, state = ssm.scan_ragged(x, dt, A, B, C, state, onehot, cfg.chunk_size)
                ssm_pool = ssm_pool.at[mi, write].set(state.astype(ssm_pool.dtype), mode="drop")
            y = y + mp["D"].astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)
        with jax.named_scope("gate_norm"):
            y = ssm.gated_norm(y.reshape(T, D), z, mp["norm"]["weight"], G,
                               cfg.layer_norm_epsilon).astype(h.dtype)
        with jax.named_scope("out_proj"):
            return y @ mp["out_proj"]["kernel"].astype(h.dtype), (ssm_pool, conv_pool)

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
