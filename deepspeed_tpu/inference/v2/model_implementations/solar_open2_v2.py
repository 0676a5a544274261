"""Solar Open 2 ragged inference model (``model_type="solar_open2"``), over the
parameter tree of :mod:`deepspeed_tpu.models.solar_open2`.

A layer is a mixer and the experts, each under its own norm and residual; the
mixer is softmax attention where the layer is in ``gqa_layers`` and the gated
delta rule everywhere else; a layer's index in its cache is its ordinal among
the layers of its kind. What the architecture asks of the engine:

- **a per-sequence state group** (``sequence_state``): a delta-rule mixer
  keeps, for each live sequence and whatever its length, a float32 state
  ``[heads, d_k, d_v]`` (4 MiB at the published widths) and the last
  ``short_conv_kernel_size - 1`` rows of its THREE convolutions' inputs (q, k
  and v side by side, folded into whole tiles: ``ssm.conv_slot``). Two pools
  ``[delta-rule layers, slots, ...]`` ride beside the K/V array in the one
  cache pytree, as the Mamba-2 families' do (``mamba2_base.py``): a slot's
  content counts from the sequence's first token, padding rows point one past
  the last slot and their writes drop;
- **two forms of the delta rule** (``modules/kda.py``), both IN the pool: a
  ``put`` step scans by segment (``kda.scan_in_place``: the chunked form's
  visits by one kernel a layer, ``ops/pallas/kda_chunk.py``), a ``decode_loop``
  step runs the recurrence by one kernel a layer over the pool
  (``ops/pallas/kda_step.py``). Neither program holds a state a row, nor a
  result shaped like the pool;
- **the K/V array holds the softmax layers only** (``num_kv_layers``), ONE
  layer group; no rotary embedding; an output gate (``attn/gate``);
- **one chip's share of the experts**: ``RaggedMoE`` told ``held`` /
  ``first_held``, SwiGLU banks, a shared expert beside them;
- **one block-table bucket** (``min_table_bucket``): the whole table. One
  layer in four reads it.

Scopes in the device trace: ``kda/qkv_proj``, ``kda/conv``, ``kda/gates``,
``kda/scan`` (the chunked form) or ``kda/step`` (the recurrence),
``kda/gate_norm``, ``kda/out_proj``; ``attn`` with ``attn/gate``; ``moe`` with
``moe/shared`` beside ``RaggedMoE``'s own.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _root, _swiglu
from deepspeed_tpu.inference.v2.model_implementations.mamba2_base import Mamba2Model
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.inference.v2.modules import kda, ssm
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.manager_configs import SequenceStateSpec
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import _pow2_pad
from deepspeed_tpu.models.solar_open2 import SolarOpen2Config


class SolarOpen2V2Model(DSTransformerModelBase):

    def __init__(self, params, config: SolarOpen2Config, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        if not config.gqa_here or not config.kda_here:
            raise NotImplementedError(
                f"gqa_layers {config.gqa_layers} over {config.num_hidden_layers} layers: the "
                f"engine's pool is a K/V array beside a per-sequence state group, and a model "
                f"without a softmax layer or without a delta-rule layer would leave one empty")
        # a layer's index among the layers of its kind: its cache index
        self._ordinal = {li: n for kind in (config.gqa_here, config.kda_here)
                         for n, li in enumerate(kind)}
        ep_cfg = getattr(engine_config, "expert_parallel", None)
        share = config.experts_held < config.n_routed_experts
        self._moes = [
            RaggedMoE(num_experts=config.n_routed_experts, top_k=config.num_experts_per_tok,
                      capacity_factor=(ep_cfg.capacity_factor if ep_cfg is not None else 2.0),
                      layer_id=li, norm_topk_prob=config.norm_topk_prob, score_func="sigmoid",
                      route_scale=config.routed_scaling_factor,
                      held=config.experts_held if share else None,
                      first_held=config.first_expert_held)
            for li in range(config.num_hidden_layers)]
        if share:
            self.moe_count_names = ("moe_banks", "moe_assignments_local")

    # ----------------------------------------------------------- properties --
    @property
    def num_layers(self):
        return self._config.num_hidden_layers

    @property
    def num_kv_layers(self):
        return len(self._config.gqa_here)

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
        return (SequenceStateSpec(name="kda", layers=len(cfg.kda_here), dtype="float32",
                                  shape=(cfg.linear_num_heads, cfg.linear_head_dim,
                                         cfg.linear_head_dim)),
                SequenceStateSpec(name="conv", layers=len(cfg.kda_here),
                                  dtype=np.dtype(cfg.dtype).name,
                                  shape=ssm.conv_slot(cfg.short_conv_kernel_size - 1,
                                                      3 * cfg.kda_width)))

    @property
    def min_table_bucket(self):
        """The whole table (``max_context``), a power of two of blocks."""
        sm = self._engine_config.state_manager
        return _pow2_pad(-(-sm.max_context // self._engine_config.kv_block_size))

    # -------------------------------------------------------------- counters --
    def moe_path(self, n_padded):
        return self._moes[0].path(n_padded, self._config.moe_intermediate_size)

    def dispatch_counts(self, n_padded, n_tokens, steps=1):
        """As ``DeepseekV32V2Model.dispatch_counts``."""
        path = self.moe_path(n_padded)
        counts = {"moe_path": path,
                  "moe_rows": steps * sum(m.expert_rows(n_padded, 1, path) for m in self._moes),
                  "moe_assignments": steps * n_tokens * sum(m.top_k for m in self._moes)}
        if path == "capacity":
            counts["moe_banks"] = steps * sum(m.experts_here for m in self._moes)
        return counts

    def batch_counts(self, ragged_batch, steps=1):
        """Beside the attention kernels' passes: ``kda_rows``, rows that went
        through a delta-rule mixer (live tokens x such layers, over the
        ``steps`` of a chunk); ``kda_segments``, sequence segments scanned (a
        segment a live sequence a layer a step); ``kda_chunk_visits``, the
        visits the scan made through the chunked form (a visit a chunk of
        ``kda_chunk`` rows of the batch a segment of more than one row has rows
        in, a layer; 0 for a ``decode_loop`` chunk, whose segments are one
        row); ``kda_chunk_visits_in_kernel``, those of them the chunk kernel
        made in the pool (all, or 0 where the pool or the chunk is off its
        shape rule); ``kda_rows_in_place``, the rows whose state the
        recurrence's kernel updated in its slot (the segments of one row: every
        row of a ``decode_loop`` chunk), or 0 where the pool is off the kernel's
        shape rule; and the state group's slots held as the step is dispatched,
        under the Mamba-2 families' names (``ssm_slots_live`` /
        ``ssm_slots_total``)."""
        counts = super().batch_counts(ragged_batch, steps)
        counts.update(self._kda_counts(ragged_batch, steps))
        return counts

    def _kda_counts(self, ragged_batch, steps):
        """:meth:`batch_counts`' own entries; ``cache[1]`` is the state pool
        whatever holds the rows a token keeps."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        layers = len(self._config.kda_here)
        kv = self._state_manager.kv_cache
        seq = np.asarray(batch["seq_meta"])
        ntok, valid = seq[:, 1], seq[:, 3] > 0
        rows = min(self._config.kda_chunk, batch["tok_meta"].shape[1])
        _, visits = kda.visits_of(seq[:, 2] - ntok + 1, ntok, valid & (ntok > 1), rows)
        one_row = int((valid & (ntok == 1)).sum()) if kda.in_place(kv.cache[1]) else 0
        in_kernel = int(visits.sum()) if kda.chunks_in_kernel(kv.cache[1], rows) else 0
        return dict(kda_rows=steps * int(batch["n_tokens"]) * layers,
                    kda_segments=steps * int(batch["n_seqs"]) * layers,
                    kda_chunk_visits=steps * int(visits.sum()) * layers,
                    kda_chunk_visits_in_kernel=steps * in_kernel * layers,
                    kda_rows_in_place=steps * one_row * layers,
                    ssm_slots_live=kv.num_slots - (kv.free_slots or 0),
                    ssm_slots_total=kv.num_slots)

    # --------------------------------------------------------------- phases --
    @jax.named_scope("embed")
    def embed(self, params, ids):
        return _root(params)["embed_tokens"]["embedding"][ids].astype(self._config.dtype)

    @jax.named_scope("unembed")
    def unembed(self, params, x):
        r = _root(params)
        x = _rms(x, r["norm"]["weight"], self._config.rms_norm_eps)
        return x @ r["lm_head"]["kernel"].astype(x.dtype)

    @jax.named_scope("attn")
    def _attn_phase(self, ap, ai, h, kv, attn_fn):
        """Softmax layer ``ai`` (its ordinal: its layer of the K/V array):
        grouped-query, causal, no position encoding, the heads' output gated
        from the layer's input."""
        T = h.shape[0]
        H, KVH, D = self.num_heads, self.num_kv_heads, self.head_dim
        q = (h @ ap["q_proj"]["kernel"].astype(h.dtype)).reshape(T, H, D)
        k = (h @ ap["k_proj"]["kernel"].astype(h.dtype)).reshape(T, KVH, D)
        v = (h @ ap["v_proj"]["kernel"].astype(h.dtype)).reshape(T, KVH, D)
        out, kv = attn_fn(q, k, v, kv, ai)
        out = out.reshape(T, H * D).astype(h.dtype)
        if "gate_proj" in ap:
            with jax.named_scope("gate"):
                out = out * jax.nn.sigmoid(h @ ap["gate_proj"]["kernel"].astype(h.dtype))
        return out @ ap["o_proj"]["kernel"].astype(h.dtype), kv

    # a kernel over a pool where it lies, every device of a mesh over the pool it
    # holds whole: the Mamba-2 families' wrapper, which reads the state manager alone
    _in_the_pool = Mamba2Model._in_the_pool

    @jax.named_scope("kda")
    def _kda_phase(self, mp, mi, h, pools, batch):
        """Delta-rule mixer ``mi`` (its ordinal) over the step's rows ``h`` [T,
        M]; ``pools`` = (state [layers, slots, H, d_k, d_v], conv [layers,
        slots, *``ssm.conv_slot``]). Returns the mixer's output and the pools
        with the step's states."""
        cfg = self._config
        T = h.shape[0]
        H, D, W, K = (cfg.linear_num_heads, cfg.linear_head_dim, cfg.kda_width,
                      cfg.short_conv_kernel_size)
        state_pool, conv_pool = pools

        def lin(x, name):
            return x @ mp[name]["kernel"].astype(x.dtype)

        with jax.named_scope("qkv_proj"):
            qkv = jnp.concatenate([lin(h, f"{n}_proj") for n in "qkv"], axis=-1)
        slot = batch["state_slot"]
        # a sequence with nothing seen starts from zero whatever its slot held
        started = batch["seq_valid"] & (batch["seq_seen"] > 0)
        one_token = batch["one_token_rows"]
        if one_token:  # decode_loop: row t is sequence token_seq[t]'s one token
            of = batch["token_seq"]
            slot, started = slot[of], started[of]
            live = batch["token_valid"]
        else:  # put: a sequence without tokens in the step keeps its state
            live = batch["seq_valid"] & (batch["seq_ntok"] > 0)
        seq_start = batch["last_tok"] - batch["seq_ntok"] + 1

        with jax.named_scope("conv"):
            # the step's own tails out of their slots and back, as the Mamba-2 mixers'
            tail = ssm.unfold_tails(self._in_the_pool(ssm.load, conv_pool, mi, slot, started),
                                    K - 1, 3 * W)
            wt = jnp.concatenate([mp[f"{n}_conv1d"]["kernel"] for n in "qkv"], axis=0)
            no_bias = jnp.zeros((3 * W, ), jnp.float32)
            if one_token:
                qkv, tail = ssm.conv_step(qkv, wt, no_bias, tail)
            else:
                qkv, tail = ssm.conv_ragged(qkv, wt, no_bias, tail, batch["token_seq"], seq_start,
                                            batch["seq_ntok"])
            conv_pool = self._in_the_pool(ssm.store_in_place, conv_pool, mi, slot, live,
                                          ssm.fold_tails(tail, conv_pool.shape[2:]))
            q, k, v = (a.reshape(T, H, D) for a in jnp.split(jax.nn.silu(qkv), 3, axis=-1))
        with jax.named_scope("gates"):
            q, k = kda.l2_normed(q, D**-0.5), kda.l2_normed(k)
            g = kda.decay(lin(lin(h, "f_a_proj"), "f_b_proj"), mp["dt_bias"], mp["A_log"], H)
            beta = cfg.beta_scale * jax.nn.sigmoid(lin(h, "b_proj").astype(jnp.float32))
            gate = lin(lin(h, "g_a_proj"), "g_b_proj")
        with jax.named_scope("step" if one_token else "scan"):
            if one_token:
                o, state_pool = self._in_the_pool(kda.step_in_place, state_pool, mi, slot, live,
                                                  started, q, k, v, jnp.exp(g), beta)
            else:
                o, state_pool = self._in_the_pool(
                    functools.partial(kda.scan_in_place, rows=cfg.kda_chunk), state_pool, mi,
                    slot, live, started, seq_start, batch["seq_ntok"], batch["token_seq"],
                    batch["token_valid"], q, k, v, g, beta)
        with jax.named_scope("gate_norm"):
            o = kda.gated_norm(o, gate, mp["o_norm"]["weight"], cfg.rms_norm_eps).astype(h.dtype)
        with jax.named_scope("out_proj"):
            return lin(o, "o_proj"), (state_pool, conv_pool)

    @jax.named_scope("moe")
    def _ffn_phase(self, lp, li, x, batch):
        h = _rms(x, lp["post_attention_layernorm"]["weight"], self._config.rms_norm_eps)
        mp = lp["mlp"]
        out = self._moes[li](h, mp["gate"], mp["experts"]["wi"], mp["experts"]["wo"],
                             activation=jax.nn.silu, select_bias=mp["e_score_correction_bias"],
                             token_valid=batch["token_valid"],
                             banks_out=batch.get("moe_banks")).astype(x.dtype)
        if "shared_experts" in mp:  # always on: every token, once
            with jax.named_scope("shared"):
                out = out + _swiglu(h, mp["shared_experts"])
        return x + out

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        h = _rms(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
        kv, *pools = cache
        if cfg.is_gqa(li):
            out, kv = self._attn_phase(lp["self_attn"], self._ordinal[li], h, kv, attn_fn)
        else:
            out, pools = self._kda_phase(lp["linear_attn"], self._ordinal[li], h, pools, batch)
        x = x + out.astype(x.dtype)
        return self._ffn_phase(lp, li, x, batch), (kv, *pools)
