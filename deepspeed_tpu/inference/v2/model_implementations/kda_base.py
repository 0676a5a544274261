"""What the ragged models with gated delta-rule (KDA) mixers share
(``solar_open2_v2.py``: beside gated GQA; ``kimi_linear_v2.py``: beside latent
attention): the per-sequence state group they ask of the engine, the mixer
over a step's rows, and the step's counters.

- **a per-sequence state group** (``sequence_state``; ``sequence_slots.py``):
  a delta-rule mixer keeps, for each live sequence and whatever its length, a
  float32 state ``[heads, d_k, d_v]`` (4 MiB at the published widths) and the
  last ``short_conv_kernel_size - 1`` rows of its THREE convolutions' inputs
  (q, k and v side by side, folded into whole tiles: ``ssm.conv_slot``), in two
  pools ``[delta-rule layers, slots, ...]``;
- **two forms of the delta rule** (``modules/kda.py``), both IN the pool: a
  ``put`` step scans by segment (``kda.scan_in_place``: the chunked form's
  visits by one kernel a layer, ``ops/pallas/kda_chunk.py``), a ``decode_loop``
  step runs the recurrence by one kernel a layer over the pool
  (``ops/pallas/kda_step.py``). Neither program holds a state a row, nor a
  result shaped like the pool.

Reads the config's ``kda_here`` (the delta-rule layers), ``linear_num_heads`` /
``linear_head_dim`` / ``kda_width`` / ``kda_chunk``, ``short_conv_kernel_size``,
``beta_scale``, ``rms_norm_eps`` and ``dtype``, and the state manager's cache
(``cache[1]`` is the state pool whatever holds the rows a token keeps).

Scopes in the device trace, under ``kda``: ``qkv_proj``, ``conv``, ``gates``,
``scan`` (the chunked form) or ``step`` (the recurrence), ``gate_norm``,
``out_proj``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.model_implementations.sequence_slots import SequenceSlots
from deepspeed_tpu.inference.v2.modules import kda, ssm
from deepspeed_tpu.inference.v2.ragged.manager_configs import SequenceStateSpec


class GatedDeltaRule(SequenceSlots):
    """A subclass calls :meth:`_kda_phase` where a layer has such a mixer, with
    the layer's ordinal among them, and adds :meth:`_kda_counts` to its
    ``batch_counts``."""

    @property
    def sequence_state(self):
        cfg = self._config
        return (SequenceStateSpec(name="kda", layers=len(cfg.kda_here), dtype="float32",
                                  shape=(cfg.linear_num_heads, cfg.linear_head_dim,
                                         cfg.linear_head_dim)),
                self._conv_slot_spec(len(cfg.kda_here), cfg.short_conv_kernel_size,
                                     3 * cfg.kda_width))

    def _kda_counts(self, ragged_batch, steps):
        """``kda_rows``, rows that went through a delta-rule mixer (live tokens
        x such layers, over the ``steps`` of a chunk); ``kda_segments``,
        sequence segments scanned (a segment a live sequence a layer a step);
        ``kda_chunk_visits``, the visits the scan made through the chunked form
        (a visit a chunk of ``kda_chunk`` rows of the batch a segment of more
        than one row has rows in, a layer; 0 for a ``decode_loop`` chunk, whose
        segments are one row); ``kda_chunk_visits_in_kernel``, those of them
        the chunk kernel made in the pool (all, or 0 where the pool or the
        chunk is off its shape rule); ``kda_rows_in_place``, the rows whose
        state the recurrence's kernel updated in its slot (the segments of one
        row: every row of a ``decode_loop`` chunk), or 0 where the pool is off
        the kernel's shape rule; and the state group's slots held as the step
        is dispatched (``ssm_slots_live`` / ``ssm_slots_total``)."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        layers = len(self._config.kda_here)
        state_pool = self._state_manager.kv_cache.cache[1]
        seq = np.asarray(batch["seq_meta"])
        ntok, valid = seq[:, 1], seq[:, 3] > 0
        rows = min(self._config.kda_chunk, batch["tok_meta"].shape[1])
        _, visits = kda.visits_of(seq[:, 2] - ntok + 1, ntok, valid & (ntok > 1), rows)
        one_row = int((valid & (ntok == 1)).sum()) if kda.in_place(state_pool) else 0
        in_kernel = int(visits.sum()) if kda.chunks_in_kernel(state_pool, rows) else 0
        return dict(kda_rows=steps * int(batch["n_tokens"]) * layers,
                    kda_segments=steps * int(batch["n_seqs"]) * layers,
                    kda_chunk_visits=steps * int(visits.sum()) * layers,
                    kda_chunk_visits_in_kernel=steps * in_kernel * layers,
                    kda_rows_in_place=steps * one_row * layers, **self._slot_counts())

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
        slot, started, live, one_token = self._slot_rows(batch)
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
