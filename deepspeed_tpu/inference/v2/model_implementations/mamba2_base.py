"""What the ragged models with Mamba-2 mixers share (``nemotron_h_v2.py``: one
mixer a block, some of them Mamba-2; ``falcon_h1_v2.py``: a Mamba-2 mixer
beside attention in every layer): the per-sequence state group they ask of the
engine, the mixer over a step's rows, and the step's counters.

- **a per-sequence state group** (``sequence_state``; ``sequence_slots.py``
  has what every such group shares): a Mamba-2 mixer keeps, for each live
  sequence and whatever its length, a float32 state ``[heads, head_dim,
  state]`` and the last ``conv_kernel - 1`` rows of its convolution's input,
  in two pools ``[Mamba-2 mixers, slots, ...]``. Both slots are stated in
  whole (sublane, lane) tiles where the widths allow (the state as it is; the
  tails folded, ``ssm.conv_slot``), so that a step moves its own rows by a
  kernel over the pool where it lies and the compiler adds no pass over the
  pool;
- **two forms of the scan** (``modules/ssm.py``), both IN the pool: a ``put``
  step scans by segment (``ssm.scan_in_place``), each sequence's rows
  starting from its slot's state and leaving its final state there — the
  segments of one row through the recurrence's kernel (row i the sequence's
  one row), a longer one through the chunked form against ITS state alone, a
  visit a chunk of its rows; a ``decode_loop`` step (``one_token_rows``) runs
  the recurrence, one token a sequence: one kernel a mixer reads a row's
  slot, updates it and writes it back (``ops/pallas/ssm_step.py``). Neither
  program holds a ``[rows, H, P, N]`` or a ``[sequences, H, P, N]``. A pool
  off the kernel's shape rule (``ssm.in_place``) runs ``ssm.scan_ragged`` on
  every sequence's state between two slot-copy kernels
  (``ops/pallas/ssm_store.py``; XLA's gather and scatter off
  ``ssm.whole_slots`` too) / ``ssm.step`` between a gather and a scatter.

Scopes in the device trace, under ``ssm``: ``in_proj``, ``conv``, ``scan``
(the chunked form) or ``step`` (the recurrence), ``gate_norm``, ``out_proj``.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.model_implementations.sequence_slots import SequenceSlots
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import (
    DSTransformerModelBase, scaled_dot)
from deepspeed_tpu.inference.v2.modules import ssm
from deepspeed_tpu.inference.v2.ragged.manager_configs import SequenceStateSpec


class Mamba2Shape(NamedTuple):
    """A model's Mamba-2 mixers: how many, their widths, and the scalars the
    family puts around the projections (1 / None: none)."""
    mixers: int
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    eps: float
    in_scale: float = 1.0  # on the mixer's input
    column_scale: Optional[np.ndarray] = None  # float32 [in_proj's width]: on its output
    out_scale: float = 1.0  # on out_proj's output

    @property
    def d_inner(self):
        return self.heads * self.head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.groups * self.state


class Mamba2Model(SequenceSlots, DSTransformerModelBase):
    """A subclass states :attr:`mamba2` and calls :meth:`_mamba_phase` where a
    layer has such a mixer, with the mixer's ordinal among them."""

    @property
    def mamba2(self) -> Mamba2Shape:
        raise NotImplementedError

    @property
    def sequence_state(self):
        w = self.mamba2
        return (SequenceStateSpec(name="ssm", layers=w.mixers, dtype="float32",
                                  shape=(w.heads, w.head_dim, w.state)),
                self._conv_slot_spec(w.mixers, w.conv_kernel, w.conv_dim))

    def batch_counts(self, ragged_batch, steps=None):
        """Beside the attention kernels' passes: ``ssm_tokens``, rows that went
        through a Mamba-2 mixer (live tokens x such mixers, over the ``steps``
        of a chunk); ``ssm_segments``, sequence segments scanned (a segment a
        live sequence a mixer a step); ``ssm_slots_live`` / ``ssm_slots_total``,
        the per-sequence state group's slots held as the step is dispatched;
        ``ssm_segments_in_place``, those of a ``put``'s ``ssm_segments`` whose
        final state a kernel left in its slot of the pool: all of them, or 0
        where the pool is off ``ssm.whole_slots``'s rule (nothing reads a
        chunk's entry: its rows are ``ssm_rows_in_place``'s);
        ``ssm_segments_scanned_in_place``, those of a ``put``'s ``ssm_segments``
        scanned in their slot by their own rows alone (``ssm.scan_in_place`` on a
        pool on ``ssm.in_place``'s rule): all of them, or 0 where the step falls
        back to ``ssm.scan_ragged`` on every state between the slot copies;
        ``ssm_conv_rows_in_place``, those of the step's ``ssm_segments`` whose
        convolution tails a kernel loaded from and left in their slots: all of
        them, or 0 where the conv pool's slot is off ``ssm.whole_slots``'s rule
        (XLA's gather and scatter); where the caller gives ``steps`` (the engine
        does, a chunk's or 1 for a ``put``, whose entry nothing reads)
        ``ssm_rows_in_place``, those of a ``decode_loop`` chunk's ``ssm_tokens``
        whose state the kernel updated in its slot: all of them, or 0 where the
        pool is off its shape rule."""
        chunk, steps = steps is not None, steps or 1
        counts = super().batch_counts(ragged_batch, steps)
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        w = self.mamba2
        kv = self._state_manager.kv_cache
        counts.update(ssm_tokens=steps * int(batch["n_tokens"]) * w.mixers,
                      ssm_segments=steps * int(batch["n_seqs"]) * w.mixers,
                      **self._slot_counts())
        stored = ssm.whole_slots(kv.cache[1])
        in_place = ssm.in_place(kv.cache[1], w.groups)
        counts["ssm_segments_in_place"] = counts["ssm_segments"] if stored else 0
        counts["ssm_segments_scanned_in_place"] = counts["ssm_segments"] if in_place else 0
        counts["ssm_conv_rows_in_place"] = \
            counts["ssm_segments"] if ssm.whole_slots(kv.cache[2]) else 0
        if chunk:
            counts["ssm_rows_in_place"] = counts["ssm_tokens"] if in_place else 0
        return counts

    @jax.named_scope("ssm")
    def _mamba_phase(self, mp, mi, h, pools, batch):
        """Mamba-2 mixer ``mi`` (its ordinal) over the step's rows ``h`` [T, M];
        ``pools`` = (ssm [mixers, slots, H, P, N], conv [mixers, slots,
        *``ssm.conv_slot``]). Returns the mixer's output and the pools with the
        step's states."""
        w = self.mamba2
        T = h.shape[0]
        H, P, G, N, D = w.heads, w.head_dim, w.groups, w.state, w.d_inner
        ssm_pool, conv_pool = pools
        with jax.named_scope("in_proj"):
            if w.in_scale != 1.0:
                h = h * jnp.asarray(w.in_scale, h.dtype)
            if w.column_scale is None:
                zxbcdt = h @ mp["in_proj"]["kernel"].astype(h.dtype)
            else:
                zxbcdt = scaled_dot(h, mp["in_proj"]["kernel"], w.column_scale[None, :])
            z, xbc, dt = jnp.split(zxbcdt, [D, D + w.conv_dim], axis=-1)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"][None, :])
        A = -jnp.exp(mp["A_log"].astype(jnp.float32))
        slot, started, live, one_token = self._slot_rows(batch)

        with jax.named_scope("conv"):
            # the step's own tails out of their slots and back (modules/ssm.py: one
            # kernel a direction where a slot is whole tiles, as conv_slot states it)
            tail = ssm.unfold_tails(self._in_the_pool(ssm.load, conv_pool, mi, slot, started),
                                    w.conv_kernel - 1, w.conv_dim)
            wt, b = mp["conv1d"]["kernel"], mp["conv1d"]["bias"]
            if one_token:
                xbc, tail = ssm.conv_step(xbc, wt, b, tail)
            else:
                xbc, tail = ssm.conv_ragged(xbc, wt, b, tail, batch["token_seq"],
                                            batch["last_tok"] - batch["seq_ntok"] + 1,
                                            batch["seq_ntok"])
            conv_pool = self._in_the_pool(ssm.store_in_place, conv_pool, mi, slot, live,
                                          ssm.fold_tails(tail, conv_pool.shape[2:]))
            xbc = jax.nn.silu(xbc).astype(h.dtype)
            x, B, C = jnp.split(xbc, [D, D + G * N], axis=-1)
            x, B, C = x.reshape(T, H, P), B.reshape(T, G, N), C.reshape(T, G, N)
        with jax.named_scope("step" if one_token else "scan"):
            if one_token:
                y, ssm_pool = self._in_the_pool(ssm.step_in_place, ssm_pool, mi, slot, live,
                                                started, x, dt, A, B, C)
            else:
                y, ssm_pool = self._in_the_pool(
                    functools.partial(ssm.scan_in_place, chunk=w.chunk), ssm_pool, mi, slot, live,
                    started, batch["last_tok"] - batch["seq_ntok"] + 1, batch["seq_ntok"],
                    batch["token_seq"], batch["token_valid"], x, dt, A, B, C)
            y = y + mp["D"].astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)
        with jax.named_scope("gate_norm"):
            y = ssm.gated_norm(y.reshape(T, D), z, mp["norm"]["weight"], G, w.eps).astype(h.dtype)
        with jax.named_scope("out_proj"):
            if w.out_scale != 1.0:
                return scaled_dot(y, mp["out_proj"]["kernel"], w.out_scale), (ssm_pool, conv_pool)
            return y @ mp["out_proj"]["kernel"].astype(h.dtype), (ssm_pool, conv_pool)
