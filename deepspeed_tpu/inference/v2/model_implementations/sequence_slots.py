"""A model with a per-SEQUENCE state group (``sequence_state``): what the
families that keep a state a sequence in slots share, whatever the mixer that
owns the state (``mamba2_base.py``: Mamba-2; ``kda_base.py``: the gated delta
rule). Pools ``[mixers, slots, ...]`` ride beside the rows a token keeps in the
one cache pytree (``ragged/kv_cache.py``); a sequence's slot is a column of
``seq_meta``. A slot's content counts from the sequence's first token: a
sequence with nothing seen reads zeros whatever the slot held. Padding rows
point one past the last slot and their writes drop.

Reads the state manager's cache and ``_config.dtype``, and nothing else of the
model.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.modules import ssm
from deepspeed_tpu.inference.v2.ragged.manager_configs import SequenceStateSpec


class SequenceSlots:

    def _conv_slot_spec(self, mixers, kernel, width):
        """The pool of the last ``kernel - 1`` rows of a mixer's convolution
        input, ``width`` wide, folded into whole tiles (``ssm.conv_slot``), in
        the model's own type."""
        return SequenceStateSpec(name="conv", layers=mixers, dtype=np.dtype(self._config.dtype).name,
                                 shape=ssm.conv_slot(kernel - 1, width))

    def _slot_counts(self):
        """The state group's slots held as a step is dispatched, under the
        names the metrics read whatever the mixer."""
        kv = self._state_manager.kv_cache
        return dict(ssm_slots_live=kv.num_slots - (kv.free_slots or 0),
                    ssm_slots_total=kv.num_slots)

    @staticmethod
    def _slot_rows(batch):
        """``(slot, started, live, one_token)`` of a step's rows: by token in a
        ``decode_loop`` step (``one_token``: row t is sequence ``token_seq[t]``'s
        one token), by sequence in a ``put``."""
        slot = batch["state_slot"]
        # a sequence with nothing seen starts from zero whatever its slot held
        started = batch["seq_valid"] & (batch["seq_seen"] > 0)
        one_token = batch["one_token_rows"]
        if one_token:
            of = batch["token_seq"]
            slot, started = slot[of], started[of]
            live = batch["token_valid"]
        else:  # a sequence without tokens in the step keeps its state
            live = batch["seq_valid"] & (batch["seq_ntok"] > 0)
        return slot, started, live, one_token

    def _in_the_pool(self, update, pool, mi, *rows):
        """``update(pool, mi, *rows)``: a kernel of ``modules/ssm.py`` or
        ``modules/kda.py`` on mixer ``mi`` of a pool where it lies. The SPMD
        partitioner cannot split a Mosaic kernel: on a mesh every device runs
        it over the pool it holds whole (``kv_cache._pool_sharding``), as
        ``_paged_attention`` runs its kernel."""
        placed = None if self._state_manager is None else self._state_manager.kv_cache.sharding
        if placed is None or placed.mesh.size == 1:
            return update(pool, mi, *rows)
        from jax.sharding import PartitionSpec as P
        return jax.shard_map(update, mesh=placed.mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(pool, jnp.int32(mi), *rows)
