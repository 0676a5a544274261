"""SDAR ragged inference model (``model_type="sdar_moe"``: SDAR-30B-A3B-Chat).

``LayerTypedMoEModel``'s glue over the parameter tree of
:mod:`deepspeed_tpu.models.sdar_moe`: Llama's attention phase with the tree's
``q_norm`` / ``k_norm`` (an RMS norm a head, as Trinity's), rotary angles
computed in the program, Mellum's router (softmax, top-k, renormalised). What
the family adds is said in three properties and lives elsewhere:

- ``attention_block`` = ``block_length``: the paged kernel's tile grid and the
  XLA arm mask up to the end of a query's block
  (``ops/pallas/paged_attention.py``), at EVERY bucket
  (``modules/heuristics.py``), and the batch holds every feed to whole blocks
  (``ragged/ragged_wrapper.py``);
- generation by blocks is the base class's ``block_forward`` / ``block_loop``
  (``transformer_base.py``, "block steps"), which read ``denoising_steps`` and
  ``mask_token_id`` off the config;
- **one sequence bucket** (``max_ragged_sequence_count``), **one block-table
  bucket** (the whole table) and a token bucket of at least a BLOCK a sequence
  row and a whole tile: a block step of few sequences runs the program a full
  one runs, and a cold start compiles two ``put`` programs and one block loop.
"""

from deepspeed_tpu.inference.v2.model_implementations.mellum_v2 import LayerTypedMoEModel
from deepspeed_tpu.models.sdar_moe import SdarMoeConfig
from deepspeed_tpu.ops.pallas.paged_attention import TQ


class SdarMoeV2Model(LayerTypedMoEModel):
    one_table_bucket = True
    one_sequence_bucket = True

    def __init__(self, params, config: SdarMoeConfig, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager,
                         sparse_layers=config.num_hidden_layers,
                         norm_topk_prob=config.norm_topk_prob)

    @property
    def attention_block(self):
        return self._config.block_length

    @property
    def attention_window(self):
        return 0

    @property
    def min_token_bucket(self):
        """A block a row of the sequence bucket, and never less than a tile of
        the paged kernel's tile grid, the one grid a block mask takes."""
        return max(TQ, self.min_sequence_bucket * self._config.block_length)
